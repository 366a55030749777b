"""Meta-heuristic schedulers — simulated annealing, tabu search, genetic —
the paper's §IV MASB suite (meta-heuristics of [22]).

All three search over (P, N) preference matrices and score candidates with
the SAME cheap surrogate (:func:`argmax_surrogate`): every task goes to its
argmax node, capacity ignored, and the objective is the balance of the
resulting trial reservation — the finaliser enforces capacity later. The
surrogate used to be copy-pasted into each scheduler; it is deduplicated
here, behaviour locked by the scheduler determinism tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ordered import sum_columns, tree_sum
from repro.sched.base import NEG
from repro.sched.registry import register_scheduler
from repro.sched.table import SchedContext, TableForm, context_from_state


def balance_objective(reserved, total, active):
    """Variance of per-node reservation fraction (lower = better balanced).

    Summed in a fixed order (``core.ordered``): the searches compare
    energies strictly, so an energy that rounds differently on the TPU
    than on the CPU would steer them apart."""
    frac = jnp.where(active[:, None], reserved / jnp.maximum(total, 1e-9), 0.0)
    f = sum_columns(frac) / frac.shape[-1]
    na = jnp.maximum(active.sum(), 1)
    mu = tree_sum(f) / na
    return tree_sum(jnp.where(active, (f - mu) ** 2, 0.0)) / na


def _surrogate(req, node_reserved, node_total, node_active, valid, base_ok):
    """Array-level core of :func:`argmax_surrogate` — also reachable from a
    :class:`SchedContext` (switchless table forms), which carries exactly
    these slices."""
    N = base_ok.shape[1]
    weight = (valid & base_ok.any(1))[:, None]

    def trial_reserved(pref_m):
        choice = jnp.argmax(jnp.where(base_ok, pref_m, NEG), axis=1)
        onehot = jax.nn.one_hot(choice, N, dtype=jnp.float32) * weight
        # HIGHEST: at the default precision a TPU runs this f32 matmul as
        # one bf16 pass, rounding the requests — and the energies that
        # decide every annealing / tabu / GA step would leave the CPU's
        return node_reserved + jnp.matmul(
            onehot.T, req, precision=jax.lax.Precision.HIGHEST)

    def energy(pref_m):
        return balance_objective(trial_reserved(pref_m), node_total,
                                 node_active)

    return trial_reserved, energy


def argmax_surrogate(state, idx, valid, base_ok):
    """The shared trial-placement surrogate: ``(trial_reserved, energy)``.

    trial_reserved(pref_m): cheap surrogate placement — every task goes to
    its argmax node (capacity ignored; the finaliser enforces it later) and
    the implied requests are summed onto the current reservation matrix.

    energy(pref_m): post-placement reservation balance of that trial
    (lower = better). GA fitness is its negation.
    """
    return _surrogate(state.task_req[idx], state.node_reserved,
                      state.node_total, state.node_active, valid, base_ok)


def _ctx_surrogate(ctx: SchedContext):
    return _surrogate(ctx.req, ctx.node_reserved, ctx.node_total,
                      ctx.node_active, ctx.valid, ctx.base_ok)


def tf_simulated_annealing(cfg, ctx: SchedContext, rng, params):
    """Table form of :func:`propose_simulated_annealing` — identical search
    over the shared base-pass context; params = (n_steps, t0)."""
    n_steps, t0 = int(params[0]), float(params[1])
    P, N = ctx.base_ok.shape
    k_init, k_steps = jax.random.split(rng)
    pref = jax.random.uniform(k_init, (P, N))
    _, energy = _ctx_surrogate(ctx)

    def body(i, carry):
        pref_m, e, key = carry
        key, k1, k2, k3 = jax.random.split(key, 4)
        p = jax.random.randint(k1, (), 0, P)
        n = jax.random.randint(k2, (), 0, N)
        cand = pref_m.at[p, n].add(1.0)       # push task p toward node n
        e_new = energy(cand)
        temp = t0 * (1.0 - i / n_steps) + 1e-6
        accept = (e_new < e) | (jax.random.uniform(k3) <
                                jnp.exp(-(e_new - e) / temp))
        pref_m = jnp.where(accept, cand, pref_m)
        e = jnp.where(accept, e_new, e)
        return pref_m, e, key

    pref, _, _ = jax.lax.fori_loop(0, n_steps, body,
                                   (pref, energy(pref), k_steps))
    return pref


def propose_simulated_annealing(state, cfg, rng, idx, valid, base_ok,
                                scores, n_steps: int = 64, t0: float = 0.1):
    """Anneal a random feasible preference toward balanced placements.
    Objective: post-placement reservation balance."""
    ctx = context_from_state(state, idx, valid, base_ok, scores)
    return tf_simulated_annealing(cfg, ctx, rng, (n_steps, t0))


def tf_tabu_search(cfg, ctx: SchedContext, rng, params):
    """Table form of :func:`propose_tabu_search`; params = (n_steps,
    tenure)."""
    n_steps, tenure = int(params[0]), int(params[1])
    P, N = ctx.base_ok.shape
    scores = ctx.scores
    k_init, k_steps = jax.random.split(rng)
    pref = jnp.where(jnp.isfinite(scores), scores, 0.0) + \
        0.01 * jax.random.uniform(k_init, (P, N))
    _, energy = _ctx_surrogate(ctx)

    def body(i, carry):
        pref_m, e_best, best, tabu_until, key = carry
        key, k1, k2 = jax.random.split(key, 3)
        p = jax.random.randint(k1, (), 0, P)
        n = jax.random.randint(k2, (), 0, N)
        allowed = tabu_until[p] <= i
        cand = pref_m.at[p, n].add(jnp.where(allowed, 1.0, 0.0))
        e_new = energy(cand)
        improve = (e_new < e_best) & allowed
        # aspiration: accept any improving move; otherwise keep best-so-far
        pref_m = jnp.where(improve, cand, pref_m)
        best = jnp.where(improve, cand, best)
        e_best = jnp.where(improve, e_new, e_best)
        tabu_until = tabu_until.at[p].set(
            jnp.where(allowed, i + tenure, tabu_until[p]))
        return pref_m, e_best, best, tabu_until, key

    e0 = energy(pref)
    _, _, best, _, _ = jax.lax.fori_loop(
        0, n_steps, body, (pref, e0, pref, jnp.zeros((P,), jnp.int32),
                           k_steps))
    return best


def propose_tabu_search(state, cfg, rng, idx, valid, base_ok, scores,
                        n_steps: int = 48, tenure: int = 8):
    """Tabu search (paper §IV names it among the MASB schedulers): greedy
    local moves on the preference surrogate with a short-term memory that
    forbids revisiting recently-touched (task) coordinates."""
    ctx = context_from_state(state, idx, valid, base_ok, scores)
    return tf_tabu_search(cfg, ctx, rng, (n_steps, tenure))


def tf_genetic(cfg, ctx: SchedContext, rng, params):
    """Table form of :func:`propose_genetic`; params = (pop, gens,
    mut_rate)."""
    pop, gens, mut_rate = int(params[0]), int(params[1]), float(params[2])
    P, N = ctx.base_ok.shape
    scores = ctx.scores
    keys = jax.random.split(rng, pop + 1)
    population = jax.vmap(lambda k: jax.random.uniform(k, (P, N)))(keys[:pop])
    # seed one individual with the best-fit scores (the paper's 'seeded GA')
    population = population.at[0].set(
        jnp.where(jnp.isfinite(scores), scores, 0.0))
    _, energy = _ctx_surrogate(ctx)

    def fitness(pref_m):
        return -energy(pref_m)

    def gen_step(carry, key):
        population = carry
        fit = jax.vmap(fitness)(population)
        order = jnp.argsort(-fit)
        elite = population[order[: pop // 2]]
        k1, k2 = jax.random.split(key)
        parents = jnp.concatenate([elite, elite], axis=0)
        mask = jax.random.uniform(k1, parents.shape) < mut_rate
        noise = jax.random.uniform(k2, parents.shape)
        children = jnp.where(mask, noise, parents)
        children = children.at[0].set(elite[0])   # elitism
        return children, None

    population, _ = jax.lax.scan(gen_step, population,
                                 jax.random.split(keys[pop], gens))
    fit = jax.vmap(fitness)(population)
    return population[jnp.argmax(fit)]


def propose_genetic(state, cfg, rng, idx, valid, base_ok, scores,
                    pop: int = 8, gens: int = 4, mut_rate: float = 0.15):
    """Small GA over preference matrices (the paper's 4 GA variants, seeded
    and unseeded, distilled): tournament-free truncation selection + mutation;
    fitness = placement balance of the argmax surrogate."""
    ctx = context_from_state(state, idx, valid, base_ok, scores)
    return tf_genetic(cfg, ctx, rng, (pop, gens, mut_rate))


# All three are external table forms (rng-driven searches — nothing for the
# fused kernel to derive from scores alone), but registering them makes
# mixed fleets switchless: a lane's SA/tabu/GA loop runs over ONLY the
# lanes that asked for it instead of taxing every lane through the vmapped
# lax.switch. params mirror the propose_* defaults.
simulated_annealing = register_scheduler(
    "simulated_annealing", propose_simulated_annealing,
    doc="Simulated annealing toward balanced placements.",
    table_form=TableForm(tf_simulated_annealing, (64.0, 0.1)))
tabu_search = register_scheduler(
    "tabu_search", propose_tabu_search,
    doc="Tabu search with short-term move memory.",
    table_form=TableForm(tf_tabu_search, (48.0, 8.0)))
genetic = register_scheduler(
    "genetic", propose_genetic,
    doc="Genetic algorithm over preference matrices (seeded GA).",
    table_form=TableForm(tf_genetic, (8.0, 4.0, 0.15)))
