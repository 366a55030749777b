"""Placement-commit kernel: equivalence + invariant suite.

The finaliser invariant ("no proposal can overcommit a node") used to be
proven only through the engine; with the commit pass kernelised it is proven
at the kernel boundary itself:

* kernel-vs-ref **bitwise-identical** ``node_of`` over random preference
  matrices — static pref, dynamic best-fit, the traced dispatch flag the
  scenario fleet uses, tile sweeps, and the vmapped batch path;
* replaying any returned assignment against the initial tally never exceeds
  node capacity, whatever the proposal ranked.

The deterministic seed sweeps always run; the hypothesis versions widen the
input space when hypothesis is installed (CI does).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.kernels.placement_commit.ops import placement_commit
from repro.kernels.placement_commit.ref import placement_commit_ref


def _inputs(r, P, N, R=3):
    """Random commit inputs shaped like sched.commit.finalize's operands."""
    pref = jnp.asarray(r.standard_normal((P, N)), jnp.float32)
    req = jnp.asarray(r.uniform(0.0, 0.4, (P, R)), jnp.float32)
    base_ok = jnp.asarray(r.random((P, N)) > 0.3)
    valid = jnp.asarray(r.random(P) > 0.2)
    node_total = jnp.asarray(r.uniform(0.3, 1.0, (N, R)), jnp.float32)
    active = jnp.asarray(r.random(N) > 0.2)
    total = jnp.where(active[:, None], node_total, -1.0)
    denom = jnp.maximum(node_total, 1e-6)
    reserved0 = node_total * jnp.asarray(r.uniform(0, 0.6, (N, R)),
                                         jnp.float32)
    return pref, req, base_ok, valid, total, denom, reserved0


def _assert_kernel_bitwise(seed, dyn, traced, P=None, N=None,
                           tile_p=16, tile_n=32, derive_ok=False):
    r = np.random.default_rng(seed)
    P = P or int(r.integers(4, 48))
    N = N or int(r.integers(4, 64))
    args = list(_inputs(r, P, N))
    if derive_ok:
        # the greedy call: no mask handed in, the kernel derives it as the
        # reference does, isfinite(pref)
        args[0] = jnp.where(args[2], args[0], -jnp.inf)
        want = placement_commit_ref(args[0], *args[1:2],
                                    jnp.isfinite(args[0]), *args[3:], dyn)
        args[2] = None
    flag = jnp.asarray(dyn) if traced else dyn
    ref = placement_commit(*args, flag, use_kernel=False)
    ker = placement_commit(*args, flag, use_kernel=True, tile_p=tile_p)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
    if derive_ok:
        np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(ker))
    # node-streamed: tile_n-column tiles with the cross-tile argmax carry
    streamed = placement_commit(*args, flag, use_kernel=True,
                                stream_n=tile_n)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(streamed))
    if traced:
        # the traced flag must agree with the static specialisation too
        stat = placement_commit(*args, dyn, use_kernel=True, tile_p=tile_p)
        np.testing.assert_array_equal(np.asarray(ker), np.asarray(stat))


def _assert_no_overcommit(seed, dyn, use_kernel):
    r = np.random.default_rng(seed)
    P, N = int(r.integers(4, 48)), int(r.integers(4, 64))
    pref, req, base_ok, valid, total, denom, reserved0 = _inputs(r, P, N)
    # adversarial proposal: huge preference for the fullest nodes
    pref = pref + jnp.asarray(reserved0.sum(-1)[None, :] * 100.0, jnp.float32)
    node_of = np.asarray(placement_commit(
        pref, req, base_ok, valid, total, denom, reserved0, dyn,
        use_kernel=use_kernel, tile_p=16))
    reqn, okn, validn = np.asarray(req), np.asarray(base_ok), np.asarray(valid)
    tally = np.asarray(reserved0).copy()
    assigned = np.zeros(N, bool)
    for i in range(P):
        n = int(node_of[i])
        if n < 0:
            continue
        assert validn[i] and okn[i, n], (i, n)
        tally[n] += reqn[i]
        assigned[n] = True
    # every node that RECEIVED work stays within capacity (nodes whose
    # starting tally already exceeded the folded capacity — inactive rows —
    # simply never receive anything). Slack: 1e-9 fit epsilon per step plus
    # float32 accumulation rounding.
    overage = tally - np.asarray(total)
    assert (overage[assigned] <= 1e-9 * (P + 1) + 1e-5).all(), \
        overage[assigned].max()
    # nothing was ever assigned to an inactive (capacity -1) node
    dead = (np.asarray(total) < 0).any(-1)
    assert not dead[node_of[node_of >= 0]].any()


@pytest.mark.parametrize("P,N,tile_p,tile_n", [
    (32, 32, 32, 32),       # exact tiles
    (40, 50, 32, 32),       # padding in both dims
    (128, 96, 64, 32),      # multi-tile grid (sequential tally carry)
    (8, 200, 8, 128),       # wide node dim
    (13, 130, 8, 64),       # P off the row tile, N off 128, streamed pad
])
@pytest.mark.parametrize("dyn", [False, True])
@pytest.mark.parametrize("derive_ok", [False, True])
def test_commit_kernel_bitwise_matches_ref(P, N, tile_p, tile_n, dyn,
                                           derive_ok):
    _assert_kernel_bitwise(seed=P * 1000 + N, dyn=dyn, traced=False,
                           P=P, N=N, tile_p=tile_p, tile_n=tile_n,
                           derive_ok=derive_ok)


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("dyn", [False, True])
@pytest.mark.parametrize("traced", [False, True])
def test_commit_kernel_bitwise_seed_sweep(seed, dyn, traced):
    _assert_kernel_bitwise(seed, dyn, traced)


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("dyn", [False, True])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_no_proposal_can_overcommit(seed, dyn, use_kernel):
    """Replay the returned assignment: initial tally + assigned requests
    never exceeds any node's capacity, and every assignment respects the
    base feasibility mask and the validity mask — whatever the proposal
    ranked. The engine invariant, proven at the kernel boundary."""
    _assert_no_overcommit(seed, dyn, use_kernel)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), dyn=st.booleans(),
           traced=st.booleans())
    def test_commit_kernel_property_bitwise(seed, dyn, traced):
        """Over random matrices, kernel node_of == ref node_of bit-for-bit,
        for the static paths AND the traced flag the fleet's lax.switch
        dispatch feeds (a jax.Array scalar, resolved from data)."""
        _assert_kernel_bitwise(seed, dyn, traced)

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), dyn=st.booleans(),
           use_kernel=st.booleans())
    def test_no_overcommit_property(seed, dyn, use_kernel):
        _assert_no_overcommit(seed, dyn, use_kernel)


def test_commit_kernel_vmapped_matches_ref():
    """The scenario fleet vmaps the commit over lanes with a per-lane traced
    dynamic_bestfit — the batched kernel must match the batched ref."""
    r = np.random.default_rng(0)
    P, N = 24, 20
    pref, req, base_ok, valid, total, denom, reserved0 = _inputs(r, P, N)
    prefs = jnp.stack([pref, -pref, pref * 2, pref + 1])
    flags = jnp.asarray([True, False, False, True])

    def one(p, f, use_kernel):
        return placement_commit(p, req, base_ok, valid, total, denom,
                                reserved0, f, use_kernel=use_kernel,
                                tile_p=8)

    ker = jax.vmap(lambda p, f: one(p, f, True))(prefs, flags)
    ref = jax.vmap(lambda p, f: one(p, f, False))(prefs, flags)
    np.testing.assert_array_equal(np.asarray(ker), np.asarray(ref))


@pytest.mark.parametrize("lanes", [1, 7])
@pytest.mark.parametrize("shared", [False, True])
def test_commit_kernel_vmapped_lanes_match_ref(lanes, shared):
    """The ``custom_vmap`` rule at one lane and at the fleet's seven, with
    every row operand batched or the preference alone batched (the rest
    lane-shared, as 2-D blocks), traced per-lane flags, P off the row tile
    and N off 128: node_of and the tally lane for lane equal to the
    reference's."""
    r = np.random.default_rng(lanes * 10 + shared)
    P, N = 13, 130
    ops = [_inputs(r, P, N) for _ in range(lanes)]
    stacked = [jnp.stack(x) for x in zip(*ops)]
    flags = jnp.asarray([b % 2 == 0 for b in range(lanes)])
    in_axes = (0,) + ((None,) * 6 if shared else (0,) * 6) + (0,)
    if shared:
        stacked = stacked[:1] + [x[0] for x in stacked[1:]]

    def one(pref, req, ok, valid, total, denom, res0, flag, use_kernel):
        return placement_commit(pref, req, ok, valid, total, denom, res0,
                                flag, use_kernel=use_kernel, tile_p=8,
                                return_tally=True)

    ker = jax.vmap(lambda *a: one(*a, True), in_axes)(*stacked, flags)
    ref = jax.vmap(lambda *a: one(*a, False), in_axes)(*stacked, flags)
    for a, b in zip(ker, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_commit_all_infeasible_places_nothing():
    """No feasible node anywhere -> every task stays pending (-1)."""
    r = np.random.default_rng(1)
    P, N = 8, 6
    pref, req, base_ok, valid, total, denom, reserved0 = _inputs(r, P, N)
    base_ok = jnp.zeros_like(base_ok)
    for use_kernel in (False, True):
        node_of = placement_commit(pref, req, base_ok, valid, total, denom,
                                   reserved0, False, use_kernel=use_kernel,
                                   tile_p=8)
        assert (np.asarray(node_of) == -1).all()


def test_commit_priority_order_consumes_capacity_in_row_order():
    """Row order IS priority order: when capacity suffices for one task
    only, the earlier row wins — in both impls, bitwise."""
    N, R = 3, 3
    total = jnp.asarray([[0.5] * R, [-1.0] * R, [-1.0] * R], jnp.float32)
    denom = jnp.maximum(total, 1e-6)
    req = jnp.asarray([[0.4] * R, [0.4] * R], jnp.float32)
    pref = jnp.ones((2, N), jnp.float32)
    ok = jnp.ones((2, N), bool)
    valid = jnp.ones((2,), bool)
    res0 = jnp.zeros((N, R), jnp.float32)
    for use_kernel in (False, True):
        node_of = np.asarray(placement_commit(
            pref, req, ok, valid, total, denom, res0, False,
            use_kernel=use_kernel, tile_p=2))
        assert node_of[0] == 0 and node_of[1] == -1, node_of
