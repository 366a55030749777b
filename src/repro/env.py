"""Computation-environment configuration: platform selection, the persistent
compile cache and fake host devices, shared by the CLIs (``simulate
--platform``, ``whatif --platform``), the benchmark scripts and
``chip_smoke.py``.

All of these only take effect at the very start of a program — before jax
initialises its backend — so the CLIs call them first thing in ``main()``,
ahead of any jnp import side effects. The simulator itself is
platform-agnostic (pure JAX; Pallas kernels compile for a TPU and run in
the interpreter elsewhere, see ``repro.kernels.by_platform``); these helpers
are the one place backend choice lives, and the BENCH_* writers record
:func:`backend` next to their numbers so runs from different platforms
never get compared silently.
"""
from __future__ import annotations

import os
import pathlib
import re

import jax

# the checkout's root: src/repro/env.py -> ../..
REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]
COMPILE_CACHE_DIR = REPO_ROOT / ".jax_cache"


def set_platform(platform: str | None) -> None:
    """Pin jax to ``'cpu'`` / ``'tpu'`` (None = jax's default
    auto-detection). Call before any computation runs."""
    if platform is None:
        return
    if platform not in ("cpu", "tpu"):
        raise ValueError(f"platform {platform!r} not in (cpu, tpu)")
    jax.config.update("jax_platform_name", platform)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is changed. Otherwise the cache goes to a fixed path
    inside the checkout (``<repo>/.jax_cache``, listed in .gitignore): the
    path is part of a cache entry's key, so only a directory that never
    moves is ever read back by the next run. Every compile is cached,
    however short, under a key that includes the op metadata (with file
    names relative to the checkout)."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # a device trace names each op by the metadata its executable was
    # compiled with (the pass scopes of core/trace.py): an executable cached
    # from a build with other metadata would report other or no passes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # the metadata holds source file names: name those of this checkout
    # from its root, so a checkout that moves still reads its entries back
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(REPO_ROOT) + os.sep))
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def set_host_device_count(n: int) -> None:
    """Expose ``n`` fake CPU devices (``--xla_force_host_platform_device_
    count``) so mesh-sharded fleets can be exercised on one host. Must run
    before jax's backend initialises."""
    flags = os.environ.get("XLA_FLAGS", "")
    prefix = "--xla_force_host_platform_device_count"
    flags = " ".join(f for f in flags.split() if not f.startswith(prefix))
    os.environ["XLA_FLAGS"] = (flags + f" {prefix}={n}").strip()


def jax_enable_x64(use_x64: bool = True) -> None:
    """Flip jax's default float width to 64-bit (the simulator itself is
    f32-native; this exists for debugging accumulation-drift hypotheses)."""
    jax.config.update("jax_enable_x64", bool(use_x64))


def set_debug_nans(debug: bool = True) -> None:
    """Make jax error out on NaN production (slow — debugging only)."""
    jax.config.update("jax_debug_nans", bool(debug))


def backend() -> str:
    """The active jax backend name ('cpu' / 'tpu') — the key the BENCH_*
    writers record next to their numbers."""
    return jax.default_backend()
