"""The persistent compile cache lands where ``repro.env`` promises: in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at the fixed
in-checkout path — and never anywhere that moves between runs."""
import os
import subprocess
import sys

import pytest

from repro import env

PROBE = ("from repro import env; import jax; d = env.enable_compile_cache(); "
         "print(d); print(jax.config.jax_compilation_cache_dir)")


@pytest.mark.parametrize("env_dir", [None, "/nonexistent/agocs-cache"])
def test_compile_cache_dir(env_dir):
    child = dict(os.environ, JAX_PLATFORMS="cpu")
    child.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        child["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", PROBE], env=child,
                         cwd=env.REPO_ROOT, capture_output=True, text=True,
                         timeout=120,
                         check=True).stdout.split()
    want = env_dir or str(env.COMPILE_CACHE_DIR)
    assert out == [want, want]
    assert env.COMPILE_CACHE_DIR.parent == env.REPO_ROOT
    assert (env.REPO_ROOT / "src" / "repro" / "env.py").is_file()
