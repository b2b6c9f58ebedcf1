"""repro.compile_cache: where the persistent compilation cache lands.

Each case runs a fresh interpreter (JAX reads ``JAX_COMPILATION_CACHE_DIR``
once, at import) on a copy of the module laid out as a checkout under
``tmp_path``, so the checkout's own ``.jax_cache`` is never touched.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import repro.compile_cache

PROBE = """
from repro.compile_cache import use_compile_cache
print(use_compile_cache())
import jax, jax.numpy as jnp
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [True, False],
                         ids=["env_dir_set", "checkout_default"])
def test_cache_entries_land_in_one_place(tmp_path, env_dir):
    checkout = tmp_path / "checkout"
    pkg = checkout / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    shutil.copy(repro.compile_cache.__file__, pkg / "compile_cache.py")
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(checkout / "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    want = checkout / ".jax_cache"
    if env_dir:
        want = tmp_path / "placed"
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    out = subprocess.run([sys.executable, "-c", PROBE], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    assert Path(out.stdout.split()[-1]) == want
    assert any(want.iterdir()), "no cache entry written"
    assert {p.name for p in tmp_path.iterdir()} == {"checkout"} | (
        {"placed"} if env_dir else set())
    assert (checkout / ".jax_cache").exists() == (not env_dir)
