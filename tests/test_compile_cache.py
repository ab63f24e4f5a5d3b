"""Where the entry points keep JAX's persistent compilation cache: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else the
fixed in-checkout path. Each case runs in a fresh interpreter, so the
test process itself never turns the cache on."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]

PROBE = """
import jax
from repro.launch.compile_cache import CHECKOUT_CACHE, enable_compile_cache
path = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == path, \\
    (jax.config.jax_compilation_cache_dir, path)
print(path)
print(CHECKOUT_CACHE)
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_directory(tmp_path, from_env):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    r = subprocess.run([sys.executable, "-c", PROBE], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    path, checkout = r.stdout.split()
    if from_env:
        assert path == str(tmp_path / "cc")
    else:
        assert path == checkout == str(REPO / ".jax_cache")
