"""The entry points' persistent compilation cache lands where it should."""
import os
import subprocess
import sys

import pytest

from repro.launch.compile_cache import DEFAULT_DIR

# run in a child so the cache setting never leaks into this test process
SCRIPT = """
import os, jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
where = enable_compile_cache()
jax.jit(lambda x: jnp.sin(x) * 3.0 + x).lower(jnp.ones((7, 5))).compile()
print(where)
print(jax.config.jax_compilation_cache_dir)
print(len(os.listdir(where)))
"""


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_dir(tmp_path, env_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(DEFAULT_DIR)
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    where, configured, n_files = out.stdout.split()[-3:]
    assert where == configured == want
    assert int(n_files) > 0
