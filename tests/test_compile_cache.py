"""Where JAX's persistent compilation cache lives: the directory
``JAX_COMPILATION_CACHE_DIR`` names when it is set, else the fixed,
git-ignored ``.jax_cache`` at the root of the checkout. Each case runs
in a fresh process, so the test process's own jax config is untouched.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import jax, jax.numpy as jnp
from repro.utils.cache import enable_compilation_cache
d = enable_compilation_cache()
assert jax.config.jax_compilation_cache_dir == d
jax.jit(lambda x: x * 2 + 1)(jnp.ones(8)).block_until_ready()
print(d)
"""


def _cache_dir(tmp_path, **env_extra) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
               **env_extra)
    out = subprocess.run([sys.executable, "-c", _PROBE], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_cache_goes_where_the_environment_says(tmp_path):
    target = tmp_path / "cache"
    got = _cache_dir(tmp_path, JAX_COMPILATION_CACHE_DIR=str(target),
                     JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                     JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    assert got == str(target)
    assert any(target.iterdir()), "nothing was written to the cache"
    assert [p.name for p in tmp_path.iterdir()] == ["cache"]


def test_cache_defaults_to_a_fixed_path_in_the_checkout(tmp_path):
    got = _cache_dir(tmp_path)
    assert got == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
