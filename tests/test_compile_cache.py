"""The persistent compilation cache every entry point switches on
(``repro.launch.cache``): ``$JAX_COMPILATION_CACHE_DIR`` when set, else the
fixed ``<checkout>/.jax_cache``.

The cache directory is process-global JAX state, so each case that compiles
runs in a child process of its own (CPU only)."""
import os
import subprocess
import sys

from repro.launch import cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# compile one small program after enable_compile_cache(); print the
# directory it returned and the directory JAX ended up using
_CHILD = """
import jax, jax.numpy as jnp
from repro.launch import cache
{patch}
got = cache.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(8)).block_until_ready()
print(got)
print(jax.config.jax_compilation_cache_dir)
"""


def _run_child(env_dir=None, patch=""):
    env = {k: v for k, v in os.environ.items()
           if k != cache.ENV_VAR}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    if env_dir is not None:
        env[cache.ENV_VAR] = env_dir
    r = subprocess.run([sys.executable, "-c", _CHILD.format(patch=patch)],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_default_dir_is_fixed_in_checkout():
    """No temp, pid or time in the path: the cache key includes it."""
    assert cache.DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")


def test_env_dir_wins_and_receives_programs(tmp_path):
    d = str(tmp_path / "from_env")
    got, used = _run_child(env_dir=d)
    assert got == used == d
    assert os.listdir(d), "no compiled program landed in the env cache dir"


def test_default_dir_receives_programs(tmp_path):
    """Without the variable the helper sets DEFAULT_DIR (redirected here to
    a temporary directory so the test leaves the checkout alone)."""
    d = str(tmp_path / "default")
    got, used = _run_child(patch=f"cache.DEFAULT_DIR = {d!r}")
    assert got == used == d
    assert os.listdir(d), "no compiled program landed in the default dir"
