"""The persistent compilation cache lands where it should.

Each case runs in a fresh interpreter: the cache directory is process-wide
JAX config, which a test must not leave set for the tests after it.
"""
import os
import subprocess
import sys

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
SRC = os.path.join(REPO, "src")

CODE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
path = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == path, path
jax.jit(lambda x: jnp.sin(x) * 3 + 1)(jnp.arange(7.0)).block_until_ready()
print(path)
"""


def _run(env_dir):
    env = dict(os.environ, PYTHONPATH=SRC,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                         text=True, env=env, timeout=90)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_cache_follows_env_var(tmp_path):
    assert _run(tmp_path) == str(tmp_path)
    assert os.listdir(tmp_path), "no compiled program was cached"


def test_cache_defaults_to_ignored_dir_in_checkout():
    path = _run(None)
    assert os.path.realpath(path) == os.path.realpath(
        os.path.join(REPO, ".jax_cache"))
    assert os.listdir(path), "no compiled program was cached"
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), ".jax_cache is not git-ignored"
