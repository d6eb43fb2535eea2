"""Where the persistent compilation cache goes (repro.launch.compile_cache):
``JAX_COMPILATION_CACHE_DIR`` when set — written there and nowhere else —
else the fixed ``<checkout>/.jax_cache/``.  Each case runs in a fresh
interpreter, since JAX reads the variable when it is first imported."""

import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(8)).block_until_ready()
"""


def _probe(env_dir, compile_):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE.format(compile=compile_)], env=env,
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def _entries(d):
    return set(os.listdir(d)) if d.is_dir() else set()


def test_env_dir_is_used_and_written(tmp_path):
    cache = tmp_path / "cache"
    default_before = _entries(ROOT / ".jax_cache")
    chosen, config_dir = _probe(cache, True)
    assert chosen == config_dir == str(cache)
    assert any(cache.iterdir())
    assert _entries(ROOT / ".jax_cache") == default_before


def test_default_is_fixed_dir_in_checkout():
    chosen, config_dir = _probe(None, False)
    assert chosen == config_dir == str(ROOT / ".jax_cache")


_SCOPED = """
import os, jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
d = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
def make(scope):
    def step(x):
        with jax.named_scope(scope):
            return jnp.sin(x) * 3.0
    return step
counts = []
for scope in ("a", "b"):
    jax.jit(make(scope))(jnp.ones(8)).block_until_ready()
    counts.append(len(os.listdir(d)))
print(*counts)
"""


def test_programs_differing_only_in_scopes_are_cached_apart(tmp_path):
    """The same ops under another ``named_scope`` are a cache miss, so a
    program never runs with the op names of an older one (a profile then
    names its ops as the code that ran)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, "-c", _SCOPED], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    first, second = map(int, out.stdout.split())
    assert 0 < first < second
