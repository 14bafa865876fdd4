"""The persistent compilation cache lands where ``launch.compile_cache``
says: in ``JAX_COMPILATION_CACHE_DIR`` when it is set, and nowhere else;
otherwise in ``<checkout>/.jax_cache``, which git ignores.

Each case compiles, in a fresh process, a function whose name is unique
to the run, so its cache entry (``jit_<name>-<key>-cache``) can be told
apart from entries other processes write at the same time."""

import os
import subprocess
import sys
import uuid

from _subproc import SRC

CHECKOUT = os.path.dirname(SRC)
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")

_PROBE = r'''
import sys
import jax, jax.numpy as jnp
from repro.launch import compile_cache
chosen = compile_cache.enable()
print("CHOSEN", chosen)
f = lambda x: x * 1.5 + 1
f.__name__ = sys.argv[1]
jax.jit(f)(jnp.ones(3)).block_until_ready()
'''


def _compile_probe(env_extra):
    name = "probe_" + uuid.uuid4().hex
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0", **env_extra)
    r = subprocess.run([sys.executable, "-c", _PROBE, name], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return name, r.stdout


def _entries(root, name):
    if not os.path.isdir(root):
        return []
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs
            if f.startswith(f"jit_{name}-")]


def test_cache_goes_to_env_dir_and_nowhere_else(tmp_path):
    env_dir = str(tmp_path / "env_cache")
    name, out = _compile_probe({"JAX_COMPILATION_CACHE_DIR": env_dir})
    assert "CHOSEN None" in out
    assert len(_entries(env_dir, name)) == 1
    assert _entries(CHECKOUT, name) == []


def test_cache_defaults_to_checkout_dir_which_git_ignores():
    name, out = _compile_probe({})
    assert f"CHOSEN {DEFAULT_DIR}" in out
    made = _entries(DEFAULT_DIR, name)
    try:
        assert len(made) == 1
    finally:
        for path in made:
            os.remove(path)
    with open(os.path.join(CHECKOUT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


_SCOPED = r'''
import sys
import jax, jax.numpy as jnp
from repro.serve.batching import _compiled_ops
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
def f(x):
    with jax.named_scope(sys.argv[2]):
        return jnp.tanh(x @ x) * 1.5
f.__name__ = "scoped_" + sys.argv[3]
g = jax.jit(f)
x = jnp.ones((8, 8))
g(x).block_until_ready()
loaded = g.lower(x).compile().as_text()
_, ops = _compiled_ops(g, (x,))
print("LOADED", [s for s in ("alpha", "beta") if s in loaded])
print("OPS", sorted({s for p in ops.values() for s in ("alpha", "beta")
                     if s in p.split("/")}))
'''


def test_compiled_ops_read_the_program_s_own_scopes(tmp_path):
    """The executable a call loads from the persistent cache may carry
    the scopes of an older version of the program (JAX keeps metadata
    out of the key); the op map a profile is read by does not."""
    tag = uuid.uuid4().hex
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    outs = []
    for scope in ("alpha", "beta"):
        r = subprocess.run([sys.executable, "-c", _SCOPED,
                            str(tmp_path / "cache"), scope, tag], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(r.stdout)
    assert "LOADED ['alpha']" in outs[0] and "OPS ['alpha']" in outs[0]
    assert "LOADED ['alpha']" in outs[1]          # stale, from the cache
    assert "OPS ['beta']" in outs[1]
