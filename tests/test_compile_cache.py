"""`repro.compile_cache`: the caller's JAX_COMPILATION_CACHE_DIR wins and
nothing is set in code; otherwise the one fixed in-checkout directory.
Each case runs in a subprocess so the test process's JAX config stays
untouched."""
import json
import os
import subprocess
import sys

from repro import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_PROBE = """
import json, sys, jax, jax.numpy as jnp
from repro import compile_cache
placed = compile_cache.enable()
if sys.argv[1] == "compile":
    jax.jit(lambda x: jnp.sin(x) * 2.0 + 1.0)(jnp.ones(4)).block_until_ready()
print(json.dumps({"placed": placed,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_extra, drop=(), action="compile"):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               **env_extra)
    out = subprocess.run([sys.executable, "-c", _PROBE, action], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_receives_entries(tmp_path):
    where = tmp_path / "cache"
    res = _probe({compile_cache.ENV: str(where)})
    assert res["placed"] == res["config"] == str(where)
    assert where.is_dir() and any(where.iterdir())


def test_default_is_the_fixed_checkout_dir():
    assert compile_cache.DEFAULT_DIR == \
        compile_cache.pathlib.Path(ROOT).resolve() / ".jax_cache"
    # no compile: the checkout's cache directory is not written by tests
    res = _probe({}, drop=(compile_cache.ENV,), action="place")
    assert res["placed"] == res["config"] == str(compile_cache.DEFAULT_DIR)
