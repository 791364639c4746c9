"""ops/jaxenv: where compiled programs are cached, and what it counts.

JAX decides once per process whether its persistent compilation cache
is in use, and its config is process-global — so each placement case
runs in a fresh interpreter.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD = """
import json, os, sys
from garage_tpu.ops import jaxenv
used = jaxenv.setup()
assert jaxenv.setup() == used  # idempotent
import jax, jax.numpy as jnp
jax.jit(lambda x: x * 3 + 1)(jnp.arange(16)).block_until_ready()
print(json.dumps({"used": used,
                  "configured": jax.config.jax_compilation_cache_dir,
                  "fixed": jaxenv.FIXED_CACHE_DIR,
                  "stats": jaxenv.compile_stats(),
                  "verdict": jaxenv.verdict()}))
"""


def _child(env_dir: str | None) -> dict:
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)  # conftest turns it off
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", _CHILD], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_cache_dir_from_environment_and_nothing_else(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program writes there and
    configures no other directory in code; a second process compiling
    the same program is served from it."""
    d = str(tmp_path / "xla-cache")
    first = _child(d)
    assert first["used"] == d and first["configured"] == d
    assert first["used"] != first["fixed"]
    assert os.listdir(d), "nothing was cached in the directory given"
    assert first["stats"]["compiles"] >= 1
    second = _child(d)
    assert second["stats"]["cache_hits"] >= 1
    assert second["stats"]["compiles"] < first["stats"]["compiles"]
    v = second["verdict"]  # XLA_FLAGS may give several virtual devices
    assert v["platform"] == "cpu" and v["device_kind"] == "cpu" \
        and v["count"] >= 1


def test_cache_dir_without_environment_is_the_fixed_one():
    """Without the variable the cache is one fixed, git-ignored path
    inside the checkout — never a temporary name, a pid or a time."""
    got = _child(None)
    fixed = os.path.join(REPO, ".jax_cache")
    assert got["used"] == got["configured"] == got["fixed"] == fixed
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
