"""Process-wide JAX facts: where compiled programs are cached, what
was compiled, and which device this process got.

One chip belongs to one process. Whoever calls `jax.devices()` first in
a process holds the chip, so the verdict on what that process got —
`platform`, `device_kind`, device count — is taken here, in that same
process, once (`verdict`). Nothing forks to ask, and nothing is kept
outside the process.

`setup()` must run before the first jit of every process that uses the
device (the feeder's backend thread, chip_smoke's kernel phase,
bench.py): JAX decides once per process, at its first compilation,
whether the persistent compilation cache is in use. The directory is
part of the cache key's home, so it never moves: `JAX_COMPILATION_CACHE_DIR`
when the environment sets it (JAX reads that itself; nothing is set in
code), otherwise `.jax_cache/` at the root of the checkout
(git-ignored).

Compilations are counted from JAX's own monitoring events, so a
program that recompiles per batch shape shows up even where the
feeder's own shape accounting (`feeder_recompiles`) does not see it.
"""

from __future__ import annotations

import os
import threading

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — garage_tpu/ops/jaxenv.py is three levels down
FIXED_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_cache_dir: str | None = None
_verdict: dict | None = None
_counts = {"compile_requests": 0, "compile_seconds": 0.0, "cache_hits": 0}


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        with _lock:
            _counts["compile_requests"] += 1
            _counts["compile_seconds"] += seconds


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        with _lock:
            _counts["cache_hits"] += 1


def setup() -> str:
    """Place the compile cache and start counting compilations.
    Idempotent; -> the cache directory in force."""
    global _cache_dir
    with _lock:
        if _cache_dir is not None:
            return _cache_dir
        import jax
        from jax import monitoring

        if not os.environ.get(CACHE_ENV):
            jax.config.update("jax_compilation_cache_dir", FIXED_CACHE_DIR)
        # cache every program, not only those that took over a second:
        # a restarted server re-launches all of its shapes
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _cache_dir = os.environ.get(CACHE_ENV) or FIXED_CACHE_DIR
        return _cache_dir


def compile_stats() -> dict:
    """{"compile_requests", "compile_seconds", "cache_hits", "compiles"}
    since setup(). A request served from the persistent cache counts
    in `cache_hits`; `compiles` is what XLA really built."""
    with _lock:
        out = dict(_counts)
    out["compiles"] = out["compile_requests"] - out["cache_hits"]
    out["compile_seconds"] = round(out["compile_seconds"], 3)
    return out


def verdict() -> dict:
    """What this process got from `jax.devices()`, asked once:
    {"platform", "device_kind", "count"}. Raises what JAX raises when
    no backend initialises (a chip another process holds)."""
    global _verdict
    setup()
    with _lock:
        if _verdict is not None:
            return _verdict
    import jax

    devs = jax.devices()
    res = {"platform": devs[0].platform,
           "device_kind": devs[0].device_kind,
           "count": len(devs)}
    with _lock:
        _verdict = res
    return res
