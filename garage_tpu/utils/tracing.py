"""Distributed tracing: contextvar trace ids, timed spans, JSONL export.

Ref parity: the reference wraps every RPC, table op, block IO and PUT
pipeline stage in OpenTelemetry spans and exports OTLP
(src/garage/tracing_setup.rs:13-37, src/rpc/rpc_helper.rs:172-190,
src/api/s3/put.rs:395,424,452). This build keeps the same span
topology with a dependency-free tracer:

- a contextvar carries (trace_id, span_id) across awaits, so every
  nested span knows its parent without explicit plumbing
- `span("name", **attrs)` works as a sync or async context manager;
  when tracing is disabled it costs one attribute read
- finished spans go to an in-memory ring (admin API /trace tail) and,
  when `GARAGE_TPU_TRACE=<path>` (or `enable(path)`) is set, to a
  JSON-lines file — one object per span with trace/span/parent ids,
  name, start (unix us), dur_us, and attrs
- one clock: a span's start is a `perf_counter` stamp taken at enter,
  moved onto unix time by one (wall, perf_counter) pair read when the
  tracer is made or enabled, so every span of a run differs from the
  monotonic clock by one fixed offset. `record(name, t0, t1)` emits a
  finished span from two such stamps — how work timed on another
  thread (the feeder's stage threads) gets a span on the same clock
- the rpc layer propagates the trace id on the wire (conn.call header)
  so one S3 request's spans correlate across nodes
"""

from __future__ import annotations

import atexit
import contextvars
import json
import os
import random
import threading
import time
from collections import deque
from typing import Optional

_ctx: contextvars.ContextVar = contextvars.ContextVar(
    "garage_tpu_trace", default=None)  # (trace_id: str, span_id: str)

RING_MAX = 2048


_FLUSH_EVERY = 128  # spans buffered before one batched write() syscall

# Ids name spans, they keep no secret: a generator seeded from the
# system's entropy once. `secrets.token_hex` asks the kernel for every
# id, and on the chip's host those calls were 15-21 % of the loop
# thread's CPU in a traced run (PERF.md section 6, PR 29).
_ids = random.Random()
os.register_at_fork(after_in_child=_ids.seed)  # gateway workers fork


def _trace_id() -> str:
    return f"{_ids.getrandbits(64):016x}"


def _span_id() -> str:
    return f"{_ids.getrandbits(32):08x}"


class Tracer:
    def __init__(self):
        self.enabled = bool(os.environ.get("GARAGE_TPU_TRACE"))
        self._path = os.environ.get("GARAGE_TPU_TRACE") or None
        if self._path in ("1", "ring"):  # ring-only mode
            self._path = None
        self._file = None
        self._buf: list[str] = []
        self._lock = threading.Lock()
        self.ring: deque = deque(maxlen=RING_MAX)
        # extra span consumers (e.g. the OTLP exporter, utils/otlp.py);
        # each gets every finished span record and must not block
        self.sinks: list = []
        self._anchor = (time.time_ns(), time.perf_counter_ns())
        # the event loop thread's CPU clock, for /metrics: the scrape
        # renders in a worker thread, where time.thread_time() would
        # read the wrong thread
        self._loop_clock: Optional[int] = None

    def unix_us(self, t: float) -> int:
        """A `time.perf_counter()` stamp as unix microseconds."""
        wall_ns, perf_ns = self._anchor
        return (wall_ns + int(t * 1e9) - perf_ns) // 1000

    def mark_loop_thread(self) -> None:
        """Call once on the event loop's thread, at loop start."""
        get = getattr(time, "pthread_getcpuclockid", None)
        self._loop_clock = (get(threading.get_ident())
                            if get is not None else None)

    def cpu_seconds(self) -> dict[str, float]:
        """CPU seconds (user + system) by thread: "all" is the process,
        "loop" the marked thread — absent, not 0, where the platform
        has no per-thread clock or no thread was marked."""
        out = {"all": sum(os.times()[:2])}
        if self._loop_clock is not None:
            try:
                out["loop"] = time.clock_gettime(self._loop_clock)
            except OSError:
                pass  # the marked thread is gone
        return out

    def enable(self, path: Optional[str] = None) -> None:
        self._anchor = (time.time_ns(), time.perf_counter_ns())
        self.enabled = True
        if path:
            self._close()
            self._path = path

    def disable(self) -> None:
        self.enabled = False
        self._close()

    def _close(self) -> None:
        with self._lock:
            self._flush_locked()
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def _flush_locked(self) -> None:
        if not self._buf or self._path is None:
            return
        if self._file is None:
            try:
                self._file = open(self._path, "a")
            except OSError:
                self._path = None
                self._buf.clear()
                return
        try:
            self._file.write("".join(self._buf))
            self._file.flush()
        except OSError:
            pass
        self._buf.clear()

    def emit(self, rec: dict) -> None:
        self.ring.append(rec)
        for sink in self.sinks:
            sink(rec)
        if self._path is None:
            return
        # buffer; one write() per _FLUSH_EVERY spans keeps the export
        # off the hot path (a 4 MiB PUT emits ~200 spans)
        with self._lock:
            self._buf.append(json.dumps(rec, separators=(",", ":")) + "\n")
            if len(self._buf) >= _FLUSH_EVERY:
                self._flush_locked()


tracer = Tracer()
atexit.register(tracer.flush)


def current_trace_id() -> Optional[str]:
    """Wire form "trace_id:span_id" — the caller's span id rides along
    so remote-side spans parent-link into the caller's tree."""
    cur = _ctx.get()
    return f"{cur[0]}:{cur[1]}" if cur else None


def set_remote_context(wire: Optional[str]) -> None:
    """Adopt a trace context that arrived over the wire (handler side)."""
    if wire and ":" in wire:
        trace_id, span_id = wire.split(":", 1)
        _ctx.set((trace_id, span_id))
    elif wire:
        _ctx.set((wire, "remote"))


def detach() -> None:
    """Drop the inherited trace context of the current task: a
    long-lived task (the feeder's dispatcher) is created inside
    whichever request came first and must not parent its spans, and
    those of the tasks it creates, to that request for ever."""
    _ctx.set(None)


def _emit(ids: tuple, name: str, t0: float, t1: float, attrs: dict,
          exc_type=None) -> None:
    trace_id, span_id, parent_id = ids
    rec = {
        "trace": trace_id,
        "span": span_id,
        "parent": parent_id,
        "name": name,
        "start_us": tracer.unix_us(t0),
        "dur_us": int((t1 - t0) * 1e6),
    }
    if attrs:
        rec["attrs"] = {k: (v.hex()[:16] if isinstance(v, bytes) else v)
                        for k, v in attrs.items()}
    if exc_type is not None:
        rec["error"] = exc_type.__name__
    tracer.emit(rec)


def record(name: str, t0: float, t1: float, **attrs) -> None:
    """Emit a finished span from two `time.perf_counter()` stamps, as a
    child of the calling context's span (a trace of its own without
    one). For work that was timed where no context lives — a stage
    thread stamps, the coroutine that waited for it records."""
    if not tracer.enabled:
        return
    trace_id, parent_id = _ctx.get() or (_trace_id(), None)
    _emit((trace_id, _span_id(), parent_id), name, t0, t1, attrs)


class open_span:
    """A span opened in one loop callback and closed in another, which
    no context variable can follow (the feeder's batch: dispatched by
    one callback, ended by its last leg's). It never becomes the
    current context: its children are recorded with `child`."""

    __slots__ = ("name", "attrs", "t0", "ids")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.ids = None
        if tracer.enabled:
            trace_id, parent_id = _ctx.get() or (_trace_id(), None)
            self.ids = (trace_id, _span_id(), parent_id)
            self.t0 = time.perf_counter()

    def child(self, name: str, t0: float, t1: float, **attrs) -> None:
        """`record` under this span."""
        if self.ids is not None:
            _emit((self.ids[0], _span_id(), self.ids[1]), name, t0, t1,
                  attrs)

    def close(self) -> None:
        if self.ids is not None:
            _emit(self.ids, self.name, self.t0, time.perf_counter(),
                  self.attrs)
            self.ids = None


class span:
    """with span("table.insert", table=name): ...  (sync or async)."""

    __slots__ = ("name", "attrs", "t0", "ids", "token")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.token = None

    def _enter(self):
        if not tracer.enabled:
            return self
        parent = _ctx.get()
        if parent is None:
            trace_id = _trace_id()
            parent_id = None
        else:
            trace_id, parent_id = parent
        span_id = _span_id()
        self.ids = (trace_id, span_id, parent_id)
        self.token = _ctx.set((trace_id, span_id))
        self.t0 = time.perf_counter()
        return self

    def _exit(self, exc_type):
        if self.token is None:
            return False
        _emit(self.ids, self.name, self.t0, time.perf_counter(), self.attrs,
              exc_type)
        _ctx.reset(self.token)
        self.token = None
        return False

    def __enter__(self):
        return self._enter()

    def __exit__(self, exc_type, exc, tb):
        return self._exit(exc_type)

    async def __aenter__(self):
        return self._enter()

    async def __aexit__(self, exc_type, exc, tb):
        # lint: ignore[GL10] emit buffers; the open+write is one amortized page-cache append per _FLUSH_EVERY spans on an already-open file
        return self._exit(exc_type)
