"""A sampling profile of the event loop's thread: whose work the loop
runs and where it burns, CPU and wall apart.

On exactly when `GARAGE_TPU_TRACE` is set and the loop runs on the
process's main thread (`cli/server.py` starts it where it marks the
loop's thread); otherwise there is no handler and no timer.

- `ITIMER_REAL` raises `SIGALRM` every `PERIOD_S`. Python runs the
  handler on the main thread at its next bytecode with the interrupted
  frame, so the sample is the loop's own stack (a sampler THREAD
  reading `sys._current_frames()` runs only when the loop lets go of
  the interpreter lock, and sees little but `socket.send` and
  `sqlite3.execute`).
- The handler reads `perf_counter()` and `thread_time()` (it is on the
  loop's thread, so that is the loop's CPU clock), walks `f_back` up to
  the loop's own frames, gives the stack two labels and adds the wall
  and the CPU seconds since the previous sample to plain dicts. It
  takes no lock and calls neither the metrics registry nor the tracer:
  it may have interrupted either. `/metrics` copies the dicts when it
  renders.
- `root`, whose work it is: the outermost `garage_tpu` frame inside the
  loop's `Handle._run`, by module (`ROOTS`). `leaf`, where the thread
  was: the innermost frame (`LEAVES`).
- `idle`, the loop asleep in its selector, is measured and not
  sampled: `select` is wrapped for as long as the profile runs (a
  zero-timeout poll between callbacks passes through: the loop's own
  work). A sample's deltas reach back over one period, and the loop
  changes between work and sleep faster than the timer ticks: shared
  out by the state at the tick alone, a node at a tenth of a core
  handed a third of its CPU to the idle samples and called as much of
  the busy samples' wall "blocked". So a sample takes the busy part of
  its deltas only, and where the tick fell into the sleep that part is
  carried to the next busy sample. busy + idle = the window. The
  wrapper reads the wall clock alone (two `perf_counter()` a sleep): a
  `thread_time()` is a system call, 6 us on the chip's host, and two of
  them around every sleep cost a one-uploader node a tenth of its
  throughput. So what the thread burns INSIDE `select` (falling
  asleep, waking) is wall time of `idle` and CPU of whoever is sampled
  next: where the loop sleeps thousands of times a second, `blocked`
  reads low by that much.
- busy wall - CPU = the loop's thread had work and was
  not on a core: it waited for the interpreter lock, for the db lock,
  or in a blocking call. Rendered as `clock="blocked"`.
- At `stop()` the heaviest stacks go to `<GARAGE_TPU_TRACE>.loop.folded`
  as `frame;frame;... cpu_us wall_us` (outermost first, the innermost
  with its line), for flamegraph tools and for eyes.
"""

from __future__ import annotations

import os
import signal
import threading
import time
from typing import Optional

PERIOD_S = 0.003
MAX_DEPTH = 40
MAX_STACKS = 20000  # distinct stacks kept; the rest add up under "(more)"
FOLDED_TOP = 200

ROOTS = ("s3", "feeder", "rpc", "net", "bg", "block", "other", "idle")
LEAVES = ("sqlite", "socket", "asyncio", "codec", "python", "idle")

_PKG = "/garage_tpu/"
# module prefix inside the package -> root; the first match wins
_ROOT_OF_MODULE = (
    ("api/", "s3"),
    ("block/feeder.py", "feeder"),
    ("block/device_backend.py", "feeder"),
    ("rpc/rpc_helper.py", "rpc"),
    ("net/", "net"),
    ("table/", "bg"),
    ("utils/background.py", "bg"),
    ("block/resync.py", "bg"),
    ("block/repair.py", "bg"),
    ("block/manager.py", "block"),
    ("block/cache", "block"),
)
# the selector transport's own callbacks: with no garage_tpu frame
# above them they are the connections' cost
_TRANSPORT_CALLBACKS = frozenset((
    "_read_ready", "_read_ready__data_received", "_read_ready__get_buffer",
    "_read_ready__on_eof", "_write_ready", "_write_send", "_write_sendmsg",
    "_read_from_self", "_accept_connection", "_accept_connection2"))
# ... and those of them that sit in the socket call itself
_SOCKET_CALLS = frozenset((
    "write", "writelines", "_write_ready", "_write_send", "_write_sendmsg",
    "_read_ready__data_received", "_read_ready__get_buffer"))
_CODEC_PARTS = ("/cryptography/", "/msgpack/", "/zstd", "/zstandard/",
                _PKG + "native/")


def frame_info(filename: str, name: str) -> tuple:
    """(stop, root, transport, leaf) of one frame: `stop` = the loop's
    own scaffolding starts here (`Handle._run`, `_run_once`); `root` =
    the root its module stands for, None outside the package; `leaf` =
    the label it gives when it is the innermost frame."""
    fn = filename
    in_asyncio = "/asyncio/" in fn
    stop = in_asyncio and (
        (name == "_run" and fn.endswith("/events.py"))
        or (name == "_run_once" and fn.endswith("/base_events.py")))
    root, mod = None, ""
    at = fn.rfind(_PKG)
    if at >= 0:
        mod = fn[at + len(_PKG):]
        root = next((r for p, r in _ROOT_OF_MODULE if mod.startswith(p)),
                    "other")
    transport = in_asyncio and name in _TRANSPORT_CALLBACKS
    if name == "select" and fn.endswith("/selectors.py"):
        leaf = "idle"
    elif mod.startswith("db/"):
        leaf = "sqlite"
    elif in_asyncio and fn.endswith("/selector_events.py") \
            and name in _SOCKET_CALLS:
        leaf = "socket"
    elif in_asyncio:
        leaf = "asyncio"
    elif any(p in fn for p in _CODEC_PARTS):
        leaf = "codec"
    else:
        leaf = "python"
    return stop, root, transport, leaf


def classify(stack: list) -> tuple[str, str]:
    """(root, leaf) of a stack of (filename, function name), innermost
    first."""
    infos = [frame_info(f, n) for f, n in stack[:MAX_DEPTH]]
    return _labels(infos)


def _labels(infos: list) -> tuple[str, str]:
    leaf = infos[0][3] if infos else "python"
    root, transport = None, False
    for stop, r, tr, _leaf in infos:
        if stop:
            break
        if r is not None:
            root = r  # the outermost so far
        transport = transport or tr
    if root is None:
        root = ("idle" if leaf == "idle" else
                "net" if transport else "other")
    return root, leaf


class LoopProfiler:
    def __init__(self):
        self.running = False
        self.samples = 0
        self.faults = 0  # samples lost to an error of the handler's own
        # label -> [cpu seconds, wall seconds]
        self.by_root = {r: [0.0, 0.0] for r in ROOTS if r != "idle"}
        self.by_leaf = {v: [0.0, 0.0] for v in LEAVES if v != "idle"}
        # (code objects innermost first, line of the innermost) -> [cpu, wall]
        self.stacks: dict = {}
        self._info: dict = {}  # code object -> frame_info
        self._in_handler = False
        self._t_wall = self._t_cpu = 0.0
        # asleep in select(timeout > 0): wall seconds so far, exact; what
        # of them earlier samples have taken off their deltas; when the
        # sleep that is on began
        self._idle_wall = 0.0
        self._idle_seen = 0.0
        self._sel_t0: Optional[float] = None
        self._carry_cpu = self._carry_wall = 0.0
        self._selector = None
        self._path: Optional[str] = None

    def start(self, loop) -> bool:
        """Install the handler, the timer and the selector's wrapper:
        only under GARAGE_TPU_TRACE, only on the main thread, where
        Python runs signal handlers, and only on a selector loop — call
        it on the loop's thread."""
        path = os.environ.get("GARAGE_TPU_TRACE")
        selector = getattr(loop, "_selector", None)
        if (not path or self.running or selector is None
                or threading.current_thread() is not threading.main_thread()):
            return False
        self._path = None if path in ("1", "ring") else path
        self._selector, self._select = selector, selector.select
        selector.select = self._timed_select  # shadows the class's method
        self._t_wall, self._t_cpu = time.perf_counter(), time.thread_time()
        self.running = True
        signal.signal(signal.SIGALRM, self._on_alarm)
        # a system call that SIGALRM lands in restarts: libtpu's and
        # JAX's threads share the process with this timer
        signal.siginterrupt(signal.SIGALRM, False)
        signal.setitimer(signal.ITIMER_REAL, PERIOD_S, PERIOD_S)
        return True

    def stop(self) -> None:
        """Timer and wrapper off, folded stacks written. SIGALRM stays
        ignored, not default: one more may be on its way, and the
        default action kills the process."""
        if not self.running:
            return
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, signal.SIG_IGN)
        self.running = False
        del self._selector.select
        self._selector = None
        if self._path:
            try:
                self.write_folded(self._path + ".loop.folded")
            except OSError:
                pass  # a profile that cannot be written stops no server

    # -- on the loop's thread ------------------------------------------

    def _timed_select(self, timeout=None):
        if timeout is not None and timeout <= 0:
            return self._select(timeout)
        self._sel_t0 = time.perf_counter()
        try:
            return self._select(timeout)
        finally:
            # the handler moves _sel_t0 up to each sample it takes
            t0, self._sel_t0 = self._sel_t0, None
            self._idle_wall += time.perf_counter() - t0

    def _on_alarm(self, _signum, frame) -> None:
        if self._in_handler or frame is None:
            return
        self._in_handler = True  # a late handler is not sampled itself
        try:
            self._sample(frame)
        except Exception:
            # raised here it would surface in whatever the loop was
            # running: a profile must not be able to fail a request
            self.faults += 1
        finally:
            self._in_handler = False

    def _sample(self, frame) -> None:
        now, cpu = time.perf_counter(), time.thread_time()
        wall_d, cpu_d = now - self._t_wall, cpu - self._t_cpu
        self._t_wall, self._t_cpu = now, cpu
        asleep = self._sel_t0 is not None
        if asleep:  # the part of this sleep that is behind us
            self._idle_wall += now - self._sel_t0
            self._sel_t0 = now
        idle_d = self._idle_wall - self._idle_seen
        self._idle_seen = self._idle_wall
        cpu_d += self._carry_cpu
        wall_d += self._carry_wall - idle_d
        self.samples += 1
        if asleep:
            self._carry_cpu, self._carry_wall = cpu_d, wall_d
            return
        self._carry_cpu = self._carry_wall = 0.0
        codes, infos = [], []
        cache = self._info
        f = frame
        while f is not None and len(codes) < MAX_DEPTH:
            code = f.f_code
            info = cache.get(code)
            if info is None:
                info = cache[code] = frame_info(code.co_filename,
                                                code.co_name)
            codes.append(code)
            infos.append(info)
            if info[0]:
                break  # the loop's own frame: the stack's foot
            f = f.f_back
        root, leaf = _labels(infos)
        if leaf == "idle":  # awake: this is select(0), the loop's own poll
            root, leaf = "other", "asyncio"
        key = (tuple(codes), frame.f_lineno)
        stack = self.stacks.get(key)
        if stack is None:
            if len(self.stacks) >= MAX_STACKS:
                key = ((), 0)
            stack = self.stacks.setdefault(key, [0.0, 0.0])
        for acc in (self.by_root[root], self.by_leaf[leaf], stack):
            acc[0] += cpu_d
            acc[1] += wall_d

    # -- on any thread ---------------------------------------------------

    def snapshot(self) -> Optional[dict]:
        """{"samples", "root": {label: (cpu, wall)}, "leaf": {...}} —
        None while the profiler is off (absent, not 0, on /metrics).
        The label sets never change and `tuple(v)` is one step for the
        interpreter, so a sample taken meanwhile tears no pair."""
        if not self.running:
            return None
        root = {k: tuple(v) for k, v in self.by_root.items()}
        leaf = {k: tuple(v) for k, v in self.by_leaf.items()}
        root["idle"] = leaf["idle"] = (0.0, self._idle_wall)
        return {"samples": self.samples, "root": root, "leaf": leaf}

    def write_folded(self, path: str) -> None:
        def name(code, line=None):
            fn = code.co_filename
            at = fn.rfind(_PKG)
            short = (fn[at + 1:] if at >= 0
                     else "/".join(fn.rsplit("/", 2)[-2:]))
            where = f"{short}:{getattr(code, 'co_qualname', code.co_name)}"
            return where if line is None else f"{where}:{line}"

        def frames(key) -> str:
            codes, line = key
            if not codes:
                return "(more)"
            return ";".join([name(c) for c in reversed(codes[1:])]
                            + [name(codes[0], line)])

        rows = [(cpu, wall, key) for key, (cpu, wall) in self.stacks.items()]
        rows.sort(key=lambda r: r[:2], reverse=True)
        with open(path, "w") as f:
            f.write(f"idle 0 {int(self._idle_wall * 1e6)}\n")
            for cpu, wall, key in rows[:FOLDED_TOP]:
                f.write(f"{frames(key)} {int(cpu * 1e6)} "
                        f"{int(wall * 1e6)}\n")


profiler = LoopProfiler()
