"""The sampling profile of the event loop's thread (PR 29):
utils/loopprof.py labels a stack by whose work it is (`root`) and
where the thread was (`leaf`), a SIGALRM handler on the main thread
adds the wall and CPU seconds since the previous sample to plain
dicts, /metrics renders them as loop_profile_{root,leaf}_seconds, and
the benchmark's eight metric files read shares of the loop's CPU from
them. Off — no handler, no timer, no series — unless GARAGE_TPU_TRACE
is set and the caller is the main thread."""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import signal
import sys
import threading
import time
import urllib.request

import pytest

from garage_tpu.utils import loopprof
from garage_tpu.utils.loopprof import LoopProfiler, classify
from garage_tpu.utils.metrics import registry
from garage_tpu.utils.tracing import tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PY = "/usr/lib/python3.12/"
G = "/srv/app/garage_tpu/"
SITE = "/usr/lib/python3.12/site-packages/"

# frames, innermost first, as (file, function)
RUN = (PY + "asyncio/events.py", "_run")
RUN_ONCE = (PY + "asyncio/base_events.py", "_run_once")
# what is outside the loop's own frames must never decide a label
OUTSIDE = [RUN_ONCE, (PY + "asyncio/base_events.py", "run_forever"),
           (PY + "asyncio/runners.py", "run"), (G + "cli/server.py", "main")]
SQLITE = [(G + "db/db.py", "get"), (G + "db/db.py", "get")]
SOCK_WRITE = [(PY + "asyncio/selector_events.py", "write"),
              (PY + "asyncio/streams.py", "write")]

STACKS = {
    # name: (stack inside the loop's frame, root, leaf)
    "s3_put_block_sqlite": (
        SQLITE + [(G + "table/table.py", "queue_insert_local_many"),
                  (G + "api/s3/put.py", "put_one")], "s3", "sqlite"),
    "s3_handler_over_block_manager": (
        [(G + "block/manager.py", "rpc_put_block"),
         (G + "api/s3/put.py", "put_one"), (G + "api/http.py", "_conn")],
        "s3", "python"),
    "s3_response_socket": (
        SOCK_WRITE + [(G + "api/http.py", "write_response"),
                      (G + "api/http.py", "_conn")], "s3", "socket"),
    "feeder_batch": (
        [(PY + "asyncio/tasks.py", "wait_for"),
         (G + "block/feeder.py", "_exec_device_leg")], "feeder", "asyncio"),
    "feeder_backend_submit": (
        [(G + "block/device_backend.py", "submit"),
         (G + "block/device_backend.py", "run_leg")], "feeder", "python"),
    "rpc_one_with_local_handler": (
        SQLITE + [(G + "table/table.py", "_handle"),
                  (G + "net/endpoint.py", "handle"),
                  (G + "net/netapp.py", "call"),
                  (G + "rpc/rpc_helper.py", "one")], "rpc", "sqlite"),
    "rpc_tracked_call_span_codec": (
        [(SITE + "msgpack/__init__.py", "packb"),
         (G + "net/endpoint.py", "call"),
         (G + "rpc/rpc_helper.py", "_tracked_call")], "rpc", "codec"),
    "net_send_loop_socket": (
        SOCK_WRITE + [(G + "net/conn.py", "send_frame"),
                      (G + "net/conn.py", "_send_one_chunk"),
                      (G + "net/conn.py", "_send_loop")], "net", "socket"),
    "net_recv_loop_crypto": (
        [(SITE + "cryptography/hazmat/primitives/ciphers/aead.py", "decrypt"),
         (G + "net/conn.py", "recv_frame"), (G + "net/conn.py", "_recv_loop")],
        "net", "codec"),
    "net_handler_runs_a_table_write": (
        SQLITE + [(G + "table/table.py", "_handle"),
                  (G + "net/conn.py", "_handle_request")], "net", "sqlite"),
    "net_transport_read_no_package_frame": (
        [(PY + "asyncio/selector_events.py", "_read_ready__data_received"),
         (PY + "asyncio/selector_events.py", "_read_ready")],
        "net", "socket"),
    "net_stream_feed_no_package_frame": (
        [(PY + "asyncio/streams.py", "feed_data"),
         (PY + "asyncio/streams.py", "data_received"),
         (PY + "asyncio/selector_events.py", "_read_ready__data_received"),
         (PY + "asyncio/selector_events.py", "_read_ready")],
        "net", "asyncio"),
    "net_self_pipe": (
        [(PY + "asyncio/selector_events.py", "_read_from_self")],
        "net", "asyncio"),
    "bg_merkle_poll": (
        [(G + "db/db.py", "length"), (G + "db/db.py", "__len__"),
         (G + "table/merkle.py", "wait_for_work")], "bg", "sqlite"),
    "bg_worker_over_rpc_helper": (
        [(G + "rpc/rpc_helper.py", "try_write_many_sets"),
         (G + "table/table.py", "insert_many"),
         (G + "table/queue.py", "work"),
         (G + "utils/background.py", "_run_worker")], "bg", "python"),
    "bg_resync": (
        [(G + "native/__init__.py", "blake3"),
         (G + "block/resync.py", "resync_iter")], "bg", "codec"),
    "bg_repair": (
        [(SITE + "zstandard/__init__.py", "decompress"),
         (G + "block/repair.py", "work")], "bg", "codec"),
    "block_manager_task": (
        [(G + "block/manager.py", "_write_file"),
         (G + "block/manager.py", "write_local_shard")], "block", "python"),
    "block_cache_tier_task": (
        [(G + "block/cache_tier.py", "_prefetch_loop")], "block", "python"),
    "other_package_module": (
        [(G + "rpc/system.py", "_status_exchange_loop")], "other", "python"),
    "other_boot_task": (
        [(G + "model/garage.py", "run"),
         (G + "cli/server.py", "_run_server_locked"),
         (G + "cli/server.py", "run_server")], "other", "python"),
    "other_asyncio_timer_callback": (
        [(PY + "asyncio/timeouts.py", "_on_timeout")], "other", "asyncio"),
    "other_the_loop_itself": ([], "other", "asyncio"),
}


@pytest.mark.parametrize("name", sorted(STACKS))
def test_classify(name):
    inner, root, leaf = STACKS[name]
    assert classify(inner + [RUN] + OUTSIDE) == (root, leaf)


def test_classify_idle_is_the_selector_under_run_once():
    idle = [(PY + "selectors.py", "select")] + OUTSIDE
    assert classify(idle) == ("idle", "idle")
    # `select` of another module is not the selector
    assert classify([(G + "table/table.py", "select"), RUN] + OUTSIDE) == (
        "bg", "python")


def test_every_label_value_has_a_case():
    got = {STACKS[n][1] for n in STACKS} | {"idle"}
    assert got == set(loopprof.ROOTS)
    assert {STACKS[n][2] for n in STACKS} | {"idle"} == set(loopprof.LEAVES)


def test_classify_a_stack_cut_at_the_depth_cap():
    """Deeper than MAX_DEPTH the loop's frame is never reached: the
    outermost garage_tpu frame that was seen decides."""
    deep = ([(G + "db/db.py", "get")]
            + [(G + "table/table.py", "f")] * (loopprof.MAX_DEPTH + 5)
            + [(G + "api/http.py", "_conn"), RUN] + OUTSIDE)
    assert classify(deep) == ("bg", "sqlite")


# ---------------------------------------------------------------------------
# the handler and the timer
# ---------------------------------------------------------------------------


def _installed() -> tuple:
    return (signal.getsignal(signal.SIGALRM),
            signal.getitimer(signal.ITIMER_REAL))


NOTHING = (signal.SIG_DFL, (0.0, 0.0))


@pytest.fixture()
def main_thread():
    if threading.current_thread() is not threading.main_thread():
        pytest.skip("signal handlers run on the main thread alone")
    assert _installed() == NOTHING
    yield
    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, signal.SIG_DFL)


@pytest.fixture()
def loop():
    loop = asyncio.new_event_loop()
    yield loop
    loop.close()


@pytest.fixture()
def traced(main_thread, monkeypatch, tmp_path):
    path = str(tmp_path / "spans.jsonl")
    monkeypatch.setenv("GARAGE_TPU_TRACE", path)
    return path


def _spin_cpu(seconds: float) -> None:
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def _untouched(loop) -> bool:
    return _installed() == NOTHING and "select" not in vars(loop._selector)


def test_nothing_is_installed_without_the_trace_variable(main_thread, loop,
                                                         monkeypatch):
    monkeypatch.delenv("GARAGE_TPU_TRACE", raising=False)
    p = LoopProfiler()
    assert p.start(loop) is False
    assert _untouched(loop) and p.snapshot() is None
    p.stop()  # off: nothing to undo, nothing written
    assert _untouched(loop)


def test_nothing_is_installed_off_the_main_thread(traced, loop):
    """A gateway worker whose loop runs on another thread: Python would
    run the handler on the main thread, under some other stack."""
    p, got = LoopProfiler(), []
    t = threading.Thread(target=lambda: got.append(p.start(loop)))
    t.start()
    t.join()
    assert got == [False]
    assert _untouched(loop) and p.snapshot() is None


def test_a_sample_under_the_registry_and_tracer_locks(traced, loop):
    """The handler may interrupt the metrics registry or the tracer in
    the middle of an update: it takes neither's lock and calls neither,
    so samples go on while the main thread holds both."""
    p = LoopProfiler()
    assert p.start(loop) is True
    try:
        with registry()._lock, tracer._lock:
            _spin_cpu(0.05)
            held = p.samples
            # and called by hand, under the locks, it returns
            p._on_alarm(signal.SIGALRM, sys._getframe())
        assert held >= 5 and p.samples > held
    finally:
        p.stop()
    assert _installed() == (signal.SIG_IGN, (0.0, 0.0))
    assert "select" not in vars(loop._selector)


def test_a_fault_of_the_handler_stays_in_the_handler(traced, loop):
    """An exception raised in a signal handler surfaces in whatever the
    main thread was running — here that is a request of the node."""
    p = LoopProfiler()

    def broken(frame):
        raise KeyError("a label nobody foresaw")

    p._sample = broken
    assert p.start(loop) is True
    try:
        _spin_cpu(0.03)
    finally:
        p.stop()
    assert p.faults >= 3 and p.samples == 0


def test_toy_loop_reads_busy_idle_and_blocked(traced):
    """A loop that alternates a coroutine burning CPU, an asyncio.sleep
    and a blocking time.sleep: CPU, idle and blocked seconds come out
    within a third of what each phase was measured to put in, the CPU
    over all roots within a tenth of the thread's own clock, and the
    heaviest stacks land beside the span file."""
    p = LoopProfiler()
    put = {"cpu": 0.0, "idle": 0.0, "blocked": 0.0}

    async def phase(kind, what):
        w0, c0 = time.perf_counter(), time.thread_time()
        r = what()
        if r is not None:
            await r
        w, c = time.perf_counter() - w0, time.thread_time() - c0
        if kind == "idle":
            put["idle"] += w
        else:  # the loop's thread had work: on a core, or not
            put["cpu"] += c
            put["blocked"] += w - c

    async def go():
        assert p.start(asyncio.get_running_loop()) is True
        c0 = time.thread_time()
        for _ in range(10):
            await phase("busy", lambda: _spin_cpu(0.04))
            await phase("idle", lambda: asyncio.sleep(0.04))
            await phase("blocked", lambda: time.sleep(0.04))
        snap, cpu = p.snapshot(), time.thread_time() - c0
        p.stop()
        return snap, cpu

    snap, thread_cpu = asyncio.run(go())
    root, leaf = snap["root"], snap["leaf"]
    busy = [k for k in root if k != "idle"]
    cpu = sum(root[k][0] for k in busy)
    blocked = sum(root[k][1] - root[k][0] for k in busy)
    assert snap["samples"] >= 150  # ~1.2 s at 3 ms, sleeps coalesce
    assert p.faults == 0
    assert cpu == pytest.approx(put["cpu"], rel=1 / 3)
    assert root["idle"][1] == pytest.approx(put["idle"], rel=1 / 3)
    assert blocked == pytest.approx(put["blocked"], rel=1 / 3)
    # this file is not garage_tpu's: the work is nobody's
    assert root["other"][0] == pytest.approx(cpu, rel=0.05)
    assert sum(v[0] for v in root.values()) == pytest.approx(thread_cpu,
                                                             rel=0.10)
    for clock in (0, 1):  # the two labels split the same seconds
        assert sum(v[clock] for v in leaf.values()) == pytest.approx(
            sum(v[clock] for v in root.values()))
    assert p.snapshot() is None  # stopped: absent again

    lines = open(traced + ".loop.folded").read().splitlines()
    assert lines[0].split()[0] == "idle" and len(lines) <= 1 + 200
    stack, cpu_us, wall_us = lines[1].rsplit(" ", 2)  # the heaviest
    assert int(wall_us) >= int(cpu_us) > 0
    assert stack.startswith("asyncio/events.py:Handle._run;")
    assert any("test_loop_profile.py" in ln and "_spin_cpu" in ln
               for ln in lines)


# ---------------------------------------------------------------------------
# /metrics of a real server process, traced and untraced, and the
# benchmark's eight metric files on it
# ---------------------------------------------------------------------------

NEW_METRICS = ("loop_s3_share", "loop_feeder_share", "loop_rpc_share",
               "loop_net_share", "loop_bg_share", "loop_sqlite_share",
               "loop_socket_share", "loop_blocked_share")
BUSY_ROOTS = [r for r in loopprof.ROOTS if r != "idle"]


def _bench(package: str, name: str):
    """benchmark/<package>/<name>.py, loaded by file as the harness
    does (benchmark/ is no package of this repo's tests)."""
    bench = os.path.join(ROOT, "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)  # readers import `lib`
    spec = importlib.util.spec_from_file_location(
        f"{package}.{name}", os.path.join(bench, package, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _catches_sigalrm(pid: int) -> bool:
    with open(f"/proc/{pid}/status") as f:
        caught = next(int(ln.split()[1], 16) for ln in f
                      if ln.startswith("SigCgt:"))
    return bool(caught >> (signal.SIGALRM - 1) & 1)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Two scrapes of a forked `garage_tpu.cli.server` around some S3
    traffic, once under GARAGE_TPU_TRACE and once without."""
    from s3util import S3Client
    from test_s3_api import Server

    def get(port: int) -> str:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                    timeout=30) as r:
            return r.read().decode()

    out = {}
    for how in ("traced", "untraced"):
        tmp = str(tmp_path_factory.mktemp(how))
        spans = os.path.join(tmp, "spans.jsonl")
        old = os.environ.pop("GARAGE_TPU_TRACE", None)
        if how == "traced":
            os.environ["GARAGE_TPU_TRACE"] = spans
        srv = Server(tmp)
        try:
            srv.start()
            srv.setup_layout_and_key()
            m0 = get(srv.admin_port)
            c = S3Client("127.0.0.1", srv.s3_port, srv.key_id, srv.secret)
            c.request("PUT", "/loopprof")
            for i in range(12):
                st, _, _ = c.request("PUT", f"/loopprof/o{i}",
                                     body=os.urandom(300_000))
                assert st == 200
            m1 = get(srv.admin_port)
            handler = _catches_sigalrm(srv.proc.pid)
        finally:
            srv.stop()
            os.environ.pop("GARAGE_TPU_TRACE", None)
            if old is not None:
                os.environ["GARAGE_TPU_TRACE"] = old
        out[how] = {"m0": m0, "m1": m1, "handler": handler,
                    "folded": spans + ".loop.folded"}
    return out


def test_series_render_when_traced_and_are_absent_when_not(served):
    on, off = served["traced"], served["untraced"]
    assert on["handler"] is True and off["handler"] is False
    assert "loop_profile_" not in off["m0"] + off["m1"]
    scrape = _bench("lib", "scrape")
    m0, m1 = (scrape.parse_metrics(on[k]) for k in ("m0", "m1"))
    assert scrape.delta(m0, m1, "loop_profile_samples") > 0
    for family, values in (("root", loopprof.ROOTS),
                           ("leaf", loopprof.LEAVES)):
        name = f"loop_profile_{family}_seconds"
        # the closed set, every value from the first scrape on: a
        # metric file that sums over roots never meets an absent term
        assert {(ls[family], ls["clock"]) for ls in scrape.labels_of(m0, name)
                } == ({(v, c) for v in values for c in ("cpu", "wall")}
                      | {(v, "blocked") for v in values if v != "idle"})
    # the CPU the profile shares out is the loop thread's own
    loop_cpu = scrape.delta(m0, m1, "node_cpu_seconds", {"thread": "loop"})
    shared = scrape.delta(m0, m1, "loop_profile_root_seconds",
                          {"clock": "cpu"})
    assert loop_cpu > 0.05 and shared == pytest.approx(loop_cpu, rel=0.10)
    assert scrape.delta(m0, m1, "loop_profile_root_seconds",
                        {"clock": "cpu", "root": "s3"}) > 0
    # SIGTERM: the timer stopped while the loop ran, the stacks written
    lines = open(on["folded"]).read().splitlines()
    assert lines[0].startswith("idle ") and len(lines) > 10
    assert any("garage_tpu/api/" in ln for ln in lines)
    assert not os.path.exists(off["folded"])


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_metric_file_by_hand(served, metric):
    """Each of the eight is data for the reader `metrics_delta`, listed
    for every cell, and on a real pair of scrapes gives what the
    series say by hand; on an untraced node it reads nothing."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"]
                     if m["name"] == metric)
    assert entry == {"name": metric, "unit": "%", "better": "lower",
                     "source": "program_counter",
                     "layer": "node 1 interpreter", "moves": "req_p50_ms"}
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           f"{metric}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "metrics_delta"
    scrape, reader = _bench("lib", "scrape"), _bench("readers",
                                                     "metrics_delta")

    class Ctx:
        primary_method = "PUT"

        def __init__(self, how):
            self.pair = [scrape.parse_metrics(served[how][k])
                         for k in ("m0", "m1")]

        def scrapes(self, over):
            return (*self.pair, 1.0)

    ctx = Ctx("traced")
    got = reader.read(spec["params"], ctx)

    def d(series, **labels):
        return scrape.delta(*ctx.pair, series, labels)

    if metric == "loop_blocked_share":
        wall = sum(d("loop_profile_root_seconds", clock="wall", root=r)
                   for r in BUSY_ROOTS)
        cpu = sum(d("loop_profile_root_seconds", clock="cpu", root=r)
                  for r in BUSY_ROOTS)
        want = 100.0 * (wall - cpu) / wall
    else:
        family = "leaf" if metric in ("loop_sqlite_share",
                                      "loop_socket_share") else "root"
        label = metric[len("loop_"):-len("_share")]
        want = 100.0 * d(f"loop_profile_{family}_seconds", clock="cpu",
                         **{family: label}) / d("node_cpu_seconds",
                                                thread="loop")
    # (a node this idle burns more inside `select` than its few busy
    # milliseconds hold apart: blocked may read below 0 here)
    assert got == pytest.approx(want, abs=0.05) and got <= 100.0
    assert got >= 0.0 or metric == "loop_blocked_share"
    assert reader.read(spec["params"], Ctx("untraced")) is None


def test_the_root_shares_add_up_to_the_loops_cpu(served):
    """The five shares the benchmark reports, `block` and `other` are
    all of the loop's CPU but what idle samples hold."""
    scrape = _bench("lib", "scrape")
    m0, m1 = (scrape.parse_metrics(served["traced"][k]) for k in ("m0", "m1"))
    loop_cpu = scrape.delta(m0, m1, "node_cpu_seconds", {"thread": "loop"})
    busy = sum(scrape.delta(m0, m1, "loop_profile_root_seconds",
                            {"clock": "cpu", "root": r}) for r in BUSY_ROOTS)
    assert 0.85 <= busy / loop_cpu <= 1.10
