"""The feeder hop and the event loop seen from inside (ISSUE 24).

Always-on counters at the hop's four hand-offs (queue wait, stage
wait, resume lag, whole hop), the loop thread's CPU clock on /metrics,
and the spans that put loop tasks and stage threads on one clock. The
"device" is the stub backend (real results, modelled stage sleeps), as
in test_feeder_pipeline.py.
"""

from __future__ import annotations

import asyncio
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from garage_tpu.block.codec import ErasureCodec  # noqa: E402
from garage_tpu.block.device_backend import StubDeviceBackend  # noqa: E402
from garage_tpu.block.feeder import DeviceFeeder  # noqa: E402
from garage_tpu.utils import tracing  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402
from garage_tpu.utils.tracing import span, tracer  # noqa: E402

HOP_SERIES = ("feeder_queue_wait_seconds", "feeder_stage_wait_seconds",
              "feeder_resume_lag_seconds", "feeder_hop_seconds")


def run(coro, timeout=60.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


@pytest.fixture()
def ring():
    tracer.enabled = True
    tracer.ring.clear()
    yield tracer.ring
    tracer.enabled = False
    tracer.ring.clear()


def counts(name: str, **match) -> tuple[int, float]:
    return registry().totals(name, **match)


def delta(before: tuple, name: str, **match) -> tuple[int, float]:
    c, s = counts(name, **match)
    return c - before[0], s - before[1]


def stub_feeder(fixed_s: float = 0.0, **kw) -> DeviceFeeder:
    """mode "require" on the stub: every queued item takes the staged
    device route, one launch per op group."""
    codec = ErasureCodec(4, 2, use_jax=False)
    stub = StubDeviceBackend(codec, h2d_gbps=1e6, compute_gbps=1e6,
                             d2h_gbps=1e6, fixed_s=fixed_s)
    return DeviceFeeder(codec=codec, mode="require", backend=stub, **kw)


def by_name(recs, name: str) -> list[dict]:
    return [r for r in recs if r["name"] == name]


def end_us(r: dict) -> int:
    return r["start_us"] + r["dur_us"]


# ---------------------------------------------------------------------------
# the tracer: one clock, record()
# ---------------------------------------------------------------------------


def test_record_from_foreign_thread(ring):
    """A stage thread has no trace context of its own: given the
    caller's, its record lands in the ring under the caller's span, on
    the clock of a loop span taken at the same instant."""
    got = {}

    def work(wire, t):
        tracing.set_remote_context(wire)
        tracing.record("thread.work", t, t + 0.002, k=1)

    async def go():
        async with span("loop.root"):
            got["wire"] = tracing.current_trace_id()
            t = time.perf_counter()
            with span("loop.same"):
                pass
            th = threading.Thread(target=work, args=(got["wire"], t))
            th.start()
            await asyncio.to_thread(th.join)

    run(go())
    root, same, rec = (by_name(ring, n)[0]
                       for n in ("loop.root", "loop.same", "thread.work"))
    assert rec["trace"] == root["trace"] and rec["parent"] == root["span"]
    assert rec["attrs"] == {"k": 1}
    assert rec["dur_us"] == pytest.approx(2000, abs=2)
    assert abs(rec["start_us"] - same["start_us"]) < 1000
    # and that clock is unix time
    assert abs(rec["start_us"] / 1e6 - time.time()) < 60


@pytest.mark.parametrize("how", ["span", "record"])
def test_start_us_ignores_wall_clock_at_exit(ring, monkeypatch, how):
    """A span's start is its perf_counter stamp at enter on the
    tracer's one anchor: a wall clock that jumps before the span ends
    (NTP step, suspend) moves nothing."""
    wall = time.time()
    if how == "span":
        sp = span("jumpy").__enter__()
    t0 = time.perf_counter()
    monkeypatch.setattr(time, "time", lambda: wall + 3600.0)
    monkeypatch.setattr(time, "time_ns", lambda: int((wall + 3600.0) * 1e9))
    if how == "span":
        sp.__exit__(None, None, None)
    else:
        tracing.record("jumpy", t0, t0 + 0.001)
    rec = by_name(ring, "jumpy")[0]
    assert abs(rec["start_us"] / 1e6 - wall) < 5.0


def test_record_without_context_starts_a_trace(ring):
    tracing.detach()
    t = time.perf_counter()
    tracing.record("alone", t, t + 0.001)
    rec = by_name(ring, "alone")[0]
    assert rec["parent"] is None and len(rec["trace"]) == 16


# ---------------------------------------------------------------------------
# spans of the hop: feeder.submit -> feeder.batch -> dev.*
# ---------------------------------------------------------------------------


async def _one_item(f: DeviceFeeder, op: str):
    rng = np.random.default_rng(24)
    block = rng.integers(0, 256, 300_000, dtype=np.uint8).tobytes()
    if op == "encode_put":
        return await f.encode_put(block)
    stripe = f.codec.encode(block)
    present = (1, 2, 3, 4)  # shard 0 lost: a real matmul
    out = await f.decode(present, [stripe[i] for i in present], len(block))
    assert out == block
    return out


@pytest.mark.parametrize("op", ["encode_put", "decode"])
def test_hop_spans_one_item(ring, op):
    """One item through the stub: feeder.submit under the caller's
    span; feeder.batch a trace of its own (the dispatcher belongs to no
    request); the three stage-thread records under feeder.batch and
    inside it in time."""
    f = stub_feeder(fixed_s=0.002)

    async def go():
        async with span("caller"):
            await _one_item(f, op)
        await f.stop()

    run(go())
    caller, = by_name(ring, "caller")
    submit, = by_name(ring, "feeder.submit")
    batch, = by_name(ring, "feeder.batch")
    assert submit["trace"] == caller["trace"]
    assert submit["parent"] == caller["span"]
    assert submit["attrs"]["op"] == op and submit["attrs"]["wait_us"] >= 0
    assert batch["parent"] is None and batch["trace"] != caller["trace"]
    assert batch["attrs"] == {"items": 1, "ops": op}
    legs = [by_name(ring, f"dev.{s}") for s in ("h2d", "compute", "d2h")]
    assert [len(x) for x in legs] == [1, 1, 1]
    at = batch["start_us"]
    for (leg,) in legs:
        assert leg["trace"] == batch["trace"]
        assert leg["parent"] == batch["span"]
        assert leg["attrs"]["op"] == op and leg["attrs"]["items"] == 1
        assert leg["attrs"]["wait_us"] >= 0
        assert leg["dur_us"] >= 1500  # the stub's 2 ms stage sleep
        # in stage order, each inside feeder.batch (1 us of rounding)
        assert leg["start_us"] >= at - 1
        at = end_us(leg)
    assert at <= end_us(batch) + 1
    # the item's hop contains its batch
    assert submit["start_us"] <= batch["start_us"] + 1
    assert end_us(batch) <= end_us(submit) + 1


def test_linger_and_slot_wait_spans_only_when_entered(ring):
    """feeder.linger appears when the dispatcher waits for sibling
    streams, feeder.slot_wait when every in-flight slot is taken; both
    are dispatcher spans (no parent). One lone item shows neither."""
    f = stub_feeder(fixed_s=0.03, max_batch=1)
    f.inflight_batches = 1

    async def go():
        await f.hash(os.urandom(4096))
        assert not by_name(ring, "feeder.linger")
        assert not by_name(ring, "feeder.slot_wait")
        # two batches, one slot: the second waits for it
        await asyncio.gather(f.hash(os.urandom(4096)),
                             f.hash(os.urandom(4096)))
        f.active_streams = 4  # four PUT streams mid-loop, one shows up
        f.max_batch = 8
        await f.hash(os.urandom(4096))
        await f.stop()

    run(go())
    slot = by_name(ring, "feeder.slot_wait")
    assert slot and all(r["parent"] is None for r in slot)
    assert slot[0]["attrs"] == {"op": "hash", "inflight": 1}
    assert slot[0]["dur_us"] >= 20_000
    linger, = by_name(ring, "feeder.linger")
    assert linger["parent"] is None
    assert linger["attrs"] == {"op": "hash", "have": 1, "want": 4}
    assert 4_000 <= linger["dur_us"] < 60_000  # the 6 ms linger ran out


# ---------------------------------------------------------------------------
# counters: always on, once per hand-off, and they add up
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("series", HOP_SERIES)
def test_hop_counters_move_with_tracing_off(series):
    tracer.enabled = False
    tracer.ring.clear()
    before = counts(series)
    f = stub_feeder()

    async def go():
        await _one_item(f, "encode_put")
        await f.stop()

    run(go())
    assert not tracer.ring
    n, secs = delta(before, series)
    # one item, one leg: three stage waits, four resumes, one of the rest
    assert n == {"feeder_stage_wait_seconds": 3,
                 "feeder_resume_lag_seconds": 4}.get(series, 1)
    assert secs > 0


def test_hop_covers_its_parts():
    """Per item hop >= queue wait; over a run of one-item launches the
    hops sum to at least queue wait + stage waits + stage busy."""
    f = stub_feeder(fixed_s=0.004, max_batch=1)
    names = HOP_SERIES

    async def go():
        b0 = {n: counts(n) for n in names}
        await f.hash(os.urandom(2048))
        one = {n: delta(b0[n], n) for n in names}
        assert one["feeder_hop_seconds"][0] == 1
        assert one["feeder_queue_wait_seconds"][0] == 1
        assert (one["feeder_hop_seconds"][1]
                >= one["feeder_queue_wait_seconds"][1])
        b1 = {n: counts(n) for n in names}
        busy0 = sum(f._pl_busy.values())
        await asyncio.gather(*[f.hash(os.urandom(2048)) for _ in range(6)])
        many = {n: delta(b1[n], n) for n in names}
        busy = sum(f._pl_busy.values()) - busy0
        await f.stop()
        return many, busy

    many, busy = run(go())
    assert many["feeder_hop_seconds"][0] == 6
    assert many["feeder_stage_wait_seconds"][0] == 18
    assert many["feeder_resume_lag_seconds"][0] == 24
    assert busy >= 6 * 3 * 0.004
    parts = (many["feeder_queue_wait_seconds"][1]
             + many["feeder_stage_wait_seconds"][1] + busy)
    assert many["feeder_hop_seconds"][1] >= parts - 0.005
    # six one-item batches through one thread per stage: they queue
    assert many["feeder_stage_wait_seconds"][1] > 0.004


@pytest.mark.parametrize("how", ["cancelled_in_queue", "hung_in_compute"])
def test_stage_job_observes_what_it_reached_once(how):
    """A job abandoned while queued observes nothing; a leg that hangs
    observes the hand-offs it got through, each once, and its item's
    hop once (the item fails with the device's error)."""
    stages = ("h2d", "compute", "d2h")
    before = {(n, s): counts(n, **{k: s})
              for n, k in (("feeder_stage_wait_seconds", "stage"),
                           ("feeder_resume_lag_seconds", "hop"))
              for s in stages + ("item",)}
    hop0 = counts("feeder_hop_seconds")

    def got(name, label, value):
        return delta(before[(name, value)], name, **{label: value})[0]

    if how == "cancelled_in_queue":
        f = stub_feeder()

        async def go():
            pl = f._pipeline()
            first = asyncio.create_task(f._stage_call(
                pl, "h2d", lambda: time.sleep(0.15), [], "t"))
            second = asyncio.create_task(f._stage_call(
                pl, "h2d", lambda: None, [], "t"))
            await asyncio.sleep(0.03)  # second sits behind first
            second.cancel()
            await asyncio.gather(first, second, return_exceptions=True)
            await asyncio.sleep(0.05)  # the thread skips the second
            await f.stop()

        run(go())
        assert got("feeder_stage_wait_seconds", "stage", "h2d") == 1
        assert got("feeder_resume_lag_seconds", "hop", "h2d") == 1
        return
    f = stub_feeder(fixed_s=0.001)
    f.batch_timeout = 0.3
    f._get_backend().hang_stage = "compute"

    async def go():
        with pytest.raises(TimeoutError):
            await f.hash(os.urandom(2048))
        await f.stop()

    run(go())
    waits = [got("feeder_stage_wait_seconds", "stage", s) for s in stages]
    lags = [got("feeder_resume_lag_seconds", "hop", s) for s in stages]
    assert waits == [1, 1, 0]  # compute was claimed, d2h never submitted
    assert lags == [1, 0, 0]   # compute never came back
    assert got("feeder_resume_lag_seconds", "hop", "item") == 1
    assert delta(hop0, "feeder_hop_seconds")[0] == 1


# ---------------------------------------------------------------------------
# the response write: timer and spans
# ---------------------------------------------------------------------------


def _http_get(port: int, path: str = "/") -> bytes:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=20) as r:
        return r.read()


def test_response_write_timer_with_tracing_off():
    """api_response_write_seconds times head + body of the response —
    the part of a GET the handler's timer never saw — and needs no
    tracing."""
    from garage_tpu.api.http import HttpServer, Response

    tracer.enabled = False
    tracer.ring.clear()

    async def handler(req):
        async def body():
            for _ in range(3):
                await asyncio.sleep(0.02)
                yield b"x" * 1000

        return Response(200, [], body())

    async def go():
        srv = HttpServer(handler, name="s3")
        await srv.start("127.0.0.1", 0)
        w0 = counts("api_response_write_seconds", api="s3", method="GET")
        r0 = counts("api_request_duration_seconds", api="s3", method="GET")
        try:
            data = await asyncio.to_thread(_http_get, srv.bound_port)
        finally:
            await srv.stop()
        assert data == b"x" * 3000
        return (delta(w0, "api_response_write_seconds", api="s3",
                      method="GET"),
                delta(r0, "api_request_duration_seconds", api="s3",
                      method="GET"))

    (wn, ws), (rn, rs) = run(go())
    assert not tracer.ring
    assert (wn, rn) == (1, 1)
    assert ws >= 0.05 > rs  # the body's three sleeps are in the write


def test_streamed_get_is_one_trace(tmp_path, ring):
    """http.exchange holds http.request and http.write; the block
    fetches of a streamed GET are tasks made while the body is written
    and carry the request's trace id, under http.write."""
    from test_model import stop_all
    from test_qos import _one_node_s3

    body = os.urandom(3 * (1 << 20) + 17)

    async def go():
        net, garages, tasks, g, srv, cli = await _one_node_s3(tmp_path)
        try:
            st, _, _ = await asyncio.to_thread(
                cli.request, "PUT", "/qos-bucket/obj", body=body)
            assert st == 200
            g.block_manager.cache.clear()
            ring.clear()
            st, _, data = await asyncio.to_thread(
                cli.request, "GET", "/qos-bucket/obj")
            assert st == 200 and data == body
        finally:
            await srv.stop()
            await stop_all(garages, tasks)

    run(go(), 120)
    exch, = by_name(ring, "http.exchange")
    req, = by_name(ring, "http.request")
    write, = by_name(ring, "http.write")
    assert exch["parent"] is None
    assert exch["attrs"] == {"api": "s3", "method": "GET"}
    for child in (req, write):
        assert child["parent"] == exch["span"]
        assert child["trace"] == exch["trace"]
        assert exch["start_us"] <= child["start_us"] + 1
        assert end_us(child) <= end_us(exch) + 1
    assert end_us(req) <= write["start_us"] + 1
    gets = by_name(ring, "block.get")
    assert len(gets) == 4
    spans = {r["span"]: r for r in ring}
    for b in gets:
        assert b["trace"] == exch["trace"]
        up = b  # the fetch task was made under http.write
        while up["parent"] in spans and up is not write:
            up = spans[up["parent"]]
        assert up is write
        assert write["start_us"] <= b["start_us"] + 1
        assert end_us(b) <= end_us(write) + 1
    assert {r["trace"] for r in by_name(ring, "block.verify")} \
        == {exch["trace"]}


# ---------------------------------------------------------------------------
# the loop thread's CPU clock on /metrics
# ---------------------------------------------------------------------------


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def _cpu(text: str) -> dict[str, float]:
    out = {}
    for line in text.splitlines():
        if line.startswith("node_cpu_seconds{"):
            out[line.split('"')[1]] = float(line.rsplit(" ", 1)[1])
    return out


@pytest.mark.parametrize("busy", ["loop", "worker", "no_thread_clock"])
def test_metrics_carry_loop_cpu_clock(tmp_path, monkeypatch, busy):
    """node_cpu_seconds{thread="loop"} is the loop thread's own CPU
    clock although /metrics renders in a worker thread: it grows when
    the loop burns CPU and stays flat when a worker does; without
    pthread_getcpuclockid the series is absent, not 0."""
    from garage_tpu.admin.http import AdminHttpServer
    from test_model import make_garage_cluster, stop_all

    if busy == "no_thread_clock":
        monkeypatch.delattr(time, "pthread_getcpuclockid")
    monkeypatch.setattr(tracer, "_loop_clock", None)

    async def go():
        net, garages, tasks = await make_garage_cluster(tmp_path, n=1, rf=1)
        srv = AdminHttpServer(garages[0])
        await srv.start("127.0.0.1", 0)
        tracer.mark_loop_thread()
        try:
            a = _cpu((await asyncio.to_thread(
                _http_get, srv.http.bound_port, "/metrics")).decode())
            if busy == "worker":
                await asyncio.to_thread(_spin, 0.4)
            else:
                _spin(0.4)
            b = _cpu((await asyncio.to_thread(
                _http_get, srv.http.bound_port, "/metrics")).decode())
        finally:
            await srv.stop()
            await stop_all(garages, tasks)
        return a, b

    a, b = run(go(), 120)
    assert b["all"] - a["all"] >= 0.3
    if busy == "no_thread_clock":
        assert "loop" not in a and "loop" not in b
    elif busy == "loop":
        assert b["loop"] - a["loop"] >= 0.3
    else:
        assert b["loop"] - a["loop"] < 0.2


# ---------------------------------------------------------------------------
# the benchmark's eight metric files read series this program exports
# ---------------------------------------------------------------------------

HOP_METRICS = ("feeder_hop_ms", "feeder_wait_ms", "stage_wait_ms",
               "stage_h2d_busy", "loop_resume_ms", "loop_cpu_share",
               "node_cpu_cores", "s3_body_ms")


@pytest.fixture(scope="module")
def metrics_text(tmp_path_factory):
    """/metrics of a node after one stub device item and one S3 GET."""
    from garage_tpu.admin.http import AdminHttpServer
    from garage_tpu.api.http import HttpServer, Response
    from test_model import make_garage_cluster, stop_all

    async def handler(req):
        return Response(200, [], b"ok")

    async def go():
        net, garages, tasks = await make_garage_cluster(
            tmp_path_factory.mktemp("hop_metrics"), n=1, rf=1)
        admin = AdminHttpServer(garages[0])
        s3 = HttpServer(handler, name="s3")
        await admin.start("127.0.0.1", 0)
        await s3.start("127.0.0.1", 0)
        tracer.mark_loop_thread()
        f = stub_feeder()
        try:
            await _one_item(f, "encode_put")
            await asyncio.to_thread(_http_get, s3.bound_port)
            return (await asyncio.to_thread(
                _http_get, admin.http.bound_port, "/metrics")).decode()
        finally:
            tracer._loop_clock = None
            await f.stop()
            await s3.stop()
            await admin.stop()
            await stop_all(garages, tasks)

    return run(go(), 120)


@pytest.mark.parametrize("metric", HOP_METRICS)
def test_metric_file_reads_series_the_node_exports(metrics_text, metric):
    """A metric file that names a series nobody exports reads None for
    ever and nothing else would notice: hold each of its terms against
    a real /metrics page (the GET cell's primary method for
    `$primary_method`)."""
    import json

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "layer_metrics",
        f"{metric}.json")
    with open(path) as fh:
        spec = json.load(fh)
    assert spec["reader"] == "metrics_delta"
    terms = [t for side in ("num", "den")
             for t in spec["params"].get(side, []) if t != "seconds"]
    assert terms
    for t in terms:
        want = [f'{k}="{"GET" if v == "$primary_method" else v}"'
                for k, v in t.get("labels", {}).items()]
        hits = [line for line in metrics_text.splitlines()
                if line.split("{")[0].split(" ")[0] == t["series"]
                and all(w in line for w in want)]
        assert hits, (t, "not on /metrics")
