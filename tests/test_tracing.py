"""Span system: nesting, context propagation, RPC trace-id on the wire.

Ref parity: the reference's OTLP span topology
(src/rpc/rpc_helper.rs:172-190, src/api/s3/put.rs:395-452); here spans
land in tracer.ring / a JSONL file instead of a collector.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import sys
import tempfile

import pytest

from garage_tpu.utils import tracing
from garage_tpu.utils.tracing import span, tracer

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture()
def ring_tracer():
    tracer.enabled = True
    tracer.ring.clear()
    yield tracer
    tracer.enabled = False
    tracer.ring.clear()


def test_span_nesting_and_ids(ring_tracer):
    with span("outer", foo=1):
        with span("inner"):
            pass
    recs = list(tracer.ring)
    assert [r["name"] for r in recs] == ["inner", "outer"]
    inner, outer = recs
    assert inner["trace"] == outer["trace"]
    assert inner["parent"] == outer["span"]
    assert outer["parent"] is None
    assert outer["attrs"] == {"foo": 1}
    assert outer["dur_us"] >= inner["dur_us"]


def test_span_disabled_is_noop():
    tracer.enabled = False
    tracer.ring.clear()
    with span("nope"):
        pass
    assert not tracer.ring


def test_span_async_context_flows_across_tasks(ring_tracer):
    async def go():
        async with span("root"):
            async def child():
                with span("child"):
                    pass
            await asyncio.gather(child(), child())

    asyncio.run(go())
    recs = {r["name"]: r for r in tracer.ring}
    root = [r for r in tracer.ring if r["name"] == "root"][0]
    childs = [r for r in tracer.ring if r["name"] == "child"]
    assert len(childs) == 2
    assert all(c["trace"] == root["trace"] for c in childs)
    assert all(c["parent"] == root["span"] for c in childs)


def test_trace_id_propagates_over_rpc(ring_tracer):
    """A block put on a loopback cluster produces remote-side spans
    carrying the same trace id as the caller's root span."""
    import bench
    from garage_tpu.rpc import ReplicationMode
    from garage_tpu.utils.data import blake3sum

    async def go():
        tmp = tempfile.mkdtemp(prefix="gt_trace_")
        try:
            rm = ReplicationMode.parse(3, erasure="4,2")
            systems, managers, tasks = await bench._build_cluster(
                tmp, 6, rm, "off")
            data = os.urandom(1 << 18)
            h = blake3sum(data)
            async with span("test.root"):
                await managers[0].rpc_put_block(h, data)
            await bench._teardown(systems, managers, tasks)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    asyncio.run(go())
    recs = list(tracer.ring)
    root = [r for r in recs if r["name"] == "test.root"][0]
    same_trace = [r for r in recs if r["trace"] == root["trace"]]
    names = {r["name"] for r in same_trace}
    # caller side
    assert {"block.put", "block.encode", "block.write_shards",
            "rpc.call"} <= names
    # remote handler side: block.remote spans? the server-side write has
    # no span of its own, but the rpc.call spans from the caller and the
    # remote-context adoption are visible via at least k+m rpc.call spans
    assert sum(1 for r in same_trace if r["name"] == "rpc.call") >= 5


def test_jsonl_export(tmp_path, ring_tracer):
    path = str(tmp_path / "spans.jsonl")
    tracer.enable(path)
    with span("exported"):
        pass
    tracer.disable()
    tracer.enabled = True  # restore for fixture teardown symmetry
    import json

    lines = [json.loads(line) for line in open(path)]
    assert any(r["name"] == "exported" for r in lines)


def test_otlp_export_to_local_collector(tmp_path):
    """Spans ship to an OTLP/HTTP collector as valid OTLP JSON with
    wire-width ids (ref: garage/tracing_setup.rs init_tracing)."""
    import http.server
    import json
    import threading

    from garage_tpu.utils import otlp as otlp_mod
    from garage_tpu.utils.otlp import OtlpExporter
    from garage_tpu.utils.tracing import span, tracer

    received = []

    class Sink(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            body = self.rfile.read(int(self.headers["Content-Length"]))
            received.append((self.path, json.loads(body)))
            self.send_response(200)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), Sink)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        exp = OtlpExporter(f"http://127.0.0.1:{srv.server_port}",
                           "0011223344556677").start()
        was_enabled = tracer.enabled
        tracer.sinks.append(exp.sink)
        tracer.enabled = True
        try:
            with span("otlp.parent", table="objtest"):
                with span("otlp.child", size=123):
                    pass
                with span("otlp.bad"):
                    try:
                        raise ValueError("boom")
                    except ValueError:
                        pass
        finally:
            tracer.enabled = was_enabled
            tracer.sinks.remove(exp.sink)
        exp.stop()
        assert exp.sent_spans == 3 and exp.failed_posts == 0
        path, payload = received[0]
        assert path == "/v1/traces"
        rs = payload["resourceSpans"][0]
        res_attrs = {a["key"]: a["value"] for a in
                     rs["resource"]["attributes"]}
        assert res_attrs["service.name"]["stringValue"] == "garage"
        assert res_attrs["service.instance.id"]["stringValue"] \
            == "0011223344556677"
        spans = rs["scopeSpans"][0]["spans"]
        by_name = {s["name"]: s for s in spans}
        assert set(by_name) == {"otlp.parent", "otlp.child", "otlp.bad"}
        parent = by_name["otlp.parent"]
        child = by_name["otlp.child"]
        assert len(parent["traceId"]) == 32 and len(parent["spanId"]) == 16
        assert child["traceId"] == parent["traceId"]
        assert child["parentSpanId"] == parent["spanId"]
        assert int(child["endTimeUnixNano"]) >= int(
            child["startTimeUnixNano"])
        attrs = {a["key"]: a["value"] for a in child["attributes"]}
        assert attrs["size"]["intValue"] == "123"
    finally:
        srv.shutdown()


def test_otlp_collector_down_never_blocks(tmp_path):
    """A dead collector drops spans; emit() and stop() stay cheap."""
    from garage_tpu.utils.otlp import OtlpExporter

    exp = OtlpExporter("http://127.0.0.1:9", "00").start()  # discard port
    for i in range(10):
        exp.sink({"trace": "ab", "span": "cd", "parent": None,
                  "name": f"s{i}", "start_us": 1, "dur_us": 1})
    exp.stop()
    assert exp.sent_spans == 0 and exp.failed_posts >= 1


@pytest.mark.parametrize("work_s,spans", [(0.005, 1), (0.0001, 0)])
def test_background_work_item_span(ring_tracer, work_s, spans):
    """A background work item of 1 ms or more is one `bg.work` span with
    its worker id and what work() returned, a trace of its own though
    the worker was spawned inside a request; a shorter one is none."""
    import time

    from garage_tpu.utils import background

    class W(background.Worker):
        name = "sleeper"

        async def work(self):
            time.sleep(work_s)  # holds the loop, as a resync item does
            return background.WState.DONE

    async def main():
        runner = background.BackgroundRunner()
        async with span("http.request") as req:
            runner.spawn_worker(W())
        await asyncio.sleep(0.05)  # the worker runs its one item
        await runner.shutdown()
        return req.ids[0]

    request_trace = asyncio.run(main())
    got = [r for r in tracer.ring if r["name"] == "bg.work"]
    assert len(got) == spans
    for r in got:
        assert r["attrs"] == {"worker": "1:sleeper", "state": "done"}
        assert r["parent"] is None and r["trace"] != request_trace
        assert r["dur_us"] >= 1000
