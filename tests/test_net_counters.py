"""What a node's wire connections do, counted in net/conn.py on the one
`WireStats` its NetApp hands every channel: frames and their bytes each
way, frames the kernel took only in part, time parked in drain(), and a
message's wait in its connection's send queue (/metrics rpc_net_*).
LocalChannel frames are not wire frames and are not counted. Also the
benchmark's metric files that read these series and node_loop_turns."""

from __future__ import annotations

import asyncio
import json
import os
import socket
import struct

import pytest

from garage_tpu.net import LocalNetwork, NetApp
from garage_tpu.net import conn as conn_mod
from garage_tpu.net.conn import (MAX_CHUNK, Conn, PlainChannel,
                                 SecureChannel, WireStats, pack_body)
from garage_tpu.net.message import PRIO_HIGH, PRIO_NORMAL
from test_loop_profile import _bench

pytest.importorskip("cryptography")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NETID = b"test-cluster-secret"
KEY_AB, KEY_BA = b"a" * 32, b"b" * 32
TAG = 16  # ChaCha20-Poly1305


def run(coro, timeout=20.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def secure_record_len(payload: int) -> int:
    """[u32 ct_len][ct], ct = [u32 req_id][u32 field][payload] + tag."""
    return 4 + 8 + payload + TAG


async def _conn_pair(handler):
    """Two Conns over a real socketpair, each on a SecureChannel."""
    sa, sb = socket.socketpair()
    ra, wa = await asyncio.open_connection(sock=sa)
    rb, wb = await asyncio.open_connection(sock=sb)
    a = Conn(b"B" * 32, SecureChannel(ra, wa, KEY_AB, KEY_BA, WireStats()),
             handler, initiator=True)
    b = Conn(b"A" * 32, SecureChannel(rb, wb, KEY_BA, KEY_AB, WireStats()),
             handler, initiator=False)
    a.start()
    b.start()
    return a, b


def test_a_262145_byte_body_is_two_frames_on_the_wire():
    """A (4,2) shard's body leaves as a MAX_CHUNK frame and a tail: the
    sender counts two frames and the two records' wire lengths, the
    receiver the same two."""
    path = "test/shard"
    # the request's header as Conn.call packs it outside any span
    first = pack_body([path, PRIO_HIGH, False, None, {}, "data", None],
                      None)[0]
    blob = bytes(262_145 - len(first))

    async def handler(peer, p, prio, order, payload, stream):
        return {"n": len(payload["data"])}, None

    async def main():
        a, b = await _conn_pair(handler)
        try:
            sent0 = (a.wire.sent, a.wire.sent_bytes)
            resp, _ = await a.call(path, {"data": blob}, timeout=5)
            assert resp == {"n": len(blob)}
            return (a.wire.sent - sent0[0], a.wire.sent_bytes - sent0[1],
                    b.wire.recv, b.wire.recv_bytes, a.wire.queued)
        finally:
            await a.close()
            await b.close()

    frames, nbytes, recv, recv_bytes, queued = run(main())
    want = (secure_record_len(MAX_CHUNK)
            + secure_record_len(262_145 - MAX_CHUNK))
    assert (frames, nbytes) == (2, want)
    assert (recv, recv_bytes) == (2, want)
    assert queued == 1  # one message, whatever its frames


class _Transport:
    def __init__(self):
        self.left = 0

    def get_write_buffer_size(self) -> int:
        return self.left


class _SlowWriter:
    """A writer whose kernel, asked with an empty buffer, takes 1,000
    bytes less than the record; with bytes already buffered the record
    is appended whole, as asyncio's transport does. drain() parks 20 ms
    until the rest is out, or (`parks` False: the buffer is under its
    high-water mark) returns at once and leaves it."""

    def __init__(self, parks: bool = True):
        self.transport = _Transport()
        self.records: list[bytes] = []
        self.parks = parks

    def write(self, data) -> None:
        self.records.append(bytes(data))
        self.transport.left += len(data) if self.transport.left else 1000

    async def drain(self) -> None:
        if self.parks:
            await asyncio.sleep(0.02)
            self.transport.left = 0


def _channel(kind, w):
    return (PlainChannel(None, w, WireStats()) if kind == "plain"
            else SecureChannel(None, w, KEY_AB, KEY_BA, WireStats()))


@pytest.mark.parametrize("kind", ["plain", "secure"])
def test_a_partial_write_is_counted_and_the_drain_timed(kind):
    w = _SlowWriter()
    chan = _channel(kind, w)
    run(chan.send_frame(6, 0x80000003, [b"abc"]))
    (record,) = w.records
    st = chan.stats
    assert (st.sent, st.sent_bytes) == (1, len(record))
    assert (st.partial, st.partial_bytes) == (1, 1000)
    assert 0.015 <= st.drain_s < 1.0
    # what goes on the wire is as it was
    if kind == "plain":
        assert record == struct.pack("<III", 3 + 8, 6, 0x80000003) + b"abc"
    else:
        from cryptography.hazmat.primitives.ciphers.aead import \
            ChaCha20Poly1305
        (n,) = struct.unpack_from("<I", record)
        pt = ChaCha20Poly1305(KEY_AB).decrypt(bytes(12), record[4:], None)
        assert n == len(record) - 4
        assert pt == struct.pack("<II", 6, 0x80000003) + b"abc"


@pytest.mark.parametrize("kind", ["plain", "secure"])
def test_a_frame_appended_to_a_buffered_rest_is_no_partial_write(kind):
    """The second frame goes into a buffer that still holds the first
    one's rest: the kernel is not asked, so only the first counts, and
    its 1,000 bytes left are not counted again."""
    w = _SlowWriter(parks=False)
    chan = _channel(kind, w)

    async def main():
        await chan.send_frame(6, 0x80000003, [b"abc"])
        await chan.send_frame(8, 0x80000003, [b"defg"])

    run(main())
    st = chan.stats
    assert (st.sent, st.sent_bytes) == (2, sum(map(len, w.records)))
    assert (st.partial, st.partial_bytes) == (1, 1000)
    assert w.transport.left == 1000 + len(w.records[1])


class _ParkingChannel:
    """A channel whose first frame parks until released; the others go
    at once. Nothing ever arrives."""

    max_chunk = MAX_CHUNK

    def __init__(self):
        self.stats = WireStats()
        self.release = asyncio.Event()
        self.frames: list[int] = []

    async def send_frame(self, req_id, field, parts=()):
        self.frames.append(req_id)
        if len(self.frames) == 1:
            await self.release.wait()

    async def recv_frame(self):
        await asyncio.Event().wait()

    def close(self) -> None:
        pass


def test_a_message_queued_behind_a_parked_one_counts_its_wait():
    async def main():
        chan = _ParkingChannel()
        conn = Conn(b"P" * 32, chan, None, initiator=True)
        conn.start()
        try:
            first = conn.enqueue(2, PRIO_NORMAL, [b"first"])
            await asyncio.sleep(0.01)  # its frame is handed on, and parks
            assert chan.frames == [2] and conn.wire.queued == 1
            second = conn.enqueue(4, PRIO_NORMAL, [b"second"])
            conn._enqueue_ctl("cancel", 6)  # control frames: not counted
            await asyncio.sleep(0.05)
            chan.release.set()
            await asyncio.wait_for(asyncio.gather(first.done, second.done),
                                   5)
            await asyncio.sleep(0.01)
            return chan.frames, conn.wire.queued, conn.wire.queue_s
        finally:
            await conn.close()

    frames, queued, queue_s = run(main())
    assert sorted(frames) == [2, 4, 6]
    assert queued == 2
    assert 0.045 <= queue_s < 1.0  # the second waited out the park


def test_local_channel_frames_are_not_counted():
    async def main():
        net = LocalNetwork()
        a, b = NetApp(NETID), NetApp(NETID)
        net.register(a)
        net.register(b)

        async def echo(from_node, payload, stream):
            return {"n": len(payload["data"])}

        b.endpoint("test/echo").set_handler(echo)
        await a.try_connect(b.public_addr, b.id)
        resp, _ = await a.endpoint("test/echo").call(
            b.id, {"data": bytes(300_000)}, PRIO_NORMAL, timeout=5)
        assert resp == {"n": 300_000}
        return a, b

    a, b = run(main())
    assert all(c.wire is None for c in (*a.conns.values(), *b.conns.values()))
    for app in (a, b):
        assert all(getattr(app.wire, k) == 0 for k in WireStats.__slots__)


def _snap(st: WireStats) -> dict:
    return {k: getattr(st, k) for k in WireStats.__slots__}


@pytest.mark.parametrize("crypto", [True, False], ids=["secure", "plain"])
def test_tcp_node_counts_all_its_connections_on_one_wire_stats(
        crypto, monkeypatch):
    """Every channel a node builds, on either handshake, counts into its
    NetApp's one WireStats: two peers' connections add up, and what they
    did stays once they are closed."""
    monkeypatch.setattr(conn_mod, "HAVE_CRYPTO", crypto)

    async def main():
        a, b, c = (NetApp(NETID, bind_addr=("127.0.0.1", 0))
                   for _ in range(3))
        for app in (a, b, c):
            await app.listen()

        async def echo(from_node, payload, stream):
            return {"n": len(payload["data"])}

        for app in (b, c):
            app.endpoint("test/echo").set_handler(echo)
        try:
            for peer in (b, c):
                await a.try_connect(peer.bind_addr, peer.id)
                for _ in range(3):
                    await a.endpoint("test/echo").call(
                        peer.id, {"data": bytes(100_000)}, PRIO_NORMAL,
                        timeout=5)
            chans = {type(conn.chan) for conn in a.conns.values()}
            assert chans == {SecureChannel if crypto else PlainChannel}
            assert all(conn.wire is a.wire for conn in a.conns.values())
            live = _snap(a.wire)
        finally:
            for app in (a, b, c):
                await app.shutdown()
        await asyncio.sleep(0.05)  # the closed callbacks run
        return live, _snap(a.wire), _snap(b.wire), _snap(c.wire)

    live, gone, b, c = run(main())
    assert live["sent"] >= 6 and live["recv"] >= 6 and live["queued"] >= 6
    assert live["sent_bytes"] > 600_000
    # the connections are closed: what they did stays in the node's totals
    for k in WireStats.__slots__:
        assert gone[k] >= live[k], k
    # every byte sent arrived, at one peer or the other
    assert gone["sent_bytes"] == b["recv_bytes"] + c["recv_bytes"]
    assert gone["recv_bytes"] == b["sent_bytes"] + c["sent_bytes"]


# ---------------------------------------------------------------------------
# the metric files, on scrapes of the series as /metrics renders them
# ---------------------------------------------------------------------------

PUT_CELLS = ["ec42-put-mp16", "ec42-put-1stream", "rep3-put-mp16",
             "ec104-put-mp16", "ec42-put-zstd"]
# name -> (unit, better, layer, moves, workloads, value on SCRAPES)
NEW_METRICS = {
    "net_frames_per_block": ("count", "lower", "store", "put_MiBps",
                             PUT_CELLS, (1300 - 100) / (90 - 10)),
    "net_send_queue_ms": ("ms", "lower", "store", "req_p50_ms", None,
                          1000 * (0.9 - 0.1) / (400 - 80)),
    "net_partial_write_share": ("%", "lower", "store", "req_p50_ms", None,
                                100 * (70 - 10) / (1300 - 100)),
    "net_drain_ms": ("ms", "lower", "store", "req_p50_ms", None,
                     1000 * (0.5 - 0.2) / (1300 - 100)),
    "loop_turns_per_block": ("count", "lower", "node 1 interpreter",
                             "put_MiBps", PUT_CELLS,
                             (9000 - 1000) / (90 - 10)),
    "loop_turn_us": ("us", "lower", "node 1 interpreter", "req_p50_ms",
                     None, 1e6 * (12.0 - 4.0) / (9000 - 1000)),
    "net_frame_bytes": ("bytes", "higher", "store", "req_p50_ms", None,
                        ((10**8 - 10**6) + (6 * 10**7 - 5 * 10**5))
                        / ((1300 - 100) + (1200 - 90))),
    "net_partial_bytes_share": ("%", "lower", "store", "req_p50_ms", None,
                                100 * (80_000 - 20_000) / (10**8 - 10**6)),
}

SCRAPE = """\
block_write_seconds_count{{mode="erasure"}} {blocks}
node_cpu_seconds{{thread="all"}} {cpu_all}
node_cpu_seconds{{thread="loop"}} {cpu_loop}
"""
WIRE = """\
rpc_net_frames_count{{dir="send"}} {sent}
rpc_net_frames_sum{{dir="send"}} {sent_bytes}
rpc_net_frames_count{{dir="recv"}} {recv}
rpc_net_frames_sum{{dir="recv"}} {recv_bytes}
rpc_net_partial_writes_count {partial}
rpc_net_partial_writes_sum {partial_bytes}
rpc_net_drain_seconds_count {sent}
rpc_net_drain_seconds_sum {drain}
rpc_net_send_queue_seconds_count {queued}
rpc_net_send_queue_seconds_sum {queue_s}
"""
TURNS = "node_loop_turns {turns}\n"
BEFORE = dict(blocks=10, cpu_all=6.0, cpu_loop=4.0, sent=100,
              sent_bytes=10**6, recv=90, recv_bytes=5 * 10**5, partial=10,
              partial_bytes=20_000, drain=0.2, queued=80, queue_s=0.1,
              turns=1000)
AFTER = dict(blocks=90, cpu_all=20.0, cpu_loop=12.0, sent=1300,
             sent_bytes=10**8, recv=1200, recv_bytes=6 * 10**7, partial=70,
             partial_bytes=80_000, drain=0.5, queued=400, queue_s=0.9,
             turns=9000)


class _Ctx:
    primary_method = "PUT"

    def __init__(self, m0, m1):
        self.pair = (m0, m1)

    def scrapes(self, over):
        return (*self.pair, 20.0)


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_metric_file_reads_the_new_series(metric):
    """Each is data for `metrics_delta` with its entry in BENCHMARK.json,
    reads what the series say by hand, and reads nothing on a node
    without them: one that predates them, or an untraced node for the
    two that divide by loop turns."""
    unit, better, layer, moves, cells, want = NEW_METRICS[metric]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entries = json.load(f)["per_layer"]
    entry = {"name": metric, "unit": unit, "better": better,
             "source": "program_counter", "layer": layer, "moves": moves}
    if cells is not None:
        entry["workloads"] = cells
    assert next(e for e in entries if e["name"] == metric) == entry
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           f"{metric}.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "metrics_delta"
    scrape, reader = _bench("lib", "scrape"), _bench("readers",
                                                     "metrics_delta")

    def read(*parts):
        m0, m1 = (scrape.parse_metrics("".join(p.format(**v) for p in parts))
                  for v in (BEFORE, AFTER))
        return reader.read(spec["params"], _Ctx(m0, m1))

    assert read(SCRAPE, WIRE, TURNS) == pytest.approx(want)
    assert read(SCRAPE) is None  # the parent: no such series
    untraced = read(SCRAPE, WIRE)
    if metric.startswith("loop_"):
        assert untraced is None
    else:
        assert untraced == pytest.approx(want)
