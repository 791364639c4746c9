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
from time import perf_counter

import pytest

from garage_tpu.net import LocalNetwork, NetApp
from garage_tpu.net import conn as conn_mod
from garage_tpu.net.conn import (MAX_CHUNK, SEND_QUEUE_BYTES, Conn,
                                 PlainChannel, SecureChannel, WireStats,
                                 pack_body)
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


def _small_buffers(*socks) -> None:
    """The kernel's least buffers, so a record of MAX_CHUNK never fits."""
    for s in socks:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)


async def _stalled_channel(kind):
    """A channel over a socketpair with the least buffers, and its peer's
    raw socket, which nobody reads until the test says."""
    s, peer = socket.socketpair()
    _small_buffers(s, peer)
    r, w = await asyncio.open_connection(sock=s)
    chan = (PlainChannel(r, w, WireStats()) if kind == "plain"
            else SecureChannel(r, w, KEY_AB, KEY_BA, WireStats()))
    return chan, peer


async def _read_all(peer, nbytes: int) -> bytes:
    def read():
        peer.settimeout(10)
        got = bytearray()
        while len(got) < nbytes:
            chunk = peer.recv(1 << 20)
            assert chunk, "EOF before every record"
            got += chunk
        return bytes(got)

    return await asyncio.to_thread(read)


def _records(kind, wire: bytes) -> list:
    """The (req_id, field, payload) of each record on the wire, in order;
    a secure record is opened with the nonce that order gives it."""
    out, off, ctr = [], 0, 0
    while off < len(wire):
        (n,) = struct.unpack_from("<I", wire, off)
        rec = wire[off + 4:off + 4 + n]
        off += 4 + n
        if kind == "secure":
            from cryptography.hazmat.primitives.ciphers.aead import \
                ChaCha20Poly1305
            rec = ChaCha20Poly1305(KEY_AB).decrypt(
                ctr.to_bytes(12, "little"), rec, None)
            ctr += 1
        req_id, field = struct.unpack_from("<II", rec)
        out.append((req_id, field, rec[8:]))
    return out


async def _until(cond, timeout=5.0):
    deadline = asyncio.get_running_loop().time() + timeout
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, "timed out"
        await asyncio.sleep(0.005)


@pytest.mark.parametrize("kind", ["plain", "secure"])
def test_a_partial_write_is_counted_and_the_drain_timed(kind):
    """Records larger than the socket's buffer, to a peer that reads
    only after 30 ms: the first one's first send is a partial write,
    counted with the bytes it left; the send loop parks once the sender
    holds more than SEND_QUEUE_BYTES, and the park is timed; what goes
    on the wire is as it was, in order."""
    n = SEND_QUEUE_BYTES // (MAX_CHUNK + 64) + 2

    async def main():
        chan, peer = await _stalled_channel(kind)

        async def send_loop():  # one writer, as Conn._send_loop is
            for i in range(n):
                await chan.send_frame(2 * i, 0x80000000 | MAX_CHUNK,
                                      [bytes([i]) * MAX_CHUNK])

        try:
            send = asyncio.ensure_future(send_loop())
            await asyncio.sleep(0.03)
            assert not send.done()  # parked for room
            wire = await _read_all(peer, n * (
                secure_record_len(MAX_CHUNK) if kind == "secure"
                else 12 + MAX_CHUNK))
            await send
            await _until(lambda: chan.stats.sender_frames == n)
            return wire, chan.stats
        finally:
            chan.close()
            peer.close()

    wire, st = run(main())
    assert (st.sent, st.sent_bytes) == (n, len(wire))
    # the first went before the peer read at all; later ones may go whole
    # while the peer reads beside the send
    assert 1 <= st.partial <= n
    assert 0 < st.partial_bytes < st.sent_bytes
    assert 0.015 <= st.drain_s < 5.0
    assert st.sender_frames == n and st.sender_lag_s > 0.015
    assert _records(kind, wire) == [
        (2 * i, 0x80000000 | MAX_CHUNK, bytes([i]) * MAX_CHUNK)
        for i in range(n)]
    if kind == "plain":
        assert wire[:12] == struct.pack("<III", MAX_CHUNK + 8, 0,
                                        0x80000000 | MAX_CHUNK)
    else:
        assert struct.unpack_from("<I", wire)[0] == 8 + MAX_CHUNK + TAG


@pytest.mark.parametrize("kind", ["plain", "secure"])
def test_a_frame_appended_to_a_buffered_rest_is_no_partial_write(kind):
    """The second record is appended while the first one's rest still
    waits for room: the first counts once, though it took many sends,
    and the second, sent whole by its first send, not at all."""

    async def main():
        chan, peer = await _stalled_channel(kind)
        try:
            await chan.send_frame(6, 0x80000003, [bytes(MAX_CHUNK)])
            await _until(lambda: chan.stats.partial == 1)
            await asyncio.sleep(0.01)
            await chan.send_frame(8, 0x80000003, [b"defg"])
            wire = await _read_all(peer, chan.stats.sent_bytes)
            await _until(lambda: chan.stats.sender_frames == 2)
            return wire, chan.stats
        finally:
            chan.close()
            peer.close()

    wire, st = run(main())
    assert (st.sent, st.sent_bytes) == (2, len(wire))
    first = len(wire) - (secure_record_len(4) if kind == "secure" else 16)
    assert st.partial == 1 and 0 < st.partial_bytes < first
    assert st.drain_s == 0.0  # two records: far under the bound
    assert [r[:2] for r in _records(kind, wire)] == [(6, 0x80000003),
                                                      (8, 0x80000003)]


@pytest.mark.parametrize("kind", ["plain", "secure"])
def test_a_message_queued_behind_a_parked_one_counts_its_wait(kind):
    """A message that fills the sender's queue parks the send loop; a
    second one, enqueued meanwhile, waits out the park before its first
    frame goes (a control frame is not counted)."""

    async def main():
        chan, peer = await _stalled_channel(kind)
        conn = Conn(b"P" * 32, chan, None, initiator=True)
        conn.start()
        try:
            first = conn.enqueue(2, PRIO_NORMAL, [bytes(3 << 20)])
            await _until(lambda: chan.sender._room is not None)
            assert conn.wire.queued == 1
            second = conn.enqueue(4, PRIO_NORMAL, [b"second"])
            conn._enqueue_ctl("cancel", 6)  # control frames: not counted
            await asyncio.sleep(0.05)
            assert conn.wire.queued == 1  # the second still waits
            read = asyncio.ensure_future(_read_all(peer, 3 << 20))
            await asyncio.wait_for(asyncio.gather(first.done, second.done),
                                   10)
            await read
            return conn.wire.queued, conn.wire.queue_s, conn.wire.drain_s
        finally:
            await conn.close()
            peer.close()

    queued, queue_s, drain_s = run(main())
    assert queued == 2
    assert 0.045 <= queue_s < 5.0  # the second waited out the park
    assert drain_s >= 0.045


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
            senders = [conn.chan.sender for app in (a, b, c)
                       for conn in app.conns.values()]
            for app in (a, b, c):
                await app.shutdown()
        await asyncio.sleep(0.05)  # the closed callbacks run
        # a stopped node leaves no sender thread and no descriptor
        for s in senders:
            await asyncio.to_thread(s.thread.join, 5)
        assert len(senders) == 4
        assert not any(s.thread.is_alive() for s in senders)
        assert all(s._sock.fileno() == -1 for s in senders)
        return live, _snap(a.wire), _snap(b.wire), _snap(c.wire)

    live, gone, b, c = run(main())
    assert live["sent"] >= 6 and live["recv"] >= 6 and live["queued"] >= 6
    assert live["sent_bytes"] > 600_000
    # the connections are closed: what they did stays in the node's totals
    for k in WireStats.__slots__:
        assert gone[k] >= live[k], k
    # every frame handed on was written by a sender thread
    assert live["sender_frames"] >= 6 and live["sender_lag_s"] > 0
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
    "net_offload_share": ("%", "higher", "store", "req_p50_ms", None,
                          100 * (1298 - 99) / (1300 - 100)),
    "net_sender_lag_ms": ("ms", "lower", "store", "req_p50_ms", None,
                          1000 * (0.7 - 0.05) / (1298 - 99)),
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
rpc_net_sender_frames_count {sender}
rpc_net_sender_lag_seconds_count {sender}
rpc_net_sender_lag_seconds_sum {lag}
"""
TURNS = "node_loop_turns {turns}\n"
BEFORE = dict(blocks=10, cpu_all=6.0, cpu_loop=4.0, sent=100,
              sent_bytes=10**6, recv=90, recv_bytes=5 * 10**5, partial=10,
              partial_bytes=20_000, drain=0.2, queued=80, queue_s=0.1,
              turns=1000, sender=99, lag=0.05)
AFTER = dict(blocks=90, cpu_all=20.0, cpu_loop=12.0, sent=1300,
             sent_bytes=10**8, recv=1200, recv_bytes=6 * 10**7, partial=70,
             partial_bytes=80_000, drain=0.5, queued=400, queue_s=0.9,
             turns=9000, sender=1298, lag=0.7)


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


def test_sender_threads_lose_no_count_on_one_wire_stats():
    """Sixteen sender threads add to one node's WireStats at once, with
    the interpreter switching threads as often as it can: no record and
    no second of lag is lost to a race."""
    import sys
    import threading

    async def main():
        stats = WireStats()
        pairs = [socket.socketpair() for _ in range(16)]
        chans, readers = [], []
        for s, peer in pairs:
            r, w = await asyncio.open_connection(sock=s)
            chans.append(PlainChannel(r, w, stats))

            def drain(peer=peer):
                while peer.recv(1 << 16):
                    pass

            readers.append(threading.Thread(target=drain, daemon=True))
            readers[-1].start()

        async def one(chan):
            for i in range(50):
                await chan.send_frame(i, 0, [bytes(3000)])

        try:
            await asyncio.gather(*(one(c) for c in chans))
            await _until(lambda: stats.sender_frames == 16 * 50, 10)
        finally:
            for c in chans:
                c.close()
        for t in readers:
            await asyncio.to_thread(t.join, 5)
            assert not t.is_alive()
        for _, peer in pairs:
            peer.close()
        return stats

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        st = run(main())
    finally:
        sys.setswitchinterval(old)
    assert st.sent == st.sender_frames == 16 * 50
    assert st.sent_bytes == 16 * 50 * (12 + 3000)
    assert st.sender_lag_s > 0


class _ScriptedSocket:
    """Takes the bytes a script says, one entry a `sendmsg`."""

    def __init__(self, takes):
        self.takes, self.wire = list(takes), bytearray()

    def sendmsg(self, bufs, ancdata=(), flags=0):
        n = self.takes.pop(0)
        self.wire += b"".join(bytes(b) for b in bufs)[:n]
        return n


@pytest.mark.parametrize("takes, partial, partial_bytes", [
    ([180], 0, 0),  # all three records in one send
    ([100, 80], 0, 0),  # a send ends on a record's boundary
    ([120, 10, 50], 1, 30),  # B's first send takes 20 of 50
    ([60, 40, 65, 15], 2, 40 + 15),  # A, then C's first send, short
])
def test_a_batch_counts_the_record_its_send_ended_inside(
        takes, partial, partial_bytes):
    """One `sendmsg` carries every record waiting; a record counts as a
    partial write once, when the send that took its first bytes did not
    take its last, and each record's lag is counted when it is whole."""
    records = [(b"a" * 10, b"A" * 90), (b"b" * 10, b"B" * 40),
               (b"c" * 10, b"C" * 20)]

    async def main():
        s, peer = socket.socketpair()
        r, w = await asyncio.open_connection(sock=s)
        chan = PlainChannel(r, w, WireStats())
        real, fake = chan.sender._sock, _ScriptedSocket(takes)
        try:
            chan.sender._sock = fake  # the thread is idle: nothing queued
            lag = chan.sender._write([(h, b, perf_counter())
                                      for h, b in records])
        finally:
            chan.sender._sock = real
            chan.close()
            peer.close()
        return lag, fake, chan.stats

    lag, fake, st = run(main())
    assert not fake.takes  # every send the script allows, no more
    assert bytes(fake.wire) == b"".join(h + b for h, b in records)
    assert (st.partial, st.partial_bytes) == (partial, partial_bytes)
    assert lag > 0
