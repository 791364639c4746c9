"""Transport layer tests: loopback mesh, TCP handshake, streams, ordering.

Mirrors the reference's net test (src/net/test.rs:15-118 — 3-node mesh
convergence) plus deterministic in-process coverage the reference lacks.
"""

import asyncio
import hashlib
import os
import random
import socket
import struct
import threading
from time import perf_counter

import pytest

from garage_tpu.net import LocalNetwork, NetApp, PeeringManager
from garage_tpu.net.conn import (MAX_CHUNK, Conn, PlainChannel,
                                 SecureChannel, WireStats)
from garage_tpu.net.message import PRIO_BACKGROUND, PRIO_HIGH, PRIO_NORMAL
from garage_tpu.net.stream import ByteStream
from garage_tpu.net.peering import PeerConnState
from garage_tpu.utils.error import RpcError

NETID = b"test-cluster-secret"


def run(coro, timeout=30.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def make_local_node(net: LocalNetwork) -> NetApp:
    app = NetApp(NETID)
    net.register(app)
    return app


def test_loopback_call_roundtrip():
    async def main():
        net = LocalNetwork()
        a, b = make_local_node(net), make_local_node(net)

        async def handler(from_node, payload, stream):
            assert from_node == a.id
            return {"echo": payload["x"] * 2}

        b.endpoint("test/echo").set_handler(handler)
        await a.try_connect(b.public_addr, b.id)
        resp, _ = await a.endpoint("test/echo").call(b.id, {"x": 21}, PRIO_NORMAL, timeout=5)
        assert resp == {"echo": 42}

    run(main())


def test_self_call_shortcircuits():
    async def main():
        net = LocalNetwork()
        a = make_local_node(net)
        a.endpoint("test/self").set_handler(lambda f, p, s: _async({"me": True}))
        resp, _ = await a.endpoint("test/self").call(a.id, {}, PRIO_NORMAL)
        assert resp == {"me": True}

    run(main())


async def _async(v):
    return v


def test_stream_attach_and_reply():
    async def main():
        net = LocalNetwork()
        a, b = make_local_node(net), make_local_node(net)
        body = bytes(range(256)) * 1000  # 256 KB, multiple chunks

        async def handler(from_node, payload, stream):
            data = await stream.read_all()
            return {"len": len(data)}, ByteStream.from_bytes(data[::-1])

        b.endpoint("test/stream").set_handler(handler)
        await a.try_connect(b.public_addr, b.id)
        resp, reply_stream = await a.endpoint("test/stream").call(
            b.id, {}, PRIO_NORMAL, stream=ByteStream.from_bytes(body), timeout=10
        )
        assert resp == {"len": len(body)}
        back = await reply_stream.read_all()
        assert back == body[::-1]

    run(main())


def test_handler_error_propagates():
    async def main():
        net = LocalNetwork()
        a, b = make_local_node(net), make_local_node(net)

        async def handler(from_node, payload, stream):
            raise ValueError("boom")

        b.endpoint("test/err").set_handler(handler)
        await a.try_connect(b.public_addr, b.id)
        with pytest.raises(RpcError, match="boom"):
            await a.endpoint("test/err").call(b.id, {}, PRIO_NORMAL, timeout=5)

    run(main())


def test_call_timeout_and_cancel():
    async def main():
        net = LocalNetwork()
        a, b = make_local_node(net), make_local_node(net)
        started = asyncio.Event()
        cancelled = asyncio.Event()

        async def handler(from_node, payload, stream):
            started.set()
            try:
                await asyncio.sleep(60)
            except asyncio.CancelledError:
                cancelled.set()
                raise

        b.endpoint("test/slow").set_handler(handler)
        await a.try_connect(b.public_addr, b.id)
        with pytest.raises(asyncio.TimeoutError):
            await a.endpoint("test/slow").call(b.id, {}, PRIO_NORMAL, timeout=0.2)
        await asyncio.wait_for(started.wait(), 5)
        # CANCEL frame must abort the remote handler
        await asyncio.wait_for(cancelled.wait(), 5)

    run(main())


def test_ordered_dispatch():
    async def main():
        net = LocalNetwork()
        a, b = make_local_node(net), make_local_node(net)
        seen = []

        async def handler(from_node, payload, stream):
            seen.append(payload["seq"])
            return {}

        b.endpoint("test/ordered").set_handler(handler)
        await a.try_connect(b.public_addr, b.id)
        sid = 77
        # fire seq 2, 1, 0 concurrently — handlers must run 0, 1, 2
        await asyncio.gather(
            *(
                a.endpoint("test/ordered").call(
                    b.id, {"seq": s}, PRIO_NORMAL, order=(sid, s), timeout=5
                )
                for s in (2, 1, 0)
            )
        )
        assert seen == [0, 1, 2]

    run(main())


def test_three_node_mesh_convergence():
    async def main():
        net = LocalNetwork()
        nodes = [make_local_node(net) for _ in range(3)]
        # nodes 1 and 2 only know node 0's address
        pms = []
        for i, app in enumerate(nodes):
            bootstrap = [] if i == 0 else [(nodes[0].public_addr, nodes[0].id)]
            pm = PeeringManager(app, bootstrap, ping_interval=0.2, ping_timeout=1.0, retry_interval=0.2)
            pms.append(pm)
        tasks = [asyncio.create_task(pm.run()) for pm in pms]
        try:
            deadline = asyncio.get_event_loop().time() + 15
            while asyncio.get_event_loop().time() < deadline:
                if all(
                    sum(
                        1
                        for p in pm.get_peer_list()
                        if p.state == PeerConnState.CONNECTED
                    )
                    == 2
                    for pm in pms
                ):
                    break
                await asyncio.sleep(0.1)
            for pm in pms:
                connected = [p for p in pm.get_peer_list() if p.state == PeerConnState.CONNECTED]
                assert len(connected) == 2, f"mesh did not converge: {pm.get_peer_list()}"
        finally:
            for pm in pms:
                await pm.stop()
            for t in tasks:
                t.cancel()

    run(main(), timeout=40)


def test_failure_detection_and_reconnect():
    async def main():
        net = LocalNetwork()
        a, b = make_local_node(net), make_local_node(net)
        pma = PeeringManager(a, [(b.public_addr, b.id)], ping_interval=0.1, ping_timeout=0.3, retry_interval=0.3)
        pmb = PeeringManager(b, [], ping_interval=0.1, ping_timeout=0.3, retry_interval=0.3)
        tasks = [asyncio.create_task(pma.run()), asyncio.create_task(pmb.run())]
        try:
            await _wait_for(lambda: a.is_connected(b.id), 10)
            net.partition(a.id, b.id)
            await _wait_for(lambda: not a.is_connected(b.id), 10)
            net.heal(a.id, b.id)
            await _wait_for(lambda: a.is_connected(b.id), 15)
        finally:
            await pma.stop()
            await pmb.stop()
            for t in tasks:
                t.cancel()

    run(main(), timeout=45)


async def _wait_for(cond, timeout):
    deadline = asyncio.get_event_loop().time() + timeout
    while asyncio.get_event_loop().time() < deadline:
        if cond():
            return
        await asyncio.sleep(0.05)
    raise AssertionError("condition not reached")


def test_tcp_transport_end_to_end():
    async def main():
        a = NetApp(NETID, bind_addr=("127.0.0.1", 0))
        b = NetApp(NETID, bind_addr=("127.0.0.1", 0))
        await a.listen()
        await b.listen()

        async def handler(from_node, payload, stream):
            extra = await stream.read_all() if stream else b""
            return {"sum": payload["x"] + payload["y"], "extra": len(extra)}

        b.endpoint("test/tcp").set_handler(handler)
        try:
            peer = await a.try_connect(b.bind_addr, b.id)
            assert peer == b.id
            resp, _ = await a.endpoint("test/tcp").call(
                b.id, {"x": 1, "y": 2}, PRIO_NORMAL,
                stream=ByteStream.from_bytes(b"z" * 100_000), timeout=10,
            )
            assert resp == {"sum": 3, "extra": 100_000}
        finally:
            await a.shutdown()
            await b.shutdown()

    run(main())


def test_tcp_wrong_netid_rejected():
    async def main():
        a = NetApp(b"cluster-one", bind_addr=("127.0.0.1", 0))
        b = NetApp(b"cluster-two", bind_addr=("127.0.0.1", 0))
        await b.listen()
        try:
            with pytest.raises(Exception):
                await a.try_connect(b.bind_addr, b.id)
        finally:
            await a.shutdown()
            await b.shutdown()

    run(main())


def test_stream_flow_control_bounds_buffering():
    """Receiver-side buffering must stay near STREAM_WINDOW even when the
    consumer is much slower than the producer (credit-based flow ctl)."""
    from garage_tpu.net.conn import STREAM_WINDOW

    async def main():
        net = LocalNetwork()
        a, b = make_local_node(net), make_local_node(net)
        high_water = 0
        done = asyncio.Event()

        async def handler(from_node, payload, stream):
            nonlocal high_water
            total = 0
            while True:
                await asyncio.sleep(0.001)  # slow consumer
                high_water = max(high_water, stream._size)
                chunk = await stream.read_chunk(1 << 16)
                if not chunk:
                    break
                total += len(chunk)
            done.set()
            return {"total": total}

        b.endpoint("test/flow").set_handler(handler)
        await a.try_connect(b.public_addr, b.id)

        async def producer():
            s = ByteStream()

            async def pump():
                for _ in range(24):  # 24 MiB total, 6x the window
                    await s.write(b"\x00" * (1 << 20))
                s.push_eof()

            asyncio.ensure_future(pump())
            return s

        src = await producer()
        resp, _ = await a.endpoint("test/flow").call(
            b.id, {}, PRIO_NORMAL, stream=src, timeout=60
        )
        assert resp == {"total": 24 << 20}
        assert high_water <= STREAM_WINDOW + (1 << 20), (
            f"receiver buffered {high_water} bytes, window is {STREAM_WINDOW}"
        )

    run(main(), timeout=90)


def test_ordered_cancel_does_not_stall_stream():
    """A cancelled seq must be tombstoned so later seqs still run."""

    async def main():
        net = LocalNetwork()
        a, b = make_local_node(net), make_local_node(net)
        release0 = asyncio.Event()
        ran = []

        async def handler(from_node, payload, stream):
            if payload["seq"] == 0:
                await release0.wait()
            ran.append(payload["seq"])
            return {}

        b.endpoint("test/ocancel").set_handler(handler)
        await a.try_connect(b.public_addr, b.id)
        sid = 99
        t0 = asyncio.ensure_future(
            a.endpoint("test/ocancel").call(b.id, {"seq": 0}, PRIO_NORMAL, order=(sid, 0), timeout=30)
        )
        await asyncio.sleep(0.05)
        # seq 1 times out while gated behind seq 0
        with pytest.raises(asyncio.TimeoutError):
            await a.endpoint("test/ocancel").call(b.id, {"seq": 1}, PRIO_NORMAL, order=(sid, 1), timeout=0.2)
        release0.set()
        await t0
        # seq 2 must still be dispatched despite the dead seq 1
        await a.endpoint("test/ocancel").call(b.id, {"seq": 2}, PRIO_NORMAL, order=(sid, 2), timeout=5)
        assert 0 in ran and 2 in ran

    run(main(), timeout=60)


def test_shutdown_closes_connections_registered_mid_shutdown():
    """GL12 regression (ISSUE 14): shutdown() used to close a SNAPSHOT
    of conns and then clear() the map — a connection _register()ed
    while an earlier close() awaited survived the snapshot and was
    dropped from the map WITHOUT being closed (leaked socket, the peer
    kept a half-open channel). The pop-then-close loop drains late
    registrations too."""
    async def main():
        net = LocalNetwork()
        a = make_local_node(net)

        class FakeConn:
            def __init__(self):
                self.closed_flag = False

            async def close(self):
                self.closed_flag = True

        late = FakeConn()

        class SlowConn(FakeConn):
            async def close(self):
                # while this close() awaits, a peer's connect lands
                await asyncio.sleep(0)
                a.conns[b"late-peer"] = late
                self.closed_flag = True

        slow = SlowConn()
        a.conns[b"slow-peer"] = slow
        await a.shutdown()
        assert slow.closed_flag
        assert late.closed_flag, "late-registered conn leaked by shutdown"
        assert not a.conns

    run(main())


# ---------------------------------------------------------------------------
# the sender thread: a TCP channel's records are written by a thread the
# channel owns, in the order the send loop appended them
# ---------------------------------------------------------------------------

KINDS = ["plain", "secure"]
KEY_AB, KEY_BA = b"a" * 32, b"b" * 32


def _chan(kind, reader, writer, keys=(KEY_AB, KEY_BA), stats=None,
          peer=b""):
    stats = stats if stats is not None else WireStats()
    if kind == "plain":
        return PlainChannel(reader, writer, stats, peer)
    if kind == "secure":
        pytest.importorskip("cryptography")
        return SecureChannel(reader, writer, *keys, stats, peer)
    raise ValueError(kind)


def _small_buffers(*socks) -> None:
    """The kernel's least buffers, so a record of MAX_CHUNK never fits."""
    for s in socks:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
        s.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)


async def _conn_pair(kind, handler):
    """Two Conns over a real socketpair, each on a channel of `kind`."""
    sa, sb = socket.socketpair()
    ra, wa = await asyncio.open_connection(sock=sa)
    rb, wb = await asyncio.open_connection(sock=sb)
    a = Conn(b"B" * 32, _chan(kind, ra, wa, (KEY_AB, KEY_BA),
                              peer=os.urandom(32)), handler, initiator=True)
    b = Conn(b"A" * 32, _chan(kind, rb, wb, (KEY_BA, KEY_AB),
                              peer=os.urandom(32)), handler, initiator=False)
    a.start()
    b.start()
    return a, b


class _Reader(threading.Thread):
    """Reads a raw socket until EOF or stop(), keeping what came."""

    def __init__(self, sock):
        super().__init__(daemon=True)
        self.sock, self.got, self.halted = sock, bytearray(), False
        sock.settimeout(0.05)

    def run(self):
        while True:
            try:
                chunk = self.sock.recv(1 << 20)
            except TimeoutError:
                if self.halted:
                    return
                continue
            if not chunk:
                return
            self.got += chunk

    def stop(self):
        self.halted = True
        self.join(5)
        assert not self.is_alive()


async def _echo(peer, path, prio, order, payload, stream):
    if stream is not None:
        data = await stream.read_all()
        return {"h": hashlib.blake2b(data).digest()}, None
    return {"h": hashlib.blake2b(payload["data"]).digest(),
            "i": payload.get("i")}, None


@pytest.mark.parametrize("kind", KINDS)
def test_the_senders_keep_every_record_in_order(kind):
    """Both ways at once: many concurrent messages of mixed priority, a
    streamed body larger than the stream window (so CREDIT frames flow
    back), and a CANCEL all arrive intact through the sender threads,
    and every record sent was read, in nonce order where there is one
    (a record out of order would fail its tag and the connection)."""
    rng = random.Random(45)
    cancelled = asyncio.Event()

    async def handler(peer, path, prio, order, payload, stream):
        if path == "slow":
            try:
                await asyncio.sleep(60)
            except asyncio.CancelledError:
                cancelled.set()
                raise
        return await _echo(peer, path, prio, order, payload, stream)

    async def main():
        a, b = await _conn_pair(kind, handler)
        try:
            body = rng.randbytes(6 << 20)
            slow = asyncio.ensure_future(a.call("slow", {}, timeout=0.3))
            streamed = asyncio.ensure_future(a.call(
                "stream", {}, stream=ByteStream.from_bytes(body),
                timeout=20))
            datas = [rng.randbytes(rng.choice([0, 100, 5000, 262_145,
                                               700_000]))
                     for _ in range(40)]
            calls = [(a if i % 3 else b).call(
                "echo", {"data": d, "i": i},
                prio=rng.choice([PRIO_HIGH, PRIO_NORMAL, PRIO_BACKGROUND]),
                timeout=20) for i, d in enumerate(datas)]
            results = await asyncio.gather(*calls)
            for i, (d, (resp, _)) in enumerate(zip(datas, results)):
                assert resp == {"h": hashlib.blake2b(d).digest(), "i": i}
            resp, _ = await streamed
            assert resp == {"h": hashlib.blake2b(body).digest()}
            with pytest.raises(asyncio.TimeoutError):
                await slow
            await asyncio.wait_for(cancelled.wait(), 5)
            await asyncio.sleep(0.05)
            for x, y in ((a, b), (b, a)):
                assert x.wire.sender_frames == x.wire.sent == y.wire.recv
                assert x.wire.sent_bytes == y.wire.recv_bytes
                if kind == "secure":
                    assert x.chan._send_ctr == y.chan._recv_ctr \
                        == x.wire.sent
        finally:
            await a.close()
            await b.close()

    run(main())


@pytest.mark.parametrize("kind", KINDS)
def test_a_peer_that_stops_reading_stalls_only_its_own_connection(kind):
    """The stalled peer's sender fills its queue and that connection's
    send loop parks; calls to a second peer keep flowing meanwhile. When
    the stalled peer reads again the park ends and is counted."""

    async def main():
        s, peer = socket.socketpair()
        _small_buffers(s, peer)
        r, w = await asyncio.open_connection(sock=s)
        stalled = Conn(b"S" * 32, _chan(kind, r, w, peer=os.urandom(32)),
                       None, initiator=True)
        stalled.start()
        a, b = await _conn_pair(kind, _echo)
        reader = _Reader(peer)
        try:
            item = stalled.enqueue(2, PRIO_NORMAL, [bytes(4 << 20)])
            sender = stalled.chan.sender
            await _wait_for(lambda: sender._room is not None, 5)
            for i in range(5):
                resp, _ = await a.call("echo", {"data": bytes(300_000),
                                                "i": i}, timeout=5)
                assert resp["i"] == i
            assert not item.done.done()
            assert stalled.wire.drain_s == 0.0  # still parked
            assert a.wire.drain_s == 0.0
            reader.start()
            await asyncio.wait_for(item.done, 10)
            await _wait_for(lambda: stalled.wire.sender_frames
                            == stalled.wire.sent, 10)
            assert stalled.wire.drain_s > 0.0
            assert len(reader.got) == stalled.wire.sent_bytes
        finally:
            await stalled.close()
            await a.close()
            await b.close()
            if reader.is_alive():
                reader.stop()
            peer.close()

    run(main())


@pytest.mark.parametrize("kind", KINDS)
def test_a_peer_reset_mid_frame_fails_the_connections_calls(kind):
    """A TCP peer that resets while a record is half written: the call
    in flight fails as a lost connection, as with a failed write."""

    async def main():
        lsock = socket.create_server(("127.0.0.1", 0))
        csock = socket.create_connection(lsock.getsockname())
        ssock, _ = lsock.accept()
        lsock.close()
        _small_buffers(csock, ssock)
        r, w = await asyncio.open_connection(sock=csock)
        conn = Conn(b"R" * 32, _chan(kind, r, w, peer=os.urandom(32)),
                    None, initiator=True)
        conn.start()
        sender = conn.chan.sender
        try:
            call = asyncio.ensure_future(
                conn.call("x", {"data": bytes(8 << 20)}, timeout=10))
            await _wait_for(lambda: conn.wire.partial > 0, 5)  # mid-frame
            ssock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                             struct.pack("ii", 1, 0))
            ssock.close()  # RST
            with pytest.raises(RpcError, match="^connection lost: "):
                await call
            assert conn.closed.done()
            await asyncio.to_thread(sender.thread.join, 5)
            assert not sender.thread.is_alive()
        finally:
            await conn.close()

    run(main())


@pytest.mark.parametrize("kind", KINDS)
def test_a_send_error_reaches_the_loop_and_fails_the_connection(kind):
    """With no receive loop to notice the reset, the sender's own
    error is what fails the connection: one callback on the loop, the
    pending reply failed, and the next record refused with it."""

    async def main():
        lsock = socket.create_server(("127.0.0.1", 0))
        csock = socket.create_connection(lsock.getsockname())
        ssock, _ = lsock.accept()
        lsock.close()
        _small_buffers(csock, ssock)
        r, w = await asyncio.open_connection(sock=csock)
        conn = Conn(b"E" * 32, _chan(kind, r, w, peer=os.urandom(32)),
                    None, initiator=True)  # not started: no recv loop
        waiter = asyncio.get_running_loop().create_future()
        conn._reply_waiters[2] = waiter
        chan = conn.chan
        try:
            for i in range(4):  # under the bound: none parks
                await chan.send_frame(2, 0, [bytes(MAX_CHUNK)])
            await _wait_for(lambda: conn.wire.partial > 0, 5)
            ssock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                             struct.pack("ii", 1, 0))
            ssock.close()
            await _wait_for(conn.closed.done, 5)
            exc = conn.closed.result()
            assert isinstance(exc, (ConnectionResetError, BrokenPipeError))
            with pytest.raises(RpcError, match="^connection lost: "):
                await waiter
            with pytest.raises(type(exc)):
                await chan.send_frame(4, 0, [b"late"])
            await asyncio.to_thread(chan.sender.thread.join, 5)
            assert not chan.sender.thread.is_alive()
            assert chan.sender._sock.fileno() == -1
        finally:
            await conn.close()

    run(main())


@pytest.mark.parametrize("kind", KINDS)
def test_close_returns_a_sender_stuck_on_a_full_buffer(kind):
    """close() with a record stuck in a full buffer returns at once;
    the thread ends and closes its duplicate of the socket, no thread
    named for the connection is left, and the peer reads EOF."""

    async def main():
        s, peer = socket.socketpair()
        _small_buffers(s, peer)
        r, w = await asyncio.open_connection(sock=s)
        chan = _chan(kind, r, w, peer=os.urandom(32))
        sender = chan.sender
        name = sender.thread.name
        try:
            await chan.send_frame(2, 0, [bytes(MAX_CHUNK)])
            await _wait_for(lambda: chan.stats.partial == 1, 5)
            assert name in {t.name for t in threading.enumerate()}
            t0 = perf_counter()
            chan.close()
            assert perf_counter() - t0 < 0.1
            await asyncio.to_thread(sender.thread.join, 5)
            assert not sender.thread.is_alive()
            assert sender._sock.fileno() == -1
            assert name not in {t.name for t in threading.enumerate()}
            reader = _Reader(peer)
            reader.start()
            await asyncio.to_thread(reader.join, 5)
            assert not reader.is_alive()  # it read EOF
            assert 0 < len(reader.got) < MAX_CHUNK
            assert chan.stats.sender_frames == 0
        finally:
            chan.close()
            peer.close()

    run(main())


_SIGPIPE_SCRIPT = """
import asyncio, signal, socket, sys
signal.signal(signal.SIGPIPE, signal.SIG_DFL)  # as the CLI has it
from garage_tpu.net import conn
if len(sys.argv) > 1:
    conn._NOSIGNAL = 0
async def main():
    s, peer = socket.socketpair()
    r, w = await asyncio.open_connection(sock=s)
    chan = conn.PlainChannel(r, w, conn.WireStats())
    errors = []
    chan.sender.on_error = errors.append
    peer.close()
    await chan.send_frame(2, 0, [bytes(100_000)])
    for _ in range(500):
        if errors:
            break
        await asyncio.sleep(0.01)
    chan.close()
    print(type(errors[0]).__name__)
asyncio.run(main())
"""


def test_a_send_to_a_closed_peer_kills_no_process():
    """The CLI gives SIGPIPE its default action; a sender thread's send
    to a peer that is gone fails with EPIPE and raises no signal."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", _SIGPIPE_SCRIPT], cwd=repo,
                       capture_output=True, text=True, timeout=60,
                       env=dict(os.environ, PYTHONPATH=repo))
    assert (r.returncode, r.stdout.strip()) == (0, "BrokenPipeError"), \
        r.stderr
