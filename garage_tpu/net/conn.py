"""Authenticated channel + multiplexed, priority-scheduled framing.

Ref parity: src/net/{client,server,send,recv}.rs. Same wire concepts —
version-tag handshake gate, cluster-secret check, mutual public-key auth,
chunked frames `[u32 request_id][u16 flags|len][bytes]` with a max chunk
size, per-priority round-robin between in-flight streams, CANCEL frames —
rebuilt for asyncio, plus per-stream credit flow control (the reference
gets backpressure from its poll-driven scheduler; an asyncio push model
needs explicit credits or slow consumers buffer whole transfers).

Crypto: the reference uses the Secret-Handshake protocol + BoxStream
(kuska). Here: ed25519 identity keys sign a transcript that includes
X25519 ephemerals and an HMAC over the cluster `netid` (the shared
secret gate), then both directions run ChaCha20-Poly1305 with counter
nonces — same properties (mutual auth, cluster gate, confidentiality,
forward secrecy) with standard primitives from `cryptography`.

Frame flags (in the u32 len field):
  0x80000000 CONTINUES — more chunks follow for this section
  0x40000000 ERROR     — section is an error payload
  0x20000000 STREAM    — chunk belongs to the attached byte stream
  len = field & 0x0FFFFFFF, <= the channel's max_chunk
  field == 0xFFFFFFFF  — CANCEL marker for this request id
  field == 0xFFFFFFFE  — CREDIT grant; payload = u32 additional window

Body section layout (v3): [u32 hlen][msgpack header][raw blob bytes].
The header's last element is a blob key: when a request/reply payload
is a dict with one large bytes value (a block/shard), that value rides
OUTSIDE msgpack as the raw tail of the body and is re-attached on
receive. Together with scatter-gather frames (channels accept lists of
buffers; LocalChannel passes them through untouched) this removes ~5
full-payload copies per block RPC vs msgpack-embedding the bytes
(r4 profile: the copies were a top-3 cost on the PUT path).

Concurrency invariant: ALL outgoing records flow through _send_loop (the
single writer), which builds, encrypts and numbers them on the event
loop — the AEAD nonce counter and frame ordering both depend on it.
CANCEL/CREDIT are enqueued control items, never written directly. Each
TCP channel's records are then written to its socket by one sender
thread that the channel owns (`_Sender`), strictly in the order they
were appended; the loop's thread makes no `send` for a wire frame.

What a node's wire connections do is counted in plain integers on one
`WireStats` that its NetApp hands to every channel it builds: frames
and their bytes each way (counted on the loop when a record is
appended), records the kernel took in part, time the send loop parked
for room in its sender's queue, how long a message waited in the send
queue before its first frame went, and, counted by the sender threads
under one lock, the records they wrote and each one's lag from append
to its last byte taken by the kernel. /metrics renders it as
`rpc_net_*`. LocalChannel frames are no wire frames and are not
counted.
"""

from __future__ import annotations

import asyncio
import collections
import hashlib
import hmac as hmac_mod
import itertools
import logging
import os
import select
import socket
import struct
import threading
from time import perf_counter
from typing import Awaitable, Callable, Optional

try:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
        Ed25519PublicKey,
    )
    from cryptography.hazmat.primitives.asymmetric.x25519 import (
        X25519PrivateKey,
        X25519PublicKey,
    )
    from cryptography.hazmat.primitives.ciphers.aead import ChaCha20Poly1305
    HAVE_CRYPTO = True
except ModuleNotFoundError:
    # bare image: the in-process LocalChannel transport (net/local.py)
    # carries no crypto and keeps working; only the TCP SecureChannel
    # needs these wheels, and its handshakes refuse with a clear error
    HAVE_CRYPTO = False
    Ed25519PrivateKey = Ed25519PublicKey = None
    X25519PrivateKey = X25519PublicKey = ChaCha20Poly1305 = None

from ..chaos import injector as _chaos
from ..utils.error import RpcError
from .message import PRIO_HIGH, pack, unpack
from .stream import ByteStream

log = logging.getLogger("garage_tpu.net")

MAGIC = b"GRGTPU\x04\x00"  # protocol version gate (ref: net/netapp.rs:35-40)
# distinct magic for the no-crypto fallback wire: a crypto-equipped
# node and a bare one REJECT each other's hellos instead of silently
# downgrading the whole cluster to plaintext
PLAIN_MAGIC = b"GRGTPP\x04\x00"
# 256 KiB chunks on TCP: per-chunk costs (AEAD pass + header + writer
# wakeup) were the dominant CPU on the block path at the reference-style
# ~8 KiB (a 1.5 MiB shard transfer = ~190 chunks); at ~1 ms
# serialization per chunk the priority round-robin still keeps pings
# fresh. The in-process LocalChannel has no serialization cost, so it
# takes whole messages in one frame (local.py sets max_chunk huge).
MAX_CHUNK = 0x3FFF0
F_CONT = 0x80000000
F_ERROR = 0x40000000
F_STREAM = 0x20000000
LEN_MASK = 0x0FFFFFFF
CANCEL = 0xFFFFFFFF
CREDIT = 0xFFFFFFFE

# payload-dict values at least this big ride as a raw blob instead of
# being embedded in msgpack (saves a serialize + parse copy per side)
BLOB_MIN = 4096


def split_blob(payload):
    """-> (payload_without_blob, blob_key|None, blob|None). The largest
    qualifying bytes value of a dict payload is hoisted out of msgpack.
    Never mutates the caller's dict."""
    if type(payload) is dict:
        best_k, best = None, BLOB_MIN - 1
        for k, v in payload.items():
            if isinstance(v, (bytes, bytearray, memoryview)) \
                    and len(v) > best:
                best_k, best = k, len(v)
        if best_k is not None:
            rest = {k: v for k, v in payload.items() if k != best_k}
            return rest, best_k, payload[best_k]
    return payload, None, None


def _attach_blob(payload, blob_key, blob):
    """Re-attach a hoisted blob value into its dict payload."""
    if blob_key is not None and type(payload) is dict:
        payload[blob_key] = blob if blob is not None else b""
    return payload


def pack_body(header_obj, blob) -> list:
    """Body = [u32 hlen][msgpack header][blob] as a scatter list.
    u32: table-sync pushes batch whole entries into the header (e.g.
    sync.py Items with 64 x multi-KiB entries), which blows a u16 cap."""
    h = pack(header_obj)
    first = struct.pack("<I", len(h)) + h
    return [first, blob] if blob is not None else [first]


def parse_body(parts: list):
    """Inverse of pack_body over received buffers. Returns
    (header_obj, blob: bytes|None). parts arrive either exactly as sent
    (LocalChannel) or re-chunked (TCP); both shapes are handled."""
    first = parts[0]
    if len(first) >= 4:
        (hlen,) = struct.unpack_from("<I", first)
        if len(first) >= 4 + hlen:
            header = unpack(bytes(first[4:4 + hlen]))
            tail = first[4 + hlen:]
            blobs = ([tail] if len(tail) else []) + parts[1:]
            if not blobs:
                return header, None
            if len(blobs) == 1:
                b = blobs[0]
                return header, b if isinstance(b, bytes) else bytes(b)
            return header, b"".join(bytes(x) for x in blobs)
    # header split across frames (TCP re-chunking of a tiny first part)
    body = b"".join(bytes(p) for p in parts)
    (hlen,) = struct.unpack_from("<I", body)
    header = unpack(body[4:4 + hlen])
    blob = body[4 + hlen:] or None
    return header, blob

# Stream flow control: sender may have this many un-acked stream bytes in
# flight per request; receiver grants more as the consumer drains.
STREAM_WINDOW = 4 << 20
CREDIT_BATCH = 1 << 20  # grant credits in chunks this big

# Bytes a connection's sender thread may hold unwritten before the send
# loop parks: eight full frames. A 1 MiB block's copy to a peer is five
# records, so one whole block and the start of the next fit, and on
# loopback, where the kernel takes a record as fast as the thread hands
# it over, the send loop does not park; it parks where the socket itself
# stops taking bytes (a peer that stops reading), and stream credits stay
# the end-to-end flow control.
SEND_QUEUE_BYTES = 8 * (MAX_CHUNK + 64)
# records one `sendmsg` may carry (two buffers each; Linux's IOV_MAX
# is 1,024)
_BATCH = 256
# a send on a socket that close() shut down fails with EPIPE and raises
# no SIGPIPE, which the CLI restores to its default (kill the process)
_NOSIGNAL = getattr(socket, "MSG_NOSIGNAL", 0)


def _hmac(key: bytes, *parts: bytes) -> bytes:
    return hmac_mod.new(key, b"".join(parts), hashlib.blake2b).digest()[:32]


def _hkdf(secret: bytes, info: bytes) -> bytes:
    # compress info (label + both ephemerals, >64 B) into a full-width
    # key so the whole transcript context feeds key derivation
    ikey = hashlib.blake2b(info, digest_size=64).digest()
    return hashlib.blake2b(secret, key=ikey, digest_size=32).digest()


class HandshakeError(RpcError):
    pass


class WireStats:
    """What a node's wire connections did, live and closed, as plain
    integers and seconds: the hot path pays additions."""

    __slots__ = ("sent", "sent_bytes", "recv", "recv_bytes", "partial",
                 "partial_bytes", "drain_s", "queued", "queue_s",
                 "sender_frames", "sender_lag_s")
    # the sender threads of every channel add under this lock (the
    # loop's own counters above need none: one thread writes them)
    thread_lock = threading.Lock()

    def __init__(self):
        # records appended to a sender, their wire bytes (on the loop)
        self.sent = self.sent_bytes = 0
        self.recv = self.recv_bytes = 0  # frames read, wire bytes
        # records the kernel took only in part in the sender's first
        # send of them, and the bytes that send left (sender threads)
        self.partial = self.partial_bytes = 0
        # seconds the send loop parked for room in a sender's queue,
        # over `sent` (0 for a record that did not park)
        self.drain_s = 0.0
        self.queued = 0  # messages (not control frames) sent
        self.queue_s = 0.0  # enqueue -> first frame handed to send_frame
        # records a sender thread wrote whole, and the seconds from each
        # one's append to its last byte taken by the kernel
        self.sender_frames = 0
        self.sender_lag_s = 0.0


def _wake(fut: asyncio.Future) -> None:
    if not fut.done():
        fut.set_result(None)


class _Sender:
    """The thread that writes one TCP channel's records to its socket,
    in the order they were appended, so the event loop's thread pays
    for no `send` (on loopback the kernel copies the record and runs the
    whole TCP path inside it). It writes through its own duplicate of
    the transport's socket, non-blocking like asyncio's, so asyncio's
    reads on that socket are untouched: the records waiting go out in
    one `sendmsg`, each as two buffers, its length prefix and its body,
    and while the kernel's buffer is full the thread waits in `poll`.
    The thread takes the interpreter lock back after every system call,
    so one call a batch, not a record, is what keeps it from falling
    behind a busy loop. Records are owned `bytes`, never views of
    buffers that are recycled. The send loop parks only while the queue
    holds more than SEND_QUEUE_BYTES, and the thread wakes it with one
    `call_soon_threadsafe`; a send error reaches the loop the same way,
    through `on_error`. `close()` shuts the socket down, which also
    returns a send that waits on a full buffer; the thread then closes
    its descriptor and ends."""

    def __init__(self, writer, stats: WireStats, name: str):
        self.stats = stats
        self.on_error: Optional[Callable[[Exception], None]] = None
        self.error: Optional[Exception] = None
        self._loop = asyncio.get_running_loop()
        fd = writer.get_extra_info("socket").fileno()
        self._sock = socket.socket(fileno=os.dup(fd))
        self._sock.setblocking(False)
        self._cond = threading.Condition(threading.Lock())
        self._queue: collections.deque = collections.deque()
        self._queued = 0  # bytes in _queue, the batch being written too
        self._room: Optional[asyncio.Future] = None  # the parked loop's
        self._closing = False
        self.thread = threading.Thread(target=self._run, name=name,
                                       daemon=True)
        self.thread.start()

    def put_record(self, head: bytes, body: bytes
                   ) -> Optional[asyncio.Future]:
        """Hand a record to the thread (on the loop). Returns a future
        to park on while the queue is over its bound, else None."""
        with self._cond:
            if self._closing or self.error is not None:
                raise self.error or ConnectionResetError("channel closed")
            self._queue.append((head, body, perf_counter()))
            self._queued += len(head) + len(body)
            self._cond.notify()
            if self._queued <= SEND_QUEUE_BYTES:
                return None
            if self._room is None or self._room.done():
                self._room = self._loop.create_future()
            return self._room

    def close(self) -> None:
        """On the loop: stop the thread, dropping what it has not
        written. Idempotent."""
        with self._cond:
            self._closing = True
            room, self._room = self._room, None
            self._cond.notify()
            try:
                self._sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass  # already closed by the thread, or never connected
        if room is not None and not room.done():
            room.set_exception(ConnectionResetError("channel closed"))

    def _failed(self, exc: Exception) -> None:
        """On the loop: the thread stopped on a send error."""
        with self._cond:
            self.error = exc
            room, self._room = self._room, None
        if room is not None and not room.done():
            room.set_exception(exc)
        if self.on_error is not None:
            self.on_error(exc)

    def _run(self) -> None:
        try:
            while True:
                with self._cond:
                    while not self._queue and not self._closing:
                        self._cond.wait()
                    if self._closing:
                        return
                    batch = list(itertools.islice(self._queue, _BATCH))
                lag = self._write(batch)
                if lag is None:
                    return  # closed while it waited for room
                with WireStats.thread_lock:
                    self.stats.sender_frames += len(batch)
                    self.stats.sender_lag_s += lag
                with self._cond:
                    for head, body, _ in batch:
                        self._queue.popleft()
                        self._queued -= len(head) + len(body)
                    room = None
                    if self._room is not None \
                            and self._queued <= SEND_QUEUE_BYTES:
                        room, self._room = self._room, None
                if room is not None:
                    self._loop.call_soon_threadsafe(_wake, room)
        except OSError as e:
            if not self._closing:
                try:
                    self._loop.call_soon_threadsafe(self._failed, e)
                except RuntimeError:
                    pass  # the loop is closed: nobody is left to tell
        finally:
            with self._cond:
                self._sock.close()

    def _write(self, batch: list) -> Optional[float]:
        """The batch's records, whole and in order, through as few
        `sendmsg` calls as the kernel allows: each interpreter-lock
        acquisition of the thread carries every record waiting. Returns
        the sum of their lags (append → the send that took a record's
        last byte), or None when the channel closed first. A record is a
        partial write when the send that took its first bytes did not
        take its last."""
        bufs = [b for head, body, _ in batch for b in (head, body)]
        i = took = 0  # record i is the first not taken whole: `took` of it
        lag = 0.0
        while i < len(batch):
            try:
                sent = self._sock.sendmsg(bufs, (), _NOSIGNAL)
            except BlockingIOError:
                if not self._wait_writable():
                    return None
                continue
            now = perf_counter()
            fresh = not took  # this send took the first bytes of record i
            n = sent
            while i < len(batch):
                size = len(batch[i][0]) + len(batch[i][1])
                if took + n < size:
                    break
                n -= size - took
                lag += now - batch[i][2]
                i, took, fresh = i + 1, 0, True
            if n:  # the send ended inside record i
                if fresh:
                    with WireStats.thread_lock:
                        self.stats.partial += 1
                        self.stats.partial_bytes += size - n
                took += n
            bufs = _advance(bufs, sent)
        return lag

    def _wait_writable(self) -> bool:
        """Wait for room in the kernel's buffer (close() ends the wait:
        a socket shut down polls ready). False: the channel closed."""
        poller = select.poll()
        poller.register(self._sock, select.POLLOUT)
        poller.poll()
        return not self._closing


def _advance(bufs: list, n: int) -> list:
    """The buffers left once the first n bytes of bufs went."""
    out = []
    for b in bufs:
        if n >= len(b):
            n -= len(b)
        else:
            out.append(memoryview(b)[n:] if n else b)
            n = 0
    return out


def _sender_name(writer, peer: bytes) -> str:
    return "net-send " + (peer.hex()[:16] if peer
                          else str(writer.get_extra_info("peername")))


async def _write_record(sender: _Sender, head: bytes, body: bytes) -> None:
    """One record to the channel's sender thread — counted: the frame
    and its bytes now, and the time the send loop (and every message
    queued behind it) parked for room in the sender's queue."""
    room = sender.put_record(head, body)
    stats = sender.stats
    stats.sent += 1
    stats.sent_bytes += len(head) + len(body)
    if room is not None:
        t0 = perf_counter()
        await room
        stats.drain_s += perf_counter() - t0


class PlainChannel:
    """No-crypto record layer for the `cryptography`-less fallback:
    [u32 len][u32 req_id][u32 field][payload]. Cluster membership is
    still gated (HMAC over netid in the plain handshake below) but
    there is NO confidentiality or per-record integrity — dev/test
    transport, never a production one."""

    max_chunk = MAX_CHUNK

    def __init__(self, reader, writer, stats: WireStats,
                 peer: bytes = b""):
        self.reader = reader
        self.writer = writer
        self.stats = stats
        self.sender = _Sender(writer, stats, _sender_name(writer, peer))

    async def send_frame(self, req_id: int, field: int,
                         parts: list = ()) -> None:
        payload = b"".join(
            p if isinstance(p, (bytes, bytearray)) else bytes(p)
            for p in parts)
        await _write_record(self.sender, struct.pack(
            "<III", len(payload) + 8, req_id, field), payload)

    async def recv_frame(self) -> tuple[int, int, list]:
        (n,) = struct.unpack("<I", await self.reader.readexactly(4))
        body = await self.reader.readexactly(n)
        self.stats.recv += 1
        self.stats.recv_bytes += 4 + n
        req_id, field = struct.unpack_from("<II", body)
        return req_id, field, [memoryview(body)[8:]]

    def close(self) -> None:
        self.sender.close()
        try:
            self.writer.close()
        except Exception:
            pass  # lint: ignore[GL05] transport close is best-effort


async def _plain_client_handshake(reader, writer, netid: bytes, privkey,
                                  stats: WireStats
                                  ) -> tuple[bytes, PlainChannel]:
    """Initiator side of the no-crypto fallback: three messages so BOTH
    directions prove LIVE knowledge of the cluster secret (each side's
    final MAC covers the other side's fresh nonce — a recorded
    handshake replays into neither role); identity is the
    HashIdentityKey public id. Only reachable when BOTH ends lack the
    wheel — the distinct PLAIN_MAGIC makes mixed pairs fail closed."""
    import os as _os

    pub = privkey.public_key().public_bytes_raw()
    nonce = _os.urandom(16)
    hello = PLAIN_MAGIC + pub + nonce
    writer.write(hello + _hmac(netid, b"hello-plain", hello))
    await writer.drain()

    srv = await reader.readexactly(len(PLAIN_MAGIC) + 32 + 16 + 32)
    if srv[: len(PLAIN_MAGIC)] != PLAIN_MAGIC:
        raise HandshakeError(
            "protocol mismatch (peer has crypto transport; this node "
            "lacks the `cryptography` wheel)")
    off = len(PLAIN_MAGIC)
    srv_pub = srv[off : off + 32]
    head = srv[: off + 48]
    srv_mac = srv[off + 48 : off + 80]
    # server's MAC covers OUR nonce (inside hello): server is live
    if not hmac_mod.compare_digest(
            srv_mac, _hmac(netid, b"srv-plain", hello, head)):
        raise HandshakeError("peer does not know the cluster secret")
    # confirm over the server's fresh nonce (inside head): we are live
    writer.write(_hmac(netid, b"cli-plain", hello, head))
    await writer.drain()
    return srv_pub, PlainChannel(reader, writer, stats, srv_pub)


async def _plain_server_handshake(reader, writer, netid: bytes, privkey,
                                  stats: WireStats
                                  ) -> tuple[bytes, PlainChannel]:
    """Acceptor side of the no-crypto fallback. The hello MAC alone is
    replayable (it covers only client-chosen bytes), so the channel is
    granted ONLY after the client's confirm MAC over our fresh nonce —
    a recorded handshake cannot be replayed into a usable channel."""
    import os as _os

    hello = await reader.readexactly(len(PLAIN_MAGIC) + 32 + 16)
    mac = await reader.readexactly(32)
    if hello[: len(PLAIN_MAGIC)] != PLAIN_MAGIC:
        raise HandshakeError(
            "protocol mismatch (peer has crypto transport; this node "
            "lacks the `cryptography` wheel)")
    if not hmac_mod.compare_digest(mac,
                                   _hmac(netid, b"hello-plain", hello)):
        raise HandshakeError("peer does not know the cluster secret")
    cli_pub = hello[len(PLAIN_MAGIC) : len(PLAIN_MAGIC) + 32]
    pub = privkey.public_key().public_bytes_raw()
    head = PLAIN_MAGIC + pub + _os.urandom(16)
    writer.write(head + _hmac(netid, b"srv-plain", hello, head))
    await writer.drain()
    confirm = await reader.readexactly(32)
    if not hmac_mod.compare_digest(
            confirm, _hmac(netid, b"cli-plain", hello, head)):
        raise HandshakeError("peer failed the liveness confirm")
    return cli_pub, PlainChannel(reader, writer, stats, cli_pub)


async def client_handshake(
    reader: asyncio.StreamReader,
    writer: asyncio.StreamWriter,
    netid: bytes,
    privkey: Ed25519PrivateKey,
    stats: WireStats,
) -> tuple[bytes, "SecureChannel"]:
    """Initiator side. Returns (peer node id, channel); the channel
    counts into `stats`."""
    if not HAVE_CRYPTO:
        return await _plain_client_handshake(reader, writer, netid,
                                             privkey, stats)
    pub = privkey.public_key().public_bytes_raw()
    eph = X25519PrivateKey.generate()
    eph_pub = eph.public_key().public_bytes_raw()
    hello = MAGIC + pub + eph_pub
    writer.write(hello + _hmac(netid, b"hello", hello))
    await writer.drain()

    srv = await reader.readexactly(len(MAGIC) + 32 + 32 + 32 + 64)
    if srv[: len(MAGIC)] != MAGIC:
        raise HandshakeError("protocol version mismatch")
    off = len(MAGIC)
    srv_pub, srv_eph, srv_mac = srv[off : off + 32], srv[off + 32 : off + 64], srv[off + 64 : off + 96]
    srv_sig = srv[off + 96 :]
    transcript = b"srv" + hello + srv[: off + 64]
    if not hmac_mod.compare_digest(srv_mac, _hmac(netid, transcript)):
        raise HandshakeError("peer does not know the cluster secret")
    Ed25519PublicKey.from_public_bytes(srv_pub).verify(srv_sig, transcript)

    sig = privkey.sign(b"cli" + transcript)
    writer.write(sig)
    await writer.drain()

    shared = eph.exchange(X25519PublicKey.from_public_bytes(srv_eph))
    secret = _hkdf(shared, b"garage-tpu-channel" + eph_pub + srv_eph)
    chan = SecureChannel(reader, writer, send_key=_hkdf(secret, b"c2s"), recv_key=_hkdf(secret, b"s2c"),
                         stats=stats, peer=srv_pub)
    return srv_pub, chan


async def server_handshake(
    reader: asyncio.StreamReader,
    writer: asyncio.StreamWriter,
    netid: bytes,
    privkey: Ed25519PrivateKey,
    stats: WireStats,
) -> tuple[bytes, "SecureChannel"]:
    """Acceptor side. Returns (peer node id, channel); the channel
    counts into `stats`."""
    if not HAVE_CRYPTO:
        return await _plain_server_handshake(reader, writer, netid,
                                             privkey, stats)
    hello = await reader.readexactly(len(MAGIC) + 32 + 32)
    mac = await reader.readexactly(32)
    if hello[: len(MAGIC)] != MAGIC:
        raise HandshakeError("protocol version mismatch")
    if not hmac_mod.compare_digest(mac, _hmac(netid, b"hello", hello)):
        raise HandshakeError("peer does not know the cluster secret")
    off = len(MAGIC)
    cli_pub, cli_eph = hello[off : off + 32], hello[off + 32 : off + 64]

    pub = privkey.public_key().public_bytes_raw()
    eph = X25519PrivateKey.generate()
    eph_pub = eph.public_key().public_bytes_raw()
    head = MAGIC + pub + eph_pub
    transcript = b"srv" + hello + head
    srv_mac = _hmac(netid, transcript)
    sig = privkey.sign(transcript)
    writer.write(head + srv_mac + sig)
    await writer.drain()

    cli_sig = await reader.readexactly(64)
    Ed25519PublicKey.from_public_bytes(cli_pub).verify(cli_sig, b"cli" + transcript)

    shared = eph.exchange(X25519PublicKey.from_public_bytes(cli_eph))
    secret = _hkdf(shared, b"garage-tpu-channel" + cli_eph + eph_pub)
    chan = SecureChannel(reader, writer, send_key=_hkdf(secret, b"s2c"), recv_key=_hkdf(secret, b"c2s"),
                         stats=stats, peer=cli_pub)
    return cli_pub, chan


class SecureChannel:
    """ChaCha20-Poly1305 record layer: [u32 ct_len][ct]; counter nonces.
    Frames are [u32 req_id][u32 field][payload] inside the record."""

    max_chunk = MAX_CHUNK

    def __init__(self, reader, writer, send_key: bytes, recv_key: bytes,
                 stats: WireStats, peer: bytes = b""):
        self.reader = reader
        self.writer = writer
        self._send = ChaCha20Poly1305(send_key)
        self._recv = ChaCha20Poly1305(recv_key)
        self._send_ctr = 0
        self._recv_ctr = 0
        self.stats = stats
        self.sender = _Sender(writer, stats, _sender_name(writer, peer))

    def _nonce(self, ctr: int) -> bytes:
        return ctr.to_bytes(12, "little")

    async def send_frame(self, req_id: int, field: int,
                         parts: list = ()) -> None:
        pt = struct.pack("<II", req_id, field) + b"".join(
            p if isinstance(p, (bytes, bytearray)) else bytes(p)
            for p in parts)
        ct = self._send.encrypt(self._nonce(self._send_ctr), pt, None)
        self._send_ctr += 1
        await _write_record(self.sender, struct.pack("<I", len(ct)), ct)

    async def recv_frame(self) -> tuple[int, int, list]:
        (n,) = struct.unpack("<I", await self.reader.readexactly(4))
        ct = await self.reader.readexactly(n)
        self.stats.recv += 1
        self.stats.recv_bytes += 4 + n
        pt = self._recv.decrypt(self._nonce(self._recv_ctr), ct, None)
        self._recv_ctr += 1
        req_id, field = struct.unpack_from("<II", pt)
        return req_id, field, [memoryview(pt)[8:]]

    def close(self) -> None:
        self.sender.close()
        try:
            self.writer.close()
        except Exception:
            pass  # lint: ignore[GL05] transport close is best-effort


class _SendItem:
    """One in-flight outgoing message: body section then optional stream.

    Stream chunks are prefetched by a side task so a stalled stream
    source never parks the connection's single send loop (the reference
    gets this for free from its polled scheduler, src/net/send.rs).
    """

    __slots__ = (
        "req_id", "prio", "body", "buf_idx", "pos", "body_done", "stream",
        "is_error", "done", "kind", "next_chunk", "chunk_state", "prefetch",
        "window", "order_clock", "t_enq",
    )

    def __init__(self, req_id, prio, body, stream, is_error, kind="msg"):
        self.req_id = req_id
        self.prio = prio
        self.body = body  # list of buffers (scatter-gather)
        self.buf_idx = 0
        self.pos = 0
        self.body_done = False
        self.stream = stream
        self.is_error = is_error
        self.kind = kind  # "msg" | "cancel" | "credit"
        self.next_chunk: Optional[bytes] = None
        self.chunk_state = "none"  # none|fetching|ready|eof|error
        self.prefetch: Optional[asyncio.Task] = None
        self.window = STREAM_WINDOW
        self.order_clock = 0
        # perf_counter() at Conn.enqueue until the first frame is
        # handed to the channel, then 0.0 (control items: never set)
        self.t_enq = 0.0
        self.done = asyncio.get_event_loop().create_future()


class _RecvState:
    """Reassembly of one incoming message."""

    __slots__ = ("parts", "stream", "is_error", "credited")

    def __init__(self):
        self.parts: list = []
        self.stream: Optional[ByteStream] = None
        self.is_error = False
        self.credited = 0


class Conn:
    """One duplex multiplexed connection to a peer.

    Either side can issue requests; the initiator uses even request ids,
    the acceptor odd (the reference instead opens two connections,
    src/net/netapp.rs server_conns/client_conns — one duplex socket is
    the asyncio-native shape).
    """

    def __init__(
        self,
        peer_id: bytes,
        channel: SecureChannel,
        handler: Callable[..., Awaitable],
        initiator: bool,
        local_id: bytes = b"",
    ):
        self.peer_id = peer_id
        # our own node id, for chaos scoping only (partition_zone needs
        # BOTH endpoints of a frame; defaulted empty for bare tests)
        self.local_id = local_id
        self.chan = channel
        self.wire: Optional[WireStats] = channel.stats  # None: LocalChannel
        sender = getattr(channel, "sender", None)  # TCP channels own one
        if sender is not None:
            sender.on_error = self._fail
        self.handler = handler  # (peer_id, path, prio, order, payload, stream)
        self._next_id = 2 if initiator else 3
        self._send_items: dict[int, _SendItem] = {}
        self._ctl_items: list[_SendItem] = []
        self._send_wakeup = asyncio.Event()
        self._send_clock = 0
        self._recv_states: dict[int, _RecvState] = {}
        self._reply_waiters: dict[int, asyncio.Future] = {}
        self._handler_tasks: dict[int, asyncio.Task] = {}
        self._tasks: list[asyncio.Task] = []
        self.closed = asyncio.get_event_loop().create_future()

    def start(self) -> None:
        self._tasks = [
            asyncio.create_task(self._send_loop()),
            asyncio.create_task(self._recv_loop()),
        ]
        for t in self._tasks:
            # supervised by close(): not leaks for the sanitizer's
            # loop-teardown check
            t._garage_background = True

    # ---- outgoing ------------------------------------------------------

    def _alloc_id(self) -> int:
        i = self._next_id
        self._next_id += 2
        return i

    def enqueue(
        self,
        req_id: int,
        prio: int,
        body: list,
        stream: Optional[ByteStream] = None,
        is_error: bool = False,
    ) -> _SendItem:
        item = _SendItem(req_id, prio, body, stream, is_error)
        if self.wire is not None:
            item.t_enq = perf_counter()
        self._send_items[req_id] = item
        self._send_wakeup.set()
        return item

    def _enqueue_ctl(self, kind: str, req_id: int, payload: bytes = b"") -> None:
        item = _SendItem(req_id, 0, [payload], None, False, kind=kind)
        self._ctl_items.append(item)
        self._send_wakeup.set()

    async def call(
        self,
        path: str,
        payload,
        prio: int = PRIO_HIGH,
        stream: Optional[ByteStream] = None,
        timeout: Optional[float] = None,
        order: Optional[tuple[int, int]] = None,
    ):
        """Send a request, await (payload, reply_stream)."""
        from ..utils.tracing import current_trace_id

        if self.closed.done():
            # a dead conn stays in netapp.conns until the done-callback
            # runs (next loop tick); a call landing in that window would
            # enqueue into a conn whose loops are gone and wait out its
            # full timeout instead of failing fast
            raise RpcError("connection closed")
        req_id = self._alloc_id()
        rest, blob_key, blob = split_blob(payload)
        body = pack_body([path, prio, stream is not None, order, rest,
                          blob_key, current_trace_id()], blob)
        fut = asyncio.get_event_loop().create_future()
        self._reply_waiters[req_id] = fut
        self.enqueue(req_id, prio, body, stream)
        try:
            return await asyncio.wait_for(fut, timeout)
        except (asyncio.TimeoutError, asyncio.CancelledError):
            self._abort_send(req_id)
            self._enqueue_ctl("cancel", req_id)
            raise
        finally:
            self._reply_waiters.pop(req_id, None)

    def _abort_send(self, req_id: int) -> None:
        item = self._send_items.pop(req_id, None)
        if item is not None and item.prefetch is not None:
            item.prefetch.cancel()

    # ---- send scheduler ------------------------------------------------

    async def _send_loop(self) -> None:
        try:
            while True:
                item = self._pick_item()
                if item is None:
                    # lint: ignore[GL12] wakeup handshake: clear, re-check _pick_item, then wait — a set() racing the clear is caught by the re-check; a spurious wake costs one loop turn
                    self._send_wakeup.clear()
                    # re-check: a prefetch may have completed in between
                    if self._pick_item() is None:
                        await self._send_wakeup.wait()
                    continue
                await self._send_one_chunk(item)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            self._fail(e)

    def _pick_item(self) -> Optional[_SendItem]:
        """Control frames first; then highest priority, round-robin within
        the level by least-recently-sent (ref: src/net/send.rs:48-60)."""
        if self._ctl_items:
            return self._ctl_items[0]
        best: Optional[_SendItem] = None
        for item in self._send_items.values():
            if not self._sendable(item):
                continue
            if (
                best is None
                or item.prio < best.prio
                or (item.prio == best.prio and item.order_clock < best.order_clock)
            ):
                best = item
        return best

    def _sendable(self, item: _SendItem) -> bool:
        if not item.body_done:
            return True
        if item.stream is None:
            return True  # finished body, will finalize
        if item.chunk_state == "fetching":
            return False
        if item.chunk_state in ("ready", "eof", "error"):
            return item.window > 0 or item.chunk_state in ("eof", "error")
        # chunk_state == "none": start a prefetch, not sendable yet
        self._start_prefetch(item)
        return False

    def _start_prefetch(self, item: _SendItem) -> None:
        item.chunk_state = "fetching"

        async def fetch():
            try:
                chunk = await item.stream.read_chunk(MAX_CHUNK)
                item.next_chunk = chunk
                item.chunk_state = "eof" if not chunk else "ready"
            except Exception:
                item.chunk_state = "error"
            self._send_wakeup.set()

        item.prefetch = asyncio.create_task(fetch())

    @staticmethod
    def _next_body_parts(item: _SendItem, max_chunk: int) -> tuple[list, int]:
        """Advance the body cursor by up to max_chunk bytes; returns the
        scatter list (memoryview slices — no copies) and its length."""
        parts: list = []
        n = 0
        while item.buf_idx < len(item.body) and n < max_chunk:
            buf = item.body[item.buf_idx]
            blen = len(buf)
            take = min(blen - item.pos, max_chunk - n)
            if take == blen and item.pos == 0:
                parts.append(buf)
            else:
                parts.append(memoryview(buf)[item.pos:item.pos + take])
            item.pos += take
            n += take
            if item.pos >= blen:
                item.buf_idx += 1
                item.pos = 0
        return parts, n

    async def _chaos_net(self, direction: str, nbytes: int) -> bool:
        """Chaos seam (net): delay/drop/disconnect/slow-drip scoped by
        the remote peer id. True = proceed, False = drop the frame.
        No-op fast path when chaos is disarmed."""
        if _chaos.ACTIVE is None:
            return True
        return await _chaos.ACTIVE.net_frame(direction, self.local_id,
                                             self.peer_id, nbytes)

    async def _send_one_chunk(self, item: _SendItem) -> None:
        if item.kind == "cancel":
            self._ctl_items.remove(item)
            await self.chan.send_frame(item.req_id, CANCEL)
            return
        if item.kind == "credit":
            self._ctl_items.remove(item)
            await self.chan.send_frame(item.req_id, CREDIT, item.body)
            return
        self._send_clock += 1
        item.order_clock = self._send_clock
        flags_base = F_ERROR if item.is_error else 0
        if not item.body_done:
            if item.t_enq:  # its first frame: the wait behind the others
                self.wire.queued += 1
                self.wire.queue_s += perf_counter() - item.t_enq
                item.t_enq = 0.0
            parts, n = self._next_body_parts(item, self.chan.max_chunk)
            item.body_done = item.buf_idx >= len(item.body)
            flags = flags_base | (0 if item.body_done else F_CONT)
            if await self._chaos_net("send", n):
                await self.chan.send_frame(item.req_id, flags | n, parts)
            if item.body_done and item.stream is None:
                self._finish_item(item)
            return
        # stream section
        if item.chunk_state == "error":
            await self.chan.send_frame(item.req_id, F_STREAM | F_ERROR)
            self._finish_item(item)
            return
        if item.chunk_state == "eof":
            await self.chan.send_frame(item.req_id, F_STREAM)
            self._finish_item(item)
            return
        assert item.chunk_state == "ready"
        chunk = item.next_chunk or b""
        send_now = chunk[: max(0, item.window)]
        rest = chunk[len(send_now) :]
        if rest:
            item.next_chunk = rest  # window-limited; stays ready
        else:
            item.next_chunk = None
            item.chunk_state = "none"
        item.window -= len(send_now)
        if await self._chaos_net("send", len(send_now)):
            await self.chan.send_frame(
                item.req_id, F_STREAM | F_CONT | len(send_now), [send_now])

    def _finish_item(self, item: _SendItem) -> None:
        self._send_items.pop(item.req_id, None)
        if not item.done.done():
            item.done.set_result(None)

    # ---- incoming ------------------------------------------------------

    async def _recv_loop(self) -> None:
        try:
            while True:
                req_id, field, parts = await self.chan.recv_frame()
                if _chaos.ACTIVE is not None and not await self._chaos_net(
                        "recv", sum(len(p) for p in parts)):
                    continue  # frame lost on the (simulated) wire
                if field == CANCEL:
                    self._handle_cancel(req_id)
                elif field == CREDIT:
                    self._handle_credit(
                        req_id, bytes(parts[0][:4]) if parts else b"")
                else:
                    self._handle_chunk(req_id, field, parts)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            self._fail(e)

    def _handle_cancel(self, req_id: int) -> None:
        task = self._handler_tasks.pop(req_id, None)
        if task is not None:
            task.cancel()
        self._abort_send(req_id)
        st = self._recv_states.pop(req_id, None)
        if st is not None and st.stream is not None:
            st.stream.push_error(RpcError("cancelled by peer"))

    def _handle_credit(self, req_id: int, payload: bytes) -> None:
        item = self._send_items.get(req_id)
        if item is not None and len(payload) >= 4:
            item.window += struct.unpack("<I", payload[:4])[0]
            self._send_wakeup.set()

    def _grant_credit(self, req_id: int, stream: ByteStream) -> None:
        """Wire consumer progress to CREDIT grants back to the sender."""
        state = {"pending": 0}

        def consumed(n: int) -> None:
            state["pending"] += n
            if state["pending"] >= CREDIT_BATCH:
                grant, state["pending"] = state["pending"], 0
                self._enqueue_ctl("credit", req_id, struct.pack("<I", grant))

        stream.on_consume = consumed

    def _handle_chunk(self, req_id: int, field: int, parts: list) -> None:
        mine = (req_id % 2 == 0) == (self._next_id % 2 == 0)
        st = self._recv_states.get(req_id)
        if st is None:
            st = self._recv_states[req_id] = _RecvState()
        if field & F_STREAM:
            if st.stream is None:
                st.stream = ByteStream()
            if field & F_ERROR:
                st.stream.push_error(RpcError("peer stream failed"))
                self._recv_states.pop(req_id, None)
            elif field & F_CONT:
                for p in parts:
                    if len(p):
                        st.stream.push(p if isinstance(p, bytes)
                                       else bytes(p))
            else:
                for p in parts:
                    if len(p):
                        st.stream.push(p if isinstance(p, bytes)
                                       else bytes(p))
                st.stream.push_eof()
                self._recv_states.pop(req_id, None)
            return
        st.parts.extend(parts)
        st.is_error = st.is_error or bool(field & F_ERROR)
        if field & F_CONT:
            return
        try:
            header, blob = parse_body(st.parts)
        except Exception:
            # fragment of a cancelled request whose state we dropped —
            # drop it; the request id is dead
            self._recv_states.pop(req_id, None)
            return
        if mine:
            self._deliver_reply(req_id, st, header, blob)
        else:
            self._dispatch_request(req_id, st, header, blob)

    @staticmethod
    def _expect_stream(header) -> bool:
        # reply header: [ok, payload, has_stream, blob_key]
        return bool(header[2]) if isinstance(header, list) and len(header) >= 3 else False

    def _deliver_reply(self, req_id: int, st: _RecvState, header, blob) -> None:
        # reply header: [ok, payload, has_stream, blob_key]
        fut = self._reply_waiters.pop(req_id, None)
        has_stream = self._expect_stream(header)
        if has_stream and st.stream is None:
            st.stream = ByteStream()
        if not has_stream:
            self._recv_states.pop(req_id, None)
        if fut is None or fut.done():
            if st.stream:
                st.stream.discard()
            return
        if st.is_error or (isinstance(header, list) and not header[0]):
            msg = header[1] if isinstance(header, list) else "remote error"
            fut.set_exception(RpcError(str(msg)))
        else:
            if st.stream is not None:
                self._grant_credit(req_id, st.stream)
            bkey = header[3] if len(header) > 3 else None
            fut.set_result((_attach_blob(header[1], bkey, blob), st.stream))

    def _dispatch_request(self, req_id: int, st: _RecvState, header, blob) -> None:
        # request header:
        # [path, prio, has_stream, order, payload, blob_key, trace_id]
        path, prio, has_stream, order, payload, bkey = header[:6]
        trace_id = header[6] if len(header) > 6 else None
        payload = _attach_blob(payload, bkey, blob)
        if has_stream and st.stream is None:
            st.stream = ByteStream()
        if st.stream is not None:
            self._grant_credit(req_id, st.stream)
        if not has_stream:
            self._recv_states.pop(req_id, None)
        task = asyncio.create_task(
            self._run_handler(req_id, path, prio, order, payload, st.stream,
                              trace_id)
        )
        # supervised: tracked in _handler_tasks, cancelled by close()
        task._garage_background = True
        self._handler_tasks[req_id] = task
        task.add_done_callback(lambda t: self._handler_tasks.pop(req_id, None))

    async def _run_handler(self, req_id, path, prio, order, payload, stream,
                           trace_id=None) -> None:
        try:
            if trace_id is not None:
                from ..utils.tracing import set_remote_context

                set_remote_context(trace_id)
            result, reply_stream = await self.handler(
                self.peer_id, path, prio, order, payload, stream
            )
            rest, blob_key, blob = split_blob(result)
            body = pack_body([True, rest, reply_stream is not None,
                              blob_key], blob)
            self.enqueue(req_id, prio, body, reply_stream)
        except asyncio.CancelledError:
            pass
        except Exception as e:
            log.debug("handler error on %s: %s", path, e, exc_info=True)
            self.enqueue(req_id, prio, pack_body(
                [False, f"{type(e).__name__}: {e}", False, None], None))

    # ---- lifecycle -----------------------------------------------------

    def _fail(self, exc: Exception) -> None:
        for fut in self._reply_waiters.values():
            if not fut.done():
                fut.set_exception(RpcError(f"connection lost: {exc}"))
        self._reply_waiters.clear()
        for st in self._recv_states.values():
            if st.stream:
                st.stream.push_error(RpcError("connection lost"))
        self._recv_states.clear()
        for item in self._send_items.values():
            if item.prefetch is not None:
                item.prefetch.cancel()
        self._send_items.clear()
        for t in self._handler_tasks.values():
            t.cancel()
        if not self.closed.done():
            self.closed.set_result(exc)
        self.chan.close()

    async def close(self) -> None:
        for t in self._tasks:
            t.cancel()
        self._fail(RpcError("closed"))
