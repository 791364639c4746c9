"""Block store tests: local file store, replicate + erasure cluster
paths, refcounts, resync healing, scrub corruption detection."""

import asyncio
import os
import time

import pytest

from garage_tpu.block import (
    BlockManager,
    DataBlock,
    DataLayout,
    ErasureCodec,
    ReplicateCodec,
)
from garage_tpu.block.codec import shard_nodes_of
from garage_tpu.block.manager import pack_shard, unpack_shard
from garage_tpu.db import open_db
from garage_tpu.net import LocalNetwork, NetApp
from garage_tpu.rpc import ReplicationMode, System
from garage_tpu.rpc.layout import NodeRole
from garage_tpu.utils.data import blake2sum

try:
    import zstandard  # noqa: F401
    HAVE_ZSTD = True
except ModuleNotFoundError:
    HAVE_ZSTD = False  # block.py falls back to the zlib scheme

NETID = b"block-test"


def run(coro, timeout=120.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


async def make_block_cluster(tmp_path, n=3, rf=3, erasure=None,
                             cache_tier=False):
    # cache_tier=False by default: these suites pin the NODE-LOCAL
    # cache semantics (PR 3); the cluster tier's own routing semantics
    # live in tests/test_cache_tier.py
    net = LocalNetwork()
    systems, managers = [], []
    rm = (ReplicationMode.parse(rf, erasure="%d,%d" % erasure)
          if erasure else ReplicationMode.parse(rf))
    for i in range(n):
        app = NetApp(NETID)
        net.register(app)
        meta = str(tmp_path / f"node{i}")
        s = System(app, rm, meta, status_interval=0.2, ping_interval=0.2)
        systems.append(s)
    tasks = [asyncio.create_task(s.run()) for s in systems]
    for s in systems[1:]:
        await s.netapp.try_connect(systems[0].netapp.public_addr, systems[0].id)
        s.peering.add_peer(systems[0].netapp.public_addr, systems[0].id)
    deadline = asyncio.get_event_loop().time() + 15
    while asyncio.get_event_loop().time() < deadline:
        if all(len(s.netapp.conns) == n - 1 for s in systems):
            break
        await asyncio.sleep(0.05)
    lm = systems[0].layout_manager
    for s in systems:
        lm.history.stage_role(s.id, NodeRole(zone="z1", capacity=1 << 30))
    lm.apply_staged(None)
    while asyncio.get_event_loop().time() < deadline:
        if all(s.layout_manager.history.current().version == 1 for s in systems):
            break
        await asyncio.sleep(0.05)
    for i, s in enumerate(systems):
        db = open_db(str(tmp_path / f"node{i}" / "db"), engine="memory")
        lay = DataLayout.single(str(tmp_path / f"node{i}" / "data"))
        managers.append(BlockManager(s, db, lay, cache_tier=cache_tier))
    return net, systems, managers, tasks


async def stop_all(systems, tasks):
    for s in systems:
        await s.stop()
    for t in tasks:
        t.cancel()


# ---- pure local tests --------------------------------------------------


def test_datablock_roundtrip():
    data = b"hello world " * 100
    h = blake2sum(data)
    blk = DataBlock.compress(data)
    # compressible -> zstd (ref default); zlib scheme when the wheel
    # is absent (block.py fallback)
    assert blk.compression == (2 if HAVE_ZSTD else 1)
    blk.verify(h)
    assert blk.plain_bytes() == data
    rt = DataBlock.unpack(blk.pack())
    rt.verify(h)
    rnd = os.urandom(4096)
    blk2 = DataBlock.compress(rnd)
    assert blk2.compression == 0  # incompressible stays plain


def test_datablock_legacy_zlib_decodes():
    """Blocks written by pre-zstd builds (scheme byte 1) still decode."""
    import zlib

    data = b"legacy block payload " * 64
    h = blake2sum(data)
    legacy = DataBlock(1, zlib.compress(data, 1))
    legacy.verify(h)
    assert legacy.plain_bytes() == data
    assert legacy.file_suffix() == ".zlib"
    rt = DataBlock.unpack(legacy.pack())
    assert rt.plain_bytes() == data


def test_shard_file_roundtrip():
    raw = pack_shard(b"shard-bytes", 12345)
    data, plen = unpack_shard(raw)
    assert data == b"shard-bytes" and plen == 12345


def test_erasure_codec_roundtrip():
    codec = ErasureCodec(4, 2, use_jax=False)
    data = os.urandom(100_000)
    parts = codec.encode(data)
    assert len(parts) == 6
    # any 4 parts reconstruct
    for keep in [(0, 1, 2, 3), (1, 2, 4, 5), (0, 3, 4, 5), (2, 3, 4, 5)]:
        sub = {i: parts[i] for i in keep}
        assert codec.decode(sub, len(data)) == data
    # repair rebuilds exactly the lost shards
    lost = codec.repair_parts({i: parts[i] for i in (0, 2, 3, 5)}, (1, 4))
    assert lost[1] == parts[1] and lost[4] == parts[4]
    assert codec.parity_ok({i: parts[i] for i in range(6)}, blake2sum(data))


def test_erasure_codec_batch():
    codec = ErasureCodec(4, 2, use_jax=False)
    blocks = [os.urandom(n) for n in (1000, 5000, 3333)]
    outs = codec.encode_batch(blocks)
    for b, parts in zip(blocks, outs):
        assert parts == codec.encode(b)


def test_local_store_and_corruption(tmp_path):
    class _Sys:
        id = b"x" * 32
        meta_dir = str(tmp_path)
        replication = ReplicationMode.parse(1)

        class netapp:
            id = b"x" * 32

            @staticmethod
            def endpoint(path):
                class E:
                    def set_handler(self, h):
                        return self

                return E()

    db = open_db(str(tmp_path / "db"), engine="memory")
    lay = DataLayout.single(str(tmp_path / "data"))
    m = BlockManager.__new__(BlockManager)
    m.system = _Sys()
    m.db = db
    m.data_layout = lay
    m.compression = True
    m.fsync = False
    from garage_tpu.block.rc import BlockRc
    from garage_tpu.block.resync import BlockResyncManager

    m.rc = BlockRc(db)
    m.codec = ReplicateCodec(1)
    m.metrics = {"bytes_read": 0, "bytes_written": 0, "corruptions": 0,
                 "resync_sent": 0, "resync_recv": 0}
    m.resync = BlockResyncManager(m, db)

    data = b"some block content" * 50
    h = blake2sum(data)
    m.write_local(h, DataBlock.compress(data).pack())
    assert m.has_local(h)
    out = DataBlock.unpack(m.read_local(h))
    assert out.plain_bytes() == data

    # a pre-zstd .zlib file on disk still reads; a fresh write_local
    # replaces it with the zstd variant
    import zlib as _zlib
    from garage_tpu.block.block import BLOCK_SUFFIXES

    old = b"older zlib-era block" * 40
    h_old = blake2sum(old)
    os.makedirs(os.path.dirname(lay.block_path(h_old, ".zlib")), exist_ok=True)
    with open(lay.block_path(h_old, ".zlib"), "wb") as f:
        f.write(_zlib.compress(old, 1))
    assert DataBlock.unpack(m.read_local(h_old)).plain_bytes() == old
    m.write_local(h_old, DataBlock.compress(old).pack())
    if HAVE_ZSTD:
        assert m._find(h_old, [".zlib"]) is None  # old variant dropped
        assert m._find(h_old, [".zst"]) is not None
    else:
        # zlib fallback: the rewrite lands on the same-suffix path
        assert m._find(h_old, [".zlib"]) is not None

    # corrupt the file on disk: read detects, quarantines, queues resync
    path = m._find(h, BLOCK_SUFFIXES)
    with open(path, "r+b") as f:
        f.seek(5)
        f.write(b"\xff\xff\xff\xff")
    assert m.read_local(h) is None
    assert m.metrics["corruptions"] == 1
    assert os.path.exists(path + ".corrupted")
    assert m.resync.queue_len() == 1


def test_rc_lifecycle(tmp_path):
    from garage_tpu.block.rc import BlockRc

    db = open_db(str(tmp_path), engine="memory")
    rc = BlockRc(db, gc_delay=0.0)
    h = blake2sum(b"b")
    newly = []
    db.transaction(lambda tx: newly.append(rc.block_incref(tx, h)))
    assert newly == [True] and rc.is_needed(h)
    db.transaction(lambda tx: newly.append(rc.block_incref(tx, h)))
    assert rc.get(h) == ("present", 2)
    db.transaction(lambda tx: rc.block_decref(tx, h))
    assert rc.is_needed(h)
    dele = []
    db.transaction(lambda tx: dele.append(rc.block_decref(tx, h)))
    assert dele == [True] and rc.is_deletable_now(h)
    # recalculate from callbacks
    rc.register_calculator(lambda hh: 3 if hh == h else 0)
    assert rc.recalculate(h) == 3
    assert rc.get(h) == ("present", 3)


def test_shard_placement_distinct_and_stable():
    from garage_tpu.rpc.layout import LayoutHistory

    h = LayoutHistory.new(3)
    import hashlib

    nodes = [hashlib.sha256(b"n%d" % i).digest() for i in range(8)]
    for i, n in enumerate(nodes):
        h.stage_role(n, NodeRole(zone="z%d" % (i % 4), capacity=1 << 30))
    h.apply_staged_changes()
    v = h.current()
    bh = blake2sum(b"someblock")
    p = shard_nodes_of(v, bh, 6)
    assert len(p) == len(set(p)) == 6
    assert p == shard_nodes_of(v, bh, 6)  # deterministic
    assert p[:3] == v.nodes_of_hash(bh)  # prefix = the ring nodes


# ---- cluster tests -----------------------------------------------------


def test_replicate_put_get(tmp_path):
    async def main():
        net, systems, managers, tasks = await make_block_cluster(tmp_path)
        try:
            data = os.urandom(200_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            # put returns at write quorum (2/3); the third write keeps
            # running in background by design — await convergence
            deadline = asyncio.get_event_loop().time() + 10
            while asyncio.get_event_loop().time() < deadline:
                if sum(1 for m in managers if m.has_local(h)) == 3:
                    break
                await asyncio.sleep(0.02)
            assert sum(1 for m in managers if m.has_local(h)) == 3
            got = await managers[2].rpc_get_block(h)
            assert got == data
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_replicate_get_survives_two_down(tmp_path):
    async def main():
        net, systems, managers, tasks = await make_block_cluster(tmp_path)
        try:
            data = b"important" * 1000
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            await systems[1].netapp.shutdown()
            await systems[2].netapp.shutdown()
            got = await managers[0].rpc_get_block(h)  # local read
            assert got == data
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_erasure_put_get_and_degraded_read(tmp_path):
    async def main():
        net, systems, managers, tasks = await make_block_cluster(
            tmp_path, n=6, rf=3, erasure=(4, 2)
        )
        try:
            data = os.urandom(300_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            # every node holds exactly one shard; the put acks at write
            # quorum (5/6) and the last shard lands in background
            held: list[int] = []
            for _ in range(100):
                parts = [m.local_parts(h) for m in managers]
                held = sorted(i for ps in parts for i in ps)
                if held == [0, 1, 2, 3, 4, 5]:
                    break
                await asyncio.sleep(0.02)
            assert held == [0, 1, 2, 3, 4, 5]
            got = await managers[3].rpc_get_block(h)
            assert got == data
            # kill two nodes -> still decodable from any 4 shards
            await systems[4].netapp.shutdown()
            await systems[5].netapp.shutdown()
            got = await managers[0].rpc_get_block(h)
            assert got == data
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_erasure_read_survives_forged_len_whose_decode_raises(tmp_path):
    """_get_erasure's packed_len fallthrough, exception-class coverage:
    a forged length can make the DECODE ITSELF blow up (packed_len=0 →
    join_stripe yields b"" → DataBlock.unpack raises IndexError), not
    just fail the content check. Forge the header on a MAJORITY of the
    gathered shards so the bad candidate is genuinely tried first (the
    length field sits outside the shard checksum, so local validation
    still passes) — the read must fall through to the minority
    candidate and recover the block."""
    async def main():
        net, systems, managers, tasks = await make_block_cluster(
            tmp_path, n=6, rf=3, erasure=(4, 2)
        )
        try:
            data = os.urandom(150_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            for _ in range(100):
                held = sorted(i for m in managers for i in m.local_parts(h))
                if held == [0, 1, 2, 3, 4, 5]:
                    break
                await asyncio.sleep(0.02)
            assert held == [0, 1, 2, 3, 4, 5]

            # the gather fetches systematic shards 0..3 first: forging
            # 0, 1 and 2 makes packed_len=0 the 3-vote majority against
            # shard 3's lone true header
            for idx in (0, 1, 2):
                victim = next(m for m in managers
                              if idx in m.local_parts(h))
                payload, _plen = unpack_shard(
                    victim.read_local_shard(h, idx))
                victim.write_local_shard(h, idx, pack_shard(payload, 0))
                # forged header still passes local validation
                assert victim.read_local_shard(h, idx) is not None

            reader = managers[1]
            reader.cache.clear()  # force the real gather+decode path
            decodes: list[int] = []
            orig_decode = reader.codec.decode

            def counting_decode(parts, plain_len):
                decodes.append(plain_len)
                return orig_decode(parts, plain_len)

            reader.codec.decode = counting_decode
            got = await reader.rpc_get_block(h)
            assert got == data
            # the majority (forged) candidate really was tried first
            assert decodes[0] == 0 and len(decodes) >= 2
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_erasure_resync_rebuilds_lost_shard(tmp_path):
    async def main():
        net, systems, managers, tasks = await make_block_cluster(
            tmp_path, n=6, rf=3, erasure=(4, 2)
        )
        try:
            data = os.urandom(123_456)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            # find the manager holding shard 2 and destroy its file
            victim = next(m for m in managers if 2 in m.local_parts(h))
            victim.delete_local(h)
            assert not victim.has_local(h)
            # mark needed + resync: shard is rebuilt from the other 5
            victim.db.transaction(lambda tx: victim.rc.block_incref(tx, h))
            await victim.resync.resync_block(h)
            assert victim.local_parts(h) == [2]
            # and the rebuilt shard is byte-identical: full read works
            got = await victim.rpc_get_block(h)
            assert got == data
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_replicate_resync_fetches_missing(tmp_path):
    async def main():
        net, systems, managers, tasks = await make_block_cluster(tmp_path)
        try:
            data = b"resync me" * 500
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            managers[1].delete_local(h)
            managers[1].db.transaction(
                lambda tx: managers[1].rc.block_incref(tx, h)
            )
            await managers[1].resync.resync_block(h)
            assert managers[1].has_local(h)
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_offload_unneeded_block(tmp_path):
    async def main():
        net, systems, managers, tasks = await make_block_cluster(tmp_path)
        try:
            data = b"temp" * 100
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            m0 = managers[0]
            m0.rc.gc_delay = 0.0
            # never incref'd -> absent rc; make it deletable-now via
            # incref+decref cycle
            m0.db.transaction(lambda tx: m0.rc.block_incref(tx, h))
            m0.db.transaction(lambda tx: m0.rc.block_decref(tx, h))
            assert m0.rc.is_deletable_now(h)
            await m0.resync.resync_block(h)
            assert not m0.has_local(h)
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_deep_scrub_detects_and_repairs_forged_shard(tmp_path):
    """Cross-shard deep scrub (ref parity: src/block/repair.rs:169-528
    whole-block rehash — the erasure-mode equivalent): a shard that is
    internally consistent (valid pack_shard checksum) but holds the
    WRONG bytes passes every local check; the stripe's scrub leader
    gathers all shards, the parity detect flags the stripe, and
    localization + repair push the corrected shard back to its
    holder."""
    async def main():
        from garage_tpu.block import ScrubWorker

        net, systems, managers, tasks = await make_block_cluster(
            tmp_path, n=6, rf=3, erasure=(4, 2)
        )
        try:
            data = os.urandom(200_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            for _ in range(100):
                held = sorted(i for m in managers for i in m.local_parts(h))
                if held == [0, 1, 2, 3, 4, 5]:
                    break
                await asyncio.sleep(0.02)
            assert held == [0, 1, 2, 3, 4, 5]

            layout = systems[0].layout_helper.current()
            placement = shard_nodes_of(layout, h, 6)
            leader = next(m for m in managers
                          if m.system.id == placement[0])

            # forge shard 1 on its holder: same length, valid framing,
            # wrong bytes — local checksum scrub CANNOT see this
            victim = next(m for m in managers if 1 in m.local_parts(h))
            raw = victim.read_local_shard(h, 1)
            payload, packed_len = unpack_shard(raw)
            forged = bytes(b ^ 0xFF for b in payload[:64]) + payload[64:]
            assert forged != payload
            victim.write_local_shard(h, 1, pack_shard(forged, packed_len))
            assert victim.read_local_shard(h, 1) is not None  # passes local

            sw = ScrubWorker(leader)
            bad = await sw.scrub_batch([h])
            assert bad == 1  # deep pass flagged the stripe

            # repair pushed the corrected shard to the holder
            fixed, _ = unpack_shard(victim.read_local_shard(h, 1))
            assert fixed == payload
            # stripe is consistent again: a re-scrub is clean and a
            # full read returns the original bytes
            assert await sw.scrub_batch([h]) == 0
            assert await managers[2].rpc_get_block(h) == data

            # non-leader nodes skip the deep pass (exactly one gather
            # per stripe per scrub round)
            non_leader = next(m for m in managers
                              if m.system.id != placement[0])
            assert await ScrubWorker(non_leader).scrub_batch([h]) == 0
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_deep_scrub_repairs_wrong_length_shard(tmp_path):
    """The misplaced-file class: a shard file holding a valid-framed
    shard of a DIFFERENT block (different length, different packed_len
    header) passes local validation; deep scrub must flag it WITHOUT
    crashing the batch (unequal lengths can't stack into the parity
    kernel), repair it, and the majority packed_len rule must keep the
    corrupt header from poisoning the localization decode."""
    async def main():
        from garage_tpu.block import ScrubWorker

        net, systems, managers, tasks = await make_block_cluster(
            tmp_path, n=6, rf=3, erasure=(4, 2)
        )
        try:
            data = os.urandom(150_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            for _ in range(100):
                held = sorted(i for m in managers for i in m.local_parts(h))
                if held == [0, 1, 2, 3, 4, 5]:
                    break
                await asyncio.sleep(0.02)
            assert held == [0, 1, 2, 3, 4, 5]

            layout = systems[0].layout_helper.current()
            placement = shard_nodes_of(layout, h, 6)
            leader = next(m for m in managers
                          if m.system.id == placement[0])

            victim = next(m for m in managers if 3 in m.local_parts(h))
            true_raw = victim.read_local_shard(h, 3)
            true_payload, _ = unpack_shard(true_raw)
            # a stray shard: wrong length AND wrong packed_len header
            stray = pack_shard(os.urandom(len(true_payload) + 512),
                               999_999)
            victim.write_local_shard(h, 3, stray)

            sw = ScrubWorker(leader)
            bad = await sw.scrub_batch([h])
            assert bad == 1
            fixed, _ = unpack_shard(victim.read_local_shard(h, 3))
            assert fixed == true_payload
            assert await sw.scrub_batch([h]) == 0
            assert await managers[1].rpc_get_block(h) == data
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_deep_scrub_repairs_rotted_header(tmp_path):
    """Header rot (ADVICE r5): the shard header's packed_len sits
    OUTSIDE the shard checksum, so a rotted header passes local
    validation AND the cross-shard parity check (parity covers payload
    bytes only) — invisible to every scrub pass before this one. Deep
    scrub must compare each shard's header against the stripe majority
    and push a rewritten shard (same payload, corrected header) to the
    disagreeing holder."""
    async def main():
        from garage_tpu.block import ScrubWorker

        net, systems, managers, tasks = await make_block_cluster(
            tmp_path, n=6, rf=3, erasure=(4, 2)
        )
        try:
            data = os.urandom(180_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            for _ in range(100):
                held = sorted(i for m in managers for i in m.local_parts(h))
                if held == [0, 1, 2, 3, 4, 5]:
                    break
                await asyncio.sleep(0.02)
            assert held == [0, 1, 2, 3, 4, 5]

            layout = systems[0].layout_helper.current()
            placement = shard_nodes_of(layout, h, 6)
            leader = next(m for m in managers
                          if m.system.id == placement[0])

            # rot shard 2's header: SAME payload, forged packed_len —
            # local checksum scrub and the parity kernel both pass
            victim = next(m for m in managers if 2 in m.local_parts(h))
            raw = victim.read_local_shard(h, 2)
            payload, true_len = unpack_shard(raw)
            victim.write_local_shard(h, 2, pack_shard(payload, 999_999))
            assert victim.read_local_shard(h, 2) is not None  # passes local

            sw = ScrubWorker(leader)
            bad = await sw.scrub_batch([h])
            # payload is intact, so this is NOT a content corruption...
            assert bad == 0
            # ...but the header was rewritten back to the majority value
            assert sw.header_repaired == 1
            fixed_payload, fixed_len = unpack_shard(
                victim.read_local_shard(h, 2))
            assert fixed_payload == payload
            assert fixed_len == true_len
            # clean second pass: nothing left to repair
            assert await sw.scrub_batch([h]) == 0
            assert sw.header_repaired == 1
            assert await managers[1].rpc_get_block(h) == data
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_deep_scrub_repairs_data_plus_parity_double_corruption(tmp_path):
    """RS(4,2) tolerates two losses; deep scrub localizes a double
    corruption of one DATA and one PARITY shard: the data exclusion
    must substitute the *healthy* parity shard (trying each in turn),
    then the re-encode fixes both."""
    async def main():
        from garage_tpu.block import ScrubWorker

        net, systems, managers, tasks = await make_block_cluster(
            tmp_path, n=6, rf=3, erasure=(4, 2)
        )
        try:
            data = os.urandom(180_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            for _ in range(100):
                held = sorted(i for m in managers for i in m.local_parts(h))
                if held == [0, 1, 2, 3, 4, 5]:
                    break
                await asyncio.sleep(0.02)
            assert held == [0, 1, 2, 3, 4, 5]

            layout = systems[0].layout_helper.current()
            placement = shard_nodes_of(layout, h, 6)
            leader = next(m for m in managers
                          if m.system.id == placement[0])

            originals = {}
            for part in (2, 4):  # data shard 2, parity shard 4
                holder = next(m for m in managers
                              if part in m.local_parts(h))
                payload, plen = unpack_shard(
                    holder.read_local_shard(h, part))
                originals[part] = (holder, payload)
                forged = bytes(b ^ 0xA5 for b in payload[:128]) \
                    + payload[128:]
                holder.write_local_shard(h, part, pack_shard(forged, plen))

            sw = ScrubWorker(leader)
            assert await sw.scrub_batch([h]) == 1
            for part, (holder, payload) in originals.items():
                fixed, _ = unpack_shard(holder.read_local_shard(h, part))
                assert fixed == payload, f"shard {part} not repaired"
            assert await sw.scrub_batch([h]) == 0
            assert await managers[0].rpc_get_block(h) == data
        finally:
            await stop_all(systems, tasks)

    run(main())


def test_deep_scrub_skips_unreachable_stripes(tmp_path):
    """A down shard holder must not wedge or fail the deep pass: the
    gather comes back short, the stripe is skipped (absence is
    resync/repair's job), and the batch completes with 0 corruptions."""
    async def main():
        from garage_tpu.block import ScrubWorker

        net, systems, managers, tasks = await make_block_cluster(
            tmp_path, n=6, rf=3, erasure=(4, 2)
        )
        try:
            data = os.urandom(100_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            for _ in range(100):
                held = sorted(i for m in managers for i in m.local_parts(h))
                if held == [0, 1, 2, 3, 4, 5]:
                    break
                await asyncio.sleep(0.02)

            layout = systems[0].layout_helper.current()
            placement = shard_nodes_of(layout, h, 6)
            leader = next(m for m in managers
                          if m.system.id == placement[0])
            # kill a NON-leader holder
            downed = next(s for s in systems
                          if s.id == placement[3])
            await downed.netapp.shutdown()

            sw = ScrubWorker(leader)
            assert await asyncio.wait_for(sw.scrub_batch([h]), 30) == 0
            assert sw.deep_checked == 0  # skipped, not silently passed
        finally:
            await stop_all(systems, tasks)

    run(main())


@pytest.mark.parametrize("due,errored,parked", [(1, 0, 0), (40, 7, 0),
                                                 (300, 20, 500)])
def test_resync_pop_due_reads_the_head_of_the_queue(tmp_path, due, errored,
                                                    parked):
    """_pop_due takes the due entries in order, re-parks those whose
    error backoff has not run out, leaves the future alone — and reads
    a bounded head of the queue a call, not all of it (a backlog of a
    thousand entries made draining it quadratic, PR 37)."""
    from garage_tpu.block.resync import BlockResyncManager
    from garage_tpu.db import open_db

    db = open_db(str(tmp_path / "db"), engine="sqlite")
    r = BlockResyncManager(None, db)
    now = time.time()
    hashes = [bytes([i % 256, i // 256]) + bytes(30) for i in range(due)]
    for i, h in enumerate(hashes):
        r.push_at(h, now - 10 + i * 1e-3)
    later = int((now + 3600) * 1000)
    for h in hashes[:errored]:
        r.errors.insert(h, (1).to_bytes(4, "big") + later.to_bytes(8, "big"))
    for i in range(parked):
        r.push_at(bytes([i % 256, i // 256, 1]) + bytes(29), now + 600 + i)
    read = []
    real_iter = r.queue.iter

    def counting_iter(*a, **kw):
        rows = list(real_iter(*a, **kw))
        read.append(len(rows))
        return iter(rows)

    r.queue.iter = counting_iter
    got = []
    while (h := r._pop_due()) is not None:
        got.append(h)
    assert got == hashes[errored:]
    assert max(read) <= 16
    # the errored ones wait at their retry time, the parked ones stay
    assert r.queue_len() == errored + parked
    assert r.due_len() == 0
    db.close()
