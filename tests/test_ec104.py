"""erasure(10,4) on fourteen shard holders in four zones (ISSUE 28): the
served path held to the plain reference (tests/ec_reference.py) on
seeded bytes, at a small size on the CPU.

Fourteen real Garage nodes on the loopback transport (clusterbox.py),
zones of 4, 4, 3 and 3 nodes as benchmark/configs/ec104-14n.json lays
them out, `zone_redundancy = maximum`, a 20,000-byte block: packed it
is 20,001 bytes, shards of 2,001 and a 9-byte zero tail, so a stripe
never divides evenly. S3 traffic goes through real `S3ApiServer`s with
the ingest pool on (the default).
"""

from __future__ import annotations

import asyncio
import http.client
import json
import os
import socket
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from clusterbox import ClusterBox  # noqa: E402
import ec_reference  # noqa: E402
from ec_reference import (  # noqa: E402
    parse_shard_file,
    reference_block,
    reference_stripe,
)
from s3util import S3Client, xml_find  # noqa: E402

from garage_tpu.api.s3.api_server import S3ApiServer  # noqa: E402
from garage_tpu.block.codec import shard_nodes_of  # noqa: E402
from garage_tpu.block.feeder import DeviceFeeder  # noqa: E402
from garage_tpu.block.hostbuf import HostBufPool  # noqa: E402
from garage_tpu.model.helper import GarageHelper, allow_all  # noqa: E402
from garage_tpu.rpc.layout.version import N_PARTITIONS  # noqa: E402
from garage_tpu.rpc.replication_mode import ReplicationMode  # noqa: E402
from garage_tpu.utils import tracing  # noqa: E402
from garage_tpu.utils.config import TpuConfig  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K, M, N = 10, 4, 14
BLOCK = 20_000
ZONES = [f"z{i % 4 + 1}" for i in range(N)]  # z1 = 0, 4, 8, 12; ...
BUCKET = "ec104"
# three parts, each two whole blocks (leased) and a ragged last one
PART_BYTES = (2 * BLOCK + 7_777, 2 * BLOCK + 1, 3 * BLOCK - 1)


def run(coro, timeout=120.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def seeded(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def zone_members(zone: str) -> list[int]:
    return [i for i, z in enumerate(ZONES) if z == zone]


def test_the_configuration_file_is_this_layout():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ec104-14n.json")) as f:
        cfg = json.load(f)
    assert cfg["zones"] == ZONES and cfg["nodes"] == N
    assert cfg["toml"]["erasure_coding"] == f"{K},{M}"
    assert cfg["toml"]["block_size"] == 1 << 20
    # the harness counts nodes from 1
    kill = cfg["guarantees"]["readback"]["then_kill"]
    assert [i - 1 for i in kill] == zone_members("z2")


class Box:
    """A box of nodes with S3 frontends on some of them: fourteen in
    four zones on erasure(10,4) unless told otherwise."""

    def __init__(self, tmp_path, s3_nodes=(0, 1), device_node=None,
                 n=N, erasure=(K, M), zones=ZONES):
        self.tmp, self.s3_nodes, self.device_node = (tmp_path, s3_nodes,
                                                     device_node)
        self.n, self.erasure, self.zones = n, erasure, zones
        self.servers: dict[int, S3ApiServer] = {}
        self.clients: dict[int, S3Client] = {}

    async def __aenter__(self) -> "Box":
        self.box = await ClusterBox(
            self.tmp, n=self.n, rf=3, erasure=self.erasure,
            zones=self.zones,
            zone_redundancy="maximum" if self.zones else None,
            block_size=BLOCK).start()
        self.ids = [nd.id for nd in self.box.nodes]
        if self.device_node is not None:
            # the staged device route, JAX on the CPU platform standing
            # in for the chip: every item of this node takes it
            mgr = self.box.nodes[self.device_node].manager
            mgr.feeder = DeviceFeeder(codec=mgr.codec, mode="require",
                                      tpu_cfg=TpuConfig(platform="cpu"))
            mgr.feeder._device_ok = True
        helper = GarageHelper(self.box.nodes[0].garage)
        key = await helper.create_key("ec104")
        bucket = await helper.create_bucket(BUCKET)
        self.bucket_id = bucket.id
        await helper.set_bucket_key_permissions(bucket.id, key.key_id,
                                                allow_all())
        for i in self.s3_nodes:
            g = self.box.nodes[i].garage
            srv = S3ApiServer(g)
            with socket.socket() as s:
                s.bind(("127.0.0.1", 0))
                port = s.getsockname()[1]
            await srv.start("127.0.0.1", port)
            self.servers[i] = srv
            self.clients[i] = S3Client(
                "127.0.0.1", port, key.key_id, key.params.secret_key,
                region=g.config.s3_region)
        return self

    async def __aexit__(self, *exc) -> None:
        for srv in self.servers.values():
            await srv.stop()
        await self.box.stop()

    async def request(self, node: int, *args, **kw):
        return await asyncio.to_thread(self.clients[node].request,
                                       *args, **kw)

    async def begin(self, node: int, name: str, parts: list[bytes]):
        """CreateMultipartUpload and an UploadPart a part. -> (upload
        id, the parts' ETags), or (None, (status, body)) of the part
        that failed."""
        path = f"/{BUCKET}/{name}"
        st, _, body = await self.request(node, "POST", path,
                                         query=[("uploads", "")])
        assert st == 200, body
        upload_id = xml_find(body, "UploadId")[0]
        etags = []
        for i, p in enumerate(parts, start=1):
            st, hdrs, body = await self.request(
                node, "PUT", path, body=p, unsigned_payload=True,
                query=[("partNumber", str(i)), ("uploadId", upload_id)])
            if st != 200:
                return None, (st, body)
            etags.append(hdrs["etag"].strip('"'))
        return upload_id, etags

    async def complete(self, node: int, name: str, upload_id: str,
                       etags: list[str]):
        """CompleteMultipartUpload -> (status, body)."""
        xml = "".join(f"<Part><PartNumber>{i}</PartNumber><ETag>\"{e}\""
                      f"</ETag></Part>" for i, e in enumerate(etags, 1))
        st, _, body = await self.request(
            node, "POST", f"/{BUCKET}/{name}",
            query=[("uploadId", upload_id)],
            body=f"<CompleteMultipartUpload>{xml}"
                 f"</CompleteMultipartUpload>".encode())
        return st, body

    async def upload(self, node: int, name: str, parts: list[bytes]):
        """Multipart: Create, UploadPart x len(parts), Complete.
        -> (status, body) of the Complete, or of the part that failed."""
        upload_id, etags = await self.begin(node, name, parts)
        if upload_id is None:
            return etags
        return await self.complete(node, name, upload_id, etags)

    async def stop_nodes(self, indices) -> None:
        await asyncio.gather(*(self.box.stop_node(self.box.nodes[i])
                               for i in indices))

    def forget_cached_blocks(self) -> None:
        """A PUT writes through to the read cache of the block's owner
        in the cluster cache tier; a read served from there gathers and
        decodes nothing."""
        for nd in self.box.live():
            nd.manager.cache.clear()
            nd.manager.packed_cache.clear()

    def decode_items(self) -> int:
        """Erasure decodes through the feeders of the live nodes: the
        reader's own, or the tier owner's that it asked."""
        return sum(nd.manager.feeder.stats["decode_items"]
                   for nd in self.box.live())


def parts_of(seed: int) -> list[bytes]:
    return [seeded(seed + i, n) for i, n in enumerate(PART_BYTES)]


def blocks_of(parts: list[bytes]) -> list[bytes]:
    return [p[o:o + BLOCK] for p in parts for o in range(0, len(p), BLOCK)]


def test_served_put_equals_the_reference_on_every_holder(tmp_path):
    """Multipart PUT through node 0, whose feeder runs the staged device
    route (JAX on the CPU platform): every node holds exactly one shard
    of every block, each shard file equal to the reference's payload at
    the index `shard_nodes_of` gives that node; any ten of the files
    give the block back through the reference's decode; the object
    reads back whole through two nodes; each partition's metadata sits
    in three distinct zones."""
    parts = parts_of(2700)

    async def main():
        async with Box(tmp_path, device_node=0) as b:
            st, body = await b.upload(0, "o", parts)
            assert st == 200, body
            mgr0 = b.box.nodes[0].manager
            lv = mgr0.system.layout_helper.current()
            zone = {nd.id: lv.node_role(nd.id).zone for nd in b.box.nodes}
            assert [zone[i] for i in b.ids] == ZONES
            for p in range(N_PARTITIONS):
                assert len({zone[n] for n in lv.nodes_of(p)}) == 3
            # stragglers of the quorum write finish behind the ack
            blocks = blocks_of(parts)
            want = [reference_stripe(blk, K, M) for blk in blocks]
            await b.box.wait(lambda: all(
                nd.manager.local_parts(h) for nd in b.box.nodes
                for h, _, _ in want), 20, "all fourteen shards landed")
            rng = np.random.default_rng(27)
            for blk, (h, shards, packed_len) in zip(blocks, want):
                place = shard_nodes_of(lv, h, N)
                assert sorted(place) == sorted(b.ids)
                assert len({zone[n] for n in place[:3]}) == 3
                files = []
                for idx, node_id in enumerate(place):
                    m = b.box.nodes[b.ids.index(node_id)].manager
                    assert m.local_parts(h) == [idx]
                    payload, plen = parse_shard_file(
                        bytes(m.read_local_shard(h, idx)))
                    assert payload == shards[idx], (idx, len(blk))
                    assert plen == packed_len == 1 + len(blk)
                    files.append(payload)
                present = sorted(rng.choice(N, K, replace=False))
                assert reference_block(present, [files[i] for i in present],
                                       K, M, packed_len) == blk
            # every block went through the device route: hash + encode
            assert mgr0.feeder.stats["device_items"] >= 2 * len(blocks)
            assert mgr0.feeder.stats["device_errors"] == 0
            assert mgr0.feeder.stats["host_reruns"] == 0
            for node in (0, 1):
                st, _, got = await b.request(node, "GET", f"/{BUCKET}/o")
                assert st == 200 and got == b"".join(parts)
            await mgr0.feeder.stop()

    run(main(), 240)


@pytest.mark.parametrize("zone", ["z1", "z2", "z3", "z4"])
def test_read_back_with_a_whole_zone_down(tmp_path, zone):
    """z1 and z2 lose four holders, z3 and z4 three; z1 takes the S3
    node itself and data shards 0.., the later zones more parity: both
    sizes of loss, data-heavy and parity-heavy patterns. The bytes that
    come back are the seeded ones and the reader decoded to get them."""
    parts = parts_of(2710 + int(zone[1]))
    whole = b"".join(parts)

    async def main():
        async with Box(tmp_path) as b:
            st, body = await b.upload(0, "o", parts)
            assert st == 200, body
            for node in (0, 1):
                st, _, got = await b.request(node, "GET", f"/{BUCKET}/o")
                assert st == 200 and got == whole
            down = zone_members(zone)
            await b.stop_nodes(down)
            reader = next(i for i in b.s3_nodes if i not in down)
            b.forget_cached_blocks()
            before = b.decode_items()
            st, _, got = await b.request(reader, "GET", f"/{BUCKET}/o")
            assert st == 200 and got == whole
            assert b.decode_items() > before

    run(main())


def test_range_gets_with_zone_z2_lost_equal_the_reference(tmp_path):
    """The deployment ec104-14n-z2lost at a small size: objects written
    with fourteen up, zone z2's four holders stopped, every part read
    as a Range GET through node 1. Each body is the seeded bytes, and
    block by block the ten shard files left on disk give the same block
    through the reference's decode, for every present-set the layout
    produces (which four indices z2 held differs from block to
    block)."""
    objects = {f"o{i}": parts_of(3700 + 10 * i) for i in range(3)}
    down = zone_members("z2")

    async def main():
        async with Box(tmp_path, s3_nodes=(0,), device_node=0) as b:
            for name, parts in objects.items():
                st, body = await b.upload(0, name, parts)
                assert st == 200, body
            lv = b.box.nodes[0].manager.system.layout_helper.current()
            stripes = {name: [reference_stripe(blk, K, M)
                              for blk in blocks_of(parts)]
                       for name, parts in objects.items()}
            await b.box.wait(lambda: all(
                nd.manager.local_parts(h) for nd in b.box.nodes
                for want in stripes.values() for h, _, _ in want),
                20, "all fourteen shards landed")
            await b.stop_nodes(down)
            b.forget_cached_blocks()
            mgr = b.box.nodes[0].manager
            before = mgr.feeder.stats["decode_device_items"]
            for name, parts in objects.items():
                at = 0
                for p in parts:
                    st, _, got = await b.request(
                        0, "GET", f"/{BUCKET}/{name}", headers={
                            "range": f"bytes={at}-{at + len(p) - 1}"})
                    assert st == 206 and got == p, (name, at)
                    at += len(p)
            assert mgr.feeder.stats["decode_device_items"] > before
            assert mgr.feeder.stats["device_errors"] == 0
            await mgr.feeder.stop()
            gone = {b.ids[i] for i in down}
            present_sets = set()
            for name, parts in objects.items():
                for blk, (h, _, packed_len) in zip(blocks_of(parts),
                                                   stripes[name]):
                    place = shard_nodes_of(lv, h, N)
                    present = [i for i, n in enumerate(place)
                               if n not in gone]
                    assert len(present) == K  # exactly m lost, none spare
                    files = []
                    for idx in present:
                        m = b.box.nodes[b.ids.index(place[idx])].manager
                        payload, plen = parse_shard_file(
                            bytes(m.read_local_shard(h, idx)))
                        assert plen == packed_len
                        files.append(payload)
                    assert reference_block(present, files, K, M,
                                           packed_len) == blk
                    present_sets.add(tuple(present))
            return present_sets

    present_sets = run(main(), 240)
    # more than one pattern, and a data shard among the lost in some
    assert len(present_sets) > 1
    assert any(ps[K - 1] >= K for ps in present_sets)


def test_five_holders_gone_fails_cleanly(tmp_path):
    """The guarantee's other edge: ten shards are needed and nine are
    left. The block read raises, and the S3 GET answers an error or
    breaks off; it never returns a body, and it does not hang."""
    parts = parts_of(2720)

    async def main():
        async with Box(tmp_path) as b:
            st, body = await b.upload(0, "o", parts)
            assert st == 200, body
            await b.stop_nodes(zone_members("z2") + [3])
            mgr = b.box.nodes[0].manager
            b.forget_cached_blocks()
            h, _, _ = reference_stripe(blocks_of(parts)[0], K, M)
            with pytest.raises(Exception) as e:
                await asyncio.wait_for(mgr.rpc_get_block(h), 30)
            assert not isinstance(e.value, asyncio.TimeoutError)
            try:
                st, _, got = await asyncio.wait_for(
                    b.request(0, "GET", f"/{BUCKET}/o"), 60)
            except (http.client.HTTPException, OSError):
                return  # broken off mid-body
            assert st >= 500 and b"".join(parts) not in got

    run(main())


def test_quorums_of_10_4():
    rm = ReplicationMode.parse(3, erasure="10,4")
    assert rm.storage_width == N
    assert rm.block_read_need == K
    assert rm.block_write_quorum == 12  # as ec104-14n.json states it
    assert (rm.read_quorum, rm.write_quorum) == (2, 2)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ec104-14n.json")) as f:
        said = json.load(f)["guarantees"]["block_write_quorum"]
    assert said.startswith(f"{rm.block_write_quorum} of {rm.storage_width} ")


def test_write_quorum_edges(tmp_path):
    """12 of 14: with 14 - 12 = 2 holders down a PUT is acknowledged
    and reads back; with one more down it is refused. The three are of
    one zone, so no partition loses more than one metadata copy and the
    refusal is the block quorum's."""
    quorum = ReplicationMode.parse(3, erasure="10,4").block_write_quorum
    down = zone_members("z4")
    assert len(down) == N - quorum + 1
    a, c = parts_of(2730), parts_of(2740)

    async def main():
        async with Box(tmp_path) as b:
            await b.stop_nodes(down[:N - quorum])
            st, body = await b.upload(0, "a", a)
            assert st == 200, body
            for node in (0, 1):
                st, _, got = await b.request(node, "GET", f"/{BUCKET}/a")
                assert st == 200 and got == b"".join(a)
            await b.stop_nodes(down[N - quorum:])
            st, body = await b.upload(0, "c", c)
            assert st >= 500, (st, body)
            st, _, _ = await b.request(0, "GET", f"/{BUCKET}/c")
            assert st == 404  # never completed, never visible

    run(main())


@pytest.mark.parametrize("k, block_size", [(10, 1 << 20), (10, BLOCK),
                                           (4, 1 << 20)])
def test_a_full_lease_is_the_stripe(k, block_size):
    """`stripe()` of a full lease is the reference's own split of
    prefix + body,
    and the tail past the body is still zero when the buffer comes
    round again: (10, 1 MiB) is 10 x 104,858 with a 3-byte tail."""
    pool = HostBufPool(k, block_size, 1)
    if (k, block_size) == (10, 1 << 20):
        assert (pool.slen, k * pool.slen - 1 - block_size) == (104_858, 3)

    async def main():
        for seed in (1, 2):
            lease = await pool.acquire()
            body = seeded(seed, block_size)
            lease.body_mv()[:] = body
            lease.length = block_size
            lease.set_scheme(0)
            assert lease.full
            want = ec_reference.split(b"\0" + body, k)
            assert np.array_equal(lease.stripe(), want)
            assert not lease.buf[1 + block_size:].any()
            lease.release()

    run(main())


# ---- the series and the span the deployment brought ----------------------


def unrendered_series_of(name: str) -> set[str]:
    """The series layer_metrics/<name>.json reads that /metrics would
    not show now."""
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           f"{name}.json")) as f:
        params = json.load(f)["params"]
    rendered = {line.split("{")[0].split(" ")[0]
                for line in registry().render()}
    return {t["series"] for side in ("num", "den")
            for t in params[side]} - rendered


@pytest.mark.parametrize("mode, n, erasure", [
    ("erasure", 6, (4, 2)), ("replicate", 3, None)])
def test_block_write_series_and_span(tmp_path, mode, n, erasure):
    """`block_write_seconds{mode}` moves once a block, in both modes,
    under the names write_fanout_ms.json reads; the
    `block.write_shards` span carries width and quorum."""
    reg = registry()
    before = reg.totals("block_write_seconds", mode=mode)
    tracing.tracer.enabled = True
    tracing.tracer.ring.clear()

    async def main():
        box = await ClusterBox(tmp_path, n=n, rf=3, erasure=erasure).start()
        try:
            mgr = box.nodes[0].manager
            for seed in (1, 2):
                data = seeded(seed, 70_000)
                await mgr.rpc_put_block(await mgr.hash_block(data), data)
            return mgr.codec.width, mgr.codec.write_quorum
        finally:
            await box.stop()

    try:
        width, quorum = run(main())
        spans = [r for r in tracing.tracer.ring
                 if r["name"] == "block.write_shards"]
    finally:
        tracing.tracer.enabled = False
        tracing.tracer.ring.clear()
    count, seconds = reg.totals("block_write_seconds", mode=mode)
    assert count - before[0] == 2 and seconds > before[1]
    assert len(spans) == 2
    assert all(s["attrs"] == {"width": width, "quorum": quorum}
               for s in spans)
    assert not unrendered_series_of("write_fanout_ms")


def test_ingest_wait_series():
    """`s3_ingest_wait_seconds` is observed on every acquisition, 0
    when a buffer was free, under the names ingest_wait_ms.json reads;
    `s3_ingest_buf_wait` still counts the parks."""
    reg = registry()
    pool = HostBufPool(K, BLOCK, 1)
    before = reg.totals("s3_ingest_wait_seconds")
    parks = reg.totals("s3_ingest_buf_wait")[0]

    async def main():
        first = await pool.acquire()
        count, seconds = reg.totals("s3_ingest_wait_seconds")
        assert (count - before[0], seconds - before[1]) == (1, 0.0)
        waiter = asyncio.create_task(pool.acquire())
        await asyncio.sleep(0.05)
        first.release()
        (await waiter).release()

    run(main())
    count, seconds = reg.totals("s3_ingest_wait_seconds")
    assert count - before[0] == 2 and seconds - before[1] >= 0.04
    assert reg.totals("s3_ingest_buf_wait")[0] - parks == 1
    assert not unrendered_series_of("ingest_wait_ms")
