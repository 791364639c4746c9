"""Compressible blocks in erasure(4,2), as the deployment ec42-6n3z-zstd
stores them: a block that shrinks at zstd level 1 leaves its ingest
lease and reaches RS encode as a shorter zstd frame, which the backend
stages into the same (k, full shard length) stripe as a lease, so one
launch holds both and takes the program the boot built.

Seeded blocks of three classes (uniform random bytes, Zipf text, JSON
log records) go through `BlockManager.rpc_put_block` on the staged
device route, JAX on the CPU platform standing in for the chip; every
holder's shard is held to the plain reference (tests/ec_reference.py),
which checks a compressed block by decompressing its frame with
`zstandard` itself; the blocks read back with m = 2 holders down. Then:
after the boot warm-up none of this asks the compiler for anything,
and the compress step has its span and series.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys

import numpy as np
import pytest
import zstandard

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from clusterbox import ClusterBox  # noqa: E402
from ec_reference import (  # noqa: E402
    SCHEME_PLAIN,
    SCHEME_ZSTD,
    check_stored_stripe,
    parse_shard_file,
)

from garage_tpu.block.codec import ErasureCodec, shard_nodes_of  # noqa: E402
from garage_tpu.block.device_backend import (  # noqa: E402
    JaxDeviceBackend,
    bucket_len,
)
from garage_tpu.block.feeder import DeviceFeeder  # noqa: E402
from garage_tpu.block.hostbuf import HostBufPool  # noqa: E402
from garage_tpu.ops import jaxenv, treehash  # noqa: E402
from garage_tpu.utils import tracing  # noqa: E402
from garage_tpu.utils.config import TpuConfig  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K, M, N = 4, 2, 6
# packed 45,001 bytes: shards of 11,251 in the 16,384 bucket; no other
# test of the suite launches this length, so the compiler's requests
# counted below are this file's own
BLOCK = 45_000
LEASES = 8
WORDS = [bytes(w) for w in np.random.default_rng(0x70CA).integers(
    ord("a"), ord("z") + 1, (2_000, 7), dtype=np.uint8)]


def run(coro, timeout=180.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def random_block(seed: int, n: int = BLOCK) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def text_block(seed: int, n: int = BLOCK) -> bytes:
    """Words drawn Zipf(1.15) from a fixed vocabulary: ~3x at level 1."""
    rng = np.random.default_rng(seed)
    p = np.arange(1, len(WORDS) + 1) ** -1.15
    idx = rng.choice(len(WORDS), n // 4, p=p / p.sum())
    return b" ".join(WORDS[i][:2 + i % 6] for i in idx)[:n]


def json_block(seed: int, n: int = BLOCK) -> bytes:
    """JSON log records: ~6x at level 1."""
    rng = np.random.default_rng(seed)
    t, out = 1_700_000_000_000, []
    while sum(map(len, out)) < n:
        t += int(rng.integers(0, 40))
        out.append(json.dumps({
            "ts": t, "host": f"web-{rng.integers(0, 16):02d}",
            "status": int(rng.choice([200, 200, 200, 404, 500])),
            "bytes": int(rng.integers(100, 60_000)),
            "path": f"/api/v1/items/{rng.integers(0, 100_000)}"}).encode()
            + b"\n")
    return b"".join(out)[:n]


MAKE = {"random": random_block, "text": text_block, "json": json_block}
SCHEME = {"random": SCHEME_PLAIN, "text": SCHEME_ZSTD, "json": SCHEME_ZSTD}


def requests() -> int:
    return jaxenv.compile_stats()["compile_requests"]


class Zstd42:
    """Six nodes in erasure(4,2), node 0 on the staged device route with
    its boot warm-up done, and whatever `encode_put` launches it made."""

    def __init__(self, tmp_path):
        self.tmp = tmp_path
        self.launches: list[list[str]] = []

    async def __aenter__(self) -> "Zstd42":
        self.box = await ClusterBox(self.tmp, n=N, rf=3, erasure=(K, M),
                                    block_size=BLOCK).start()
        self.ids = [nd.id for nd in self.box.nodes]
        mgr = self.mgr = self.box.nodes[0].manager
        # one device, as a node has: the suite's eight virtual CPU
        # devices would send eight items through the mesh path
        f = mgr.feeder = DeviceFeeder(
            codec=mgr.codec, mode="require",
            tpu_cfg=TpuConfig(platform="cpu", mesh_min_items=10 ** 6))
        await mgr.warm_device(BLOCK, LEASES, 1)
        self.pool = mgr.ingest_pool(BLOCK, LEASES)
        be = f._get_backend()
        stage = be.stage

        def noted(op, blobs):
            if op == "encode_put":
                self.launches.append(["lease" if hasattr(b, "stripe")
                                      else "bytes" for b in blobs])
            return stage(op, blobs)

        be.stage = noted
        return self

    async def __aexit__(self, *exc) -> None:
        await self.mgr.feeder.stop()
        await self.box.stop()

    async def put(self, blocks: list[bytes], leased: list[bool]) -> list:
        """PUT the blocks at once, each a lease or bytes as told; they
        line up in one launch. -> their content hashes."""
        f = self.mgr.feeder
        f.active_streams, f.batch_linger = len(blocks), 5.0
        hashes = [treehash.blake3_py(b) for b in blocks]

        async def one(h, blk, lease):
            if not lease:
                return await self.mgr.rpc_put_block(h, blk)
            lease = self.pool.try_acquire()
            lease.body_mv()[:len(blk)] = blk
            lease.length = len(blk)
            try:
                await self.mgr.rpc_put_block(h, lease)
            finally:
                lease.release()

        try:
            await asyncio.gather(*map(one, hashes, blocks, leased))
        finally:
            f.active_streams = 0
        await self.box.wait(lambda: all(
            nd.manager.local_parts(h) for nd in self.box.nodes
            for h in hashes), 20, "all six shards landed")
        return hashes

    def stored(self, h: bytes) -> tuple[list[bytes], int]:
        """-> (the six shard payloads in shard-index order, the packed
        length every shard file states)."""
        lv = self.mgr.system.layout_helper.current()
        payloads, plens = [], set()
        for idx, node_id in enumerate(shard_nodes_of(lv, h, N)):
            m = self.box.nodes[self.ids.index(node_id)].manager
            payload, plen = parse_shard_file(bytes(m.read_local_shard(h, idx)))
            payloads.append(payload)
            plens.add(plen)
        assert len(plens) == 1
        return payloads, plens.pop()

    async def read_degraded(self, hashes: list) -> list[bytes]:
        """Nodes 4 and 5 stopped: each block gathered from the four
        holders left and decoded on node 0 (no cache, no tier)."""
        await asyncio.gather(*(self.box.stop_node(self.box.nodes[i])
                               for i in (4, 5)))
        return [await self.mgr.rpc_get_block(h, cacheable=False)
                for h in hashes]


def test_compressed_and_plain_blocks_equal_the_reference(tmp_path):
    """Three blocks of each class, a random and a text tail as bytes:
    leases and frames in one launch. Every holder's shard is the
    reference's (data shards at their real length, the compressed
    frame decompressing to the block, the parity the reference's
    encode); every block reads back with two holders down."""
    names = ["random", "text", "json"] * 3
    blocks = [MAKE[c](4300 + i) for i, c in enumerate(names)]
    leased = [True] * len(blocks)
    names += ["random", "text"]
    blocks += [random_block(4390, 7_777), text_block(4391, 20_001)]
    leased += [False, False]

    async def main():
        async with Zstd42(tmp_path) as z:
            # r t j r t | j r t j + the two tails
            hashes = (await z.put(blocks[:5], leased[:5])
                      + await z.put(blocks[5:], leased[5:]))
            assert [sorted(set(le)) for le in z.launches] == [
                ["bytes", "lease"]] * 2
            lv = z.mgr.system.layout_helper.current()
            lost_data = 0
            for name, blk, h in zip(names, blocks, hashes):
                payloads, plen = z.stored(h)
                assert check_stored_stripe(blk, payloads, K, M,
                                           plen) == SCHEME[name], name
                assert {len(p) for p in payloads} == {-(-plen // K)}
                if name != "random":
                    assert plen < len(blk) // 2
                place = shard_nodes_of(lv, h, N)
                lost_data += any(place.index(z.ids[i]) < K for i in (4, 5))
            before = z.mgr.feeder.stats["decode_device_items"]
            assert await z.read_degraded(hashes) == blocks
            assert lost_data > 0
            assert z.mgr.feeder.stats["decode_device_items"] > before
            assert z.mgr.feeder.stats["device_errors"] == 0

    run(main())


def test_after_the_warm_up_compressed_blocks_build_nothing(tmp_path):
    """A launch of each class alone, then one that mixes them with a
    short tail, then degraded reads of them all: the compiler is asked
    for nothing the boot did not build."""
    singles = [MAKE[c](4400 + i) for i, c in enumerate(MAKE)]
    mixed = [MAKE[c](4410 + i) for i, c in enumerate(MAKE)] + [
        json_block(4419, 9_999)]

    async def main():
        async with Zstd42(tmp_path) as z:
            seen = requests()
            hashes = []
            for blk in singles:
                hashes += await z.put([blk], [True])
            hashes += await z.put(mixed, [True, True, True, False])
            assert requests() == seen
            assert len(z.launches) == 4 and len(z.launches[-1]) == 4
            assert await z.read_degraded(hashes) == singles + mixed
            assert requests() == seen

    run(main())


def test_a_frame_is_staged_as_a_zero_tailed_stripe():
    """The backend alone. A lease, a compressed frame and a short plain
    body are one stripe launch, nothing re-packed, at the bucket of the
    full block's shard length: the warm-up's (`stripe_len`), else the
    lease's own. Only a launch without a lease on a backend whose
    warm-up never ran is packed at its items' own lengths."""
    pool = HostBufPool(K, BLOCK, 1)
    body = random_block(4500)
    lease = pool.try_acquire()
    lease.body_mv()[:] = body
    lease.length = BLOCK
    lease.set_scheme(SCHEME_PLAIN)
    frame = zstandard.ZstdCompressor(level=1).compress(text_block(4501))
    short = random_block(4502, 3_000)
    items = [lease, (bytes([SCHEME_ZSTD]), frame),
             (bytes([SCHEME_PLAIN]), short)]
    plain = [body, text_block(4501), short]
    full = bucket_len(pool.slen)
    try:
        for stripe_len, first, smax_want in (
                (pool.slen, 0, full), (0, 0, full),
                (0, 1, bucket_len(-(-(1 + len(frame)) // K)))):
            be = JaxDeviceBackend(codec=ErasureCodec(K, M))
            be.stripe_len = stripe_len
            copies = registry().totals("s3_put_copy_bytes",
                                       path="stage_pack")[1]
            staged = be.stage("encode_put", items[first:])
            _blocks, _slens, batch, _dev, _mesh, smax = staged[2]
            assert registry().totals("s3_put_copy_bytes",
                                     path="stage_pack")[1] == copies
            assert smax == smax_want
            assert isinstance(batch, list) == (not first)
            out = be.readback("encode_put", be.compute("encode_put", staged))
            for blk, shards in zip(plain[first:], out):
                files = [parse_shard_file(s) for s in shards]
                check_stored_stripe(blk, [p for p, _ in files], K, M,
                                    files[0][1])
    finally:
        lease.release()


# ---- the compress step's span and series --------------------------------


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


@pytest.mark.parametrize("erasure, n", [((K, M), N), (None, 3)])
def test_the_compress_step_span_and_series(tmp_path, erasure, n):
    """One text block and one random block, in both modes: a
    `block.compress` span under each `block.put` with the bytes in, the
    bytes stored and the scheme; `block_compress_seconds{scheme}` once
    a block; `block_compress_bytes{dir}` in and out; one block stored a
    scheme, under the names compress_ms.json, compress_ratio.json and
    the cell's `correct` rules read."""
    reg = registry()
    blocks = {"zstd": text_block(4600), "none": random_block(4601)}
    frame = len(zstandard.ZstdCompressor(level=1).compress(blocks["zstd"]))
    before = {s: (reg.totals("block_compress_seconds", scheme=s),
                  reg.totals("block_put_blocks", scheme=s)[0])
              for s in blocks}
    bytes_before = {d: reg.totals("block_compress_bytes", dir=d)[1]
                    for d in ("in", "out")}
    tracing.tracer.enabled = True
    tracing.tracer.ring.clear()

    async def main():
        box = await ClusterBox(tmp_path, n=n, rf=3, erasure=erasure,
                               block_size=BLOCK).start()
        try:
            mgr = box.nodes[0].manager
            for blk in blocks.values():
                await mgr.rpc_put_block(treehash.blake3_py(blk), blk)
                assert await mgr.rpc_get_block(
                    treehash.blake3_py(blk), cacheable=False) == blk
        finally:
            await box.stop()

    try:
        run(main())
        spans = list(tracing.tracer.ring)
    finally:
        tracing.tracer.enabled = False
        tracing.tracer.ring.clear()
    puts = {s["span"] for s in spans if s["name"] == "block.put"}
    comp = [s for s in spans if s["name"] == "block.compress"]
    assert len(puts) == len(comp) == 2
    assert all(s["parent"] in puts for s in comp)
    assert [s["attrs"] for s in comp] == [
        {"size": BLOCK, "out": frame, "scheme": "zstd"},
        {"size": BLOCK, "out": BLOCK, "scheme": "none"}]
    for s, ((count, seconds), stored) in before.items():
        now = reg.totals("block_compress_seconds", scheme=s)
        assert now[0] - count == 1 and now[1] > seconds
        assert reg.totals("block_put_blocks", scheme=s)[0] - stored == 1
    assert (reg.totals("block_compress_bytes", dir="in")[1]
            - bytes_before["in"]) == 2 * BLOCK
    assert (reg.totals("block_compress_bytes", dir="out")[1]
            - bytes_before["out"]) == frame + BLOCK
    assert not unrendered_series_of("compress_ms")
    assert not unrendered_series_of("compress_ratio")
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "put-mp16x8-zstd.json")) as f:
        rules = json.load(f)["correct"]
    rendered = list(reg.render())
    for r in rules:
        if not r["series"].startswith("block_"):
            continue  # the feeder's own series, rendered apart
        labels = ",".join(f'{k}="{v}"' for k, v in r.get("labels", {}).items())
        want = f"{r['series']}{{{labels}}}" if labels else r["series"]
        assert any(line.startswith(want) for line in rendered), want


def test_a_compressed_block_is_decompressed_once_a_read(tmp_path,
                                                        monkeypatch):
    """`DataBlock.verify` hands back the plain bytes it checked, so a
    GET of a compressed block runs zstd once, not once to verify and
    again to return; a corrupt frame still raises CorruptData."""
    from garage_tpu.block import block as block_mod
    from garage_tpu.utils.error import CorruptData

    made = []

    class Counting:
        ZstdCompressor, ZstdError = (zstandard.ZstdCompressor,
                                     zstandard.ZstdError)

        @staticmethod
        def ZstdDecompressor():
            made.append(1)
            return zstandard.ZstdDecompressor()

    monkeypatch.setattr(block_mod, "zstandard", Counting)
    blk = text_block(4700)
    h = treehash.blake3_py(blk)
    frame = zstandard.ZstdCompressor(level=1).compress(blk)
    assert block_mod.DataBlock(SCHEME_ZSTD, frame).verify(h) == blk
    with pytest.raises(CorruptData):
        block_mod.DataBlock(SCHEME_ZSTD, frame[:-9]).verify(h)

    async def main():
        box = await ClusterBox(tmp_path, n=N, rf=3, erasure=(K, M),
                               block_size=BLOCK).start()
        try:
            mgr = box.nodes[0].manager
            await mgr.rpc_put_block(h, blk)
            made.clear()
            assert await mgr.rpc_get_block(h, cacheable=False) == blk
            return len(made)
        finally:
            await box.stop()

    assert run(main()) == 1
