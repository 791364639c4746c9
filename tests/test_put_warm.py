"""A node that must serve from its device builds its PUT programs at
boot (PR 28): the all-lease RS leg asks for one program an item BUCKET
and equals the plain reference at every item count; after
`warm_put_programs` a served batch asks the compiler for nothing; and
`BlockManager.warm_device` hands its lease back.

JAX on the CPU platform stands in for the chip ([tpu] platform = "cpu").
"""

from __future__ import annotations

import asyncio
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import ec_reference  # noqa: E402
from clusterbox import ClusterBox  # noqa: E402

from garage_tpu.block.codec import ErasureCodec  # noqa: E402
from garage_tpu.block.device_backend import JaxDeviceBackend  # noqa: E402
from garage_tpu.block.feeder import DeviceFeeder  # noqa: E402
from garage_tpu.block.hostbuf import HostBufPool  # noqa: E402
from garage_tpu.ops import jaxenv  # noqa: E402
from garage_tpu.utils.config import TpuConfig  # noqa: E402

K, M, BLOCK = 10, 4, 20_000  # shards of 2,001 bytes, a 9-byte zero tail
# the process keeps every program it has built, so a test that counts
# the compiler's requests brings shapes that no other test has
BLOCK_BUCKET, BLOCK_WARM = 25_000, 30_000


def run(coro, timeout=120.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


def seeded(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def full_leases(pool: HostBufPool, n: int) -> tuple[list, list[bytes]]:
    leases, bodies = [], []
    for i in range(n):
        lease = pool.try_acquire()
        body = seeded(100 + i, pool.cap)
        lease.body_mv()[:] = body
        lease.length = pool.cap
        lease.set_scheme(0)
        leases.append(lease)
        bodies.append(body)
    return leases, bodies


def requests() -> int:
    return jaxenv.compile_stats()["compile_requests"]


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8])
def test_the_lease_leg_equals_the_reference_at_any_item_count(n):
    """Items short of the bucket are zero stripes stacked in on the
    device; every real item's fourteen shards are the reference's."""
    be = JaxDeviceBackend(codec=ErasureCodec(K, M))
    pool = HostBufPool(K, BLOCK, 8)
    leases, bodies = full_leases(pool, n)
    out = be.readback("encode_put", be.compute(
        "encode_put", be.stage("encode_put", leases)))
    assert len(out) == n
    for body, shards in zip(bodies, out):
        _, want, packed_len = ec_reference.reference_stripe(body, K, M)
        got = [ec_reference.parse_shard_file(s) for s in shards]
        assert [g[0] for g in got] == want
        assert {g[1] for g in got} == {packed_len}


def test_the_lease_leg_asks_for_programs_by_bucket():
    """Three, then four items: the same bucket, so the second batch
    asks the compiler for nothing (it asked once an item COUNT)."""
    jaxenv.setup()
    be = JaxDeviceBackend(codec=ErasureCodec(K, M))
    pool = HostBufPool(K, BLOCK_BUCKET, 4)
    leases, _ = full_leases(pool, 4)
    be.compute("encode_put", be.stage("encode_put", leases[:3]))
    seen = requests()
    be.compute("encode_put", be.stage("encode_put", leases))
    assert requests() == seen


def test_after_the_warm_up_a_served_batch_builds_nothing():
    codec = ErasureCodec(K, M)
    f = DeviceFeeder(codec=codec, mode="require",
                     tpu_cfg=TpuConfig(platform="cpu"))
    pool = HostBufPool(K, BLOCK_WARM, 8)

    async def main():
        warm = pool.try_acquire()
        warm.length = warm.cap
        try:
            await f.warm_put_programs(BLOCK_WARM, warm, max_items=5)
            warm.release()
            # warm to 5 items is warm to the bucket of 5: eight
            seen, items = requests(), f.stats["device_items"]
            assert items == 0  # the warm-up counts no item
            f.active_streams = 7
            leases, bodies = full_leases(pool, 7)
            outs = await asyncio.gather(
                *[f.encode_put(le) for le in leases],
                *[f.hash(b) for b in bodies])
        finally:
            await f.stop()
        assert requests() == seen
        assert f.stats["device_items"] == 14
        for body, shards, digest in zip(bodies, outs[:7], outs[7:]):
            want_hash, want, _ = ec_reference.reference_stripe(body, K, M)
            assert digest == want_hash
            assert [ec_reference.parse_shard_file(s)[0]
                    for s in shards] == want

    run(main())


def test_a_node_without_the_required_device_fails_its_warm_up():
    f = DeviceFeeder(codec=ErasureCodec(K, M), mode="require",
                     tpu_cfg=TpuConfig(platform="tpu"))

    async def main():
        try:
            with pytest.raises(RuntimeError, match="device required"):
                await f.warm_put_programs(BLOCK, None, max_items=1)
        finally:
            await f.stop()

    run(main())


@pytest.mark.parametrize("erasure, n", [((4, 2), 6), (None, 3)])
def test_warm_device_of_a_node(tmp_path, erasure, n):
    """Erasure: hash and encode legs through a pool lease, which goes
    back. Replicate-3: no pool, the content hash alone."""

    async def main():
        box = await ClusterBox(tmp_path, n=n, rf=3, erasure=erasure,
                               block_size=BLOCK).start()
        try:
            mgr = box.nodes[0].manager
            mgr.feeder = DeviceFeeder(
                codec=mgr.codec if erasure else None, mode="require",
                tpu_cfg=TpuConfig(platform="cpu"))
            try:
                await mgr.warm_device(BLOCK, 4)
                pool = mgr.ingest_pool(BLOCK, 4)
                if erasure:
                    assert pool.outstanding() == 0 and len(pool._free) == 4
                    assert not any(buf.any() for buf in pool._free)
                else:
                    assert pool is None
                shapes = mgr.feeder._get_backend()._shapes_seen
                assert {s[2] for s in shapes if s[0] == "hash"} == {1, 2, 4}
                assert ({s[3] for s in shapes if s[0] == "encode"}
                        == ({1, 2, 4} if erasure else set()))
            finally:
                await mgr.feeder.stop()
        finally:
            await box.stop()

    run(main())
