"""Staged device pipeline: overlap, padded launches, mesh sharding,
watchdog hang-fallback and lifecycle (ISSUE 12).

Everything here runs on this deviceless box: the stub backend
(block/device_backend.py StubDeviceBackend) emulates transfer/compute
latency deterministically over the host kernels, and the jax backend's
"device" is the cpu platform (conftest pins JAX_PLATFORMS=cpu with 8
virtual devices), which exercises the real staging/padding/mesh code
paths — the routing and pipelining, not the silicon, are under test.
"""

from __future__ import annotations

import asyncio
import os
import time

import pytest

from garage_tpu.block import feeder as fmod
from garage_tpu.block import host_legs
from garage_tpu.block.codec import ErasureCodec
from garage_tpu.block.device_backend import (StubDeviceBackend,
                                             bucket_items, bucket_len)
from garage_tpu.block.feeder import DeviceFeeder, _Item
from garage_tpu.utils.data import blake3sum


def run(coro):
    return asyncio.run(coro)


# ---------------------------------------------------------------------------
# overlap proof (acceptance criterion): wall < serial sum of stage sleeps
# ---------------------------------------------------------------------------


def test_pipeline_overlap_beats_serial_sum():
    """With depth-2 in-flight batches and per-stage latencies of
    `fixed_s` each, N batches must complete in measurably less wall
    time than the serial sum N * (h2d + compute + d2h) — the pinned
    proof that transfer overlaps compute instead of the old one
    blocking hop per batch."""
    fixed = 0.04
    nbatches = 4
    stub = StubDeviceBackend(None, h2d_gbps=1e6, compute_gbps=1e6,
                             d2h_gbps=1e6, fixed_s=fixed)
    # max_batch=1: every submission is its own batch, so the queue
    # can't coalesce the four items into one launch
    f = DeviceFeeder(mode="require", max_batch=1, backend=stub)
    f._device_ok = True
    blobs = [os.urandom(1024) for _ in range(nbatches)]
    serial_sum = nbatches * 3 * fixed

    async def go():
        t0 = time.perf_counter()
        digs = await asyncio.gather(*[f.hash(b) for b in blobs])
        wall = time.perf_counter() - t0
        assert list(digs) == [blake3sum(b) for b in blobs]
        stats = dict(f.stats)
        ps = f.pipeline_stats()
        await f.stop()
        return wall, stats, ps

    wall, stats, ps = run(go())
    assert stats["device_items"] == nbatches
    assert stats["device_batches"] == nbatches
    # pipelined ideal here is ~(N+2)*fixed = 0.24s vs serial 0.48s;
    # the 0.85 margin absorbs CI scheduling noise while still failing
    # hard if the pipeline ever degrades to one-batch-at-a-time
    assert wall < serial_sum * 0.85, (wall, serial_sum)
    # busy/wall > 1 is only possible when stages of different batches
    # genuinely ran concurrently
    assert ps["overlap_efficiency"] > 1.0, ps
    assert ps["wall_s"] > 0


# ---------------------------------------------------------------------------
# watchdog: mid-pipeline hang with depth-2 in flight
# ---------------------------------------------------------------------------


def _auto_feeder_on_stub(monkeypatch, stub, **kw) -> DeviceFeeder:
    """mode="auto" feeder on the stub: the stub says what it is at the
    first request, the route opens and every queued batch takes it.
    conftest exports GARAGE_TPU_DEVICE=off, which would turn auto into
    off."""
    monkeypatch.delenv("GARAGE_TPU_DEVICE", raising=False)
    return DeviceFeeder(mode="auto", backend=stub, **kw)


def test_pipeline_hang_reruns_all_inflight_host_side(monkeypatch):
    """mode="auto", injected device hang with two batches in flight:
    BOTH re-run host-side, every caller future resolves with a correct
    digest, the device path is shut and says why — and nothing is
    written anywhere outside the process."""
    stub = StubDeviceBackend(None, fixed_s=0.01)
    stub.hang_stage = "compute"  # next batch entering compute wedges
    f = _auto_feeder_on_stub(monkeypatch, stub, max_batch=4)
    f.batch_timeout = 1.0  # shrink the 300 s watchdog for the test
    blobs = [os.urandom(65536) for _ in range(8)]

    async def go():
        t0 = time.perf_counter()
        digs = await asyncio.gather(*[f.hash(b) for b in blobs])
        wall = time.perf_counter() - t0
        dev_ok = f._device_ok
        await f.stop()
        return digs, wall, dev_ok

    digs, wall, dev_ok = run(go())
    # no caller future lost, results correct via the host re-run
    assert list(digs) == [blake3sum(b) for b in blobs]
    # the sibling batch must NOT have waited out its own full watchdog
    # on top of the first one's: the abort event fails it over at once
    assert wall < 2 * f.batch_timeout + 1.0
    assert dev_ok is False  # device path shut
    assert f.route == "host" and "stuck" in f.route_reason
    assert f.stats["device_items"] == 0  # nothing credited to the device
    assert f.stats["host_reruns"] >= 1
    assert f.stats["device_errors"] >= 1


# ---------------------------------------------------------------------------
# mode="require": no host result may stand in for a device error
# ---------------------------------------------------------------------------


class _HostLegSpy:
    """Counts the op groups a feeder runs on the host."""

    def __init__(self, f: DeviceFeeder):
        self.calls = 0
        real = f._exec_group

        def spy(*a):
            self.calls += 1
            return real(*a)

        f._exec_group = spy


class _RaisingStub(StubDeviceBackend):
    """Device whose compute stage raises — a compile error, an
    out-of-memory launch, a kernel the chip refuses."""

    def compute(self, op, staged):
        raise MemoryError("RESOURCE_EXHAUSTED: out of HBM (injected)")


def test_require_device_error_fails_items_and_runs_no_host_leg():
    """Under "require" a device leg that raises fails the caller's
    future with the device's own error, credits nothing to the device
    and runs no host leg."""
    f = DeviceFeeder(mode="require", max_batch=4, backend=_RaisingStub(None))
    spy = _HostLegSpy(f)

    async def go():
        res = await asyncio.gather(*[f.hash(os.urandom(4096))
                                     for _ in range(4)],
                                   return_exceptions=True)
        await f.stop()
        return res

    res = run(go())
    assert all(isinstance(r, MemoryError) and "out of HBM" in str(r)
               for r in res), res
    assert spy.calls == 0
    assert f.stats["device_items"] == 0
    assert f.stats["host_reruns"] == 0
    assert f.stats["device_errors"] >= 1


def test_auto_device_error_reruns_on_host(monkeypatch):
    """Under "auto" the same failing device leg is re-run on the host:
    correct results, counted in host_reruns."""
    f = _auto_feeder_on_stub(monkeypatch, _RaisingStub(None), max_batch=4)
    spy = _HostLegSpy(f)
    blobs = [os.urandom(4096) for _ in range(4)]

    async def go():
        digs = await asyncio.gather(*[f.hash(b) for b in blobs])
        await f.stop()
        return digs

    assert list(run(go())) == [blake3sum(b) for b in blobs]
    assert spy.calls >= 1
    assert f.stats["host_reruns"] >= 1
    assert f.stats["device_items"] == 0


def test_require_hang_fails_items_with_timeout_not_host_result():
    """Under "require" a hung device stage fails its items with a
    TimeoutError naming the op; no host leg runs, and the next request
    is refused at once with the reason."""
    stub = StubDeviceBackend(None, fixed_s=0.01)
    stub.hang_stage = "compute"
    f = DeviceFeeder(mode="require", max_batch=4, backend=stub)
    f.batch_timeout = 0.5
    spy = _HostLegSpy(f)

    async def go():
        res = await asyncio.gather(*[f.hash(os.urandom(2048))
                                     for _ in range(3)],
                                   return_exceptions=True)
        try:
            await f.hash(os.urandom(2048))
            later = None
        except RuntimeError as e:
            later = str(e)
        await f.stop()
        return res, later

    res, later = run(go())
    assert all(isinstance(r, TimeoutError) and "stuck" in str(r)
               for r in res), res
    assert spy.calls == 0
    assert f.route == "refused"
    assert later is not None and "device required" in later \
        and "stuck" in later


def test_require_mesh_failure_is_an_error(monkeypatch):
    """Several devices are visible (conftest: 8 virtual cpu devices) and
    the mesh cannot be built: the leg that asked for it fails — no
    quiet single-device launch."""
    from garage_tpu.parallel import mesh as pmesh

    def boom(*a, **k):
        raise RuntimeError("mesh build failed (injected)")

    monkeypatch.setattr(pmesh, "data_plane_mesh", boom)
    codec = ErasureCodec(4, 2, use_jax=False)
    f = DeviceFeeder(codec=codec, mode="require", max_batch=16)
    f._device_ok = True
    blocks = [os.urandom(3000) for _ in range(f.mesh_min_items)]

    async def go():
        batch = [_Item("encode", b, asyncio.get_running_loop()
                       .create_future()) for b in blocks]
        res = await f._run_batch_staged(batch)
        await f.stop()
        return res

    res = run(go())
    assert all(isinstance(r, RuntimeError) and "mesh build failed" in str(r)
               for r in res), res
    assert f.stats["mesh_batches"] == 0 and f.stats["device_items"] == 0


def test_stage_executor_never_runs_cancelled_queued_jobs():
    """A job cancelled while still QUEUED behind a slow sibling must
    never execute — stage fns carry side effects (the d2h MD5 lane
    advance), and running one after its batch already failed over to
    the host path would apply them twice (review finding: silent ETag
    corruption)."""
    from garage_tpu.block.device_backend import StageExecutor

    async def go():
        loop = asyncio.get_running_loop()
        ex = StageExecutor("d2h", {"d2h": 0.0})
        ran = []
        slow = ex.submit(loop, lambda: time.sleep(0.15))
        victim = ex.submit(loop, lambda: ran.append("side-effect"))
        victim.fut.cancel()  # abandoned while queued
        await asyncio.wait({slow.fut})
        assert slow.claimed and slow.busy >= 0.1
        await asyncio.sleep(0.1)  # give the worker time to (not) run it
        assert ran == [], "cancelled queued job executed its side effect"
        assert victim.claimed is False

    run(go())


def test_hash_md5_hang_fallback_advances_etag_exactly_once(monkeypatch):
    """mode="auto", depth-2 hash_md5 batches, device hang mid-pipeline:
    both re-run host-side and every serial MD5 ETag chain advances
    EXACTLY once (hashlib parity) — the side-effecting edition of the
    hang test."""
    import hashlib

    from garage_tpu import native

    if not native.available():
        pytest.skip("no native toolchain")
    stub = StubDeviceBackend(None, fixed_s=0.01)
    stub.hang_stage = "compute"
    f = _auto_feeder_on_stub(monkeypatch, stub, max_batch=2)
    f.batch_timeout = 1.0
    f.active_streams = 4
    blobs = [os.urandom(4096) for _ in range(4)]
    accs = [native.Md5() for _ in blobs]
    refs = [hashlib.md5() for _ in blobs]

    async def go():
        digs = await asyncio.gather(*[
            f.hash_with_md5(b, a) for b, a in zip(blobs, accs)])
        await f.stop()
        return digs

    digs = run(go())
    for r, b in zip(refs, blobs):
        r.update(b)
    assert list(digs) == [blake3sum(b) for b in blobs]
    assert [a.hexdigest() for a in accs] == [r.hexdigest() for r in refs]
    assert f._device_ok is False


def test_stop_with_inflight_batches_resolves_every_future():
    """stop() while depth-2 batches sit mid-stage: every waiter gets
    RuntimeError("feeder stopped") (or its result), nothing hangs."""
    stub = StubDeviceBackend(None, fixed_s=0.2)
    f = DeviceFeeder(mode="require", max_batch=1, backend=stub)
    f._device_ok = True

    async def go():
        tasks = [asyncio.create_task(f.hash(os.urandom(2048)))
                 for _ in range(3)]
        await asyncio.sleep(0.05)  # let two enter the pipeline
        await f.stop()
        outcomes = []
        for t in tasks:
            try:
                outcomes.append(await asyncio.wait_for(t, 2.0))
            except RuntimeError as e:
                assert "feeder stopped" in str(e)
                outcomes.append(None)
            except asyncio.TimeoutError:
                raise AssertionError("caller future stranded by stop()")
        return outcomes

    outcomes = run(go())
    assert len(outcomes) == 3


# ---------------------------------------------------------------------------
# the route: a function of the mode and the one device verdict
# ---------------------------------------------------------------------------


class _FakeDevice(StubDeviceBackend):
    """A device that is asked: its verdict names `platform`, or nothing
    answers. (The stub proper is accepted by name and never asked.)"""

    name = "fake"

    def __init__(self, platform):
        super().__init__(None, h2d_gbps=1e6, compute_gbps=1e6, d2h_gbps=1e6)
        self.platform = platform

    def verdict(self) -> dict:
        if self.platform is None:
            raise RuntimeError("no chip here (injected)")
        return {"platform": self.platform, "device_kind": "fake", "count": 1}


_WRONG = "platform 'cpu' found where 'tpu' is required"
_NONE = "no device answered (RuntimeError: no chip here (injected))"


@pytest.mark.parametrize("mode,platform,route,reason,where", [
    ("off", "tpu", "host", "mode off", "host"),
    ("require", "tpu", "device", "1 x fake (tpu)", "device"),
    ("require", "cpu", "refused", _WRONG, "nowhere"),
    ("require", None, "refused", _NONE, "nowhere"),
    ("auto", "tpu", "device", "1 x fake (tpu)", "device"),
    ("auto", "cpu", "host", _WRONG, "host"),
    ("auto", None, "host", _NONE, "host"),
])
def test_route_is_mode_and_verdict(monkeypatch, mode, platform, route,
                                   reason, where):
    """The whole routing rule: nothing but the mode and what the device
    said decides where a batch runs — a two-item batch and a lone item
    go the same way."""
    from garage_tpu import native

    monkeypatch.delenv("GARAGE_TPU_DEVICE", raising=False)
    dev = _FakeDevice(platform)
    f = DeviceFeeder(mode=mode, backend=dev, max_batch=8)
    blobs = [os.urandom(4096) for _ in range(4)]

    async def go():
        first = await asyncio.gather(f.hash(blobs[0]),
                                     return_exceptions=True)
        if mode == "auto":
            # the first batch asked in the background and ran host-side
            assert f.stats["device_items"] == 0
            await f._verdict_task
        before = dict(f.stats)
        pair = await asyncio.gather(f.hash(blobs[1]), f.hash(blobs[2]),
                                    return_exceptions=True)
        lone = await asyncio.gather(f.hash(blobs[3]),
                                    return_exceptions=True)
        await f.stop()
        return first + pair + lone, before

    res, before = run(go())
    assert (f.route, f.route_reason) == (route, reason)
    on_device = f.stats["device_items"] - before["device_items"]
    inline = f.stats["inline_items"] - before["inline_items"]
    queued = f.stats["items"] - before["items"]
    if where == "nowhere":
        assert all(isinstance(r, RuntimeError)
                   and f"device required but {reason}" in str(r)
                   for r in res), res
        assert (on_device, inline, queued) == (0, 0, 0)
        return
    assert res == [blake3sum(b) for b in blobs]
    if where == "device":
        assert (on_device, inline, queued) == (3, 0, 3)
    elif native.loaded():
        # the host for good: the queue hop is skipped
        assert (on_device, inline, queued) == (0, 3, 0)
    else:
        assert (on_device, inline, queued) == (0, 0, 3)
    if mode == "off":
        assert f.device_info is None and f._backend is None  # never asked


def test_auto_on_stub_sends_lone_small_items_to_the_device(monkeypatch):
    """mode="auto" with the route open: a lone 4 KiB decode and a lone
    hash take the device route — no floor keeps a small batch on the
    host."""
    import numpy as np

    from garage_tpu.ops import rs

    k, m = 4, 2
    codec = ErasureCodec(k, m, use_jax=False)
    f = _auto_feeder_on_stub(
        monkeypatch, StubDeviceBackend(codec, fixed_s=0.0), codec=codec)
    block = os.urandom(4096)
    stripe = codec.encode(block)
    present = (1, 2, 3, 4)  # degraded: shard 0 lost

    async def go():
        out = await f.decode(present, [stripe[i] for i in present],
                             len(block))
        dig = await f.hash(block)
        await f.stop()
        return out, dig

    out, dig = run(go())
    assert out == block and dig == blake3sum(block)
    assert f.route == "device"
    assert f.stats["decode_device_items"] == 1
    assert f.device_items_by_op == {"decode": 1, "hash": 1}
    assert f.stats["inline_items"] == 0 and f.stats["host_reruns"] == 0
    st = np.stack([np.frombuffer(s, dtype=np.uint8) for s in stripe])
    assert out == rs.join_stripe(
        rs.decode_np(k, m, present, st[list(present)]), len(block))


def test_stub_backend_needs_no_feeder():
    """The stub computes with block/host_legs.py and its own codec: no
    back-pointer to a feeder, and its three stages run without one."""
    codec = ErasureCodec(4, 2, use_jax=False)
    stub = StubDeviceBackend(codec, h2d_gbps=1e6, compute_gbps=1e6,
                             d2h_gbps=1e6)
    assert not hasattr(stub, "feeder")
    block = os.urandom(10_000)
    stripe = codec.encode(block)
    present = (0, 2, 3, 5)
    cases = [
        ("hash", [block]),
        ("verify", [(blake3sum(block), block), (b"\x00" * 32, block)]),
        ("sha256", [block]),
        ("encode", [block]),
        ("encode_put", [(b"\x00", block)]),
        ("parity_check", [stripe]),
        ("decode", [(present, [stripe[i] for i in present], len(block))]),
        ("repair", [(present, (1, 4), [stripe[i] for i in present])]),
    ]
    for op, blobs in cases:
        got = stub.readback(op, stub.compute(op, stub.stage(op, blobs)))
        assert got == host_legs.run(codec, op, blobs), op
    assert stub.readback("repair", stub.compute("repair", stub.stage(
        "repair", cases[-1][1]))) == [{1: stripe[1], 4: stripe[4]}]


# ---------------------------------------------------------------------------
# fixed-shape padded launches (jax backend on the cpu "device")
# ---------------------------------------------------------------------------


def test_bucket_helpers():
    assert bucket_items(3, (1, 2, 4, 8)) == 4
    assert bucket_items(8, (1, 2, 4, 8)) == 8
    assert bucket_items(9, (1, 2, 4, 8)) == 9  # above the ladder: as-is
    assert bucket_len(1) == 1024
    assert bucket_len(1024) == 1024
    assert bucket_len(1025) == 2048
    assert bucket_len(262144) == 262144


def mk_batch(op, datas):
    loop = asyncio.get_event_loop_policy().new_event_loop()
    try:
        return [_Item(op, d, loop.create_future()) for d in datas]
    finally:
        loop.close()


def test_padded_launches_correct_and_shape_stable():
    """The staged jax route pads items to bucket shapes: results stay
    byte-identical to the host path, pad waste is accounted, and a
    second batch with the same bucket shape compiles NOTHING new
    (feeder_recompiles unchanged — the whole point of bucketing)."""
    import numpy as np

    codec = ErasureCodec(4, 2, use_jax=False)
    f = DeviceFeeder(codec=codec, mode="require", max_batch=8)
    f._device_ok = True
    rng = np.random.default_rng(7)

    def items(n, base):
        return [(b"\x00", rng.integers(0, 256, base + i, dtype=np.uint8)
                 .tobytes()) for i in range(n)]

    async def go():
        from garage_tpu.block.manager import unpack_shard

        # wave 1: 5 encode_put items -> bucket 8, padded shard len
        batch = [_Item("encode_put", it, asyncio.get_running_loop()
                       .create_future()) for it in items(5, 65536)]
        res = await f._run_batch_staged(batch)
        host = host_legs.encode_put(codec, [it.data for it in batch])
        for pa, pb in zip(res, host):
            for sa, sb in zip(pa, pb):
                da, la = unpack_shard(bytes(sa))
                db, lb = unpack_shard(bytes(sb))
                assert la == lb and bytes(da) == bytes(db)
        waste1 = f.stats["pad_waste_bytes"]
        rc1 = f.stats["recompiles"]
        assert waste1 > 0  # 5 -> 8 items plus shard-len rounding
        assert rc1 >= 1
        # wave 2: 6 items, same sizes -> same bucket -> zero recompiles
        batch2 = [_Item("encode_put", it, asyncio.get_running_loop()
                        .create_future()) for it in items(6, 65536)]
        res2 = await f._run_batch_staged(batch2)
        host2 = host_legs.encode_put(codec, [it.data for it in batch2])
        for pa, pb in zip(res2, host2):
            for sa, sb in zip(pa, pb):
                da, la = unpack_shard(bytes(sa))
                db, lb = unpack_shard(bytes(sb))
                assert la == lb and bytes(da) == bytes(db)
        assert f.stats["recompiles"] == rc1, "bucket shape recompiled"
        assert f.stats["pad_waste_bytes"] > waste1
        await f.stop()

    run(go())


def test_padded_hash_and_verify_and_parity_staged():
    """Hash digests from padded-item-count launches match blake3sum
    (pad rows sliced away); verify and parity_check verdicts survive
    the staged route including a corrupted stripe."""
    import numpy as np

    codec = ErasureCodec(4, 2, use_jax=False)
    f = DeviceFeeder(codec=codec, mode="require", max_batch=8)
    f._device_ok = True
    rng = np.random.default_rng(9)
    blobs = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
             for n in (1024, 5000, 65536)]

    async def go():
        batch = [_Item("hash", b, asyncio.get_running_loop()
                       .create_future()) for b in blobs]
        digs = await f._run_batch_staged(batch)
        assert digs == [blake3sum(b) for b in blobs]

        items = [(blake3sum(blobs[0]), blobs[0]),
                 (b"\x00" * 32, blobs[1])]
        vb = [_Item("verify", it, asyncio.get_running_loop()
                    .create_future()) for it in items]
        assert await f._run_batch_staged(vb) == [True, False]

        stripes = [codec.encode(b) for b in blobs]
        s = list(stripes[1])
        s[2] = bytes(x ^ 1 for x in s[2])
        stripes[1] = s
        pb = [_Item("parity_check", st, asyncio.get_running_loop()
                    .create_future()) for st in stripes]
        assert await f._run_batch_staged(pb) == [True, False, True]
        await f.stop()

    run(go())


# ---------------------------------------------------------------------------
# multi-chip mesh sharding (8 virtual cpu devices from conftest)
# ---------------------------------------------------------------------------


def test_mesh_sharded_encode_matches_host():
    import jax
    import numpy as np

    if len(jax.devices()) < 2:
        pytest.skip("single-device jax runtime")
    codec = ErasureCodec(4, 2, use_jax=False)
    f = DeviceFeeder(codec=codec, mode="require", max_batch=16)
    f._device_ok = True
    f.mesh_min_items = 4  # engage the mesh at this test's batch size
    rng = np.random.default_rng(11)
    blocks = [rng.integers(0, 256, 262144 + i, dtype=np.uint8).tobytes()
              for i in range(8)]

    async def go():
        batch = [_Item("encode", b, asyncio.get_running_loop()
                       .create_future()) for b in blocks]
        res = await f._run_batch_staged(batch)
        host = host_legs.encode(codec, blocks)
        for a, b in zip(res, host):
            assert [bytes(x) for x in a] == [bytes(x) for x in b]
        assert f.stats["mesh_batches"] >= 1

        # parity_check rides the mesh too, and still detects corruption
        stripes = [codec.encode(b) for b in blocks]
        bad = list(stripes[3])
        bad[5] = bytes(x ^ 0xFF for x in bad[5])
        stripes[3] = bad
        pb = [_Item("parity_check", st, asyncio.get_running_loop()
                    .create_future()) for st in stripes]
        verdicts = await f._run_batch_staged(pb)
        assert verdicts == [i != 3 for i in range(8)]
        assert f.stats["mesh_batches"] >= 2
        await f.stop()

    run(go())


# ---------------------------------------------------------------------------
# stub backend selection + the require live gate, config + tuning knobs
# ---------------------------------------------------------------------------


def test_stub_backend_require_live_gate(monkeypatch):
    """GARAGE_TPU_DEVICE=require with the stub backend: the stub says
    what it is and is accepted by name — device_items > 0 straight
    away. This is the CI shape of the live S3-path gate
    (script/device_smoke.py)."""
    monkeypatch.setenv("GARAGE_TPU_DEVICE_BACKEND", "stub")
    f = DeviceFeeder(mode="require")

    async def go():
        blob = os.urandom(4096)
        dig = await f.hash(blob)
        assert dig == blake3sum(blob)
        assert f.stats["device_items"] >= 1
        assert f._get_backend().name == "stub"
        await f.stop()

    run(go())


def test_tpu_config_knobs_flow_into_feeder():
    from garage_tpu.utils.config import config_from_dict

    cfg = config_from_dict({
        "metadata_dir": "/tmp/x",
        "tpu": {"inflight_batches": 2, "pad_buckets": [2, 4],
                "mesh_min_items": 5, "device_backend": "stub",
                "batch_timeout_s": 7.5, "batch_linger_ms": 2.5,
                "platform": "cpu", "batch_blocks": 64},
    })
    f = DeviceFeeder(mode="off", tpu_cfg=cfg.tpu,
                     max_batch=cfg.tpu.batch_blocks)
    assert f.inflight_batches == 2
    assert f.pad_buckets == (2, 4)
    assert f.mesh_min_items == 5
    assert f.batch_timeout == 7.5
    assert f.batch_linger == 0.0025
    assert f.platform == "cpu"
    assert f.max_batch == 64
    assert f._backend_is_stub()
    # None fields leave the feeder defaults in force
    f2 = DeviceFeeder(mode="off")
    assert f2.inflight_batches == 3
    assert f2.batch_timeout == fmod._BATCH_TIMEOUT
    assert f2.platform == fmod._DEVICE_PLATFORM


def test_s3_tuning_feeder_knobs():
    """The admin /v1/s3/tuning surface tunes the live feeder: the depth
    applies, the state echoes it, bad values 400 — and so do the four
    routing floors that went with the router, each by name."""
    from types import SimpleNamespace

    from garage_tpu.admin.http import apply_s3_tuning, s3_tuning_state
    from garage_tpu.block.cache import BlockCache
    from garage_tpu.utils.config import Config
    from garage_tpu.utils.error import BadRequest

    feeder = DeviceFeeder(mode="off")
    garage = SimpleNamespace(
        config=Config(metadata_dir="/tmp/x"),
        block_manager=SimpleNamespace(cache=BlockCache(1 << 20),
                                      feeder=feeder))
    state = apply_s3_tuning(garage, {"feeder_inflight_batches": 4})
    assert feeder.inflight_batches == 4
    assert state["feeder_inflight_batches"] == 4
    assert "feeder_pipeline" in state
    assert s3_tuning_state(garage)["feeder_inflight_batches"] == 4
    with pytest.raises(BadRequest):
        apply_s3_tuning(garage, {"feeder_inflight_batches": 0})
    with pytest.raises(BadRequest):
        apply_s3_tuning(garage, {"feeder_bogus": 1})
    for gone in ("feeder_device_min_bytes", "feeder_device_min_items",
                 "feeder_device_min_decode_bytes",
                 "feeder_device_min_decode_items"):
        with pytest.raises(BadRequest, match="unknown s3 tuning knob"):
            apply_s3_tuning(garage, {gone: 1,
                                     "feeder_inflight_batches": 9})
        assert gone not in s3_tuning_state(garage)
    # a rejected spec must not have half-applied
    assert feeder.inflight_batches == 4


def test_stop_concurrent_restart_keeps_new_dispatcher():
    """GL12 regression (ISSUE 14): stop() yields while the cancelled
    dispatcher unwinds; a concurrent _submit's _ensure_started() can
    respawn a NEW dispatcher in that window. The old `self._task =
    None` after the await nulled the live dispatcher's handle — the
    feeder then thought it was stopped while an orphan kept consuming
    a queue nothing referenced, and the next restart spawned a second
    one. stop() now snapshots-and-clears BEFORE awaiting."""
    f = DeviceFeeder(mode="off")

    async def go():
        unwound = asyncio.Event()

        async def slow_dispatcher():
            try:
                await asyncio.sleep(3600)
            finally:
                unwound.set()
                # cancellation takes a few loop ticks — the window a
                # real dispatcher's cleanup occupies
                try:
                    await asyncio.shield(asyncio.sleep(0.05))
                except asyncio.CancelledError:
                    pass

        f._task = asyncio.create_task(slow_dispatcher())
        old = f._task
        await asyncio.sleep(0)  # let the dispatcher enter its try block

        async def restart_mid_stop():
            await unwound.wait()       # inside stop()'s await window
            f._ensure_started()        # a concurrent submitter respawns
            return f._task

        rt = asyncio.create_task(restart_mid_stop())
        await f.stop()
        new = await rt
        assert new is not old
        # the respawned dispatcher's handle must survive stop()
        assert f._task is new
        assert not new.done()
        await f.stop()  # cleanup (also exercises the fixed path again)
        assert f._task is None

    run(go())


def test_stop_drains_only_its_own_queue_not_the_respawns():
    """Review regression: stop() snapshots the queue BEFORE awaiting —
    an item submitted to a dispatcher respawned mid-stop must not get
    a spurious "feeder stopped" from stop()'s drain."""
    f = DeviceFeeder(mode="off")

    async def go():
        unwound = asyncio.Event()

        async def slow_dispatcher():
            try:
                await asyncio.sleep(3600)
            finally:
                unwound.set()
                try:
                    await asyncio.shield(asyncio.sleep(0.05))
                except asyncio.CancelledError:
                    pass

        f._ensure_started()          # real queue to snapshot
        f._task.cancel()             # replace with the slow stand-in
        f._task = asyncio.create_task(slow_dispatcher())
        await asyncio.sleep(0)

        async def submit_mid_stop():
            await unwound.wait()
            f._ensure_started()      # respawn: NEW queue
            fut = asyncio.get_event_loop().create_future()
            f._q.put_nowait(_Item("hash", b"x", fut, None))
            return fut

        st = asyncio.create_task(submit_mid_stop())
        await f.stop()
        fut = await st
        # the respawned dispatcher owns that item now: it must be
        # served normally (host-path digest), NEVER failed with
        # stop()'s "feeder stopped" drain
        for _ in range(100):
            if fut.done():
                break
            await asyncio.sleep(0.01)
        assert fut.done() and fut.exception() is None, \
            "stop() drained the respawned queue"
        assert fut.result() == blake3sum(b"x")
        await f.stop()               # clean shutdown of the respawn

    run(go())
