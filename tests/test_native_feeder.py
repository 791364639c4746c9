"""Native C kernels + DeviceFeeder batching + RAM semaphore.

The C BLAKE3 (garage_tpu/native) is validated against the vendored
official empty-input vector and cross-checked against the two other
independent implementations (pure-Python spec tree in ops/treehash.py,
lane-vectorized JAX) over the official test-vector input pattern
(byte i = i % 251) at every tree-shape edge case.
"""

from __future__ import annotations

import asyncio
import os

import numpy as np
import pytest

from garage_tpu import native
from garage_tpu.block.feeder import DeviceFeeder
from garage_tpu.block.manager import _ByteSemaphore
from garage_tpu.ops import gf256, rs, treehash

EMPTY_B3 = "af1349b9f5f9a1a6a0404dea36dcc9499bcb25c9adc112b7cc9a93cae41f3262"

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C toolchain for native kernels"
)

# every tree-shape class: sub-block, block edges, chunk edges, power-of-2
# chunk counts, odd tails, deep-carry counts
VECTOR_LENGTHS = (0, 1, 2, 63, 64, 65, 127, 128, 1023, 1024, 1025,
                  2048, 2049, 3072, 3073, 4096, 4097, 5120, 6144, 7168,
                  31744, 102400)


def official_input(n: int) -> bytes:
    return bytes(i % 251 for i in range(n))


def test_blake3_empty_vector():
    assert native.blake3(b"").hex() == EMPTY_B3
    assert treehash.blake3_py(b"").hex() == EMPTY_B3


def test_blake3_c_vs_python_vs_jax():
    msgs = [official_input(n) for n in VECTOR_LENGTHS]
    c_digs = [native.blake3(m) for m in msgs]
    py_digs = [treehash.blake3_py(m) for m in msgs]
    assert c_digs == py_digs
    jax_digs = treehash.blake3_many(msgs)
    assert c_digs == jax_digs


def test_blake3_many_matches_single():
    blobs = [os.urandom(n) for n in (0, 5, 1024, 4096, 70000)]
    assert native.blake3_many(blobs) == [native.blake3(b) for b in blobs]


def test_crc_native_matches_python():
    from garage_tpu.api.checksum import _crc32c_py, _crc64nvme_py

    for blob in (b"", b"a", b"123456789", os.urandom(7),
                 os.urandom(4096), os.urandom(100001)):
        assert native.crc32c(blob) == _crc32c_py(blob)
        assert native.crc64nvme(blob) == _crc64nvme_py(blob)
    # incremental == one-shot
    a, b = os.urandom(1000), os.urandom(777)
    assert native.crc32c(b, native.crc32c(a)) == native.crc32c(a + b)
    assert native.crc64nvme(b, native.crc64nvme(a)) == native.crc64nvme(a + b)
    # known-answer: CRC-32C("123456789") = 0xE3069283
    assert native.crc32c(b"123456789") == 0xE3069283


def test_gf_matmul_matches_numpy():
    rng = np.random.default_rng(3)
    mat = rng.integers(0, 256, (4, 10), dtype=np.uint8)
    x = rng.integers(0, 256, (10, 1000), dtype=np.uint8)
    assert np.array_equal(native.gf_matmul(mat, x), gf256.gf_matmul(mat, x))


def test_native_rs_roundtrip():
    """Native encode -> numpy decode from a mixed shard subset."""
    k, m = 4, 2
    data = os.urandom(4096 + 33)
    shards = rs.split_stripe(data, k)
    parity = native.gf_matmul(rs.parity_matrix(k, m), shards)
    full = np.concatenate([shards, parity])
    present = (0, 2, 4, 5)
    dec = rs.decode_np(k, m, present, full[list(present)])
    assert rs.join_stripe(dec, len(data)) == data


# ---------------------------------------------------------------------------
# DeviceFeeder
# ---------------------------------------------------------------------------


def run(coro):
    return asyncio.run(coro)


def test_feeder_hash_coalesces_and_matches():
    from garage_tpu.utils.data import blake3sum

    async def go():
        f = DeviceFeeder(mode="off")
        blobs = [os.urandom(n) for n in (10, 1024, 5000, 1 << 16)]
        digs = await asyncio.gather(*[f.hash(b) for b in blobs])
        assert list(digs) == [blake3sum(b) for b in blobs]
        # mode="off" + native loaded takes the inline fast path; without
        # native the items flow through the batch queue
        assert (f.stats["items"] + f.stats["inline_items"]) == len(blobs)
        await f.stop()

    run(go())


def test_feeder_encode_matches_codec():
    from garage_tpu.block.codec import ErasureCodec

    async def go():
        codec = ErasureCodec(4, 2, use_jax=False)
        f = DeviceFeeder(codec=codec, mode="off")
        blocks = [os.urandom(n) for n in (100, 4096, 10000)]
        outs = await asyncio.gather(*[f.encode(b) for b in blocks])
        for blk, parts in zip(blocks, outs):
            assert parts == codec.encode(blk)
        await f.stop()

    run(go())


def test_feeder_verify_blocks():
    from garage_tpu.utils.data import blake2sum, blake3sum

    async def go():
        f = DeviceFeeder(mode="off")
        good = os.urandom(2048)
        legacy = os.urandom(100)
        res = await f.verify_blocks([
            (blake3sum(good), good),
            (blake2sum(legacy), legacy),  # legacy-algo store stays valid
            (b"\x00" * 32, good),
        ])
        assert res == [True, True, False]
        await f.stop()

    run(go())


def test_feeder_error_propagates():
    async def go():
        from garage_tpu.block.codec import ErasureCodec

        f = DeviceFeeder(codec=ErasureCodec(4, 2, use_jax=False), mode="off")
        with pytest.raises(Exception):
            await f.encode(None)  # type: ignore[arg-type]
        # feeder survives the bad item
        assert (await f.hash(b"x")) == (await f.hash(b"x"))
        await f.stop()

    run(go())


# ---------------------------------------------------------------------------
# _ByteSemaphore
# ---------------------------------------------------------------------------


def test_byte_semaphore_limits_and_fifo():
    async def go():
        sem = _ByteSemaphore(100)
        order = []

        async def worker(name, n, hold):
            await sem.acquire(n)
            order.append(("in", name))
            await asyncio.sleep(hold)
            sem.release(n)
            order.append(("out", name))

        await asyncio.gather(
            worker("a", 60, 0.02), worker("b", 60, 0.01), worker("c", 50, 0.0)
        )
        assert sem.in_use == 0
        # b and c could not fit alongside a; FIFO: b enters before c
        assert order.index(("in", "a")) < order.index(("in", "b"))
        assert order.index(("in", "b")) < order.index(("in", "c"))

    run(go())


def test_byte_semaphore_oversize_alone():
    async def go():
        sem = _ByteSemaphore(10)
        await sem.acquire(50)  # oversize allowed when alone
        assert sem.in_use == 50
        blocked = asyncio.create_task(sem.acquire(1))
        await asyncio.sleep(0.01)
        assert not blocked.done()
        sem.release(50)
        await blocked
        sem.release(1)
        assert sem.in_use == 0

    run(go())


def test_byte_semaphore_cancel_waiter():
    async def go():
        sem = _ByteSemaphore(10)
        await sem.acquire(10)
        t = asyncio.create_task(sem.acquire(5))
        await asyncio.sleep(0.01)
        t.cancel()
        with pytest.raises(asyncio.CancelledError):
            await t
        sem.release(10)
        assert sem.in_use == 0
        await sem.acquire(10)  # capacity fully recovered
        sem.release(10)

    run(go())


# ---------------------------------------------------------------------------
# rs_encode_packed: the fused PUT hot-path kernel (split+parity+crc+headers)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k,m", [(4, 2), (10, 4), (3, 1)])
@pytest.mark.parametrize("dlen", [0, 1, 7, 4096, (1 << 20) - 3, 1 << 20])
def test_rs_encode_packed_matches_reference(k, m, dlen):
    """The one-call C kernel must agree byte-for-byte with the composed
    reference path: split_stripe + encode_np + pack_shard."""
    from garage_tpu.block.manager import pack_shard, unpack_shard

    rng = np.random.default_rng(dlen % 97)
    prefix = b"\x00"
    data = rng.integers(0, 256, dlen, dtype=np.uint8).tobytes()
    block = prefix + data
    payloads = native.rs_encode_packed(data, k, m, rs.parity_matrix(k, m),
                                       prefix=prefix)
    shards = rs.split_stripe(block, k)
    parity = rs.encode_np(k, m, shards)
    assert len(payloads) == k + m
    for i, p in enumerate(payloads):
        got, plen = unpack_shard(bytes(p))
        assert plen == len(block)
        ref = shards[i] if i < k else parity[i - k]
        assert bytes(got) == ref.tobytes(), f"shard {i}"
        # and the composed python path produces the identical payload
        assert bytes(p) == pack_shard(ref.tobytes(), len(block))


def _staged_feeder(codec, **kw) -> DeviceFeeder:
    """mode "require" with the CPU standing in for the chip: every
    queued item takes the staged device route (JAX backend)."""
    from garage_tpu.utils.config import TpuConfig

    return DeviceFeeder(codec=codec, mode="require",
                        tpu_cfg=TpuConfig(platform="cpu"), **kw)


def test_encode_put_backends_agree():
    """The host leg (native, or numpy fallback) and the staged device
    route must emit interchangeable encode_put payloads (same shard
    bytes after unpack)."""
    from garage_tpu.block import host_legs
    from garage_tpu.block.codec import ErasureCodec
    from garage_tpu.block.manager import unpack_shard

    codec = ErasureCodec(4, 2, use_jax=False)
    f = _staged_feeder(codec)
    rng = np.random.default_rng(3)
    items = [(b"\x00", rng.integers(0, 256, n, dtype=np.uint8).tobytes())
             for n in (100, 65536, (1 << 20) + 5)]
    a = host_legs.encode_put(codec, items)

    async def go():
        out = await asyncio.gather(*[f.encode_put(d, p) for p, d in items])
        await f.stop()
        return out

    b = run(go())
    assert f.stats["device_items"] == len(items)
    for pa, pb in zip(a, b):
        for sa, sb in zip(pa, pb):
            da, la = unpack_shard(bytes(sa))
            db, lb = unpack_shard(bytes(sb))
            assert la == lb and bytes(da) == bytes(db)


def test_put_get_roundtrip_native_erasure():
    """rpc_put_block -> rpc_get_block through the native encode fast
    path on a loopback cluster returns the original bytes."""
    import shutil
    import sys
    import tempfile

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    async def go():
        from garage_tpu.rpc import ReplicationMode
        from garage_tpu.utils.data import blake3sum

        tmp = tempfile.mkdtemp(prefix="gt_rt_")
        try:
            rm = ReplicationMode.parse(3, erasure="4,2")
            systems, managers, tasks = await bench._build_cluster(
                tmp, 6, rm, "off")
            data = os.urandom((1 << 20) + 17)
            h = blake3sum(data)
            await managers[0].rpc_put_block(h, data)
            assert managers[0].feeder.stats["inline_items"] >= 1 \
                or managers[0].feeder.stats["items"] >= 1
            back = await managers[1].rpc_get_block(h)
            assert back == data
            await bench._teardown(systems, managers, tasks)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    run(go())


def test_native_md5_fused():
    """Md5 accumulator: hashlib parity across chained fused/plain
    updates, and the fused call returns the block's blake3."""
    import hashlib

    import numpy as np

    from garage_tpu import native
    from garage_tpu.utils.data import blake3sum

    if not native.available():
        import pytest

        pytest.skip("no native toolchain")
    rng = np.random.default_rng(5)
    m = native.Md5()
    ref = hashlib.md5()
    assert m.fused
    for i, n in enumerate((0, 1, 63, 64, 65, 1024, 1025, 70_000,
                           (1 << 20) + 3)):
        data = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        if i % 2:
            assert m.update_with_blake3(data) == blake3sum(data)
        else:
            m.update(data)
        ref.update(data)
        assert m.hexdigest() == ref.hexdigest(), n  # mid-stream digests


def test_native_md5_multilane_batch():
    """gt_md5_update_many / gt_b3_md5_many: hashlib parity for the
    8-way AVX2 multi-buffer path across lane counts 1..9, mixed
    lengths (lockstep + per-lane remainder), pre-seeded states, and a
    buffered (unaligned) state that must take the scalar fallback."""
    import hashlib

    import numpy as np
    import pytest

    from garage_tpu import native

    if not native.available():
        pytest.skip("no native toolchain")
    rng = np.random.default_rng(11)
    lengths = [1 << 20, 300_000, 64, 63, 1_000_001, 128, 7, 65536, 4096]
    for nlanes in range(1, 10):
        items, refs = [], []
        for i in range(nlanes):
            d = rng.integers(0, 256, lengths[i], dtype=np.uint8).tobytes()
            m = native.Md5()
            r = hashlib.md5()
            if i % 3 == 0:  # pre-seeded state; i%3==1 leaves it fresh
                m.update(b"seed%d" % i)
                r.update(b"seed%d" % i)
            elif i % 3 == 2:  # unaligned buffered state -> scalar path
                m.update(b"x" * 7)
                r.update(b"x" * 7)
            items.append((m, d))
            refs.append((r, d))
        outs = native.b3_md5_many(items)
        for (m, d), (r, rd), o in zip(items, refs, outs):
            r.update(rd)
            assert m.hexdigest() == r.hexdigest(), (nlanes, len(d))
            assert o == native.blake3(d)
    # plain md5_update_many (no blake3) chains correctly across calls
    ms = [native.Md5() for _ in range(4)]
    rs = [hashlib.md5() for _ in range(4)]
    for _round in range(3):
        ds = [rng.integers(0, 256, 1 << 18, dtype=np.uint8).tobytes()
              for _ in range(4)]
        native.md5_update_many(list(zip(ms, ds)))
        for r, d in zip(rs, ds):
            r.update(d)
    assert [m.hexdigest() for m in ms] == [r.hexdigest() for r in rs]


def test_feeder_hash_md5_batches_and_device_route():
    """hash_with_md5: queued cross-request batching produces correct
    blake3 digests AND the right ETag-MD5 chains; mode="require"
    forces the device route (jax backend — cpu-pinned in tests), which
    batch-advances MD5 host-side while the content hash rides the
    device path and device_items counts it (the live-S3 proof metric)."""
    import hashlib

    from garage_tpu import native
    from garage_tpu.utils.data import blake3sum

    if not native.available():
        import pytest

        pytest.skip("no native toolchain")

    async def drive(mode):
        f = DeviceFeeder(mode=mode)
        if mode == "require":
            # skip the device verdict (it would refuse platform "cpu"):
            # the "device" backend in the test env is the cpu-pinned
            # jax path, which is exactly the routing (not the silicon)
            # this test covers
            f._device_ok = True
        f.active_streams = 4  # several "requests": engage lane gather
        accs = [native.Md5() for _ in range(4)]
        refs = [hashlib.md5() for _ in range(4)]
        blobs = [os.urandom(n) for n in (2048, 4096, 1024, 3000)]
        digs = await asyncio.gather(*[
            f.hash_with_md5(b, a) for b, a in zip(blobs, accs)])
        for r, b in zip(refs, blobs):
            r.update(b)
        assert list(digs) == [blake3sum(b) for b in blobs]
        assert [a.hexdigest() for a in accs] == \
            [r.hexdigest() for r in refs]
        stats = dict(f.stats)
        await f.stop()
        return stats

    stats = run(drive("off"))  # host route (queued when streams > 1)
    assert stats["items"] >= 1  # rode the queue, not the inline path
    stats = run(drive("require"))  # device route, cpu jax backend
    assert stats["device_items"] >= 4


def test_feeder_stop_mid_gather_window_resolves_waiters():
    """Cancelling the dispatcher while it sits in the hash_md5
    lane-gather wait must fail the already-dequeued items' futures
    (r5 review finding: they were stranded and PUT streams hung)."""
    from garage_tpu import native

    if not native.available():
        import pytest

        pytest.skip("no native toolchain")

    async def go():
        f = DeviceFeeder(mode="off")
        f.active_streams = 4  # force the gather window on first item
        acc = native.Md5()
        task = asyncio.create_task(
            f.hash_with_md5(os.urandom(2048), acc))
        # let the dispatcher dequeue the item and enter the window
        await asyncio.sleep(0.002)
        await f.stop()
        try:
            await asyncio.wait_for(task, 2.0)
        except RuntimeError as e:
            assert "feeder stopped" in str(e)
        except asyncio.TimeoutError:
            raise AssertionError("hash_with_md5 waiter stranded")

    run(go())


def test_feeder_hash_md5_device_failure_fallback_etag_correct(monkeypatch):
    """mode="auto": a failing device hash must NOT have advanced the
    MD5 states before the host re-run repeats the op — the re-run would
    otherwise double-count every byte into the ETag chain (r5 audit
    bug)."""
    import hashlib

    from garage_tpu import native
    from garage_tpu.utils.data import blake3sum

    if not native.available():
        import pytest

        pytest.skip("no native toolchain")

    async def go():
        calls = {"n": 0}

        class _BrokenBackend:
            """Staged device backend whose transfer stage always
            raises, at the seam the pipelined device route actually
            goes through."""

            name = "jax"

            def stage(self, op, blobs):
                calls["n"] += 1
                raise RuntimeError("device lost")

        # conftest exports GARAGE_TPU_DEVICE=off (auto would become off)
        monkeypatch.delenv("GARAGE_TPU_DEVICE", raising=False)
        f = DeviceFeeder(mode="auto", backend=_BrokenBackend())
        f._device_ok = True  # fake device above: no verdict to take
        f.active_streams = 2
        accs = [native.Md5(), native.Md5()]
        refs = [hashlib.md5(), hashlib.md5()]
        blobs = [os.urandom(2048), os.urandom(4096)]
        digs = await asyncio.gather(*[
            f.hash_with_md5(b, a) for b, a in zip(blobs, accs)])
        for r, b in zip(refs, blobs):
            r.update(b)
        assert calls["n"] >= 1  # the device leg really ran and failed
        assert list(digs) == [blake3sum(b) for b in blobs]
        # the load-bearing assert: ETag chains advanced exactly once
        assert [a.hexdigest() for a in accs] == \
            [r.hexdigest() for r in refs]
        assert f.stats["host_reruns"] >= 1
        await f.stop()

    run(go())


def _watch_tempdir(monkeypatch):
    """Fail on any open() under tempfile.gettempdir(): the device
    verdict lives in the process, nowhere else."""
    import builtins
    import tempfile

    tmp = os.path.realpath(tempfile.gettempdir())
    touched = []
    real_open = builtins.open

    def spy(file, *a, **k):
        if isinstance(file, (str, bytes, os.PathLike)):
            path = os.path.realpath(os.fspath(file))
            if isinstance(path, bytes):
                path = path.decode(errors="replace")
            if path.startswith(tmp + os.sep):
                touched.append(path)
        return real_open(file, *a, **k)

    monkeypatch.setattr(builtins, "open", spy)
    return touched


def test_require_refuses_platform_cpu_and_names_it(monkeypatch):
    """The verdict is taken in-process, by the backend's own stage
    thread: jax.devices() says "cpu" here (conftest pin), "require"
    wants "tpu", so the first request — and the server's boot, which
    calls device_verdict() — fails with the platform named. Nothing
    under tempfile.gettempdir() is read or written, and no process is
    forked to ask."""
    import subprocess

    touched = _watch_tempdir(monkeypatch)

    def no_fork(*a, **k):
        raise AssertionError("the device verdict forked a process")

    monkeypatch.setattr(subprocess, "run", no_fork)
    monkeypatch.setattr(subprocess, "Popen", no_fork)

    async def go():
        f = DeviceFeeder(mode="require")
        try:
            try:
                await f.device_verdict()
                raise AssertionError("platform cpu was accepted")
            except RuntimeError as e:
                boot = str(e)
            try:
                await f.hash(os.urandom(2048))
                raise AssertionError("a request ran without its device")
            except RuntimeError as e:
                first = str(e)
            return boot, first, f.device_info, f.route
        finally:
            await f.stop()

    boot, first, info, route = run(go())
    for msg in (boot, first):
        assert "device required" in msg and "'cpu'" in msg \
            and "'tpu'" in msg, msg
    assert info["platform"] == "cpu" and info["count"] >= 1
    assert route == "refused"
    assert touched == []


def test_auto_routes_host_on_wrong_platform_and_says_so(monkeypatch):
    """mode="auto" with no TPU in this process: everything is served
    from the host, the route and its reason are recorded once for
    /metrics, and the configured platform ([tpu] platform) is what the
    verdict is held to."""
    from garage_tpu.utils.config import TpuConfig
    from garage_tpu.utils.data import blake3sum

    monkeypatch.delenv("GARAGE_TPU_DEVICE", raising=False)
    touched = _watch_tempdir(monkeypatch)

    async def go(mode, tpu_cfg):
        f = DeviceFeeder(mode=mode, tpu_cfg=tpu_cfg)
        try:
            blobs = [os.urandom(2048) for _ in range(6)]
            digs = await asyncio.gather(*[f.hash(b) for b in blobs])
            assert list(digs) == [blake3sum(b) for b in blobs]
            await f.device_verdict()
            return f.route, f.route_reason, f.stats["device_items"]
        finally:
            await f.stop()

    route, reason, dev_items = run(go("auto", None))
    assert route == "host" and "'cpu'" in reason and dev_items == 0
    # the same process, told that "cpu" IS its device platform (the
    # way chip_smoke's rehearsal stands the CPU backend in for a chip):
    # "require" is satisfied and the items run on the device route
    route, reason, dev_items = run(go("require", TpuConfig(platform="cpu")))
    assert route == "device" and "cpu" in reason and dev_items == 6
    assert touched == []


def test_try_pallas_does_not_swallow_a_compile_failure(monkeypatch):
    """GARAGE_TPU_PALLAS on a TPU: a kernel that fails to compile
    raises — the XLA path does not quietly stand in for it."""
    import numpy as np
    import pytest

    from garage_tpu.ops import pallas_gf, rs

    monkeypatch.setenv("GARAGE_TPU_PALLAS", "1")
    monkeypatch.setattr(pallas_gf, "available", lambda: True)

    def refuse(mat, data, interpret=False):
        raise NotImplementedError("Mosaic refused the kernel (injected)")

    monkeypatch.setattr(pallas_gf, "gf_apply", refuse)
    data = np.zeros((2, 4, 512), dtype=np.uint8)
    with pytest.raises(NotImplementedError, match="Mosaic refused"):
        rs.encode(4, 2, data)
    # a shard length the kernel cannot tile is not a failure: XLA path
    odd = np.zeros((2, 4, 100), dtype=np.uint8)
    assert np.asarray(rs.encode(4, 2, odd)).shape == (2, 2, 100)


def test_parity_check_backends_agree_and_detect():
    """The host leg (native/numpy) and the staged device route (padded
    jax batch) agree, and both flag a stripe with one corrupted shard —
    mixed shard lengths in one batch exercise the zero-padding rule
    (linear code: zero rows encode to zero parity)."""
    from garage_tpu.block import host_legs
    from garage_tpu.block.codec import ErasureCodec

    codec = ErasureCodec(4, 2, use_jax=False)
    f = DeviceFeeder(codec=codec, mode="off")
    staged = _staged_feeder(codec)
    rng = np.random.default_rng(5)
    stripes = []
    for n in (1024, 65536, 100_000):
        block = rng.integers(0, 256, n, dtype=np.uint8).tobytes()
        stripes.append(codec.encode(block))
    s = list(stripes[1])
    s[2] = bytes(b ^ 1 for b in s[2])
    stripes[1] = s
    want = [True, False, True]
    assert host_legs.parity_check(codec, stripes) == want

    async def go():
        assert await f.parity_check(stripes) == want
        assert await staged.parity_check(stripes) == want
        assert staged.stats["device_items"] == len(stripes)
        await f.stop()
        await staged.stop()

    run(go())
