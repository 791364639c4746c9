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
from garage_tpu.block.device_backend import (STAGES, StubDeviceBackend,
                                             bucket_items, bucket_len)
from garage_tpu.block.feeder import DeviceFeeder, _Item
from garage_tpu.utils.data import blake3sum

from feeder_batch import run_batch


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
        res = await run_batch(f, "encode", blocks)
        await f.stop()
        return res

    res = run(go())
    assert all(isinstance(r, RuntimeError) and "mesh build failed" in str(r)
               for r in res), res
    assert f.stats["mesh_batches"] == 0 and f.stats["device_items"] == 0


def test_stage_executor_never_runs_cancelled_queued_jobs():
    """A job let go of while still QUEUED behind a slow sibling must
    never execute — stage fns carry side effects (the d2h MD5 lane
    advance), and running one after its leg already failed over to the
    host path would apply them twice (a silent ETag corruption). Nor
    does it cross back to the loop."""
    from garage_tpu.block.device_backend import DevicePipeline

    async def go():
        loop = asyncio.get_running_loop()
        pl = DevicePipeline({s: 0.0 for s in STAGES},
                            {s: 0 for s in STAGES})
        ran, back = [], []
        done = asyncio.Event()

        def slow_back(job):
            back.append(job)
            done.set()

        slow = pl.submit(loop, [("d2h", lambda _: time.sleep(0.15))],
                         slow_back)
        victim = pl.submit(loop, [("d2h", lambda _: ran.append("effect"))],
                           back.append)
        assert victim.abandon()  # let go of while queued
        try:
            await done.wait()
            assert back == [slow] and slow.busy[0] >= 0.1
            await asyncio.sleep(0.1)  # give the worker time to (not) run it
            assert ran == [], "abandoned queued job executed its side effect"
            assert back == [slow]
            assert victim.state == "abandoned" and victim.t_claim == [0.0]
        finally:
            pl.close()

    run(go())


def test_abandoned_chain_never_runs_its_next_stage():
    """A chain let go of while a stage runs it: that step runs to its
    end, the next stage never runs it, and nothing crosses back."""
    from garage_tpu.block.device_backend import DevicePipeline

    async def go():
        loop = asyncio.get_running_loop()
        crossings = {s: 0 for s in STAGES}
        pl = DevicePipeline({s: 0.0 for s in STAGES}, crossings)
        ran, back = [], []
        job = pl.submit(loop, [
            ("h2d", lambda _: (time.sleep(0.1), ran.append("h2d"))),
            ("compute", lambda _: ran.append("compute")),
            ("d2h", lambda _: ran.append("d2h"))], back.append)
        try:
            await asyncio.sleep(0.03)
            assert job.state == "running"
            assert not job.abandon()  # running: kept unless asked for
            assert job.abandon(running=True)
            await asyncio.sleep(0.2)
            assert ran == ["h2d"] and back == []
            assert job.t_done[0] > 0 and job.t_claim[1:] == [0.0, 0.0]
            assert crossings == {s: 0 for s in STAGES}
        finally:
            pl.close()

    run(go())


def test_chain_hand_shake_under_contention():
    """Stage threads hand jobs on while the loop lets go of some of
    them, three pipelines' threads and a short switch interval: every
    job is either called back once or let go of, never both and never
    neither; a job let go of while queued runs no step after that; and
    the crossings counted equal the callbacks."""
    import random
    import sys

    from garage_tpu.block.device_backend import DevicePipeline

    crossings = {s: 0 for s in STAGES}
    pls = [DevicePipeline({s: 0.0 for s in STAGES}, crossings)
           for _ in range(3)]

    async def go():
        loop = asyncio.get_running_loop()
        ran: dict[int, int] = {}
        back: dict[int, int] = {}
        let_go: dict[int, int] = {}  # job -> steps claimed when let go
        jobs = []
        rng = random.Random(41)

        def step(k):
            def fn(_):
                ran[k] = ran.get(k, 0) + 1
            return fn

        def called(job):
            back[id(job)] = back.get(id(job), 0) + 1

        for k in range(600):
            job = rng.choice(pls).submit(
                loop, [(s, step(k)) for s in STAGES], called)
            jobs.append((k, job))
            if k % 3 == 0:
                await asyncio.sleep(0)
                if job.abandon():
                    let_go[k] = sum(1 for t in job.t_claim if t)
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            if len(back) + len(let_go) >= len(jobs):
                break
            await asyncio.sleep(0.01)
        await asyncio.sleep(0.05)  # nothing more may arrive
        for k, job in jobs:
            assert (id(job) in back) != (k in let_go), k
            if k in let_go:
                assert ran.get(k, 0) == let_go[k], k
            else:
                assert back[id(job)] == 1 and ran[k] == 3, k
        assert sum(crossings.values()) == len(back)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        run(asyncio.wait_for(go(), 10))
    finally:
        sys.setswitchinterval(old)
        for pl in pls:
            pl.close()


def test_leg_crosses_to_the_loop_once():
    """A leg of n items goes h2d -> compute -> d2h on the stage threads
    and costs the loop exactly one callback from them, counted once in
    `feeder_loop_crossings`; its launch is one device batch."""
    n = 5
    stub = StubDeviceBackend(None, h2d_gbps=1e6, compute_gbps=1e6,
                             d2h_gbps=1e6, fixed_s=0.005)
    f = DeviceFeeder(mode="require", max_batch=n, backend=stub)
    f._device_ok = True
    blobs = [os.urandom(2048) for _ in range(n)]

    async def go():
        loop = asyncio.get_running_loop()
        real = loop.call_soon_threadsafe
        calls = []

        def counted(cb, *a, **kw):
            calls.append(cb)
            return real(cb, *a, **kw)

        loop.call_soon_threadsafe = counted
        try:
            c0 = f.pipeline_stats()["loop_crossings"]
            b0 = f.stats["device_batches"]
            res = await run_batch(f, "hash", blobs)
            assert res == [blake3sum(b) for b in blobs]
            assert len(calls) == 1
            assert f.pipeline_stats()["loop_crossings"] - c0 == 1
            assert f.stats["device_batches"] - b0 == 1
            assert f.stats["device_items"] == n
        finally:
            del loop.call_soon_threadsafe
            await f.stop()

    run(go())


def test_a_batch_is_freed_without_the_cyclic_collector():
    """A batch, its legs and their jobs hold every item's block and
    result: once the batch is answered no reference cycle may keep them
    for the cyclic collector, which on a large heap is a long pause of
    the event loop."""
    import gc

    from garage_tpu.block import device_backend as dbe
    from garage_tpu.block import feeder as feeder_mod

    codec = ErasureCodec(4, 2, use_jax=False)
    stub = StubDeviceBackend(codec, h2d_gbps=1e6, compute_gbps=1e6,
                             d2h_gbps=1e6)
    f = DeviceFeeder(codec=codec, mode="require", backend=stub)
    f._device_ok = True
    kinds = (feeder_mod._Batch, feeder_mod._Leg, dbe.StageJob)

    async def go():
        loop = asyncio.get_running_loop()
        for _ in range(3):
            items = [_Item("hash", os.urandom(4096), loop.create_future()),
                     _Item("encode", os.urandom(4096), loop.create_future())]
            f._dispatch(items)
            await asyncio.gather(*[it.future for it in items])
        await asyncio.sleep(0.05)  # the threads drop their last job
        await f.stop()

    gc.collect()
    gc.disable()
    gc.set_debug(gc.DEBUG_SAVEALL)
    try:
        run(go())
        gc.collect()
        cyclic = [o for o in gc.garbage if isinstance(o, kinds)]
    finally:
        gc.set_debug(0)
        gc.garbage.clear()
        gc.enable()
    assert cyclic == []


class _SlowEncodeStub(StubDeviceBackend):
    """The encode leg's compute waits for `release`; the hash leg runs
    at once."""

    def __init__(self, codec):
        super().__init__(codec, h2d_gbps=1e6, compute_gbps=1e6,
                         d2h_gbps=1e6)
        import threading

        self.in_compute = threading.Event()
        self.release = threading.Event()

    def compute(self, op, staged):
        if op == "encode":
            self.in_compute.set()
            self.release.wait(10)
        return super().compute(op, staged)


def test_fast_leg_resolves_while_slow_sibling_computes():
    """In a two-leg batch each leg resolves its own items when it is
    back: the hash items are answered while the encode leg of the same
    batch is still in compute, and the batch's slot is held until the
    encode leg is back too."""
    codec = ErasureCodec(4, 2, use_jax=False)
    stub = _SlowEncodeStub(codec)
    f = DeviceFeeder(codec=codec, mode="require", backend=stub)
    f._device_ok = True
    blocks = [os.urandom(4096) for _ in range(2)]

    async def go():
        loop = asyncio.get_running_loop()
        items = [_Item("hash", blocks[0], loop.create_future()),
                 _Item("encode", blocks[1], loop.create_future())]
        try:
            f._dispatch(items)
            assert await asyncio.wait_for(items[0].future, 5) \
                == blake3sum(blocks[0])
            assert stub.in_compute.is_set()
            assert not items[1].future.done()
            assert f.pipeline_stats()["inflight"] == 1
            stub.release.set()
            parts = await asyncio.wait_for(items[1].future, 5)
            assert [bytes(p) for p in parts] == [
                bytes(p) for p in codec.encode(blocks[1])]
            assert f.pipeline_stats()["inflight"] == 0
        finally:
            stub.release.set()
            await f.stop()

    run(go())


def test_a_batch_that_fails_to_dispatch_frees_its_slot(monkeypatch):
    """A batch whose second leg cannot be built (its item is malformed)
    fails its items and holds no in-flight slot: with one slot, the
    next batch still goes through, and no leg of the failed batch was
    sent down the stages."""
    stub = StubDeviceBackend(None, h2d_gbps=1e6, compute_gbps=1e6,
                             d2h_gbps=1e6)
    f = DeviceFeeder(mode="require", backend=stub)
    f._device_ok = True
    f.inflight_batches = 1
    real = fmod.group_bytes

    def group_bytes(op, blobs):
        if op == "encode":
            raise TypeError("malformed item")
        return real(op, blobs)

    monkeypatch.setattr(fmod, "group_bytes", group_bytes)

    async def go():
        loop = asyncio.get_running_loop()
        f._ensure_started()
        items = [_Item("hash", os.urandom(2048), loop.create_future()),
                 _Item("encode", os.urandom(2048), loop.create_future())]
        for it in items:
            f._q.put_nowait(it)  # one batch, greedily drained
        try:
            for it in items:
                with pytest.raises(TypeError, match="malformed"):
                    await asyncio.wait_for(it.future, 5)
            assert f.pipeline_stats()["inflight"] == 0
            assert f.stats["device_batches"] == 0
            data = os.urandom(3000)
            assert await asyncio.wait_for(f.hash(data), 5) \
                == blake3sum(data)
        finally:
            await f.stop()

    run(go())


def test_stop_ends_the_stage_threads():
    """A stopped feeder leaves no stage thread behind, and a feeder
    used again after stop() gets a fresh generation."""
    import threading

    stub = StubDeviceBackend(None, h2d_gbps=1e6, compute_gbps=1e6,
                             d2h_gbps=1e6)
    f = DeviceFeeder(mode="require", backend=stub)
    f._device_ok = True
    blobs = [os.urandom(2048) for _ in range(3)]

    def stage_threads():
        return [t for t in threading.enumerate()
                if t.name.startswith("feeder-") and t.is_alive()]

    async def go():
        before = set(stage_threads())
        assert await run_batch(f, "hash", blobs) \
            == [blake3sum(b) for b in blobs]
        mine = [t for t in stage_threads() if t not in before]
        assert len(mine) == 3
        await f.stop()
        for t in mine:
            t.join(5)
            assert not t.is_alive(), t.name
        assert await run_batch(f, "hash", blobs) \
            == [blake3sum(b) for b in blobs]
        await f.stop()

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
        wave = items(5, 65536)
        res = await run_batch(f, "encode_put", wave)
        host = host_legs.encode_put(codec, wave)
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
        wave2 = items(6, 65536)
        res2 = await run_batch(f, "encode_put", wave2)
        host2 = host_legs.encode_put(codec, wave2)
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
        digs = await run_batch(f, "hash", blobs)
        assert digs == [blake3sum(b) for b in blobs]

        items = [(blake3sum(blobs[0]), blobs[0]),
                 (b"\x00" * 32, blobs[1])]
        assert await run_batch(f, "verify", items) == [True, False]

        stripes = [codec.encode(b) for b in blobs]
        s = list(stripes[1])
        s[2] = bytes(x ^ 1 for x in s[2])
        stripes[1] = s
        assert await run_batch(f, "parity_check", stripes) \
            == [True, False, True]
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
        res = await run_batch(f, "encode", blocks)
        host = host_legs.encode(codec, blocks)
        for a, b in zip(res, host):
            assert [bytes(x) for x in a] == [bytes(x) for x in b]
        assert f.stats["mesh_batches"] >= 1

        # parity_check rides the mesh too, and still detects corruption
        stripes = [codec.encode(b) for b in blocks]
        bad = list(stripes[3])
        bad[5] = bytes(x ^ 0xFF for x in bad[5])
        stripes[3] = bad
        verdicts = await run_batch(f, "parity_check", stripes)
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
