"""DeviceFeeder: batches block math from concurrent requests onto the TPU.

The reference does its per-block CPU work (hashing, compression) inline
in each request task (src/api/s3/put.rs:413-477 spawn_blocking, one
block at a time). A TPU earns its keep only on *batches* — so the data
path here funnels every block-math request (content hash, RS encode,
RS decode/repair, scrub verify, SigV4 chunk SHA-256) through one
bounded queue. A single dispatcher drains whatever has accumulated,
groups it by operation and shape, and issues one batched launch per
group. Under load, concurrent PUTs coalesce into batches for free;
when idle, single requests take the native C path (garage_tpu/native).

One process, one chip, one verdict. The chip is attached to this
machine and belongs to whichever process touches JAX first, so the
feeder asks for it in-process: the first device batch (or the server's
boot, under `require`) has the backend's own stage thread call
`jax.devices()` once (ops/jaxenv.verdict) and keeps `platform`,
`device_kind` and the device count in `device_info`. Nothing forks to
ask and nothing is remembered outside the process. The route that
follows is logged once and shown in /metrics (`feeder_device_route`):

- mode "require": the device path is the only path. A platform other
  than the required one ([tpu] platform, default "tpu") fails the boot
  or the first request with the platform named; a device leg that
  raises or hangs fails its items with the device's own error. Nothing
  is re-run on the host, so a compile error, an out-of-memory launch
  or a refused kernel cannot pass as a slow green run.
- mode "auto": the device is asked for in the background at the first
  batch, and batches run on the host until the verdict lands. The
  required platform opens the device route, and every queued batch then
  runs on it; a missing or wrong platform routes everything host-side,
  said once. A failed or hung device leg is re-run on the host
  (`feeder_host_reruns` counts them), and a hang shuts the route.
- mode "off": host only; JAX is never imported.

The route is a function of the mode and that one verdict, and of
nothing else: not of a batch's size, not of a measured rate. The host
side of every op is block/host_legs.py.

The device route is a STAGED PIPELINE (block/device_backend.py): each
batch flows h2d -> compute -> d2h through three dedicated worker
threads, and the dispatcher keeps up to `[tpu] inflight_batches`
batches in flight — while batch N computes, batch N+1's bytes are
already moving h2d and batch N-1's results are reading back. Launch
shapes are padded to a small bucket set so XLA compiles a handful of
programs (`feeder_pad_waste_bytes` / `feeder_recompiles` price that
trade; `feeder_xla_compiles` is JAX's own count), and batches of
>= `[tpu] mesh_min_items` items shard across every visible chip via
parallel/mesh.py. The watchdog (`[tpu] batch_timeout_s`) covers every
in-flight stage: a hang abandons the stage threads and disables the
device path.
"""

from __future__ import annotations

import asyncio
import logging
import os
import threading
import time
from typing import Optional

import numpy as np

from ..utils import tracing
from ..utils.metrics import registry
from . import host_legs
from .device_backend import (DEFAULT_PAD_BUCKETS, STAGES, DevicePipeline,
                             JaxDeviceBackend, StubDeviceBackend,
                             bucket_items, group_bytes)

log = logging.getLogger("garage_tpu.block.feeder")

# inline decode/repair fast path size ceiling: above this the GF
# matmul runs in a worker thread via the queue (a multi-MiB stripe
# matmul inline would park the event loop for milliseconds per GET)
_INLINE_DECODE_MAX_BYTES = 1 << 20
# a batch stuck longer than this means the device backend hung: the
# stage threads are abandoned and the device path is disabled
_BATCH_TIMEOUT = 300.0

# ops whose per-stream cadence makes a short gather window pay: each PUT
# stream keeps at most one of these in flight per block, so lanes only
# line up if the dispatcher lingers for the other streams' submissions
# ([tpu] batch_linger_ms)
_LINGER_OPS = ("hash_md5", "hash", "encode_put", "sha256")

# the JAX platform the device path must find unless [tpu] platform says
# otherwise (tests and chip_smoke's rehearsal name "cpu")
_DEVICE_PLATFORM = "tpu"


class _DeviceHang(Exception):
    """A device pipeline stage hung (or a sibling batch's stage did and
    aborted the generation)."""


class _Item:
    """One queued request. `t_sub`, `t_disp`, `t_set` are
    `perf_counter` stamps of the hop's hand-offs: put on the queue, its
    batch handed to the pipeline, its future set (0.0 = not reached)."""

    __slots__ = ("op", "data", "future", "extra", "t_sub", "t_disp", "t_set")

    def __init__(self, op: str, data, future, extra=None):
        self.op = op
        self.data = data
        self.future = future
        self.extra = extra
        self.t_sub = time.perf_counter()
        self.t_disp = self.t_set = 0.0

    def resolve(self, res) -> None:
        """Hand the caller its result or error; the first one stands."""
        if self.future.done():
            return
        self.t_set = time.perf_counter()
        if isinstance(res, BaseException):
            self.future.set_exception(res)
        else:
            self.future.set_result(res)


class DeviceFeeder:
    """One per BlockManager. mode: "auto" (device when this process has
    the required one, host otherwise and for a device leg that fails),
    "off" (host only), "require" (device only: no host re-run, fail
    with the platform or the device's error named)."""

    def __init__(self, codec=None, mode: str = "auto",
                 max_batch: int = 256, tpu_cfg=None, backend=None):
        self.codec = codec
        # greedy-drain cap: blocks per device batch ([tpu] batch_blocks)
        self.max_batch = max(1, int(max_batch))

        # [tpu] knobs (utils/config.py TpuConfig), with the defaults a
        # direct-constructed feeder (tests, bench) gets
        def knob(name, default):
            v = getattr(tpu_cfg, name, None) if tpu_cfg is not None else None
            return default if v is None else v

        # staged-pipeline depth: batches concurrently in flight through
        # the h2d/compute/d2h stages. A batch's dispatch slot is held
        # until its d2h readback drains, so depth 2 (double buffering)
        # leaves h2d idle whenever compute+d2h of the batch ahead
        # outlast its own h2d — matching the depth to the THREE stages
        # keeps the transfer engine fed (bench_put_path: ~0.80 -> 0.86
        # frontend_efficiency at pinned stub rates)
        self.inflight_batches = max(1, int(knob("inflight_batches", 3)))
        self.pad_buckets = tuple(
            int(b) for b in knob("pad_buckets", DEFAULT_PAD_BUCKETS))
        self.mesh_min_items = int(knob("mesh_min_items", 8))
        # per-batch watchdog budget, instance-level so tests can shrink
        # it without patching every co-located feeder
        self.batch_timeout = float(knob("batch_timeout_s", _BATCH_TIMEOUT))
        # [tpu] batch_linger_ms: gather-window budget for same-op PUT
        # lanes (hash/md5/sha256/encode). 0 disables the linger — every
        # batch ships with whatever the greedy drain found.
        self.batch_linger = max(
            0.0, float(knob("batch_linger_ms", 6.0))) / 1000.0
        # the JAX platform the device path must find ([tpu] platform)
        self.platform = str(knob("platform", _DEVICE_PLATFORM))
        # device backend: "jax" (real accelerator), "stub"
        # (deterministic latency emulator — CI), or a ready object
        if backend is None:
            backend = (os.environ.get("GARAGE_TPU_DEVICE_BACKEND")
                       or knob("device_backend", "jax"))
        self._backend_sel = backend
        self._backend = None
        self._backend_lock = threading.Lock()

        env_mode = os.environ.get("GARAGE_TPU_DEVICE")
        if mode == "auto" and env_mode == "off":
            # test/CI kill-switch: JAX is never imported, no stage
            # threads are spawned
            mode = "off"
        elif mode == "auto" and env_mode == "require":
            # the device path is mandatory (chip_smoke's node 1, the
            # bench's device server): see the module docstring
            mode = "require"
        self.mode = mode
        self._q: Optional[asyncio.Queue] = None
        self._task: Optional[asyncio.Task] = None
        # the one device verdict of this process: what jax.devices()
        # gave the backend's stage thread (None until asked, and for
        # ever under mode "off"), whether the device path is open, and
        # the route taken with its reason — logged once, in /metrics
        self.device_info: Optional[dict] = None
        self._device_ok: Optional[bool] = None
        self.route = "host" if mode == "off" else "undecided"
        self.route_reason = "mode off" if mode == "off" else ""
        self._verdict_lock: Optional[asyncio.Lock] = None
        self._verdict_task: Optional[asyncio.Task] = None
        self.stats = {"batches": 0, "items": 0, "device_batches": 0,
                      "device_items": 0, "device_bytes": 0,
                      "inline_items": 0, "max_batch": 0,
                      "pad_waste_bytes": 0, "recompiles": 0,
                      "mesh_batches": 0,
                      # device legs that failed or hung, and those that
                      # were then re-run on the host (auto only; under
                      # require a failed leg fails its items)
                      "device_errors": 0, "host_reruns": 0,
                      # read-side (decode + repair) engagement counters:
                      # total items through the feeder, items/bytes that
                      # ran on the device path (the degraded-GET /
                      # rebuild twin of device_items)
                      "decode_items": 0, "decode_device_items": 0,
                      "decode_device_bytes": 0,
                      # present-sets whose decode matrix this process
                      # keeps (ops/rs.py decode_bitmat_t's cache), as of
                      # the last decode leg staged
                      "decode_patterns": 0}
        # staged pipeline state: the current executor generation, the
        # batches in flight, per-stage busy seconds and the wall-clock
        # union of windows with >= 1 device leg in flight (overlap
        # efficiency = sum(busy) / wall; > 1.0 means stages overlap)
        self._pl: Optional[DevicePipeline] = None
        self._pl_busy: dict[str, float] = {s: 0.0 for s in STAGES}
        self._pl_wall = 0.0
        self._win_open = 0
        self._win_t0 = 0.0
        self._inflight_tasks: set = set()
        # PUT streams currently inside read_and_put_blocks: sizes the
        # hash_md5 gather window (one block hash in flight per stream)
        self.active_streams = 0
        # observed throughput: (op, backend) -> [bytes, seconds], for
        # perf_summary; nothing routes by it
        self._perf: dict[tuple[str, str], list[float]] = {}
        self._perf_lock = threading.Lock()  # inline (loop) vs worker thread
        # items that ran on the device path, per feeder op
        self.device_items_by_op: dict[str, int] = {}

    def perf_summary(self) -> dict[str, float]:
        """Observed MB/s per (op, backend) — /metrics + bench surface."""
        with self._perf_lock:
            return {f"{op}/{be}": round(b / t / 1e6, 1)
                    for (op, be), (b, t) in self._perf.items() if t > 0}

    # ---- lifecycle ----------------------------------------------------

    def _ensure_started(self) -> None:
        if self._task is None or self._task.done():
            self._q = asyncio.Queue()
            self._task = asyncio.create_task(self._run(), name="device-feeder")
            # supervised by stop(): not a leak at loop teardown
            self._task._garage_background = True
        if self.mode == "off":
            self._device_ok = False
        elif self._device_ok is None and self._backend_is_stub():
            # the stub emulates a device; there is none to ask for
            self._judge(self._get_backend().verdict())

    def _backend_is_stub(self) -> bool:
        sel = self._backend_sel
        return (sel == "stub" if isinstance(sel, str)
                else getattr(sel, "name", "") == "stub")

    def _get_backend(self):
        """The staged device backend, built lazily from a pipeline
        worker thread (jax import / device discovery never run on the
        event loop, and both sit under the batch watchdog)."""
        with self._backend_lock:
            if self._backend is None:
                sel = self._backend_sel
                if not isinstance(sel, str):
                    self._backend = sel
                elif sel == "stub":
                    self._backend = StubDeviceBackend(self.codec)
                else:
                    self._backend = JaxDeviceBackend(
                        codec=self.codec, pad_buckets=self.pad_buckets,
                        mesh_min_items=self.mesh_min_items,
                        stats=self.stats)
            return self._backend

    # ---- the device verdict -------------------------------------------

    def _judge(self, info: Optional[dict], error: str = "") -> None:
        """Turn what the backend got from jax.devices() into the route,
        and say which it is and why."""
        self.device_info = info
        if info is not None and info["platform"] in (self.platform, "stub"):
            self._device_ok = True
            self.route = "device"
            self.route_reason = (f"{info['count']} x {info['device_kind']}"
                                 f" ({info['platform']})")
            log.info("feeder route: device, %s", self.route_reason)
            return
        if info is not None:
            error = (f"platform {info['platform']!r} found where "
                     f"{self.platform!r} is required")
        self._close_route(error)

    def _close_route(self, reason: str) -> None:
        """The device path is shut for this process: auto serves from
        the host from here on, require refuses."""
        self._device_ok = False
        self.route_reason = reason
        if self.mode == "require":
            self.route = "refused"
            log.error("feeder: device required but %s", reason)
        else:
            self.route = "host"
            log.warning("feeder route: host, %s", reason)

    async def device_verdict(self) -> Optional[dict]:
        """Which device this process has: {"platform", "device_kind",
        "count"} as the backend's own stage thread got them from
        jax.devices(), asked once. Under mode "require" raises, naming
        what was found, unless that is the required platform — the
        server calls this at boot so a node without its chip never
        comes up."""
        if self.mode == "off":
            return None
        if self._device_ok is None:
            if self._verdict_lock is None:
                self._verdict_lock = asyncio.Lock()
            async with self._verdict_lock:
                if self._device_ok is None:
                    await self._take_verdict()
        if self.mode == "require" and not self._device_ok:
            raise RuntimeError(
                f"device required but {self.route_reason}")
        return self.device_info

    async def _take_verdict(self) -> None:
        if self._backend_is_stub():
            self._judge(self._get_backend().verdict())
            return
        try:
            info = await asyncio.wait_for(
                self._stage_call(self._pipeline(), "h2d",
                                 lambda: self._get_backend().verdict(), [],
                                 "verdict"),
                self.batch_timeout)
        except (asyncio.TimeoutError, _DeviceHang):
            self._on_device_hang(f"jax.devices() did not return within "
                                 f"{self.batch_timeout:g}s")
        except Exception as e:
            self._judge(None, f"no device answered "
                              f"({type(e).__name__}: {e})")
        else:
            self._judge(info)

    async def warm_programs(self, block_len: int, lease=None,
                            put_items: int = 1,
                            decode_items: int = 0) -> None:
        """Launch once, for every item bucket, what full
        `block_len`-byte blocks launch on this node: up to `put_items`
        the content hash and, given a full ingest `lease`, the all-lease
        RS encode leg; up to `decode_items`, with a codec, the decode
        leg of a degraded GET (k zero shards of a full block's shard
        length — the present-set is data to the kernel, so any one
        stands for all) and, at one item, the repair leg of one missing
        shard, which is what resync launches when a straggler of a
        quorum write never landed. A node that serves from its device
        then meets no program for the first time inside a request: they
        are built (or loaded from the compile cache) here, at boot, on
        the stage threads and under the batch watchdog like any leg. A
        wave of rebuilds, or a stripe missing several shards, still
        builds its program when it comes. Results are thrown away and no
        item is counted; a failure is the caller's to raise."""
        await self.device_verdict()
        if not self._device_ok or self._backend_is_stub():
            return
        from ..ops import jaxenv
        from ..utils import data as _data
        from .hostbuf import stripe_shard_len

        put_legs = []
        if _data._content_algo == "blake3":  # blake2 never leaves the host
            put_legs.append(("hash", bytes(block_len)))
        if lease is not None and self.codec is not None:
            put_legs.append(("encode_put", lease))
        decode_legs, repair_legs = [], []
        if self.codec is not None and decode_items > 0:
            k = self.codec.k
            first_k = tuple(range(k))
            shards = [bytes(stripe_shard_len(1 + block_len, k))] * k
            decode_legs.append(("decode", (first_k, shards, 1 + block_len)))
            if self.codec.m > 0:
                repair_legs.append(("repair", (first_k, (k,), shards)))
        said = []
        for what, legs, max_items in (("PUT", put_legs, put_items),
                                      ("decode", decode_legs, decode_items),
                                      ("repair", repair_legs, 1)):
            if not legs:
                continue
            t0, before = time.perf_counter(), jaxenv.compile_stats()
            n = 1  # the fewest items that launch the next bucket
            while n <= max_items:
                for op, item in legs:
                    await asyncio.wait_for(self._staged_op(op, [item] * n),
                                           self.batch_timeout)
                n = bucket_items(n, self.pad_buckets) + 1
            after = jaxenv.compile_stats()
            said.append("%s to %d items in %.1f s (%d requested, %d built)" % (
                what, max_items, time.perf_counter() - t0,
                after["compile_requests"] - before["compile_requests"],
                after["compiles"] - before["compiles"]))
        log.info("feeder: programs of %d-byte blocks warm: %s", block_len,
                 "; ".join(said) or "none to launch")

    def _maybe_start_verdict(self) -> None:
        """auto: ask for the device in the background at the first
        batch. Everything runs host-side until the verdict lands."""
        if self.mode != "auto" or self._device_ok is not None \
                or self._verdict_task is not None:
            return
        self._verdict_task = asyncio.create_task(self.device_verdict(),
                                                 name="feeder-verdict")
        # supervised by stop(): not a leak at loop teardown
        self._verdict_task._garage_background = True

    async def stop(self) -> None:
        # snapshot-and-clear EVERYTHING this stop owns BEFORE awaiting
        # (GL12): stop() yields while the cancelled dispatcher
        # unwinds, and a concurrent _submit()'s _ensure_started() can
        # legitimately respawn a new dispatcher (with a NEW queue)
        # into self._task during that window. The old code nulled
        # self._task after the await — orphaning the live respawn —
        # and drained self._q, which by then was the RESPAWN's queue:
        # a fresh submission got a spurious "feeder stopped" while the
        # feeder was running. Only the snapshots are touched below.
        t, self._task = self._task, None
        vt, self._verdict_task = self._verdict_task, None
        q = self._q  # snapshot only: the unwinding dispatcher still
        # reads self._q between suspension points; a respawn swaps in
        # a fresh queue object, so draining the snapshot can never
        # touch the respawn's submissions
        inflight = list(self._inflight_tasks)
        self._inflight_tasks.clear()
        for bg in (t, vt):
            if bg is not None:
                bg.cancel()
                try:
                    await bg
                except (asyncio.CancelledError, Exception):
                    pass
        # cancel every in-flight pipelined batch THIS stop snapshotted:
        # each _finish_batch fails its items' futures on the way out,
        # so no caller hangs on a batch that was mid-stage
        for bt in inflight:
            bt.cancel()
            try:
                await bt
            except (asyncio.CancelledError, Exception):
                pass
        # fail anything still queued on the OLD queue so no caller
        # awaits forever
        if q is not None:
            while not q.empty():
                q.get_nowait().resolve(RuntimeError("feeder stopped"))

    # ---- public async ops ---------------------------------------------

    async def _submit(self, op: str, data, extra=None):
        t_in = time.perf_counter()
        self._ensure_started()
        if self.mode == "require" and self._device_ok is not True:
            await self.device_verdict()
            # stop() may have torn down the dispatcher while jax came
            # up; restart it or the enqueued item below would await a
            # future nothing ever resolves
            self._ensure_started()
        item = _Item(op, data, asyncio.get_running_loop().create_future(),
                     extra)
        async with tracing.span("feeder.submit", op=op) as sp:
            await self._q.put(item)
            try:
                return await item.future
            finally:
                if item.t_set:
                    # hand-off 4 and the whole hop; a caller cancelled
                    # before its item was answered observes neither
                    now = time.perf_counter()
                    reg = registry()
                    reg.observe("feeder_resume_lag_seconds",
                                now - item.t_set, hop="item")
                    reg.observe("feeder_hop_seconds", now - t_in, op=op)
                sp.attrs["wait_us"] = int(
                    max(0.0, item.t_disp - item.t_sub) * 1e6)

    async def hash(self, data: bytes) -> bytes:
        """Content hash of one block (batched with concurrent callers)."""
        if self._host_inline_ok():
            from ..utils import data as _data

            if _data._content_algo == "blake3":
                from .. import native

                self.stats["inline_items"] += 1
                t0 = time.perf_counter()
                # lint: ignore[GL10] host-inline fast path is gated to small items; the flagged open chain is the one-time native build, cached for the process lifetime
                out = native.blake3_many([data])[0]
                self._record("hash", "host", len(data),
                             time.perf_counter() - t0)
                return out
        return await self._submit("hash", data)

    async def hash_with_md5(self, data: bytes, md5acc) -> bytes:
        """Content hash + S3-ETag MD5 advance for one block. Rides the
        feeder queue so blocks from CONCURRENT requests form one batch:
        MD5 is a strict serial chain within an object but independent
        across objects, and the native kernel runs up to 8 chains in
        AVX2 lockstep (measured: 0.48 GB/s single -> 2.4 GB/s at 8
        lanes). Host route fuses blake3 into the same call; device
        route batch-advances the MD5s host-side while the content hash
        batches to the accelerator (a serial chain can't ride the
        tree-structured device path)."""
        if getattr(md5acc, "fused", False):
            from ..utils import data as _data

            if _data._content_algo == "blake3":
                if self.active_streams <= 1 \
                        and self._host_inline_ok():
                    # lone stream: no lanes to gather — the inline
                    # one-pass interleaved kernel beats the queue hop
                    # plus a 1-lane batch
                    self.stats["inline_items"] += 1
                    t0 = time.perf_counter()
                    out = md5acc.update_with_blake3(data)
                    self._record("hash", "host", len(data),
                                 time.perf_counter() - t0)
                    return out
                return await self._submit("hash_md5", (md5acc, data))
        # non-native fallback: hashlib md5 + separate content hash
        if (os.cpu_count() or 1) > 1 and len(data) >= 65536:
            out, _ = await asyncio.gather(
                self.hash(data), asyncio.to_thread(md5acc.update, data))
            return out
        md5acc.update(data)
        return await self.hash(data)


    async def sha256_hex(self, data) -> str:
        """SigV4 chunk-signature SHA-256 (hex). Chunk digests are
        independent across streams (the signature chain, not the hash,
        carries continuity), so concurrent PUTs batch into one device
        launch. A lone stream skips the queue: hashlib in a worker
        thread beats a 1-row device round trip and keeps the event loop
        free for the socket."""
        from ..ops import sha256 as _sha

        if self.active_streams <= 1 or self.mode == "off":
            t0 = time.perf_counter()
            out = await asyncio.to_thread(_sha.sha256_hex_py, data)
            self._record("sha256", "host", _sha.part_len(data),
                         time.perf_counter() - t0)
            return out
        return await self._submit("sha256", data)

    async def encode(self, packed: bytes) -> list[bytes]:
        """Erasure parts for one packed block (batched)."""
        if self.codec is None:
            raise RuntimeError("feeder has no codec")
        return await self._submit("encode", packed)

    def _host_inline_ok(self) -> bool:
        """True when the queue+thread hop is pure overhead: the route is
        the host for good (mode "off", or the verdict shut the device
        route) and the native kernel (which releases the GIL) can run
        inline on the event loop. The queue path exists to build device
        batches; paying two thread handoffs per item to then run
        host-side was a top cost in the r3 kernel-vs-system gap. False
        under "require", while the verdict is out, and when the device
        route is open."""
        from .. import native

        if not native.loaded():
            return False
        return self.mode == "off" or (self.mode == "auto"
                                      and self._device_ok is False)

    async def encode_put(self, data: bytes, prefix: bytes = b"") -> list:
        """Erasure parts for one packed block (logical stream
        prefix||data), each framed as a ready-to-send shard payload
        (pack_shard format). The host path is ONE GIL-released native
        call per block (split + parity + crc + headers fused:
        native.rs_encode_packed); the device path batches the parity
        matmul through XLA then packs host-side."""
        if self.codec is None:
            raise RuntimeError("feeder has no codec")
        lease = data if hasattr(data, "stripe") else None
        if self._host_inline_ok():
            from .. import native
            from ..ops import rs

            self.stats["inline_items"] += 1
            t0 = time.perf_counter()
            pmat = rs.parity_matrix(self.codec.k, self.codec.m)
            if lease is not None:
                # zero-copy ingest lease: body is already resident in
                # the pool buffer; hand the native kernel the view
                # (scheme byte travels as the prefix, same framing)
                # lint: ignore[GL10] host-inline fast path is gated to small items; the flagged open chain is the one-time native build, cached for the process lifetime
                out = native.rs_encode_packed(
                    lease.view(), self.codec.k, self.codec.m, pmat,
                    prefix=bytes([lease.buf[0]]))
                self._record("encode", "host", lease.total_len,
                             time.perf_counter() - t0)
                return out
            out = native.rs_encode_packed(
                data, self.codec.k, self.codec.m, pmat, prefix=prefix)
            self._record("encode", "host", len(prefix) + len(data),
                         time.perf_counter() - t0)
            return out
        if lease is not None:
            # the lease itself is the queue item: the device stage
            # reads its stripe() rows without re-packing, and the host
            # route slices the view — release stays with the PUT task,
            # which awaits this call before letting go
            return await self._submit("encode_put", lease)
        return await self._submit("encode_put", (prefix, data))

    async def verify_blocks(self, items: list[tuple[bytes, bytes]]
                            ) -> list[bool]:
        """[(hash32, plain)] -> per-item content-hash match (scrub)."""
        if not items:
            return []
        if self._host_inline_ok():
            from ..utils import data as _data

            if _data._content_algo == "blake3":
                from .. import native

                self.stats["inline_items"] += len(items)
                t0 = time.perf_counter()
                # already batched -> one thread handoff is amortized;
                # running it inline would park the event loop for the
                # whole multi-MiB native call, every scrub batch
                digs = await asyncio.to_thread(
                    native.blake3_many, [d for _, d in items])
                self._record("hash", "host", sum(len(d) for _, d in items),
                             time.perf_counter() - t0)
                return host_legs.verify_matches(digs, items)
        futs = [self._submit("verify", (h, d)) for h, d in items]
        return list(await asyncio.gather(*futs))

    async def parity_check(self, stripes: list[list[bytes]]) -> list[bool]:
        """Scrub deep pass: per-stripe cross-shard consistency. Each
        stripe is the full [k data + m parity] shard payload list
        (equal lengths within one stripe). True = the stored parity
        rows equal parity re-derived from the data rows. A corrupt DATA
        shard flips every re-derived parity row; a corrupt PARITY row
        differs only in itself — either way at least one row
        mismatches, so any single corruption is detected
        (ops/rs.parity_check on the device route; native GF matmul
        compare on the host route)."""
        if self.codec is None:
            raise RuntimeError("feeder has no codec")
        if not stripes:
            return []
        if self._host_inline_ok():
            # already batched; one thread handoff amortized over the
            # whole multi-MiB native call (same shape as verify_blocks)
            self.stats["inline_items"] += len(stripes)
            t0 = time.perf_counter()
            out = await asyncio.to_thread(host_legs.parity_check,
                                          self.codec, stripes)
            self._record("parity", "host",
                         sum(len(b) for s in stripes for b in s),
                         time.perf_counter() - t0)
            return out
        futs = [self._submit("parity_check", s) for s in stripes]
        return list(await asyncio.gather(*futs))

    def _check_stripe(self, present, shards, k: int, width: int) -> tuple:
        """Shared validation for the read-side ops, BEFORE the queue:
        a malformed item must fail its own caller, never poison the
        group-mates it would have batched with (one _exec_group
        exception fails the whole leg)."""
        present = tuple(present)
        if len(present) != k or len(shards) != k:
            raise ValueError(
                f"need exactly k={k} present shards, got "
                f"{len(present)} indices / {len(shards)} payloads")
        if len(set(present)) != k or any(
                not 0 <= int(i) < width for i in present):
            raise ValueError(
                f"present indices must be {k} distinct values in "
                f"[0, {width}); got {present}")
        slen = len(shards[0])
        if any(len(s) != slen for s in shards):
            raise ValueError("unequal shard lengths in decode/repair "
                             "stripe (corrupt or misplaced shard)")
        return present

    async def decode(self, present, shards: list, plain_len: int) -> bytes:
        """Erasure decode of one stripe: `shards` are the surviving
        payloads in ascending `present`-index order; -> the packed
        block bytes (join_stripe at plain_len). Batched with every
        concurrent caller, so degraded GETs and rebuild waves coalesce
        into one pattern-as-data device launch. The all-systematic case
        is the CALLER's fast path (pure concat, no math) — everything
        submitted here pays a real matmul somewhere."""
        if self.codec is None:
            raise RuntimeError("feeder has no codec")
        codec = self.codec
        present = self._check_stripe(present, shards, codec.k,
                                     codec.k + codec.m)
        total = sum(len(s) for s in shards)
        if total <= _INLINE_DECODE_MAX_BYTES \
                and self._host_inline_ok():
            from .. import native
            from ..ops import rs

            self.stats["inline_items"] += 1
            self.stats["decode_items"] += 1
            t0 = time.perf_counter()
            st = np.stack([np.frombuffer(s, dtype=np.uint8)
                           for s in shards])
            # lint: ignore[GL10] host-inline fast path is gated to <= _INLINE_DECODE_MAX_BYTES stripes; the flagged open chain is the one-time native build, cached for the process lifetime
            data = native.gf_matmul(
                rs.decode_matrix(codec.k, codec.m, present), st)
            out = rs.join_stripe(data, plain_len)
            self._record("decode", "host", total,
                         time.perf_counter() - t0)
            return out
        return await self._submit("decode", (present, list(shards),
                                             plain_len))

    async def repair(self, present, missing, shards: list) -> dict:
        """Rebuild the `missing` shard payloads of one stripe from the
        k `present` ones -> {missing_index: payload}. The resync /
        scrub rebuild twin of decode — concurrent rebuilds across a
        wave batch into one launch (grouped by len(missing), since one
        launch needs a uniform output row count)."""
        if self.codec is None:
            raise RuntimeError("feeder has no codec")
        codec = self.codec
        width = codec.k + codec.m
        present = self._check_stripe(present, shards, codec.k, width)
        missing = tuple(missing)
        if not missing:
            return {}
        if any(not 0 <= int(i) < width for i in missing):
            raise ValueError(
                f"missing indices must be in [0, {width}); got {missing}")
        total = sum(len(s) for s in shards)
        if total <= _INLINE_DECODE_MAX_BYTES \
                and self._host_inline_ok():
            from .. import native
            from ..ops import rs

            self.stats["inline_items"] += 1
            self.stats["decode_items"] += 1
            t0 = time.perf_counter()
            st = np.stack([np.frombuffer(s, dtype=np.uint8)
                           for s in shards])
            # lint: ignore[GL10] host-inline fast path is gated to <= _INLINE_DECODE_MAX_BYTES stripes; the flagged open chain is the one-time native build, cached for the process lifetime
            out = native.gf_matmul(
                rs.repair_matrix(codec.k, codec.m, present, missing), st)
            self._record("decode", "host", total,
                         time.perf_counter() - t0)
            return {mi: bytes(out[j]) for j, mi in enumerate(missing)}
        return await self._submit("repair", (present, missing,
                                             list(shards)))

    # ---- dispatcher ----------------------------------------------------

    async def _run(self) -> None:
        # the dispatcher is made inside whichever request came first:
        # its spans, and its batches', belong to no request
        tracing.detach()
        while True:
            first = await self._q.get()
            batch = [first]
            try:
                # greedy non-waiting drain: whatever queued while the
                # last batch was on the device becomes the next batch
                while not self._q.empty() \
                        and len(batch) < self.max_batch:
                    batch.append(self._q.get_nowait())
                n_same = sum(1 for it in batch if it.op == first.op)
                want = min(self.active_streams, 8)
                if first.op in _LINGER_OPS and self.batch_linger > 0 \
                        and self.active_streams > 1 and n_same < want:
                    # several PUT streams are mid-block-loop: a short
                    # async gather window lets their next submissions
                    # line up, multiplying the batch lane count (MD5
                    # AVX lanes, SHA-256 device rows, encode stripes).
                    # The wait burns no CPU — the event loop spends it
                    # reading the OTHER streams' sockets, which is
                    # exactly what gets them here. Only items matching
                    # the head op count toward the lane target; budget
                    # is [tpu] batch_linger_ms.
                    loop = asyncio.get_running_loop()
                    deadline = loop.time() + self.batch_linger
                    with tracing.span("feeder.linger", op=first.op,
                                      have=n_same, want=want):
                        while n_same < want:
                            left = deadline - loop.time()
                            if left <= 0:
                                break
                            try:
                                item = await asyncio.wait_for(
                                    self._q.get(), left)
                            except asyncio.TimeoutError:
                                break
                            batch.append(item)
                            if item.op == first.op:
                                n_same += 1
                self._maybe_start_verdict()
                # bounded in-flight depth: the dispatcher hands the
                # batch to the staged pipeline and goes straight back
                # to draining the queue / forming the next batch —
                # while batch N computes, batch N+1 stages h2d and
                # batch N-1 reads back. Depth is live-tunable
                # ([tpu] inflight_batches via /v1/s3/tuning).
                if len(self._inflight_tasks) >= max(
                        1, self.inflight_batches):
                    with tracing.span("feeder.slot_wait", op=first.op,
                                      inflight=len(self._inflight_tasks)):
                        while len(self._inflight_tasks) >= max(
                                1, self.inflight_batches):
                            await asyncio.wait(
                                self._inflight_tasks,
                                return_when=asyncio.FIRST_COMPLETED)
                # hand-off 1 ends here: drain, linger and slot wait
                now = time.perf_counter()
                reg = registry()
                for item in batch:
                    item.t_disp = now
                    reg.observe("feeder_queue_wait_seconds",
                                now - item.t_sub, op=item.op)
                t = asyncio.create_task(self._finish_batch(batch),
                                        name="feeder-batch")
                self._inflight_tasks.add(t)
                t.add_done_callback(self._inflight_tasks.discard)
            except BaseException as e:
                self._fail_batch(batch, e)
                if isinstance(e, asyncio.CancelledError):
                    raise

    @staticmethod
    def _fail_batch(batch: list, e: BaseException) -> None:
        err = (RuntimeError("feeder stopped")
               if isinstance(e, asyncio.CancelledError) else e)
        for item in batch:
            item.resolve(err)

    async def _finish_batch(self, batch: list) -> None:
        """Run one batch through plan + execution and resolve every
        item future — the one owner of a batch's futures, whatever the
        route (host thread, staged device pipeline, hang fallback)."""
        try:
            with tracing.span("feeder.batch", items=len(batch),
                              ops=",".join(sorted({it.op for it in batch}))):
                results = await self._run_batch_staged(batch)
            for item, res in zip(batch, results):
                item.resolve(res)
        except BaseException as e:
            self._fail_batch(batch, e)
            if isinstance(e, asyncio.CancelledError):
                raise

    async def _run_batch_staged(self, batch: list) -> list:
        """Plan the batch, then execute host legs in a worker thread
        and device legs through the staged pipeline, concurrently."""
        self.stats["batches"] += 1
        self.stats["items"] += len(batch)
        self.stats["decode_items"] += sum(
            1 for it in batch if it.op in ("decode", "repair"))
        self.stats["max_batch"] = max(self.stats["max_batch"], len(batch))
        results: list = [None] * len(batch)
        on_device, on_host = self._plan_batch(batch)
        if not on_device:
            # pure host batch: exactly the pre-pipeline behavior (one
            # thread hop), still bounded so a stalled host path fails
            # the batch instead of wedging a pipeline slot forever
            await asyncio.wait_for(
                asyncio.to_thread(self._exec_legs, batch, on_host, results),
                self.batch_timeout)
            return results
        tasks = [asyncio.create_task(
            self._exec_device_leg(op, perf_op, batch, idxs, results))
            for op, perf_op, idxs in on_device]
        if on_host:
            tasks.append(asyncio.create_task(asyncio.wait_for(
                asyncio.to_thread(self._exec_legs, batch, on_host,
                                  results),
                self.batch_timeout)))
        try:
            await asyncio.gather(*tasks)
        except BaseException:
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
            raise
        return results

    async def _exec_device_leg(self, op: str, perf_op: str, batch: list,
                               idxs: list, results: list) -> None:
        """One device-routed op group: staged h2d -> compute -> d2h
        with the watchdog over ALL stages. A leg that raises (compile
        error, out of memory, refused kernel) or hangs is a device
        error: under "require" its items fail with it, under "auto"
        the group re-runs host-side (_device_leg_failed)."""
        blobs = [batch[i].data for i in idxs]
        total = group_bytes(op, blobs)
        self._window_open()
        try:
            try:
                out, busy = await asyncio.wait_for(
                    self._staged_op(op, blobs), self.batch_timeout)
            except (asyncio.TimeoutError, _DeviceHang):
                # hung device stage: abandon the stuck stage threads
                # and shut the device path — the abort event makes
                # sibling batches take this same branch immediately
                # instead of each waiting out its own watchdog
                reason = (f"device {op} batch stuck "
                          f">{self.batch_timeout:g}s")
                self._on_device_hang(reason)
                await self._device_leg_failed(
                    op, perf_op, batch, idxs, results, TimeoutError(reason))
                return
            except Exception as e:
                await self._device_leg_failed(op, perf_op, batch, idxs,
                                              results, e)
                return
            for i, o in zip(idxs, out):
                results[i] = o
            # the EXCLUSIVE stage execution time, not this coroutine's
            # wall: wall includes queue wait behind sibling batches in
            # the single-thread stage executors, which would understate
            # device throughput by up to the in-flight depth
            self._record(perf_op, "device", total, busy)
            self._credit_device(op, len(idxs), total)
        finally:
            self._window_close()

    def _credit_device(self, op: str, n: int, total: int) -> None:
        self.stats["device_batches"] += 1
        self.stats["device_items"] += n
        self.stats["device_bytes"] += total
        self.device_items_by_op[op] = self.device_items_by_op.get(op, 0) + n
        if op in ("decode", "repair"):
            self.stats["decode_device_items"] += n
            self.stats["decode_device_bytes"] += total

    async def _device_leg_failed(self, op: str, perf_op: str, batch: list,
                                 idxs: list, results: list,
                                 err: BaseException) -> None:
        """A device leg raised or hung. "require": the items fail with
        the device's own error — no host result may stand in for it.
        "auto": re-run the group on the host, counted in host_reruns."""
        self.stats["device_errors"] += 1
        if self.mode == "require":
            log.error("device %s batch failed (%s: %s); device is "
                      "required, failing %d item(s)", op,
                      type(err).__name__, err, len(idxs))
            for i in idxs:
                results[i] = err
            return
        log.warning("device %s batch failed (%s: %s); re-running on "
                    "the host", op, type(err).__name__, err)
        self.stats["host_reruns"] += 1
        await asyncio.wait_for(
            asyncio.to_thread(self._exec_group, op, perf_op, batch, idxs,
                              results),
            self.batch_timeout)

    async def _staged_op(self, op: str, blobs: list) -> tuple[list, float]:
        """h2d -> compute -> d2h through the current pipeline
        generation's stage threads; -> (results, exclusive device
        seconds). Each stage of THIS batch runs serially, but the
        single-thread-per-stage executors let a different batch occupy
        every other stage at the same time."""
        pl = self._pipeline()
        be = self._get_backend
        busy: list[float] = []
        n = len(blobs)
        staged = await self._stage_call(
            pl, "h2d", lambda: be().stage(op, blobs), busy, op, n)
        handle = await self._stage_call(
            pl, "compute", lambda: be().compute(op, staged), busy, op, n)
        out = await self._stage_call(
            pl, "d2h", lambda: be().readback(op, handle), busy, op, n)
        return out, sum(busy)

    async def _stage_call(self, pl: DevicePipeline, stage: str, fn,
                          busy: list, op: str, items: int = 0):
        """Run `fn` on the stage's thread and wait for it. Hand-offs 2
        and 3 are observed here, on the loop, once per job and as far
        as the job got: a job abandoned in the queue observes nothing,
        one that hangs only its wait for the thread."""
        if pl.dead:
            raise _DeviceHang("pipeline aborted")
        loop = asyncio.get_running_loop()
        job = pl.submit(stage, loop, fn)
        abort = asyncio.create_task(pl.aborted.wait())
        try:
            await asyncio.wait({job.fut, abort},
                               return_when=asyncio.FIRST_COMPLETED)
            if not job.fut.done() and job.claimed:
                # the stage thread is ALREADY EXECUTING this job (the
                # hung job never yields its thread, so ours is live):
                # wait it out instead of racing a host re-run against
                # its side effects — d2h advances the serial MD5 ETag
                # chains, and abandoning it mid-flight would apply
                # them twice. Still bounded by the caller's watchdog.
                await asyncio.wait({job.fut})
            if job.fut.done():
                busy.append(job.busy)
                return job.fut.result()
            raise _DeviceHang("pipeline aborted by a sibling batch hang")
        finally:
            abort.cancel()
            now = time.perf_counter()
            t_claim, t_done = job.t_claim, job.t_done
            if t_claim:
                wait = t_claim - job.t_sub
                reg = registry()
                reg.observe("feeder_stage_wait_seconds", wait, stage=stage)
                if t_done and job.fut.done():
                    reg.observe("feeder_resume_lag_seconds", now - t_done,
                                hop=stage)
                    # lint: ignore[GL10] emit buffers; the open+write is one amortized page-cache append per _FLUSH_EVERY spans on an already-open file
                    tracing.record(f"dev.{stage}", t_claim, t_done, op=op,
                                   items=items, wait_us=int(wait * 1e6))
            if not job.fut.done():
                # abandon: a queued job is skipped outright by the
                # stage thread (never executed), a claimed one
                # completes silently with its delivery dropped
                job.fut.cancel()

    # ---- pipeline lifecycle + overlap accounting (loop thread) ---------

    def _pipeline(self) -> DevicePipeline:
        if self._pl is None or self._pl.dead:
            self._pl = DevicePipeline(self._pl_busy)
        return self._pl

    def _on_device_hang(self, reason: str) -> None:
        """First watchdog to fire wins: mark the generation dead (the
        stuck daemon threads are abandoned, never joined), wake every
        sibling batch via the abort event and shut the device path."""
        pl = self._pl
        if pl is None or pl.dead:
            return  # a sibling already handled this hang
        pl.dead = True
        pl.aborted.set()
        self._close_route(reason)

    def _window_open(self) -> None:
        if self._win_open == 0:
            self._win_t0 = time.monotonic()
        self._win_open += 1

    def _window_close(self) -> None:
        self._win_open -= 1
        if self._win_open == 0:
            self._pl_wall += time.monotonic() - self._win_t0

    def pipeline_stats(self) -> dict:
        """Overlap observability (admin /metrics + bench): per-stage
        busy seconds, the wall-clock union of in-flight windows, and
        busy/wall — > 1.0 means stages of different batches really ran
        concurrently (the double-buffering proof)."""
        busy = {k: round(v, 6) for k, v in self._pl_busy.items()}
        wall = self._pl_wall
        if self._win_open > 0:
            wall += time.monotonic() - self._win_t0
        total = sum(self._pl_busy.values())
        return {"busy_s": busy, "wall_s": round(wall, 6),
                "overlap_efficiency": round(total / wall, 3) if wall > 0
                else 0.0,
                "inflight": len(self._inflight_tasks)}

    # ---- the plan, and the host legs (worker thread) -------------------

    def _record(self, op: str, backend: str, nbytes: int, dt: float) -> None:
        with self._perf_lock:  # inline paths record from the loop thread
            ent = self._perf.setdefault((op, backend), [0.0, 0.0])
            # exponential forgetting so old (cold-compile) samples fade
            if ent[1] > 30.0:
                ent[0] *= 0.5
                ent[1] *= 0.5
            ent[0] += nbytes
            ent[1] += max(dt, 1e-6)

    def _plan_batch(self, batch: list[_Item]) -> tuple[list, list]:
        """-> (device legs, host legs), each [(op, perf_op, idxs)], one
        leg an op. The route is the mode and the one device verdict:
        the device under "require", and under "auto" once the verdict
        opened the route; the host while the verdict is out and when it
        shut the route."""
        from ..utils import data as _data

        route_open = self.mode == "require" or self._device_ok is True
        by_op: dict[str, list[int]] = {}
        for i, item in enumerate(batch):
            by_op.setdefault(item.op, []).append(i)
        on_device: list[tuple] = []
        on_host: list[tuple] = []
        for op, idxs in by_op.items():
            perf_op = ("hash" if op in ("verify", "hash_md5") else
                       "encode" if op == "encode_put" else
                       "parity" if op == "parity_check" else
                       "decode" if op == "repair" else op)
            # blake2 never runs on the device
            device = route_open and not (
                perf_op == "hash" and _data._content_algo != "blake3")
            (on_device if device else on_host).append((op, perf_op, idxs))
        return on_device, on_host

    def _exec_legs(self, batch: list, legs: list, results: list) -> None:
        for op, perf_op, idxs in legs:
            self._exec_group(op, perf_op, batch, idxs, results)

    def _exec_group(self, op: str, perf_op: str, batch: list,
                    idxs: list, results: list) -> None:
        """One op group on the host (block/host_legs.py); an error is
        its items' result."""
        blobs = [batch[i].data for i in idxs]
        t0 = time.perf_counter()
        try:
            out = host_legs.run(self.codec, op, blobs)
            for i, o in zip(idxs, out):
                results[i] = o
            self._record(perf_op, "host", group_bytes(op, blobs),
                         time.perf_counter() - t0)
        except Exception as e:
            for i in idxs:
                results[i] = e
