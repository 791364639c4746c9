"""Staged device backends for the DeviceFeeder pipeline.

The feeder used to execute each device batch as one blocking
pack->transfer->compute->readback hop in a single thread, so transfer
never overlapped compute and the dispatcher idled until the hop
returned. This module is the staged replacement:

- `StageExecutor` / `DevicePipeline`: three dedicated daemon worker
  threads — h2d (host pack + host->device transfer), compute (kernel
  launch), d2h (readback + host-side finish). Each stage is a single
  thread, so stage N of batch B+1 runs WHILE stage N+1 of batch B runs:
  with the feeder's bounded in-flight depth that is classic
  double-buffering (batch N computes while N+1's bytes move h2d and
  N-1's results read back). A device leg is one `StageJob` that the
  threads hand on h2d -> compute -> d2h among themselves; it crosses
  back to the event loop once, when it is done. Threads are daemon and
  generations are disposable: a hung device call is ABANDONED (the
  feeder marks the generation dead) instead of joined — a stuck
  non-daemon pool thread would wedge interpreter exit.

- `JaxDeviceBackend`: the real accelerator route, split into the three
  stages, with **fixed-shape padded launches**: item counts are padded
  up to a small set of bucket sizes (`[tpu] pad_buckets`) and RS shard
  lengths to the next power of two, so XLA compiles a handful of
  programs instead of one per distinct batch shape. Zero padding is
  safe for the RS ops because the code is linear (zero rows encode to
  zero parity); hash pad rows are full-length zero messages whose
  digests are sliced away
  (BLAKE3's tree shape depends on the true chunk count, so the chunk
  axis is NOT bucketed — only the item axis is). Padding waste and
  recompile count are tracked in the feeder's stats
  (`feeder_pad_waste_bytes`, `feeder_recompiles`). When more than one
  device is visible, batches of at least `[tpu] mesh_min_items` items
  route through parallel/mesh.py's (dp, tp) data-plane mesh; a mesh
  that cannot be built is an error of the leg that asked for it, not a
  quiet single-device launch. `verdict()` is where this process learns
  which device it has (ops/jaxenv): asked once, from the h2d stage
  thread, before the first jit. The READ
  side (`decode` / `repair` ops, ISSUE 13) ships the erasure pattern as
  DATA: each stripe's decode/repair bit-matrix rides alongside the
  shard bytes into one batched matmul (rs.gf_apply_batched), so the
  launch-shape key — and with it the compile count — never depends on
  which shards survived.

- `StubDeviceBackend`: a deterministic device emulator (selected via
  `[tpu] device_backend = "stub"` or GARAGE_TPU_DEVICE_BACKEND=stub)
  that computes real results with the host kernels but sleeps a
  modelled transfer/compute/readback latency per stage, so pipeline
  overlap, the watchdog hang-fallback, and the `feeder_device_items`
  live gate are all CI-testable on a box with no accelerator.
"""

from __future__ import annotations

import logging
import os
import queue
import threading
import time

import numpy as np

from . import host_legs

log = logging.getLogger("garage_tpu.block.device_backend")

STAGES = ("h2d", "compute", "d2h")

# item-count bucket ladder for fixed-shape launches ([tpu] pad_buckets)
DEFAULT_PAD_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def bucket_items(n: int, buckets) -> int:
    """Smallest bucket >= n (n itself above the ladder)."""
    for b in buckets:
        if b >= n:
            return int(b)
    return n


def bucket_len(n: int, quantum: int = 1024) -> int:
    """Next power of two >= n (minimum `quantum`) — the shard-length
    bucket for RS launches. Lengths cluster at the block size anyway;
    power-of-two rounding keeps the tail shapes finite."""
    b = quantum
    while b < n:
        b <<= 1
    return b


def group_bytes(op: str, blobs: list) -> int:
    """Payload bytes of one op group (the feeder's accounting rule)."""
    if op in ("verify", "encode_put", "hash_md5"):
        # 2-tuples, except encode_put also carries ingest leases
        # (scheme byte + body in one pool buffer, sized total_len)
        return sum(b.total_len if hasattr(b, "total_len") else len(b[1])
                   for b in blobs)
    if op == "sha256":  # item = one message: a buffer or a span list
        from ..ops.sha256 import part_len

        return sum(part_len(b) for b in blobs)
    if op == "parity_check":  # item = one stripe (shard list)
        return sum(len(b) for s in blobs for b in s)
    if op == "decode":  # item = (present, shards, plain_len)
        return sum(len(b) for it in blobs for b in it[1])
    if op == "repair":  # item = (present, missing, shards)
        return sum(len(b) for it in blobs for b in it[2])
    return sum(len(b) for b in blobs
               if isinstance(b, (bytes, bytearray, memoryview)))


class StageJob:
    """One device leg on its way through the stages. `steps` is
    [(stage, fn)]: each fn runs on its stage's thread with what the one
    before it returned (the first with None), and the stage threads hand
    the job on among themselves. When the last step returns, or any
    step raises, the thread that ran it makes ONE
    `loop.call_soon_threadsafe(done, job)`: the leg's only crossing back
    to the event loop.

    `state`, under `lock`, is the hand-shake between the threads and the
    loop: "queued" at step `at`, "running" it, "sent" (the callback is
    on its way) or "abandoned". A job abandoned before a stage claims it
    never runs there: stage fns carry side effects (the d2h MD5 lane
    advance), and running one after its leg already failed over to the
    host would apply them twice. A claimed step runs to its end; if the
    job was let go of meanwhile, nothing more runs and nothing crosses.
    Per step, `t_sub`, `t_claim`, `t_done` are `perf_counter` stamps of
    queued, claimed and returned (0.0 = not reached) and `busy` its
    execution time — the device time the feeder records, not the wall,
    which includes queueing behind sibling legs. The threads write
    them; the loop reads them once the job is back or let go of."""

    __slots__ = ("loop", "steps", "done", "lock", "state", "at", "value",
                 "error", "t_sub", "t_claim", "t_done", "busy")

    def __init__(self, loop, steps, done):
        self.loop = loop
        self.steps = steps
        self.done = done
        self.lock = threading.Lock()
        self.state = "queued"
        self.at = 0
        self.value = self.error = None
        n = len(steps)
        self.t_sub = [0.0] * n
        self.t_claim = [0.0] * n
        self.t_done = [0.0] * n
        self.busy = [0.0] * n

    @property
    def stage(self) -> str:
        return self.steps[self.at][0]

    def finished(self) -> bool:
        """Every step returned."""
        return self.error is None and self.t_done[-1] > 0.0

    def abandon(self, running: bool = False) -> bool:
        """Let go of the job from the loop: a queued job never runs
        again, and with `running` neither does one whose step is running
        now (the step ends, its result is dropped). A job whose callback
        is on its way is kept. -> True if the job is let go of."""
        with self.lock:
            if self.state == "queued" or (running
                                          and self.state == "running"):
                self.state = "abandoned"
            return self.state == "abandoned"


class StageExecutor:
    """One daemon worker thread running one pipeline stage's steps in
    the order they were queued, and handing each job on (`hand_on`).
    Busy seconds accumulate into the shared per-stage dict — the
    numerator of the overlap-efficiency metric."""

    def __init__(self, name: str, busy: dict, hand_on):
        self.name = name
        self._busy = busy
        self._hand_on = hand_on
        self._jobs: queue.SimpleQueue = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=f"feeder-{name}")
        self._thread.start()

    def put(self, job: StageJob) -> None:
        job.t_sub[job.at] = time.perf_counter()
        self._jobs.put(job)

    def close(self) -> None:
        """The thread ends once it reaches this in its queue."""
        self._jobs.put(None)

    def _loop(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:
                return
            with job.lock:
                if job.state == "abandoned":
                    continue  # let go of while queued: never run
                job.state = "running"
            i = job.at
            job.t_claim[i] = time.perf_counter()
            try:
                job.value = job.steps[i][1](job.value)
            except BaseException as e:
                job.error = e
            job.t_done[i] = time.perf_counter()
            job.busy[i] = job.t_done[i] - job.t_claim[i]
            self._busy[self.name] += job.busy[i]
            self._hand_on(self, job)


class DevicePipeline:
    """One GENERATION of the three stage executors. A job goes from
    stage to stage on the threads and crosses to its loop once, at the
    end (`crossings[stage]` counts the crossings each stage's thread
    made). On a hang the feeder marks the generation `dead`: its threads
    then hand no job on — a job whose step returns crosses back where it
    is — and the feeder lets go of every job still queued; the next
    device batch gets a fresh generation (fresh threads — the stuck ones
    are abandoned, never joined)."""

    def __init__(self, busy: dict, crossings: dict):
        self.dead = False
        self._crossings = crossings
        self._execs = {s: StageExecutor(s, busy, self._hand_on)
                       for s in STAGES}

    def submit(self, loop, steps: list, done) -> StageJob:
        job = StageJob(loop, steps, done)
        self._execs[job.stage].put(job)
        return job

    def close(self) -> None:
        """End the generation's threads when they next turn to their
        queues: a job that is still on its way through the stages is not
        run further and never crosses back, so a generation is closed
        once nothing waits for its jobs — when it is dead, or its
        feeder stopped. A thread stuck in a step ends when it returns."""
        for ex in self._execs.values():
            ex.close()

    def _hand_on(self, ex: StageExecutor, job: StageJob) -> None:
        """On a stage thread, after a step: on to the next stage, or
        back to the loop in one callback."""
        last = (job.error is not None or self.dead
                or job.at + 1 == len(job.steps))
        with job.lock:
            if job.state == "abandoned":
                return  # let go of while this step ran
            if last:
                job.state = "sent"
            else:
                job.state = "queued"
                job.at += 1
        if not last:
            self._execs[job.stage].put(job)
            return
        self._crossings[ex.name] += 1
        try:
            job.loop.call_soon_threadsafe(job.done, job)
        except RuntimeError:
            # loop already closed (feeder stopped mid-batch): the
            # callers' futures are moot, nothing to deliver to
            pass


# ---------------------------------------------------------------------------
# JAX backend: padded fixed-shape staged launches (+ multi-chip mesh)
# ---------------------------------------------------------------------------


class JaxDeviceBackend:
    """The real accelerator route, split into h2d / compute / d2h so
    the pipeline can overlap them across batches. All three methods run
    in StageExecutor worker threads (never the event loop): jax import,
    device discovery and every XLA call stay off the loop and under the
    feeder watchdog."""

    name = "jax"

    def __init__(self, codec=None, pad_buckets=DEFAULT_PAD_BUCKETS,
                 mesh_min_items: int = 8, stats: dict | None = None):
        self.codec = codec
        self.pad_buckets = tuple(sorted(int(b) for b in pad_buckets)) \
            or DEFAULT_PAD_BUCKETS
        self.mesh_min_items = max(1, int(mesh_min_items))
        self.stats = stats if stats is not None else {
            "pad_waste_bytes": 0, "recompiles": 0, "mesh_batches": 0}
        self._shapes_seen: set = set()
        self._zero_stripes: dict = {}  # (k, slen) -> device zeros
        self._mesh = None
        # shard length of the node's full block (the feeder's boot
        # warm-up sets it; 0 = not known): every RS launch of blocks up
        # to that size is staged at the programs the warm-up built
        self.stripe_len = 0

    # ---- shape accounting ------------------------------------------------

    def _note_shape(self, key: tuple, waste: int) -> None:
        if key not in self._shapes_seen:
            self._shapes_seen.add(key)
            self.stats["recompiles"] += 1
        self.stats["pad_waste_bytes"] += int(waste)

    def _shard_bucket(self, slen: int) -> int:
        """The shard-length bucket of a launch whose longest shard is
        `slen`: never shorter than a full block's, so a compressed block
        or a short tail takes the program the boot built instead of one
        of its own (zero columns encode and decode to zero columns)."""
        return bucket_len(max(slen, self.stripe_len))

    def _stripe_row(self, blocks: list, slens: list):
        """The row length at which every item of an encode leg can be
        staged as a (k, row) stripe: a lease's own shard length, else a
        full block's (`stripe_len`). None when leases disagree, when an
        item is longer than the row, or when no row is known."""
        row = next((sl for b, sl in zip(blocks, slens)
                    if hasattr(b, "stripe")), self.stripe_len)
        if not row or any(sl != row if hasattr(b, "stripe") else sl > row
                          for b, sl in zip(blocks, slens)):
            return None
        return row

    def verdict(self) -> dict:
        """{"platform", "device_kind", "count"} of this process, from
        jax.devices() — the feeder's one device verdict. Also the first
        JAX call of the process: ops/jaxenv places the compile cache
        and starts counting compilations before any jit runs."""
        from ..ops import jaxenv

        return jaxenv.verdict()

    def _get_mesh(self):
        """(dp, tp) mesh when >1 device is visible, else None. Resolved
        once, lazily, from a stage worker thread. A failure to build it
        raises into the leg that asked: several chips are visible, so a
        single-device launch would not be what was asked for."""
        if self._mesh is None and self.verdict()["count"] > 1:
            from ..parallel import mesh as pmesh

            self._mesh = pmesh.data_plane_mesh()
            log.info("feeder multi-chip mesh active: %s",
                     dict(self._mesh.shape))
        return self._mesh

    # ---- stage: host pack + pad + h2d -----------------------------------

    def stage(self, op: str, blobs: list):
        if op in ("hash", "verify", "hash_md5"):
            datas = blobs if op == "hash" else [d for _, d in blobs]
            return (op, blobs, self._stage_hash(datas))
        if op == "sha256":
            return (op, blobs, self._stage_sha256(blobs))
        if op in ("encode", "encode_put"):
            # encode_put items: (prefix, data) tuples, or ingest leases
            # whose stripe() already IS the split layout — those skip
            # the concatenate entirely
            blocks = (blobs if op == "encode" else
                      [b if hasattr(b, "stripe") else b[0] + b[1]
                       for b in blobs])
            return (op, blobs, self._stage_rs(blocks, "encode"))
        if op == "parity_check":
            return (op, blobs, self._stage_parity(blobs))
        if op in ("decode", "repair"):
            return (op, blobs, self._stage_gf(op, blobs))
        raise RuntimeError(f"unknown device op {op!r}")

    def _stage_hash(self, datas: list[bytes]):
        import jax

        from ..ops import treehash

        groups: dict[int, list[int]] = {}
        for i, d in enumerate(datas):
            groups.setdefault(treehash.n_chunks_for(len(d)), []).append(i)
        staged = []
        for c, idxs in groups.items():
            b = bucket_items(len(idxs), self.pad_buckets)
            padded = c * treehash.CHUNK_LEN
            buf = np.zeros((b, padded), dtype=np.uint8)
            # pad rows are full-length zero messages: the tree shape
            # (hence the compiled program) is per chunk count, so a
            # shorter pad length would be an invalid c-chunk message
            lengths = np.full(b, padded, dtype=np.int32)
            for row, i in enumerate(idxs):
                arr = np.frombuffer(datas[i], dtype=np.uint8)
                buf[row, : arr.size] = arr
                lengths[row] = arr.size
            waste = b * padded - sum(len(datas[i]) for i in idxs)
            self._note_shape(("hash", c, b), waste)
            staged.append((c, idxs, jax.device_put(buf),
                           jax.device_put(lengths)))
        return (len(datas), staged)

    def _stage_sha256(self, datas: list):
        import jax

        from ..ops import sha256 as sha

        groups: dict[int, list[int]] = {}
        for i, d in enumerate(datas):
            groups.setdefault(
                sha.blocks_bucket(sha.n_blocks_for(sha.part_len(d))),
                []).append(i)
        staged = []
        for npad, idxs in groups.items():
            b = bucket_items(len(idxs), self.pad_buckets)
            buf = np.zeros((b, npad * sha.BLOCK), dtype=np.uint8)
            # pad rows compress one zero block; the mask freezes the
            # rest and readback never reads them
            nbs = np.ones(b, dtype=np.int32)
            for row, i in enumerate(idxs):
                nbs[row] = sha.pad_row_into(buf[row], datas[i])
            waste = (b * npad * sha.BLOCK
                     - sum(sha.part_len(datas[i]) for i in idxs))
            self._note_shape(("sha256", npad, b), waste)
            staged.append((idxs, jax.device_put(buf),
                           jax.device_put(nbs), npad))
        return (len(datas), staged)

    def _stage_rs(self, blocks: list, tag: str):
        import jax

        from ..ops import rs

        k, m = self.codec.k, self.codec.m

        def blen(b):
            return b.total_len if hasattr(b, "total_len") else len(b)

        slens = [b.slen if hasattr(b, "slen") else rs.shard_len(len(b), k)
                 for b in blocks]
        bpad = bucket_items(len(blocks), self.pad_buckets)
        mesh = (self._get_mesh()
                if len(blocks) >= self.mesh_min_items else None)
        row = self._stripe_row(blocks, slens) if mesh is None else None
        smax = self._shard_bucket(row or max(slens))
        if mesh is not None:
            dp, tp = mesh.shape["dp"], mesh.shape["tp"]
            bpad = ((bpad + dp - 1) // dp) * dp
            smax = ((smax + tp - 1) // tp) * tp
        waste = bpad * k * smax - sum(blen(b) for b in blocks)
        self._note_shape((tag, k, m, bpad, smax, mesh is not None), waste)
        if row:
            # stripe leg: every item is a (k, row) stripe — a lease's
            # pool buffer IS that layout, so h2d reads it directly (no
            # host-side re-pack copy), and a block that came as bytes
            # (compressed, or a short tail) is split into a zero-tailed
            # stripe of the same shape. The pad to (bpad, k, smax)
            # happens on-device; readback slices each item's own shard
            # length straight from these host stripes. Missing items
            # are zero stripes stacked in, so the stack and the pad are
            # one program an item BUCKET, as the kernels are, and not
            # one an item count or a data-dependent length.
            import jax.numpy as jnp

            stripes = []
            for b, sl in zip(blocks, slens):
                if hasattr(b, "stripe"):
                    stripes.append(b.stripe())
                else:
                    st = np.zeros((k, row), dtype=np.uint8)
                    st[:, :sl] = rs.split_stripe(b, k)
                    stripes.append(st)
            devs = [jax.device_put(st) for st in stripes]
            zero = self._zero_stripes.get(devs[0].shape)
            if zero is None:
                zero = self._zero_stripes[devs[0].shape] = \
                    jnp.zeros_like(devs[0])
            dev = jnp.stack(devs + [zero] * (bpad - len(blocks)))
            if smax > row:
                dev = jnp.pad(dev, ((0, 0), (0, 0), (0, smax - row)))
            return (blocks, slens, stripes, dev, None, smax)
        batch = np.zeros((bpad, k, smax), dtype=np.uint8)
        copied = 0
        for i, b in enumerate(blocks):
            if hasattr(b, "stripe"):
                sh = b.stripe()
                copied += sh.size
            else:
                sh = rs.split_stripe(b, k)
            batch[i, :, : sh.shape[1]] = sh
        if copied and tag == "encode":
            # a lease fell off the stripe leg (mesh round-up, or an item
            # longer than a lease's stripe): the pad copy is real data-plane
            # bytes, so the wire->device copy audit must see it
            from ..utils.metrics import registry

            registry().inc("s3_put_copy_bytes", copied, path="stage_pack")
        if mesh is not None:
            from ..parallel import mesh as pmesh

            dev = jax.device_put(batch, pmesh.bytes_sharding(mesh))
        else:
            dev = jax.device_put(batch)
        return (blocks, slens, batch, dev, mesh, smax)

    def _stage_parity(self, stripes: list[list[bytes]]):
        import jax

        k, m = self.codec.k, self.codec.m
        smax = self._shard_bucket(max(len(s[0]) for s in stripes))
        bpad = bucket_items(len(stripes), self.pad_buckets)
        mesh = (self._get_mesh()
                if len(stripes) >= self.mesh_min_items else None)
        if mesh is not None:
            dp, tp = mesh.shape["dp"], mesh.shape["tp"]
            bpad = ((bpad + dp - 1) // dp) * dp
            smax = ((smax + tp - 1) // tp) * tp
        arr = np.zeros((bpad, k + m, smax), dtype=np.uint8)
        for i, s in enumerate(stripes):
            for j, b in enumerate(s):
                arr[i, j, : len(b)] = np.frombuffer(b, dtype=np.uint8)
        waste = bpad * (k + m) * smax - sum(
            len(b) for s in stripes for b in s)
        self._note_shape(("parity", k, m, bpad, smax, mesh is not None),
                         waste)
        if mesh is not None:
            from ..parallel import mesh as pmesh

            dev = jax.device_put(arr, pmesh.bytes_sharding(mesh))
        else:
            dev = jax.device_put(arr)
        return (len(stripes), dev, mesh, smax)

    def _stage_gf(self, op: str, items: list):
        """Pad + h2d for the pattern-as-data decode/repair launches.

        Items are grouped by OUTPUT ROW COUNT (decode always rebuilds
        k rows; repair rebuilds len(missing) — 1 for a resync shard
        rebuild, more for a multi-loss stripe), because one batched
        launch needs a uniform (B, 8k, 8·rows) matrix stack. Within a
        group the shard stacks pad up the usual bucket ladder and the
        per-item bit-matrices ride as DATA: the shape key deliberately
        EXCLUDES the erasure pattern, so feeder_recompiles stays flat
        across mixed present-sets — the whole point of the kernel."""
        import jax

        from ..ops import rs

        k, m = self.codec.k, self.codec.m
        shards_of = ((lambda it: it[1]) if op == "decode"
                     else (lambda it: it[2]))
        groups: dict[int, list[int]] = {}
        for i, it in enumerate(items):
            rows = k if op == "decode" else len(it[1])
            groups.setdefault(rows, []).append(i)
        staged = []
        for rows, idxs in groups.items():
            slens = [len(shards_of(items[i])[0]) for i in idxs]
            smax = self._shard_bucket(max(slens))
            bpad = bucket_items(len(idxs), self.pad_buckets)
            mesh = (self._get_mesh()
                    if len(idxs) >= self.mesh_min_items else None)
            if mesh is not None:
                dp, tp = mesh.shape["dp"], mesh.shape["tp"]
                bpad = ((bpad + dp - 1) // dp) * dp
                smax = ((smax + tp - 1) // tp) * tp
            batch = np.zeros((bpad, k, smax), dtype=np.uint8)
            # pad rows keep zero matrices: zero maps to zero output
            # rows, sliced away at readback (the code is linear)
            mats = np.zeros((bpad, 8 * k, 8 * rows), dtype=np.int8)
            for row, i in enumerate(idxs):
                it = items[i]
                present = tuple(it[0])
                for j, s in enumerate(shards_of(it)):
                    batch[row, j, : len(s)] = np.frombuffer(s,
                                                            dtype=np.uint8)
                mats[row] = (rs.decode_bitmat_t(k, m, present)
                             if op == "decode"
                             else rs.repair_bitmat_t(k, m, present,
                                                     tuple(it[1])))
            waste = bpad * k * smax - sum(
                len(b) for i in idxs for b in shards_of(items[i]))
            self._note_shape((op, k, rows, bpad, smax, mesh is not None),
                             waste)
            if mesh is not None:
                from ..parallel import mesh as pmesh

                dev = jax.device_put(batch, pmesh.bytes_sharding(mesh))
                mdev = jax.device_put(mats, pmesh.mats_sharding(mesh))
            else:
                dev = jax.device_put(batch)
                mdev = jax.device_put(mats)
            staged.append((rows, idxs, slens, mdev, dev, mesh, smax))
        return staged

    # ---- compute: launch the kernels (async dispatch, no block) ---------

    def compute(self, op: str, staged):
        op, blobs, inner = staged
        if op in ("hash", "verify", "hash_md5"):
            from ..ops import treehash

            n, groups = inner
            launched = [(c, idxs, treehash.hash_fn(c)(buf, lens))
                        for c, idxs, buf, lens in groups]
            return (op, blobs, (n, launched))
        if op == "sha256":
            from ..ops import sha256 as sha

            n, groups = inner
            launched = [(idxs, sha.hash_fn(npad)(buf, nbs))
                        for idxs, buf, nbs, npad in groups]
            return (op, blobs, (n, launched))
        if op in ("encode", "encode_put"):
            from ..ops import rs

            blocks, slens, batch, dev, mesh, smax = inner
            k, m = self.codec.k, self.codec.m
            if mesh is not None:
                from ..parallel import mesh as pmesh

                parity = pmesh.make_encode_step(mesh, k, m, smax)(dev)
                self.stats["mesh_batches"] += 1
            else:
                parity = rs.encode(k, m, dev)
            return (op, blobs, (blocks, slens, batch, parity))
        if op == "parity_check":
            from ..ops import rs

            n, dev, mesh, smax = inner
            k, m = self.codec.k, self.codec.m
            if mesh is not None:
                from ..parallel import mesh as pmesh

                ok = pmesh.make_parity_check_step(mesh, k, m, smax)(dev)
                self.stats["mesh_batches"] += 1
            else:
                ok = rs.parity_check(k, m, dev)
            return (op, blobs, (n, ok))
        if op in ("decode", "repair"):
            from ..ops import rs

            k = self.codec.k
            launched = []
            for rows, idxs, slens, mats, dev, mesh, smax in inner:
                if mesh is not None:
                    from ..parallel import mesh as pmesh

                    out = pmesh.make_gf_apply_step(mesh, k, rows,
                                                   smax)(mats, dev)
                    self.stats["mesh_batches"] += 1
                else:
                    out = rs.gf_apply_batched(mats, dev)
                launched.append((idxs, slens, out))
            return (op, blobs, launched)
        raise RuntimeError(f"unknown device op {op!r}")

    # ---- readback: d2h + host-side finish -------------------------------

    def readback(self, op: str, handle) -> list:
        op, blobs, inner = handle
        if op in ("hash", "verify", "hash_md5"):
            n, launched = inner
            digests: list = [None] * n
            for c, idxs, cvs in launched:
                # u32 cvs -> 32 little-endian digest bytes, same
                # conversion as treehash.hash_batch_jax
                arr = np.ascontiguousarray(
                    np.asarray(cvs).astype("<u4")).view(np.uint8)
                arr = arr.reshape(arr.shape[0], 32)
                for row, i in enumerate(idxs):
                    digests[i] = arr[row].tobytes()
            if op == "verify":
                return host_legs.verify_matches(digests, blobs)
            if op == "hash_md5":
                # hash results are safely back on the host FIRST: a
                # device failure raises before this point, so the host
                # retry re-runs with MD5 state untouched (no
                # double-counted ETag bytes). Only then batch-advance
                # the serial MD5 chains host-side.
                from .. import native

                native.md5_update_many(list(blobs))
            return digests
        if op == "sha256":
            from ..ops import sha256 as sha

            n, launched = inner
            out: list = [None] * n
            for idxs, cvs in launched:
                for i, hx in zip(idxs, sha.digests_to_hex(cvs)):
                    out[i] = hx
            return out
        if op in ("encode", "encode_put"):
            blocks, slens, batch, parity = inner
            k, m = self.codec.k, self.codec.m
            par = np.asarray(parity)
            out = []
            for i in range(len(blocks)):
                sl = slens[i]
                # `batch` is the staging array, or the stripe leg's host
                # stripes: lease buffers, still held by the PUT tasks,
                # which await this op before releasing
                src = batch[i]
                out.append([bytes(src[j, :sl]) for j in range(k)]
                           + [bytes(par[i, j, :sl]) for j in range(m)])
            if op == "encode_put":
                from .manager import pack_shard

                return [[pack_shard(pp, b.total_len
                                    if hasattr(b, "total_len")
                                    else len(b[0]) + len(b[1]))
                         for pp in parts]
                        for b, parts in zip(blobs, out)]
            return out
        if op == "parity_check":
            n, ok = inner
            arr = np.asarray(ok)
            return [bool(v) for v in arr[:n]]
        if op in ("decode", "repair"):
            from ..ops import rs

            results: list = [None] * len(blobs)
            for idxs, slens, out in inner:
                arr = np.asarray(out)
                for row, i in enumerate(idxs):
                    sl = slens[row]
                    if op == "decode":
                        # (present, shards, plain_len) -> packed bytes
                        results[i] = rs.join_stripe(arr[row, :, :sl],
                                                    blobs[i][2])
                    else:
                        # (present, missing, shards) -> {idx: payload}
                        results[i] = {
                            mi: bytes(arr[row, j, :sl])
                            for j, mi in enumerate(tuple(blobs[i][1]))}
            return results
        raise RuntimeError(f"unknown device op {op!r}")


# ---------------------------------------------------------------------------
# Stub backend: deterministic latency emulation over the host kernels
# ---------------------------------------------------------------------------


class StubDeviceBackend:
    """Emulated device: real results (host kernels), modelled latency.

    Each stage sleeps `fixed_s + bytes / (rate_gbps * 1e9)` with the
    op's payload bytes (d2h uses the result-size estimate), so overlap
    and watchdog behavior are measurable and DETERMINISTIC — no
    randomness anywhere. Rates come from the constructor or the
    GARAGE_TPU_STUB_GBPS env var ("h2d,compute,d2h").

    Test hook: setting `hang_stage` to one of ("h2d", "compute",
    "d2h") makes the next entry into that stage block forever —
    the injected mid-pipeline device hang the watchdog tests use.
    """

    name = "stub"

    def __init__(self, codec=None, h2d_gbps: float = 1.0,
                 compute_gbps: float = 8.0, d2h_gbps: float = 1.0,
                 fixed_s: float = 0.0):
        env = os.environ.get("GARAGE_TPU_STUB_GBPS")
        if env:
            try:
                parts = [float(x) for x in env.split(",")]
                # pad a short list with the remaining POSITIONAL
                # defaults ("1,2" keeps d2h's default, not compute's)
                defaults = [h2d_gbps, compute_gbps, d2h_gbps]
                h2d_gbps, compute_gbps, d2h_gbps = (
                    parts + defaults[len(parts):])[:3]
            except ValueError:
                log.warning("bad GARAGE_TPU_STUB_GBPS %r; using defaults",
                            env)
        self.codec = codec
        self.rates = {"h2d": h2d_gbps, "compute": compute_gbps,
                      "d2h": d2h_gbps}
        self.fixed_s = float(fixed_s)
        self.hang_stage: str | None = None

    def verdict(self) -> dict:
        """The stub says what it is; the feeder accepts it by name."""
        return {"platform": "stub", "device_kind": "stub", "count": 1}

    def _maybe_hang(self, stage: str) -> None:
        if self.hang_stage == stage:
            self.hang_stage = None  # one hang; siblings abort via event
            log.warning("stub backend: injected hang in %s stage", stage)
            threading.Event().wait()  # daemon thread, abandoned forever

    def _sleep(self, stage: str, nbytes: int) -> None:
        time.sleep(self.fixed_s + nbytes / (self.rates[stage] * 1e9))

    def stage(self, op: str, blobs: list):
        self._maybe_hang("h2d")
        nbytes = group_bytes(op, blobs)
        self._sleep("h2d", nbytes)
        return (op, blobs, nbytes)

    def compute(self, op: str, staged):
        self._maybe_hang("compute")
        op, blobs, nbytes = staged
        self._sleep("compute", nbytes)
        if op in ("verify", "hash_md5"):
            # digests only: the match rule and the MD5 advance are the
            # d2h stage's, as on the real backend
            res = host_legs.content_hashes([d for _, d in blobs])
        else:
            res = host_legs.run(self.codec, op, list(blobs))
        return (op, blobs, res)

    def readback(self, op: str, handle) -> list:
        self._maybe_hang("d2h")
        op, blobs, res = handle
        if op in ("hash", "verify", "hash_md5", "sha256"):
            out_bytes = 32 * len(res)
        elif op in ("encode", "encode_put"):
            out_bytes = sum(len(b) for parts in res for b in parts)
        elif op == "decode":
            out_bytes = sum(len(b) for b in res)
        elif op == "repair":
            out_bytes = sum(len(b) for d in res for b in d.values())
        else:
            out_bytes = len(res)
        self._sleep("d2h", out_bytes)
        if op == "verify":
            return host_legs.verify_matches(res, blobs)
        if op == "hash_md5":
            from .. import native

            native.md5_update_many(list(blobs))
        return res
