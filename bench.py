"""Headline benchmark for the TPU-native block data path.

Prints exactly one JSON line. Headline metric: RS(10,4) erasure encode
GB/s per chip (BASELINE.md driver target: 4.0 GB/s/chip). The same line
carries the system-level numbers the north star asks for ("S3 PutObject
GB/s/chip; RS encode MB/s; scrub blocks/s"):

  put_gbps             block throughput measured THROUGH
                       BlockManager.rpc_put_block on an in-process
                       6-node erasure(4,2) loopback cluster (quorum-
                       acked writes; host/native or device per the
                       feeder's mode and device verdict)
  device_put_gbps      same path with DeviceFeeder(mode="require"):
                       every encode batch forced onto the accelerator —
                       proves the device data path end to end
                       (feeder_device_items > 0)
  cpu_put_gbps         CPU BASELINE (BASELINE.md row 1): same cluster
                       shape, replicate-3 whole-block writes, feeder
                       mode="off" — the reference's replication
                       strategy on the host path
  scrub_blocks_per_s   ScrubWorker.scrub_batch over stored 1 MiB
                       blocks, content-hash verified in batched passes
  cpu_scrub_blocks_per_s  scrub with feeder mode="off" (baseline row 5)
  blake3_gbps          batched BLAKE3 content hashing on device

One process for each chip. The segment that forks a
GARAGE_TPU_DEVICE=require server runs FIRST, while this process has not
touched JAX; the server is gone before the parent imports JAX for the
in-process segments. Without a TPU the bench fails: it does not fall
back to the CPU and relabel. A segment that raises leaves its `*_error`
key in the line and makes the exit code non-zero.
"""

from __future__ import annotations

import asyncio
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np


@contextlib.contextmanager
def _env(**kv):
    """Set environment variables for a forked child only: restored on
    exit, so nothing leaks into the in-process segments that follow."""
    saved = {k: os.environ.get(k) for k in kv}
    os.environ.update(kv)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _best_of_reps(run_chain, amount: float, unit_div: float,
                  reps: int = 4) -> float:
    """Best-of-N timing. run_chain() executes one full dependency chain
    including its end-of-chain sync; the rate is amount/unit_div per
    second."""
    best = 0.0
    for _rep in range(reps):
        t0 = time.perf_counter()
        run_chain()
        dt = time.perf_counter() - t0
        best = max(best, amount / unit_div / dt)
    return best


def bench_rs_encode(jax) -> float:
    """Sustained RS(10,4) encode GB/s, measured with a DEPENDENCY CHAIN:
    each iteration's input folds in the previous parity, so iterations
    cannot overlap and a single end-of-chain sync gives wall-clock for
    exactly `iters` sequential encodes (per-call dispatch overhead
    amortized — the number a busy PUT pipeline sustains)."""
    import jax.numpy as jnp

    from garage_tpu.ops import rs

    k, m = 10, 4
    shard_len, batch, iters = 1 << 20, 8, 20  # 80 MiB per step
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(batch, k, shard_len), dtype=np.uint8)
    data = jax.device_put(data)

    @jax.jit
    def step(x):
        # the PRODUCTION encode entry point (rs.encode selects the XLA
        # bit-matmul or, with GARAGE_TPU_PALLAS, the fused Pallas
        # kernel); the xor/concat fold adds a little extra work, making
        # the figure slightly conservative
        p = rs.encode(k, m, x)
        pad = jnp.zeros((batch, k - 2 * m, shard_len), jnp.uint8)
        return x ^ jnp.concatenate([p, p, pad], axis=1)

    x = step(data)  # compile + warm
    _ = np.asarray(x[0, 0, :8])

    def chain():
        x = data
        for _ in range(iters):
            x = step(x)
        _ = np.asarray(x[0, 0, :8])  # one tiny d2h: full-chain completion

    return _best_of_reps(chain, batch * k * shard_len * iters, 1e9)


def bench_blake3(jax) -> tuple[float, float]:
    """-> (end_to_end_gbps, device_resident_gbps).

    end_to_end includes the host->device transfer each call (what a
    host-resident data path pays); device_resident chains iterations on
    device data with a digest fold (no overlap possible) — the kernel's
    own rate, which is what the PUT pipeline gets when blocks are
    already device-resident after the RS encode (DEVICE_PATH.md)."""
    import jax.numpy as jnp

    from garage_tpu.ops import treehash

    batch, iters = 32, 8
    rng = np.random.default_rng(1)
    msgs = rng.integers(0, 256, size=(batch, 1 << 20), dtype=np.uint8)
    lengths = np.full(batch, 1 << 20, dtype=np.int32)
    treehash.hash_batch_jax(msgs, lengths)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(max(iters // 2, 2)):
        treehash.hash_batch_jax(msgs, lengths)
    dt = time.perf_counter() - t0
    e2e = batch * (1 << 20) * max(iters // 2, 2) / dt / 1e9

    n_chunks = (1 << 20) // treehash.CHUNK_LEN
    rows = jnp.asarray(msgs)
    lengths_d = jax.device_put(lengths)

    @jax.jit
    def step(x):
        cv = treehash.hash_rows(x, lengths_d, n_chunks)  # (B, 8) u32
        fold = jnp.broadcast_to(cv.astype(jnp.uint8)[:, :1], x.shape)
        return x ^ fold

    x = step(rows)
    x.block_until_ready()

    def chain():
        nonlocal x
        for _ in range(iters):
            x = step(x)
        x.block_until_ready()

    best = _best_of_reps(chain, batch * (1 << 20) * iters, 1e9)
    return e2e, best


def bench_scrub_kernel(jax) -> float:
    """Device-resident parity-check scrub DETECT rate, in logical
    1 MiB blocks/s (the kernel behind BASELINE.md's "scrub ≥10×"
    target, as a number a run captures).

    This is the PRODUCT deep-scrub detect kernel
    (ScrubWorker._deep_scrub -> feeder.parity_check ->
    ops/rs.parity_check): re-derive the m parity shards from the k
    stored data shards (GF(2^8) bit-matmul — the same kernel as the
    encode headline) and compare with the stored parity; any
    single-shard corruption makes at least one parity row mismatch, so
    a clean compare certifies the stripe without per-shard hashing. Localization +
    repair (decode + content-hash, ScrubWorker._repair_stripe) run
    host-side only on flagged stripes. Chained like bench_rs_encode:
    each iteration's data folds in the previous verdict, so iterations
    cannot overlap and one end-of-chain sync times `iters` sequential
    passes. blocks/s counts logical pre-encode bytes (k·S) in MiB."""
    import jax.numpy as jnp

    from garage_tpu.ops import gf256, rs

    k, m = 10, 4
    shard_len, batch, iters = 1 << 20, 8, 20  # 80 MiB data per step
    parity_bits = gf256.bitmat_t_for(rs.parity_matrix(k, m))
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, size=(batch, k, shard_len), dtype=np.uint8)
    parity = rs.encode(k, m, data)
    shards = jnp.concatenate([jnp.asarray(data), parity], axis=1)

    @jax.jit
    def step(x):
        d = x[:, :k, :]
        p2 = gf256.bit_matmul_apply(parity_bits, d)
        bad = jnp.any(p2 != x[:, k:, :], axis=(1, 2))  # (B,) detect verdict
        # fold the verdict into the data so the next iteration depends
        # on this one (same discipline as bench_rs_encode); stored
        # parity becomes p2 so the compare work never degenerates
        fold = bad.astype(jnp.uint8)[:, None, None]
        return jnp.concatenate([d ^ fold, p2], axis=1)

    x = step(shards)  # compile + warm
    _ = np.asarray(x[0, 0, :8])

    def chain():
        x = shards
        for _ in range(iters):
            x = step(x)
        _ = np.asarray(x[0, 0, :8])

    return _best_of_reps(chain, batch * k * shard_len * iters, 1 << 20)


async def _build_cluster(tmp: str, n: int, rm, device_mode: str,
                         compression: bool = False,
                         ping_interval: float = 10.0):
    """In-process loopback cluster: n Systems + BlockManagers."""
    from garage_tpu.block import BlockManager, DataLayout
    from garage_tpu.db import open_db
    from garage_tpu.net import LocalNetwork, NetApp
    from garage_tpu.rpc import System
    from garage_tpu.rpc.layout import NodeRole

    net = LocalNetwork()
    systems, managers = [], []
    for i in range(n):
        app = NetApp(b"bench-net")
        net.register(app)
        meta = os.path.join(tmp, f"node{i}")
        os.makedirs(meta, exist_ok=True)
        s = System(app, rm, meta, status_interval=0.5,
                   ping_interval=ping_interval)
        systems.append(s)
    tasks = [asyncio.create_task(s.run()) for s in systems]
    for s in systems[1:]:
        await s.netapp.try_connect(systems[0].netapp.public_addr,
                                   systems[0].id)
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
        if all(s.layout_manager.history.current().version == 1
               for s in systems):
            break
        await asyncio.sleep(0.05)
    for i, s in enumerate(systems):
        db = open_db(os.path.join(tmp, f"node{i}", "db"), engine="memory")
        lay = DataLayout.single(os.path.join(tmp, f"node{i}", "data"))
        managers.append(BlockManager(s, db, lay, compression=compression,
                                     device_mode=device_mode))
    return systems, managers, tasks


async def _teardown(systems, managers, tasks) -> None:
    for mg in managers:
        await mg.stop()
    for s in systems:
        await s.stop()
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)


async def _settle_feeder(feeder, timeout: float = 150.0) -> None:
    """Wait for the one-time device verdict to land so
    the timed window measures steady state, not jax-import/XLA-compile
    startup cost (a server pays that once at boot, off the request
    path). No-op when the feeder is pinned host/device."""
    if feeder.mode != "auto":
        return
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if feeder._device_ok is not None:
            return
        await asyncio.sleep(0.25)


async def _pump_blocks(manager, hashes, blocks, start: int,
                       conc: int = 8) -> float:
    """Drive rpc_put_block with a fixed worker pool (no O(n^2)
    asyncio.wait churn); returns wall seconds."""
    counter = iter(range(start, len(blocks)))
    t0 = time.perf_counter()

    async def worker():
        for j in counter:
            await manager.rpc_put_block(hashes[j], blocks[j])

    await asyncio.gather(*[worker() for _ in range(conc)])
    return time.perf_counter() - t0


async def _put_cluster_bench(tmp: str, nblocks: int,
                             device_mode: str, erasure: bool) -> dict:
    """Cluster bench: pump 1 MiB blocks through BlockManager.rpc_put_block
    — the real quorum write path — then scrub what landed."""
    from garage_tpu.block.block import DataBlock
    from garage_tpu.block.repair import ScrubWorker
    from garage_tpu.db import open_db
    from garage_tpu.net import NetApp
    from garage_tpu.rpc import ReplicationMode, System
    from garage_tpu.utils.data import blake3sum

    n, k, m = 6, 4, 2
    block_len = 1 << 20
    rm = (ReplicationMode.parse(3, erasure=f"{k},{m}") if erasure
          else ReplicationMode.parse(3))
    systems, managers, tasks = await _build_cluster(tmp, n, rm, device_mode)

    rng = np.random.default_rng(2)
    blocks = [rng.integers(0, 256, block_len, dtype=np.uint8).tobytes()
              for _ in range(nblocks)]
    hashes = [blake3sum(b) for b in blocks]

    for i in range(2):  # warm/compile the encode path
        await managers[0].rpc_put_block(hashes[i], blocks[i])
    await _settle_feeder(managers[0].feeder)
    dt = await _pump_blocks(managers[0], hashes, blocks, 2)
    dt = min(dt, await _pump_blocks(managers[0], hashes, blocks, 2))
    put_gbps = (nblocks - 2) * block_len / dt / 1e9

    # ---- scrub: batched verify over locally stored whole blocks --------
    from garage_tpu.block import BlockManager, DataLayout
    from garage_tpu.net import LocalNetwork

    net1 = LocalNetwork()
    app = NetApp(b"bench-net")
    net1.register(app)
    sm = os.path.join(tmp, "scrubnode")
    os.makedirs(sm, exist_ok=True)
    s1 = System(app, ReplicationMode.parse(1), sm,
                status_interval=3600.0, ping_interval=3600.0)
    db1 = open_db(os.path.join(sm, "db"), engine="memory")
    mgr1 = BlockManager(s1, db1, DataLayout.single(os.path.join(sm, "data")),
                        compression=False, device_mode=device_mode)
    for h, b in zip(hashes, blocks):
        mgr1.write_local(h, DataBlock.plain(b).pack())
    scrubber = ScrubWorker(mgr1)
    await scrubber.scrub_batch(hashes[:4])  # warm/compile
    await _settle_feeder(mgr1.feeder)
    scrub_bps, bad = 0.0, 0
    for _rep in range(2):  # best-of-2 against co-tenant noise
        t0 = time.perf_counter()
        bad = 0
        for i in range(0, nblocks, 32):
            bad += await scrubber.scrub_batch(hashes[i:i + 32])
        scrub_bps = max(scrub_bps, nblocks / (time.perf_counter() - t0))

    feeder_stats = dict(managers[0].feeder.stats)
    feeder_pipe = managers[0].feeder.pipeline_stats()
    feeder_perf = {**managers[0].feeder.perf_summary(),
                   **{f"scrub_{k2}": v for k2, v in
                      mgr1.feeder.perf_summary().items()}}
    # wire+disk bytes per 1 MiB block: the erasure path's structural
    # advantage (k+m shards of 1/k each vs `factor` whole copies) that
    # an in-process loopback bench cannot price — on a real network and
    # disks, replicate-3 moves 2x the bytes RS(4,2) does
    if erasure:
        wire = (k + m) * ((block_len + k - 1) // k + 16) / (1 << 20)
    else:
        wire = 3.0
    await _teardown(systems + [s1], managers + [mgr1], tasks)
    return {
        "put_gbps": round(put_gbps, 3),
        "put_wire_mib_per_block": round(wire, 2),
        "scrub_blocks_per_s": round(scrub_bps, 1),
        "scrub_corrupt": bad,
        # repairs that localized from the packed cache tier (ISSUE 18)
        # instead of gathering the stripe; 0.0 on this single-node
        # whole-block lane — bench_cache_tier prices the cluster case
        "scrub_cache_hit_rate": round(
            scrubber.scrub_cache_hits
            / max(scrubber.scrub_cache_lookups, 1), 3),
        "feeder_device_items": feeder_stats["device_items"],
        "feeder_max_batch": feeder_stats["max_batch"],
        "feeder_mbps": feeder_perf,
        # staged-pipeline engagement: device-busy/wall (> 1.0 means
        # transfer really overlapped compute), the padding tax of
        # fixed-shape launches, and how many XLA programs were built —
        # so a result distinguishes "device never reached" from
        # "pipeline not overlapping"
        "feeder_overlap_efficiency": feeder_pipe["overlap_efficiency"],
        "feeder_pad_waste_pct": round(
            100.0 * feeder_stats["pad_waste_bytes"]
            / max(feeder_stats["pad_waste_bytes"]
                  + feeder_stats["device_bytes"], 1), 2),
        "feeder_recompiles": feeder_stats["recompiles"],
        "feeder_mesh_batches": feeder_stats["mesh_batches"],
    }


def bench_s3_put(nobj: int, obj_mib: int = 4, device: bool = False) -> dict:
    """The north-star metric measured at its real boundary: S3 PutObject
    through a forked single-node server — HTTP parse, SigV4, chunker,
    MD5+BLAKE3, block store — then GetObject readback. Uses the test
    harness's server fork + independent signer; UNSIGNED-PAYLOAD (the
    common SDK choice for HTTPS) so the signature pass is one HMAC, not
    a full-body SHA256.

    device=True forks the server with the TPU feeder REQUIRED on the
    live PUT path (no JAX_PLATFORMS=cpu pin) and scrapes its /metrics
    for feeder_device_items — the end-to-end proof that live S3 PUTs
    batch through the accelerator."""
    import concurrent.futures
    import shutil
    import subprocess
    import sys
    import tempfile
    import urllib.request

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tests"))
    from s3util import S3Client
    from test_s3_api import REPO, Server

    tmp = tempfile.mkdtemp(
        prefix="gt_s3bench_",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)

    class DeviceServer(Server):
        """Forked server that owns the chip: the conformance harness
        pins its servers to cpu with the feeder off; the device segment
        inherits this process's environment and requires the device.
        Under `require` the server takes its device verdict at boot and
        does not come up without a TPU (block/feeder.py)."""

        def start(self) -> None:
            import select

            pp = REPO + ((os.pathsep + os.environ["PYTHONPATH"])
                         if os.environ.get("PYTHONPATH") else "")
            env = dict(os.environ, PYTHONPATH=pp, PYTHONUNBUFFERED="1",
                       GARAGE_TPU_DEVICE="require")
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "garage_tpu.cli.server",
                 "--config", self.config_path, "--log-level", "warning"],
                cwd=REPO, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True)
            # select-with-deadline, NOT bare readline(): a server hung
            # in JAX init would block readline forever
            deadline = time.monotonic() + 240
            buf = ""
            while time.monotonic() < deadline:
                r, _, _ = select.select([self.proc.stdout], [], [], 5.0)
                if r:
                    line = self.proc.stdout.readline()
                    buf += line
                    if "ready" in line:
                        return
                if self.proc.poll() is not None:
                    raise RuntimeError("device server died at boot: "
                                       + buf[-2000:])
            self.proc.kill()
            raise RuntimeError("device server did not come up in 240s")

    srv = (DeviceServer if device else Server)(tmp)
    # the conformance harness uses tiny 64 KiB blocks; the throughput
    # bench wants the production default
    with open(srv.config_path) as f:
        cfg = f.read()
    assert "block_size = 65536" in cfg, "test harness config drifted"
    with open(srv.config_path, "w") as f:
        f.write(cfg.replace("block_size = 65536", "block_size = 1048576"))
    try:
        if device:
            srv.start()
        else:
            with _env(GARAGE_TPU_DEVICE="off"):
                srv.start()
        srv.setup_layout_and_key()
        cli = S3Client("127.0.0.1", srv.s3_port, srv.key_id, srv.secret)
        st, _, body = cli.request("PUT", "/bench")
        assert st == 200, body

        import json as _json

        def admin_tuning(spec: dict | None = None) -> dict:
            """POST (spec given) or GET the live /v1/s3/tuning knobs."""
            rq = urllib.request.Request(
                f"http://127.0.0.1:{srv.admin_port}/v1/s3/tuning",
                data=(_json.dumps(spec).encode()
                      if spec is not None else None),
                method="POST" if spec is not None else "GET",
                headers={"authorization": "Bearer test-admin-token"})
            with urllib.request.urlopen(rq, timeout=10) as r:
                return _json.loads(r.read().decode())

        # cache OFF for every cold segment: s3_put/get/range/readahead
        # numbers must keep measuring the store path (and stay
        # comparable with pre-cache rounds); the hot-cache segment
        # below re-enables it explicitly
        admin_tuning({"read_cache_max_bytes": 0})
        size = obj_mib << 20
        data = np.random.default_rng(7).integers(
            0, 256, size, dtype=np.uint8).tobytes()

        # device mode: a first compile may sit inside a request
        rq_timeout = 240.0 if device else 30.0

        def put(i):
            st, _, b = cli.request("PUT", f"/bench/o{i}", body=data,
                                   unsigned_payload=True,
                                   timeout=rq_timeout)
            assert st == 200, b[:200]

        def get(i):
            st, _, b = cli.request("GET", f"/bench/o{i}",
                                   timeout=rq_timeout)
            assert st == 200 and len(b) == size
        # warm: in device mode the first PUT pays the server's first
        # XLA compiles, the second runs the compiled programs
        put(0)
        if device:
            put(0)
        best_put = best_get = 0.0
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            for _rep in range(2 if device else 3):  # best-of
                t0 = time.perf_counter()
                list(pool.map(put, range(nobj)))
                dt = time.perf_counter() - t0
                best_put = max(best_put, nobj * size / dt / 1e9)
                t0 = time.perf_counter()
                list(pool.map(get, range(nobj)))
                dt = time.perf_counter() - t0
                best_get = max(best_get, nobj * size / dt / 1e9)
        out = {"s3_put_gbps": round(best_put, 3),
               "s3_get_gbps": round(best_get, 3)}
        if not device:
            # ---- range reads + readahead sweep (ISSUE 2) -------------
            lo, hi = size // 4, size // 4 + size // 2  # mid-object,
            # starts mid-block: exercises the partial-block slice path

            def get_range(i):
                st, _, b = cli.request(
                    "GET", f"/bench/o{i}",
                    headers={"range": f"bytes={lo}-{hi - 1}"},
                    timeout=rq_timeout)
                assert st == 206 and len(b) == hi - lo

            best_range = 0.0
            with concurrent.futures.ThreadPoolExecutor(4) as pool:
                for _rep in range(3):
                    t0 = time.perf_counter()
                    list(pool.map(get_range, range(nobj)))
                    dt = time.perf_counter() - t0
                    best_range = max(best_range,
                                     nobj * (hi - lo) / dt / 1e9)
            out["s3_get_range_gbps"] = round(best_range, 3)

            # GET throughput vs readahead depth (0 = the pre-pipeline
            # sequential behavior, the fallback switch) — flipped at
            # runtime through the admin API, no server restarts
            sweep = {}
            try:
                with concurrent.futures.ThreadPoolExecutor(4) as pool:
                    for ra in (0, 1, 3, 6):
                        admin_tuning({"get_readahead_blocks": ra})
                        best = 0.0
                        for _rep in range(2):
                            t0 = time.perf_counter()
                            list(pool.map(get, range(nobj)))
                            dt = time.perf_counter() - t0
                            best = max(best, nobj * size / dt / 1e9)
                        sweep[str(ra)] = round(best, 3)
                out["s3_get_readahead_sweep"] = sweep
                if sweep.get("0"):
                    out["s3_get_readahead_speedup"] = round(
                        max(sweep.values()) / sweep["0"], 2)
            finally:
                admin_tuning({"get_readahead_blocks": 3})

            # ---- hot-block read cache (ISSUE 3) ----------------------
            # cache on/off sweep under the SAME harness: 8 client
            # threads (the 4-thread s3_get leg above can bottleneck on
            # the Python client; hot-vs-cold is about the SERVER's
            # per-GET work, so drive it harder), cache sized to hold
            # the working set twice over, one warming pass to fill
            # probation, timed re-reads promote + hit; then the
            # identical loop with the cache off for the cold leg.
            def timed_get_pass(reps=3):
                best = 0.0
                with concurrent.futures.ThreadPoolExecutor(8) as p:
                    for _rep in range(reps):
                        t0 = time.perf_counter()
                        list(p.map(get, range(nobj)))
                        dt = time.perf_counter() - t0
                        best = max(best, nobj * size / dt / 1e9)
                return best

            try:
                admin_tuning({"read_cache_max_bytes": 2 * nobj * size})
                with concurrent.futures.ThreadPoolExecutor(8) as p:
                    list(p.map(get, range(nobj)))  # warm: miss-fill
                s0 = admin_tuning()["read_cache"]
                best_hot = timed_get_pass()
                s1 = admin_tuning()["read_cache"]
                admin_tuning({"read_cache_max_bytes": 0})  # sweep: off
                best_cold = timed_get_pass()
                dh = s1["hits"] - s0["hits"]
                dm = s1["misses"] - s0["misses"]
                out["s3_get_hot_gbps"] = round(best_hot, 3)
                out["s3_get_cold_gbps"] = round(best_cold, 3)
                out["cache_hit_rate"] = round(dh / max(dh + dm, 1), 3)
                if best_cold:
                    out["s3_get_hot_vs_cold"] = round(
                        best_hot / best_cold, 2)
            finally:
                # leave it off for the multipart leg (stays store-path)
                admin_tuning({"read_cache_max_bytes": 0})
        if not device:
            # multipart leg (BASELINE rows 3/4: big-part uploads):
            # 4 concurrent 8 MiB UploadParts + Complete, best of 2
            import xml.etree.ElementTree as ET

            part_mib, nparts = 8, 4
            pdata = np.random.default_rng(9).integers(
                0, 256, part_mib << 20, dtype=np.uint8).tobytes()
            best_mpu = 0.0
            for rep in range(2):
                st, _, b = cli.request("POST", f"/bench/mpu{rep}",
                                       query=[("uploads", "")])
                assert st == 200, b[:200]
                upload_id = ET.fromstring(b).findtext(
                    "{*}UploadId") or ET.fromstring(b).findtext("UploadId")

                def put_part(pn):
                    st, hdrs, b2 = cli.request(
                        "PUT", f"/bench/mpu{rep}",
                        query=[("partNumber", str(pn)),
                               ("uploadId", upload_id)],
                        body=pdata, unsigned_payload=True)
                    assert st == 200, b2[:200]
                    return pn, dict(hdrs)["etag"].strip('"')

                t0 = time.perf_counter()
                with concurrent.futures.ThreadPoolExecutor(4) as pool:
                    etags = dict(pool.map(put_part, range(1, nparts + 1)))
                xml_parts = "".join(
                    f"<Part><PartNumber>{pn}</PartNumber>"
                    f"<ETag>\"{etags[pn]}\"</ETag></Part>"
                    for pn in sorted(etags))
                st, _, b = cli.request(
                    "POST", f"/bench/mpu{rep}",
                    query=[("uploadId", upload_id)],
                    body=(f"<CompleteMultipartUpload>{xml_parts}"
                          f"</CompleteMultipartUpload>").encode())
                assert st == 200, b[:300]
                dt = time.perf_counter() - t0
                best_mpu = max(best_mpu,
                               nparts * (part_mib << 20) / dt / 1e9)
            out["s3_multipart_put_gbps"] = round(best_mpu, 3)
        if device:
            # scrape the LIVE server's feeder counters before stopping
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.admin_port}/metrics",
                    timeout=10) as r:
                metrics = r.read().decode()
            scr: dict[str, float] = {}
            child_platform = ""
            for line in metrics.splitlines():
                if not line.startswith("feeder_"):
                    continue
                if line.startswith("feeder_device_count{"):
                    # what the SERVER got from jax.devices() ("stub"
                    # under script/device_smoke.py's rehearsal)
                    child_platform = line.split('platform="')[1].split(
                        '"')[0]
                name = line.split()[0].split("{")[0]
                # labeled series (pipeline busy per stage) sum up
                scr[name] = scr.get(name, 0.0) + float(line.split()[-1])
            waste = scr.get("feeder_pad_waste_bytes", 0.0)
            devbytes = scr.get("feeder_device_bytes", 0.0)
            out = {"s3_device_platform": child_platform,
                   "s3_device_put_gbps": out["s3_put_gbps"],
                   "s3_device_get_gbps": out["s3_get_gbps"],
                   "s3_feeder_device_items":
                       int(scr.get("feeder_device_items", 0)),
                   "s3_feeder_device_batches":
                       int(scr.get("feeder_device_batches", 0)),
                   # pipeline engagement next to the proof counter:
                   # "never reached" reads as device_items == 0, while
                   # "engaged but serial" reads as items > 0 with
                   # overlap_efficiency <= 1.0
                   "s3_feeder_overlap_efficiency":
                       scr.get("feeder_overlap_efficiency", 0.0),
                   "s3_feeder_pipeline_busy_s": round(
                       scr.get("feeder_pipeline_busy_seconds", 0.0), 3),
                   "s3_feeder_pipeline_wall_s": round(
                       scr.get("feeder_pipeline_wall_seconds", 0.0), 3),
                   "s3_feeder_pad_waste_pct": round(
                       100.0 * waste / max(waste + devbytes, 1.0), 2),
                   "s3_feeder_recompiles":
                       int(scr.get("feeder_recompiles", 0)),
                   "s3_feeder_mesh_batches":
                       int(scr.get("feeder_mesh_batches", 0))}
        return out
    finally:
        srv.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_put_path(nobj: int = 8, obj_mib: int = 6,
                   stub_gbps: str = "0.02,0.08,0.04",
                   ingest_pool: bool = True) -> dict:
    """Stage-level proof of the wire->device PUT path (ISSUE 17): live
    S3 PUTs into an in-process erasure(4,2) cluster with the STUB
    device backend required and its stage rates pinned LOW, so the
    deterministic modelled sleeps dominate the real CPU work and the
    number that comes out measures how well the FRONTEND feeds the
    device, not the host's kernels.

    Arithmetic of the gate: every body byte rides the feeder twice
    (hash_md5 + encode_put), so per body byte the modelled h2d and
    compute stages each move 2 bytes and d2h moves (k+m)/k (the shard
    payloads). The pipelined ceiling is 1/max(stage multiples/rate);
    a path that serializes the stages gets 1/sum(...) — ~0.6 of the
    ceiling at the default rates. frontend_efficiency = achieved /
    ceiling; >= 0.8 is the CI gate (device_smoke.py).

    Also reported: the copy audit (s3_put_copy_bytes by path vs body
    bytes — the tentpole's "copy-count-one" claim, <= ~1.1x with the
    pinned ingest pool vs >= 3x for the classic path), ingest-pool
    occupancy, and a signed aws-chunked leg that proves the SigV4
    chunk-sha256 lane batches through the same device pipeline."""
    import concurrent.futures
    import pathlib
    import shutil
    import socket as _socket
    import sys
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    for p in (here, os.path.join(here, "tests")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from s3util import S3Client
    from test_model import make_garage_cluster, stop_all

    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.model.helper import GarageHelper, allow_all
    from garage_tpu.utils.metrics import registry

    rates = [float(x) for x in stub_gbps.split(",")]
    env_keys = ("GARAGE_TPU_DEVICE", "GARAGE_TPU_DEVICE_BACKEND",
                "GARAGE_TPU_STUB_GBPS", "JAX_PLATFORMS")
    saved = {k: os.environ.get(k) for k in env_keys}
    os.environ.update({"GARAGE_TPU_DEVICE": "require",
                       "GARAGE_TPU_DEVICE_BACKEND": "stub",
                       "GARAGE_TPU_STUB_GBPS": stub_gbps,
                       # the stub needs no accelerator; pinning cpu
                       # keeps plugin discovery out of the measurement
                       "JAX_PLATFORMS": "cpu"})
    tmp = tempfile.mkdtemp(
        prefix="gt_putpath_",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)
    pool = concurrent.futures.ThreadPoolExecutor(max(8, nobj))

    def copy_snapshot() -> dict[str, float]:
        return {labels.get("path", "?"): total
                for labels, _cnt, total, _mx
                in registry().series("s3_put_copy_bytes")}

    async def scenario() -> dict:
        net, garages, tasks = await make_garage_cluster(
            pathlib.Path(tmp), n=6, rf=3, erasure=(4, 2))
        g = garages[0]
        # the pool must cover every stream's in-flight window (1 block
        # being hashed + up to put_parallelism encodes) or lease
        # exhaustion stalls the chunker and the device goes idle —
        # exactly the sizing guidance in DEVICE_PATH.md.
        # ingest_pool=False (--no-ingest-pool) is the A/B control: the
        # classic copy path under identical modelled rates.
        g.config.s3_ingest_buffers = (4 * max(8, nobj)
                                      if ingest_pool else 0)
        helper = GarageHelper(g)
        key = await helper.create_key("putpath-bench")
        bucket = await helper.create_bucket("putpath")
        await helper.set_bucket_key_permissions(bucket.id, key.key_id,
                                                allow_all())
        srv = S3ApiServer(g)
        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        await srv.start("127.0.0.1", port)
        cli = S3Client("127.0.0.1", port, key.key_id,
                       key.params.secret_key, region=g.config.s3_region)
        loop = asyncio.get_running_loop()
        size = obj_mib << 20
        data = np.random.default_rng(17).integers(
            0, 256, size, dtype=np.uint8).tobytes()
        feeder = g.block_manager.feeder
        k, m = g.block_manager.codec.k, g.block_manager.codec.m

        def put(i):
            st, _, b = cli.request("PUT", f"/putpath/o{i}", body=data,
                                   unsigned_payload=True, timeout=120.0)
            assert st == 200, b[:200]

        try:
            # warm: probe verdict, pool allocation, first stub batch
            await loop.run_in_executor(pool, put, 0)
            copy0 = copy_snapshot()
            items0 = feeder.stats["device_items"]
            t0 = time.perf_counter()
            await asyncio.gather(*[loop.run_in_executor(pool, put, i)
                                   for i in range(nobj)])
            dt = time.perf_counter() - t0
            put_gbps = nobj * size / dt / 1e9
            put_items = feeder.stats["device_items"] - items0

            copy1 = copy_snapshot()
            copy_by_path = {p: copy1.get(p, 0.0) - copy0.get(p, 0.0)
                            for p in copy1
                            if copy1.get(p, 0.0) > copy0.get(p, 0.0)}
            body_bytes = float(nobj * size)

            # modelled ceiling at the pinned rates (see docstring)
            mults = (2.0, 2.0, (k + m) / k)
            ceiling = 1.0 / max(mu / r for mu, r in zip(mults, rates))
            serial = 1.0 / sum(mu / r for mu, r in zip(mults, rates))

            pl = feeder.pipeline_stats()
            ipool = getattr(g.block_manager, "_ingest_pool", None)

            # signed aws-chunked leg: per-chunk sha256 through the
            # feeder lane (1 MiB client chunks, concurrent streams)
            sha_items0 = feeder.stats["device_items"]
            chunks = [data[o:o + (1 << 20)]
                      for o in range(0, size, 1 << 20)]

            def put_signed(i):
                st, _, b = cli.put_chunked(f"/putpath/s{i}", chunks)
                assert st == 200, b[:200]

            nsig = min(nobj, 4)
            t0 = time.perf_counter()
            await asyncio.gather(*[
                loop.run_in_executor(pool, put_signed, i)
                for i in range(nsig)])
            sig_dt = time.perf_counter() - t0

            return {
                "put_path_gbps": round(put_gbps, 4),
                "put_path_modeled_ceiling_gbps": round(ceiling, 4),
                "put_path_modeled_serial_gbps": round(serial, 4),
                "frontend_efficiency": round(put_gbps / ceiling, 3),
                "put_copy_bytes_by_path": {
                    p: int(v) for p, v in sorted(copy_by_path.items())},
                "put_copy_ratio": round(
                    sum(copy_by_path.values()) / body_bytes, 3),
                "put_feeder_device_items": put_items,
                "put_pipeline_overlap": pl.get("overlap_efficiency", 0.0),
                "put_ingest_pool": (ipool.stats()
                                    if ipool is not None else None),
                "put_signed_chunked_gbps": round(
                    nsig * size / sig_dt / 1e9, 4),
                "put_sha256_device_items":
                    feeder.stats["device_items"] - sha_items0,
                "put_stub_gbps": stub_gbps,
                # per-lane throughput ledger ([MB, s] per op/backend,
                # exponentially forgotten) and the per-stage busy split
                # — the two readings the TPU recapture runbook
                # (DEVICE_PATH.md) interprets
                "put_lane_perf": {f"{o}/{be}": [round(bb / 1e6, 1),
                                                round(tt, 3)]
                                  for (o, be), (bb, tt)
                                  in feeder._perf.items()},
                "put_stage_busy": pl,
            }
        finally:
            await srv.stop()
            await stop_all(garages, tasks)

    try:
        return asyncio.run(asyncio.wait_for(scenario(), 300))
    finally:
        pool.shutdown(wait=False)
        for kk, v in saved.items():
            if v is None:
                os.environ.pop(kk, None)
            else:
                os.environ[kk] = v
        shutil.rmtree(tmp, ignore_errors=True)


def bench_qos(duration: float = 6.0, nthreads: int = 8,
              obj_mib: int = 1) -> dict:
    """QoS admission control under pressure: sustained S3 PUTs against
    an in-process erasure(4,2) cluster WHILE deep scrub re-walks the
    store, with a deliberately tight bytes/s budget. Reports admitted
    vs offered throughput, the shed rate (503 SlowDown), and what the
    feedback governor did to scrub tranquility while users were
    waiting — the traffic-control plane the qos/ subsystem exists for."""
    import concurrent.futures
    import pathlib
    import shutil
    import sys
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    for p in (here, os.path.join(here, "tests")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from s3util import S3Client
    from test_model import make_garage_cluster, stop_all

    from garage_tpu.api.s3.api_server import S3ApiServer
    from garage_tpu.model.helper import GarageHelper, allow_all
    from garage_tpu.qos.limiter import QosLimits

    tmp = tempfile.mkdtemp(
        prefix="gt_qosbench_",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)
    pool = concurrent.futures.ThreadPoolExecutor(nthreads)

    async def scenario() -> dict:
        import socket as _socket

        net, garages, tasks = await make_garage_cluster(
            pathlib.Path(tmp), n=6, rf=3, erasure=(4, 2))
        g = garages[0]
        helper = GarageHelper(g)
        key = await helper.create_key("qos-bench")
        bucket = await helper.create_bucket("qos-bench")
        await helper.set_bucket_key_permissions(bucket.id, key.key_id,
                                                allow_all())
        srv = S3ApiServer(g)
        with _socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        await srv.start("127.0.0.1", port)
        cli = S3Client("127.0.0.1", port, key.key_id,
                       key.params.secret_key, region=g.config.s3_region)
        loop = asyncio.get_running_loop()
        size = obj_mib << 20
        data = np.random.default_rng(11).integers(
            0, 256, size, dtype=np.uint8).tobytes()

        def put(name):
            st, hdrs, _ = cli.request("PUT", f"/qos-bench/{name}",
                                      body=data, unsigned_payload=True,
                                      timeout=60.0)
            return st

        try:
            # prefill (unlimited) so scrub has stripes to walk — and to
            # measure what this box can actually push, so the budget
            # below meaningfully overloads fast and slow machines alike
            t0 = time.monotonic()
            for st in await asyncio.gather(*[
                    loop.run_in_executor(pool, put, f"seed{i}")
                    for i in range(16)]):
                assert st == 200, st
            prefill_bps = 16 * size / (time.monotonic() - t0)

            # tight budget: ~1/3 of measured capacity, 1 s burst,
            # near-zero waiting room -> sustained overload MUST shed
            limit_bps = max(1 << 20, int(prefill_bps / 3))
            g.qos.set_limits(QosLimits(global_bytes_per_s=limit_bps,
                                       global_bytes_burst=limit_bps,
                                       max_wait_s=0.05))
            if g.qos_governor is not None:
                g.qos_governor.interval = 0.5  # sample fast in a short run

            # deep scrub runs CONCURRENTLY on every node, restarted
            # whenever a pass drains, throttled only by its (governed)
            # tranquility
            stop_scrub = asyncio.Event()

            async def keep_scrubbing():
                while not stop_scrub.is_set():
                    for g2 in garages:
                        sw = g2.block_manager.scrub_worker
                        if sw is not None and sw.state.cursor == b"" \
                                and not sw._due():
                            sw.command("start")
                    await asyncio.sleep(0.5)

            scrub_task = asyncio.create_task(keep_scrubbing())

            counts = {"ok": 0, "shed": 0, "other": 0}
            t_end = time.monotonic() + duration

            def hammer(i):
                n = 0
                while time.monotonic() < t_end:
                    st = put(f"w{i}-{n}")
                    n += 1
                    if st == 200:
                        counts["ok"] += 1
                    elif st == 503:
                        counts["shed"] += 1
                    else:
                        counts["other"] += 1

            t0 = time.monotonic()
            await asyncio.gather(*[loop.run_in_executor(pool, hammer, i)
                                   for i in range(nthreads)])
            dt = time.monotonic() - t0
            stop_scrub.set()
            await scrub_task

            total = counts["ok"] + counts["shed"] + counts["other"]
            deep_checked = sum(
                g2.block_manager.scrub_worker.deep_checked
                for g2 in garages
                if g2.block_manager.scrub_worker is not None)
            gov = g.qos_governor
            sw0 = g.block_manager.scrub_worker
            return {
                "qos_put_admitted_mbps": round(
                    counts["ok"] * size / dt / 1e6, 1),
                "qos_put_offered_mbps": round(
                    total * size / dt / 1e6, 1),
                "qos_limit_mbps": round(limit_bps / 1e6, 1),
                "qos_shed_rate": round(counts["shed"] / max(total, 1), 3),
                "qos_admitted": counts["ok"],
                "qos_sheds": counts["shed"],
                "qos_errors": counts["other"],
                "qos_deep_stripes_checked": deep_checked,
                "qos_governor_pressure": (round(gov.pressure, 3)
                                          if gov is not None else None),
                "qos_scrub_tranquility": (round(sw0.state.tranquility, 2)
                                          if sw0 is not None else None),
            }
        finally:
            await srv.stop()
            await stop_all(garages, tasks)

    try:
        return asyncio.run(asyncio.wait_for(scenario(), 300))
    finally:
        pool.shutdown(wait=False)
        shutil.rmtree(tmp, ignore_errors=True)


def bench_degraded(nhashes: int = 24, block_kib: int = 256) -> dict:
    """Tail latency of quorum GETs with ONE PEER HUNG, hedging on vs
    off — the number the self-healing rpc layer (PR 4) exists to move.

    An in-process 4-node replicate-3 cluster stores blocks whose read
    sets exclude node 0 (so every GET is a real remote read), then a
    chaos `rpc_hang` fault hangs every block RPC to one victim peer.
    The same GET set runs with hedging off and on; per-GET latencies
    give p50/p99. Off: a victim-first GET waits out the (adaptive)
    timeout. On: it costs one hedge delay. Both legs keep adaptive
    timeouts, so the off leg is already the IMPROVED baseline — the
    reported win is hedging's alone, on top of it."""
    import shutil
    import tempfile

    from garage_tpu.chaos import FaultSpec, arm, disarm
    from garage_tpu.rpc import ReplicationMode
    from garage_tpu.utils.data import blake3sum

    tmp = tempfile.mkdtemp(
        prefix="gt_degraded_",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)

    def pctl(xs, q):
        s = sorted(xs)
        return s[min(len(s) - 1, int(q * len(s)))]

    async def scenario() -> dict:
        rm = ReplicationMode.parse(3)
        systems, managers, tasks = await _build_cluster(tmp, 4, rm, "off")
        try:
            for m in managers:
                m.cache.configure(max_bytes=0)  # measure the rpc path
            me = systems[0].id
            peers = [s.id for s in systems[1:]]
            # blocks whose read set excludes node 0: with n=4 and rf=3
            # the read set is then exactly the other three nodes, so
            # every GET leaves the node and every peer is a candidate
            rng = np.random.default_rng(21)
            helper = systems[0].layout_helper
            hashes, salt = [], 0
            while len(hashes) < nhashes and salt < 50000:
                salt += 1
                data = rng.integers(0, 256, block_kib << 10,
                                    dtype=np.uint8).tobytes()
                h = blake3sum(data)
                if me not in helper.block_read_nodes_of(h):
                    await managers[0].rpc_put_block(h, data,
                                                    compress=False)
                    hashes.append(h)
            health = systems[0].peering.health

            async def timed_leg(hedge_on: bool):
                disarm()
                health.reset()
                # warm per-peer latency samples so adaptive timeouts
                # and hedge delays engage (the first-ranked peer — the
                # upcoming victim — serves every warm GET)
                for _ in range(3):
                    for h in hashes:
                        await managers[0].rpc_get_block(h,
                                                        cacheable=False)
                # hang whoever currently ranks FIRST, so the fault sits
                # squarely on the hot path of every GET. count=3: below
                # the breaker threshold, so the off leg measures pure
                # timeout cost (1 s, then backed-off) and stays bounded
                # — the breaker's own win is covered by tests, not here
                victim = managers[0].rpc.request_order(list(peers))[0]
                c = arm(seed=77)
                c.add(FaultSpec(kind="rpc_hang",
                                peer=victim.hex()[:8],
                                endpoint="garage_tpu/block",
                                count=3))
                health.hedging_enabled = hedge_on
                lats = []
                for h in hashes:
                    t0 = time.perf_counter()
                    got = await managers[0].rpc_get_block(
                        h, cacheable=False)
                    lats.append(time.perf_counter() - t0)
                    assert got is not None
                fired = c.total_fired
                disarm()
                return lats, fired

            # a ping-driven reorder can shuffle the victim off the hot
            # path between arming and the GETs — a leg where the hang
            # never FIRED measured nothing, so retry until both legs
            # actually injected (same rule as the tests: silent
            # non-injection proves nothing)
            for _attempt in range(3):
                off, f_off = await timed_leg(False)
                hedges0 = health.hedges_launched
                on, f_on = await timed_leg(True)
                hedges = health.hedges_launched - hedges0
                if f_off > 0 and f_on > 0:
                    break
            health.hedging_enabled = True
            out = {
                "degraded_get_p50_off_ms": round(pctl(off, 0.5) * 1e3, 1),
                "degraded_get_p99_off_ms": round(pctl(off, 0.99) * 1e3, 1),
                "degraded_get_p50_on_ms": round(pctl(on, 0.5) * 1e3, 1),
                "degraded_get_p99_on_ms": round(pctl(on, 0.99) * 1e3, 1),
                "degraded_hedges_launched": hedges,
                "degraded_faults_fired_off_on": [f_off, f_on],
            }
            if pctl(on, 0.99) > 0:
                out["degraded_p99_tail_win"] = round(
                    pctl(off, 0.99) / pctl(on, 0.99), 2)
            return out
        finally:
            disarm()
            await _teardown(systems, managers, tasks)

    try:
        return asyncio.run(asyncio.wait_for(scenario(), 300))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_decode(nblocks: int = 24, block_kib: int = 1024,
                 device_mode: str = "off") -> dict:
    """Degraded-GET + scrub-rebuild lane (ISSUE 13) — the read-side
    twin of the encode lane. An in-process 6-node erasure(4,2) cluster
    stores `nblocks`; block i's systematic shard (i % k) is then
    deleted cluster-wide, so every GET is a real degraded decode and
    the run mixes k distinct erasure patterns (the pattern-as-data
    production shape: recompiles must not scale with patterns).

      decode_get_gbps             concurrent degraded GETs end to end
                                  (gather + feeder decode + verify)
      decode_blocks_per_s/_gbps   feeder-routed decode of the gathered
                                  stripes (batched; host or device per
                                  routing/mode)
      decode_direct_blocks_per_s  pre-ISSUE-13 baseline: one serial
                                  numpy decode per stripe on the caller
      rebuild_blocks_per_s        feeder-batched shard rebuild wave
                                  (the resync/scrub repair path) vs
      rebuild_direct_blocks_per_s codec.repair_parts per stripe, serial
      decode_feeder_device_items  read-path device engagement (the
                                  degraded-GET twin of
                                  feeder_device_items)
      decode_recompiles           XLA programs built across the mixed-
                                  pattern decode/rebuild lanes (flat =
                                  the pattern-as-data proof)
    """
    import shutil
    import tempfile

    from garage_tpu.block.codec import shard_nodes_of
    from garage_tpu.ops import rs
    from garage_tpu.rpc import ReplicationMode
    from garage_tpu.utils.data import blake3sum

    k, m = 4, 2
    block_len = block_kib << 10
    tmp = tempfile.mkdtemp(
        prefix="gt_decode_",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)

    async def scenario() -> dict:
        rm = ReplicationMode.parse(3, erasure=f"{k},{m}")
        systems, managers, tasks = await _build_cluster(tmp, 6, rm,
                                                        device_mode)
        try:
            for mg in managers:
                mg.cache.configure(max_bytes=0)  # measure the decode path
            rng = np.random.default_rng(5)
            blocks = [rng.integers(0, 256, block_len,
                                   dtype=np.uint8).tobytes()
                      for _ in range(nblocks)]
            hashes = [blake3sum(b) for b in blocks]
            for h, b in zip(hashes, blocks):
                await managers[0].rpc_put_block(h, b, compress=False)
            by_id = {s.id: mg for s, mg in zip(systems, managers)}
            v = systems[0].layout_helper.current()
            # delete block i's systematic shard i%k everywhere it
            # landed: every GET degrades, patterns rotate across k
            missing = []
            for i, h in enumerate(hashes):
                placement = shard_nodes_of(v, h, k + m)
                want = i % k
                mgr = by_id[placement[want]]
                for _ in range(200):  # quorum acks at 5/6; wait for it
                    p = mgr._find(h, [f".s{want}"])
                    if p is not None:
                        break
                    await asyncio.sleep(0.01)
                if p is not None:
                    os.remove(p)
                missing.append(want)
            feeder = managers[0].feeder
            got = await managers[0].rpc_get_block(hashes[0],
                                                  cacheable=False)
            assert got == blocks[0]  # warm/compile the degraded path
            await _settle_feeder(feeder)

            async def pump_gets() -> float:
                counter = iter(range(nblocks))

                async def w():
                    for j in counter:
                        out = await managers[0].rpc_get_block(
                            hashes[j], cacheable=False)
                        assert out == blocks[j]

                t0 = time.perf_counter()
                await asyncio.gather(*[w() for _ in range(8)])
                return time.perf_counter() - t0

            get_dt = await pump_gets()
            get_dt = min(get_dt, await pump_gets())

            # gather each stripe once so the math-only lanes time the
            # decode/rebuild, not the shard fetches
            sets = []
            for h in hashes:
                placement = shard_nodes_of(v, h, k + m)
                g = await managers[0]._gather_parts(h, placement, k)
                parts, cands, _lens = g
                present = tuple(sorted(parts.keys())[:k])
                sets.append((present, [parts[i] for i in present],
                             cands[0]))
            rc0 = feeder.stats["recompiles"]

            async def feeder_decode_lane() -> float:
                t0 = time.perf_counter()
                outs = await asyncio.gather(*[
                    feeder.decode(p, s, plen) for p, s, plen in sets])
                for o, b in zip(outs, blocks):
                    assert len(o) >= len(b)
                return time.perf_counter() - t0

            fdt = await feeder_decode_lane()
            fdt = min(fdt, await feeder_decode_lane())

            def direct_decode() -> float:
                # the pre-batching shape: one numpy matmul per stripe,
                # serial on the caller thread
                t0 = time.perf_counter()
                for present, shards, plen in sets:
                    st = np.stack([np.frombuffer(s, dtype=np.uint8)
                                   for s in shards])
                    rs.join_stripe(rs.decode_np(k, m, present, st), plen)
                return time.perf_counter() - t0

            ddt = await asyncio.to_thread(direct_decode)
            ddt = min(ddt, await asyncio.to_thread(direct_decode))

            async def rebuild_lane() -> float:
                t0 = time.perf_counter()
                outs = await asyncio.gather(*[
                    feeder.repair(p, (miss,), s)
                    for (p, s, _plen), miss in zip(sets, missing)])
                assert all(missing[j] in outs[j]
                           for j in range(nblocks))
                return time.perf_counter() - t0

            rdt = await rebuild_lane()
            rdt = min(rdt, await rebuild_lane())

            codec = managers[0].codec

            def direct_rebuild() -> float:
                t0 = time.perf_counter()
                for (present, shards, _plen), miss in zip(sets, missing):
                    codec.repair_parts(dict(zip(present, shards)),
                                       (miss,))
                return time.perf_counter() - t0

            rddt = await asyncio.to_thread(direct_rebuild)
            rddt = min(rddt, await asyncio.to_thread(direct_rebuild))

            fs = dict(feeder.stats)
            waste = fs["pad_waste_bytes"]
            out = {
                "decode_get_gbps": round(
                    nblocks * block_len / get_dt / 1e9, 3),
                "decode_blocks_per_s": round(nblocks / fdt, 1),
                "decode_gbps": round(nblocks * block_len / fdt / 1e9, 3),
                "decode_direct_blocks_per_s": round(nblocks / ddt, 1),
                "decode_vs_direct": round(ddt / fdt, 2),
                "rebuild_blocks_per_s": round(nblocks / rdt, 1),
                "rebuild_direct_blocks_per_s": round(nblocks / rddt, 1),
                "rebuild_vs_direct": round(rddt / rdt, 2),
                "decode_feeder_items": fs["decode_items"],
                "decode_feeder_device_items": fs["decode_device_items"],
                "decode_recompiles": fs["recompiles"] - rc0,
                "decode_patterns_mixed": len(set(missing)),
                "decode_pad_waste_pct": round(
                    100.0 * waste
                    / max(waste + fs["decode_device_bytes"], 1), 2),
                "decode_feeder_mbps": {
                    op: v for op, v in feeder.perf_summary().items()
                    if op.startswith("decode")},
            }
            return out
        finally:
            await _teardown(systems, managers, tasks)

    try:
        return asyncio.run(asyncio.wait_for(scenario(), 300))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_cache_tier(nblocks: int = 12, block_kib: int = 512,
                     rounds: int = 4, nodes: int = 6) -> dict:
    """Cluster cache tier economics (ISSUE 15). A 6-node erasure(4,2)
    cluster serves a hot working set from EVERY node, tier off vs on:

      cache_tier_hot_get_gbps        cluster hot-GET throughput, tier on
      cache_tier_hot_get_base_gbps   node-local baseline (tier off;
                                     each node keeps its own copy)
      cache_tier_decodes             cluster-wide store decodes for the
                                     hot set with the tier on — the
                                     "~1 per block, not N" proof — vs
      cache_tier_decodes_base        N per block without it
      cache_tier_remote_hit_ms       mean GET served by a remote probe
                                     hit vs
      cache_tier_cold_decode_ms      the cold gather+decode it replaces
      cache_tier_hint_convergence_s  hot-hash hint gossip: heat node0,
                                     time until every peer knows
      cache_tier_flash_decode_amp    (ISSUE 18) cold Zipf flash crowd:
                                     cluster decodes per distinct hot
                                     block with probe leases on, vs
      ..._flash_decode_amp_nolease   the same herd with the lease
                                     wait-mode off (wait_ms=0)
      cache_tier_flash_p99_ms        herd GET p99, leases on/off —
                                     prices the park-and-wake tradeoff
      cache_tier_scrub_cache_hit_rate  stripe repairs localizing from
                                     the packed tier instead of a
                                     cluster gather
      shm_forward_*_us               shm publish+map vs loopback-socket
                                     copy per forward, by payload size
    """
    import shutil
    import socket as socketmod
    import tempfile

    from garage_tpu.rpc import ReplicationMode
    from garage_tpu.utils.data import blake3sum

    block_len = block_kib << 10
    tmp = tempfile.mkdtemp(
        prefix="gt_tier_",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)

    async def scenario() -> dict:
        rm = ReplicationMode.parse(3, erasure="4,2")
        systems, managers, tasks = await _build_cluster(
            tmp, nodes, rm, "off", ping_interval=0.3)
        try:
            rng = np.random.default_rng(15)
            blocks = [rng.integers(0, 256, block_len,
                                   dtype=np.uint8).tobytes()
                      for _ in range(nblocks)]
            hashes = [blake3sum(b) for b in blocks]
            for h, b in zip(hashes, blocks):
                await managers[0].rpc_put_block(h, b, compress=False,
                                                cacheable=False)

            def decodes() -> int:
                return sum(m.metrics["store_reads"] for m in managers)

            async def hot_sweep() -> float:
                t0 = time.perf_counter()
                for _ in range(rounds):
                    await asyncio.gather(*[
                        _read_all(m) for m in managers])
                return time.perf_counter() - t0

            async def _read_all(m) -> None:
                for h, b in zip(hashes, blocks):
                    got = await m.rpc_get_block(h)
                    assert len(got) == len(b)

            def reset_caches() -> None:
                for m in managers:
                    m.cache.clear()

            async def warm_once() -> None:
                # ONE node touches the set first (the production shape:
                # some reader is always first; the herd arrives after).
                # With the tier on this seeds the owners via the
                # write-through pushes; wait for them to land.
                await _read_all(managers[0])
                if managers[0].cache_tier.enabled:
                    deadline = time.perf_counter() + 10.0
                    by_node = {s.id: m for s, m in zip(systems,
                                                       managers)}
                    for h in hashes:
                        o = managers[0].cache_tier.owner_of(h)
                        om = by_node[o] if o is not None \
                            else managers[0]
                        while om.cache.get(h) is None \
                                and time.perf_counter() < deadline:
                            await asyncio.sleep(0.01)

            # ---- node-local baseline: tier off ------------------------
            for m in managers:
                m.cache_tier.enabled = False
            d0 = decodes()
            await warm_once()
            base_dt = await hot_sweep()
            base_decodes = decodes() - d0

            # ---- tier on ----------------------------------------------
            reset_caches()
            for m in managers:
                m.cache_tier.enabled = True
            d0 = decodes()
            await warm_once()
            tier_dt = await hot_sweep()
            tier_decodes = decodes() - d0

            # ---- latency lanes ----------------------------------------
            # remote probe-hit GETs: non-owner reads of owner-warm keys
            lat_hit, lat_cold = [], []
            for h, b in zip(hashes, blocks):
                reader = next((m for m in managers
                               if m.cache_tier.owner_of(h) is not None),
                              None)
                if reader is None:
                    continue
                t0 = time.perf_counter()
                got = await reader.rpc_get_block(h)
                dt = time.perf_counter() - t0
                if reader.cache.get(h) is None:  # really remote-served
                    lat_hit.append(dt)
                t0 = time.perf_counter()
                await reader.rpc_get_block(h, cacheable=False)
                lat_cold.append(time.perf_counter() - t0)

            # ---- hint gossip convergence ------------------------------
            # a FRESH hash (never read in the sweeps, so no earlier
            # ping can have carried it): heat it, clock the spread
            fresh = os.urandom(1 << 10)
            hot_h = blake3sum(fresh)
            m0 = managers[0]
            m0.cache.insert(hot_h, fresh)
            m0.cache.get(hot_h)  # a hit makes it gossip-worthy
            t0 = time.perf_counter()
            conv = None
            while time.perf_counter() - t0 < 20.0:
                if all(m.cache_tier.is_hot(hot_h)
                       for m in managers[1:]):
                    conv = time.perf_counter() - t0
                    break
                await asyncio.sleep(0.02)

            # ---- flash crowd: cold-herd decode amplification ----------
            # (ISSUE 18) every node hammers a Zipf-weighted sequence
            # over a fully COLD set, probe leases on vs off. The
            # prefetch lane is parked for the drill: the sweeps above
            # left 120 s-TTL hints everywhere, and owners acting on
            # them mid-herd would decode behind the count.
            from garage_tpu.block.cache_tier import (
                LEASE_WAIT_MS_DEFAULT, PREFETCH_INFLIGHT_DEFAULT)

            zipf_w = 1.0 / np.arange(1, nblocks + 1)
            zipf_w = zipf_w / zipf_w.sum()
            flash_rng = np.random.default_rng(18)
            seqs = [flash_rng.choice(nblocks, size=nblocks * 2,
                                     p=zipf_w) for _ in managers]
            distinct = len({int(i) for seq in seqs for i in seq})

            async def flash(lease_on: bool) -> tuple[float, float]:
                for m in managers:
                    m.cache.clear()
                    m.packed_cache.clear()
                    m.cache_tier.lease_wait_ms = (
                        LEASE_WAIT_MS_DEFAULT if lease_on else 0.0)
                    m.cache_tier.prefetch_inflight = 0
                d0 = decodes()
                lats: list = []

                async def hammer(m, seq):
                    for i in seq:
                        t0 = time.perf_counter()
                        await m.rpc_get_block(hashes[int(i)])
                        lats.append(time.perf_counter() - t0)

                await asyncio.gather(*[hammer(m, seq)
                                       for m, seq in zip(managers,
                                                         seqs)])
                amp = (decodes() - d0) / max(distinct, 1)
                lats.sort()
                p99 = lats[min(len(lats) - 1,
                               int(0.99 * len(lats)))] * 1e3
                return round(amp, 2), round(p99, 3)

            amp_off, p99_off = await flash(lease_on=False)
            amp_on, p99_on = await flash(lease_on=True)
            for m in managers:  # restore the knobs for the next lanes
                m.cache_tier.lease_wait_ms = LEASE_WAIT_MS_DEFAULT
                m.cache_tier.prefetch_inflight = \
                    PREFETCH_INFLIGHT_DEFAULT

            # ---- scrub repair rides the packed tier -------------------
            # forge one shard on a handful of stripes whose scrub
            # leader holds the packed bytes warm: repair localizes from
            # the cache instead of gathering the stripe
            from garage_tpu.block import ScrubWorker
            from garage_tpu.block.codec import shard_nodes_of
            from garage_tpu.block.manager import (pack_shard,
                                                  unpack_shard)

            layout = systems[0].layout_helper.current()
            by_node = {s.id: m for s, m in zip(systems, managers)}
            width = managers[0].codec.width
            sc_hits = sc_lookups = repaired = 0
            for h in hashes[:6]:
                placement = shard_nodes_of(layout, h, width)
                leader = by_node[placement[0]]
                if leader.packed_cache.get(h) is None:
                    # decode once ON the leader (tier lane parked so
                    # the probe can't shortcut it): warms its packed
                    # segment the way a foreground herd would
                    leader.cache.discard(h)
                    tier_was = leader.cache_tier.enabled
                    leader.cache_tier.enabled = False
                    await leader.rpc_get_block(h)
                    leader.cache_tier.enabled = tier_was
                victim = by_node[placement[1]]
                raw = victim.read_local_shard(h, 1)
                payload, packed_len = unpack_shard(raw)
                forged = (bytes(b ^ 0xFF for b in payload[:64])
                          + payload[64:])
                victim.write_local_shard(h, 1,
                                         pack_shard(forged, packed_len))
                sw = ScrubWorker(leader)
                repaired += await sw.scrub_batch([h])
                sc_hits += sw.scrub_cache_hits
                sc_lookups += sw.scrub_cache_lookups

            total = nodes * rounds * nblocks * block_len
            out = {
                "cache_tier_hot_get_gbps": round(total / tier_dt / 1e9,
                                                 3),
                "cache_tier_hot_get_base_gbps": round(
                    total / base_dt / 1e9, 3),
                "cache_tier_decodes": tier_decodes,
                "cache_tier_decodes_base": base_decodes,
                "cache_tier_decodes_per_block": round(
                    tier_decodes / nblocks, 2),
                "cache_tier_remote_hit_ms": round(
                    1e3 * sum(lat_hit) / max(len(lat_hit), 1), 3),
                "cache_tier_cold_decode_ms": round(
                    1e3 * sum(lat_cold) / max(len(lat_cold), 1), 3),
                "cache_tier_hint_convergence_s": (
                    round(conv, 3) if conv is not None else None),
                "cache_tier_probe_hits": sum(
                    m.cache_tier.probe_hits for m in managers),
                # ISSUE 18: cold-herd economics + packed-tier scrub
                "cache_tier_flash_decode_amp": amp_on,
                "cache_tier_flash_decode_amp_nolease": amp_off,
                "cache_tier_flash_p99_ms": p99_on,
                "cache_tier_flash_p99_ms_nolease": p99_off,
                "cache_tier_scrub_repaired": repaired,
                "cache_tier_scrub_cache_hit_rate": round(
                    sc_hits / max(sc_lookups, 1), 3),
            }
            return out
        finally:
            await _teardown(systems, managers, tasks)

    def shm_vs_socket() -> dict:
        """Micro lane: one FORWARD's payload transfer. The shm shape is
        the production one — the owner publishes a hot block once per
        lease and every subsequent forward is a reference + mmap view
        (zero payload copies); the socket shape pays the full payload
        copy through the kernel per forward. shm_publish_*_us prices
        the cold first-publish separately."""
        from garage_tpu.gateway.shm import ShmReader, ShmRing, ring_path

        out = {}
        ring = ShmRing(ring_path(tmp, 99), 64 << 20, lease_s=30.0)
        reader = ShmReader()
        for kib in (64, 256, 1024, 4096):
            payload = os.urandom(kib << 10)
            n_iter = max(8, (16 << 20) // (kib << 10))
            # cold publish: a fresh hash each time = one real write
            t0 = time.perf_counter()
            for i in range(8):
                h = (kib * 1000 + i).to_bytes(32, "big")
                ref = ring.publish(h, payload)
                assert ref is not None
            publish_dt = (time.perf_counter() - t0) / 8
            # hot forward: same block served over and over — publish
            # degrades to a slot-reuse lookup, get maps the view
            h = (kib * 1000).to_bytes(32, "big")
            t0 = time.perf_counter()
            for _ in range(n_iter):
                ref = ring.publish(h, payload)
                mv = reader.get(ref, h)
                assert mv is not None and mv.nbytes == len(payload)
            shm_dt = (time.perf_counter() - t0) / n_iter
            out[f"shm_publish_{kib}k_us"] = round(publish_dt * 1e6, 1)
            # socket: the payload crosses a loopback socketpair
            a, b = socketmod.socketpair()
            try:
                a.setsockopt(socketmod.SOL_SOCKET,
                             socketmod.SO_SNDBUF, 4 << 20)
                b.setsockopt(socketmod.SOL_SOCKET,
                             socketmod.SO_RCVBUF, 4 << 20)
                buf = bytearray(len(payload))

                def pump_one():
                    view = memoryview(buf)
                    got = 0
                    while got < len(payload):
                        got += b.recv_into(view[got:], len(payload) - got)

                import concurrent.futures as cf

                with cf.ThreadPoolExecutor(1) as pool:
                    t0 = time.perf_counter()
                    for _ in range(n_iter):
                        fut = pool.submit(pump_one)
                        a.sendall(payload)
                        fut.result()
                    sock_dt = (time.perf_counter() - t0) / n_iter
            finally:
                a.close()
                b.close()
            out[f"shm_forward_{kib}k_us"] = round(shm_dt * 1e6, 1)
            out[f"shm_socket_{kib}k_us"] = round(sock_dt * 1e6, 1)
            out[f"shm_vs_socket_{kib}k"] = round(
                sock_dt / max(shm_dt, 1e-9), 2)
        ring.close()
        return out

    try:
        res = asyncio.run(asyncio.wait_for(scenario(), 300))
        res.update(shm_vs_socket())
        return res
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_resize(n_nodes: int = 16, nobj: int = 48, obj_kib: int = 256,
                 leg_s: float = 5.0) -> dict:
    """Zero-downtime cluster resize economics (ISSUE 6): foreground
    PUT/GET p50/p99 while a layout transition (add-node, then
    drain-node) rebalances data across a 16-node cluster-in-a-box,
    vs the same workload with no resize — with the qos governor and
    breaker-aware resync placement active, rebalance must yield to
    foreground tails. Also reports the rebalance throughput itself
    (resync bytes moved / transition wall time)."""
    import pathlib
    import shutil
    import sys
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    for p in (here, os.path.join(here, "tests")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from clusterbox import ClusterBox, Workload
    from test_model import put_object_like_api

    from garage_tpu.utils.data import gen_uuid

    tmp = tempfile.mkdtemp(
        prefix="gt_resize_",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)

    async def scenario() -> dict:
        # gossip cadence scaled for a 16-node single-core sim: the
        # test default (status every 0.1 s) is thousands of status
        # RPCs/s at this fan-out and would drown the workload in
        # control-plane noise
        box = await ClusterBox(pathlib.Path(tmp), n=n_nodes, rf=3,
                               governor=True, status_interval=0.5,
                               ping_interval=2.0).start()
        try:
            # seed data so the rebalance has bytes to move
            g0 = box.nodes[0].garage
            bucket = gen_uuid()
            rng = np.random.default_rng(31)
            sem = asyncio.Semaphore(8)

            async def seed(i):
                data = rng.integers(0, 256, obj_kib << 10,
                                    dtype=np.uint8).tobytes()
                async with sem:
                    await put_object_like_api(g0, bucket, f"s{i}", data)

            await asyncio.gather(*(seed(i) for i in range(nobj)))
            await asyncio.sleep(4.0)  # let seeding's table queues drain

            # baseline leg: steady-state foreground, no resize
            wb = Workload(box, obj_kib=obj_kib, period=0.02)
            wb.start()
            await asyncio.sleep(leg_s)
            base = await wb.stop()

            # resize leg: the same workload while an add-node and then
            # a drain-node transition rebalance the cluster
            moved0 = sum(nd.manager.metrics["resync_bytes"]
                         for nd in box.live())
            wr = Workload(box, obj_kib=obj_kib, period=0.02)
            wr.start()
            t0 = time.monotonic()
            newbie = await box.add_node()
            orch = box.orchestrator()
            orch.stage_add(newbie.id, "z1", 1 << 30)
            rep_add = await orch.run(timeout=240.0)
            orch.stage_remove(box.nodes[1].id)
            rep_drain = await orch.run(timeout=240.0)
            try:
                await box.wait(lambda: box.resync_backlog() == 0, 90,
                               "rebalance backlog")
            except AssertionError:
                pass  # report what moved either way
            dt = time.monotonic() - t0
            res = await wr.stop()
            moved = sum(nd.manager.metrics["resync_bytes"]
                        for nd in box.live()) - moved0
            out = {
                "resize_nodes": n_nodes,
                "resize_add_transition_s": round(
                    rep_add.total_seconds, 2),
                "resize_drain_transition_s": round(
                    rep_drain.total_seconds, 2),
                "resize_rebalance_mb": round(moved / 1e6, 1),
                "resize_rebalance_mbps": round(
                    moved / max(dt, 1e-9) / 1e6, 2),
                "resize_ops_failed": len(res["failures"]),
                "resize_backlog_left": box.resync_backlog(),
                "resize_get_p50_ms": res["get_p50_ms"],
                "resize_get_p99_ms": res["get_p99_ms"],
                "resize_put_p50_ms": res["put_p50_ms"],
                "resize_put_p99_ms": res["put_p99_ms"],
                "resize_base_get_p99_ms": base["get_p99_ms"],
                "resize_base_put_p99_ms": base["put_p99_ms"],
            }
            if base["get_p99_ms"] and res["get_p99_ms"]:
                out["resize_get_p99_vs_baseline"] = round(
                    res["get_p99_ms"] / base["get_p99_ms"], 2)
            if base["put_p99_ms"] and res["put_p99_ms"]:
                out["resize_put_p99_vs_baseline"] = round(
                    res["put_p99_ms"] / base["put_p99_ms"], 2)
            return out
        finally:
            await box.stop()

    try:
        return asyncio.run(asyncio.wait_for(scenario(), 600))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_zone(nblocks: int = 12, block_kib: int = 256,
               rounds: int = 3, wan_ms: float = 20.0) -> dict:
    """Zone-aware read economics (ISSUE 16). A 3-zone / 6-node
    cluster-in-a-box with a chaos-injected WAN delay on every
    cross-zone link out of the reading node, reading blocks the reader
    does NOT hold locally (the remote-read shape):

      zone_local_get_p50_ms /      local-zone-first ordering serves the
      zone_local_get_p99_ms        same-zone replica: one LAN hop, the
                                   WAN delay never paid
      zone_cross_get_p50_ms /      the same reads with the same-zone
      zone_cross_get_p99_ms        replica's link severed — forced
                                   cross-zone, each GET pays the WAN
      zone_local_cross_mb /        block_cross_zone_read_bytes delta per
      zone_cross_cross_mb          leg: ~0 for the local leg is the
                                   routing claim as a byte counter
    """
    import pathlib
    import shutil
    import sys
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))
    for p in (here, os.path.join(here, "tests")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from clusterbox import ClusterBox

    from garage_tpu.chaos import FaultSpec, arm, disarm
    from garage_tpu.utils.data import blake3sum
    from garage_tpu.utils.metrics import registry

    block_len = block_kib << 10
    tmp = tempfile.mkdtemp(
        prefix="gt_zone_",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)

    async def scenario() -> dict:
        box = await ClusterBox(
            pathlib.Path(tmp), n=6, rf=3,
            zones=["z1", "z1", "z2", "z2", "z3", "z3"],
            zone_redundancy=2).start()
        try:
            m0 = box.nodes[0].manager
            layout = box.nodes[0].system.layout_helper.current()
            rng = np.random.default_rng(16)
            # blocks the reader does NOT hold: every read is remote,
            # and the spread-maximizing layout guarantees the one z1
            # replica is node 1 — the same-zone lane we then sever
            hashes = []
            while len(hashes) < nblocks:
                b = rng.integers(0, 256, block_len,
                                 dtype=np.uint8).tobytes()
                h = blake3sum(b)
                if box.nodes[0].id in layout.nodes_of_hash(h):
                    continue
                await m0.rpc_put_block(h, b, compress=False,
                                       cacheable=False)
                hashes.append(h)

            n0 = box.nodes[0].id.hex()[:8]
            n1 = box.nodes[1].id.hex()[:8]

            def wan_faults(c):
                # WAN model: every frame node0 sends across a zone
                # boundary pays wan_ms (pings included — they survive)
                for nd, zone in zip(box.nodes, box.zones):
                    if zone != "z1":
                        c.add(FaultSpec(kind="net_delay", node=n0,
                                        peer=nd.id.hex()[:8],
                                        delay_s=wan_ms / 1e3))

            async def sweep() -> list:
                lat = []
                for _ in range(rounds):
                    for h in hashes:
                        t0 = time.perf_counter()
                        got = await m0.rpc_get_block(h, cacheable=False)
                        lat.append(time.perf_counter() - t0)
                        assert len(got) == block_len
                return lat

            def pctl(xs, q):
                s = sorted(xs)
                return round(
                    s[min(len(s) - 1, int(q * len(s)))] * 1e3, 2)

            def cross_mb() -> float:
                return registry().totals(
                    "block_cross_zone_read_bytes")[1] / 1e6

            # ---- local leg: same-zone replica reachable ---------------
            c = arm(seed=16)
            wan_faults(c)
            x0 = cross_mb()
            local = await sweep()
            local_cross = cross_mb() - x0

            # ---- cross leg: sever node0 <-> node1, pay the WAN --------
            c.add(FaultSpec(kind="net_disconnect", node=n0, peer=n1))
            c.add(FaultSpec(kind="net_disconnect", node=n1, peer=n0))
            x0 = cross_mb()
            cross = await sweep()
            cross_bytes = cross_mb() - x0
            disarm()

            return {
                "zone_local_get_p50_ms": pctl(local, 0.5),
                "zone_local_get_p99_ms": pctl(local, 0.99),
                "zone_cross_get_p50_ms": pctl(cross, 0.5),
                "zone_cross_get_p99_ms": pctl(cross, 0.99),
                "zone_local_cross_mb": round(local_cross, 2),
                "zone_cross_cross_mb": round(cross_bytes, 2),
            }
        finally:
            disarm()
            await box.stop()

    try:
        return asyncio.run(asyncio.wait_for(scenario(), 300))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def bench_metadata(keys: int = 150_000, engines=("sqlite", "lsm"),
                   delim_prefixes: int = 256, list_reps: int = 24,
                   sync_missing: int = 1_000) -> dict:
    """Metadata at millions of objects (ISSUE 7): the many-small-keys
    workload every earlier bench skipped. Per engine (sqlite vs lsm),
    on one `keys`-row table shaped like a real bucket
    (`d00042/o00001234` — `delim_prefixes` distinct top-level
    prefixes):

      insert/s      bulk load through the REAL table write path
                    (TableData.update_many: CRDT merge + store write +
                    merkle todo per row)
      merkle        convergence rate draining the todo backlog through
                    MerkleUpdater.update_batch (one walk per subtree)
      list p50/p99  _collect_objects — the actual S3 lister — paged
                    from random continuation points (plain) and folding
                    the bucket into common prefixes (delimiter);
                    delimiter fetches-per-page is reported so the
                    O(distinct prefixes) skip-scan claim is a number
      sync round    a REAL TableSyncer anti-entropy round between two
                    loopback nodes: divergent (peer missing
                    `sync_missing` rows -> trie descent + push) and
                    converged (root-checksum confirmation) legs

    Keys default small enough for the main bench line; the nightly
    smoke runs --keys 1000000 and the slow tier 10M."""
    import pathlib  # noqa: F401  (parity with sibling benches)
    import random
    import shutil
    import tempfile

    from garage_tpu.api.s3 import list as s3list
    from garage_tpu.db import open_db
    from garage_tpu.table.data import TableData
    from garage_tpu.table.merkle import MerkleUpdater
    from garage_tpu.table.schema import Entry, TableSchema, tree_key

    class MetaEntry(Entry):
        VERSION_MARKER = b"BMta1"

        def __init__(self, pk, sk, value):
            self.pk, self.sk, self.value = pk, sk, value

        def partition_key(self):
            return self.pk

        def sort_key(self):
            return self.sk

        def merge(self, other):
            return other if other.value >= self.value else self

        def pack(self):
            return [self.pk, self.sk, self.value]

        @classmethod
        def unpack(cls, raw):
            return cls(raw[0], raw[1], raw[2])

        # duck-typed for the S3 list collector (_collect_objects reads
        # .key and .last_data() only)
        @property
        def key(self):
            return self.sk.decode()

        def last_data(self):
            return self

    class MetaSchema(TableSchema):
        TABLE_NAME = "benchmeta"
        ENTRY = MetaEntry

    class _Repl:  # standalone build: same partition math as the ring
        def partition_of(self, h):
            return h[0]

        def storage_nodes(self, h):
            return [b"me"]

    bucket = b"bench-bucket"
    per_prefix = max(1, keys // delim_prefixes)
    val = b"m" * 96  # ~ an object row's metadata payload

    def key_of(i: int) -> bytes:
        return b"d%05d/o%08d" % (i // per_prefix, i)

    def pctl(samples, q):
        return round(float(np.percentile(np.array(samples), q)) * 1000, 3)

    def build_and_measure(engine: str, tmp: str) -> dict:
        r: dict = {}
        db = open_db(os.path.join(tmp, "a"), engine=engine)
        schema = MetaSchema()
        data = TableData(db, schema, _Repl(), b"me")

        # 1. bulk insert through the real local write path
        insert_dt = 0.0
        for lo in range(0, keys, 10_000):
            raws = [schema.encode_entry(MetaEntry(bucket, key_of(i), val))
                    for i in range(lo, min(lo + 10_000, keys))]
            t0 = time.perf_counter()
            data.update_many(raws)
            insert_dt += time.perf_counter() - t0
        r["insert_per_s"] = round(keys / insert_dt, 1)

        # 2. merkle convergence: drain the whole todo backlog batched
        # (1024-row transactions: bulk-load drain, amortizing the upper
        # trie levels harder than the worker's foreground-friendly 256)
        m = MerkleUpdater(data)
        t0 = time.perf_counter()
        while True:
            todo = list(data.merkle_todo.iter(limit=4096))
            if not todo:
                break
            for i in range(0, len(todo), 1024):
                m.update_batch(todo[i:i + 1024])
        r["merkle_items_per_s"] = round(
            keys / (time.perf_counter() - t0), 1)

        if engine == "lsm":
            # read-optimized steady state for the list legs (the
            # maintenance worker reaches it on an idle node)
            db._engine.compact_full()
            es = db.engine_stats()
            r["segments"] = es["segments"]
            r["flushes"] = es["flushes"]
            r["compactions"] = es["compactions"]

        # 3. list latencies through the real S3 collector
        class _Ctx:
            bucket_id = bucket
            fetches = 0

            def __init__(self):
                self.garage = self
                self.object_table = self

            async def get_range(self, pk, start_sk=None, flt=None,
                                limit=1000, prefix_sk=None, **kw):
                self.fetches += 1
                raws = data.read_range(pk, start_sk, None, limit,
                                       prefix_sk=prefix_sk)
                return [schema.decode_entry(x) for x in raws]

        rng = random.Random(7)

        async def list_legs():
            ctx = _Ctx()
            plain, delim = [], []
            # warm-up: one page of each shape untimed, so the p99
            # measures the steady state, not first-touch cache fills
            await s3list._collect_objects(ctx, "", None, "", 1000)
            await s3list._collect_objects(ctx, "", None, "/", 1000)
            for _ in range(list_reps):
                resume = ("k", key_of(rng.randrange(keys)).decode())
                t0 = time.perf_counter()
                await s3list._collect_objects(ctx, "", resume, "", 1000)
                plain.append(time.perf_counter() - t0)
            ctx.fetches = 0
            t0 = time.perf_counter()
            _, cps, _, _ = await s3list._collect_objects(
                ctx, "", None, "/", 1000)
            first_dt = time.perf_counter() - t0
            fetches = ctx.fetches
            delim.append(first_dt)
            for _ in range(list_reps - 1):
                t0 = time.perf_counter()
                await s3list._collect_objects(ctx, "", None, "/", 1000)
                delim.append(time.perf_counter() - t0)
            return plain, delim, len(cps), fetches

        plain, delim, n_prefixes, delim_fetches = asyncio.run(list_legs())
        r["list_p50_ms"] = pctl(plain, 50)
        r["list_p99_ms"] = pctl(plain, 99)
        r["delim_list_p50_ms"] = pctl(delim, 50)
        r["delim_list_p99_ms"] = pctl(delim, 99)
        r["delim_prefixes"] = n_prefixes
        # the skip-scan claim as a number: range reads per delimiter
        # page ~ distinct prefixes, independent of keys under them
        r["delim_fetches_per_page"] = delim_fetches

        # 4. real anti-entropy round between two loopback nodes; peer B
        # starts as a snapshot of A missing `sync_missing` rows
        db.snapshot(os.path.join(tmp, "b"))
        db_b = open_db(os.path.join(tmp, "b"), engine=engine)
        data_b = TableData(db_b, MetaSchema(), _Repl(), b"me")
        missing = rng.sample(range(keys), min(sync_missing, keys))

        def drop(tx):
            for i in missing:
                k = tree_key(bucket, key_of(i))
                tx.remove(data_b.store, k)
                tx.insert(data_b.merkle_todo, k, b"")

        db_b.transaction(drop)
        mb = MerkleUpdater(data_b)
        while True:
            todo = list(data_b.merkle_todo.iter(limit=4096))
            if not todo:
                break
            for i in range(0, len(todo), MerkleUpdater.TX_STEP):
                mb.update_batch(todo[i:i + MerkleUpdater.TX_STEP])

        from garage_tpu.net import LocalNetwork, NetApp
        from garage_tpu.rpc import ReplicationMode, RpcHelper, System
        from garage_tpu.rpc.layout import NodeRole
        from garage_tpu.table import Table, TableShardedReplication
        from garage_tpu.table.sync import TableSyncer

        async def sync_round():
            net = LocalNetwork()
            systems = []
            for i in range(2):
                app = NetApp(b"bench-meta")
                net.register(app)
                s = System(app, ReplicationMode.parse(2),
                           os.path.join(tmp, f"node{i}"),
                           status_interval=0.2, ping_interval=0.2)
                systems.append(s)
            tasks = [asyncio.create_task(s.run()) for s in systems]
            try:
                await systems[1].netapp.try_connect(
                    systems[0].netapp.public_addr, systems[0].id)
                systems[1].peering.add_peer(
                    systems[0].netapp.public_addr, systems[0].id)
                deadline = time.monotonic() + 15
                while time.monotonic() < deadline:
                    if all(len(s.netapp.conns) == 1 for s in systems):
                        break
                    await asyncio.sleep(0.05)
                lm = systems[0].layout_manager
                for s in systems:
                    lm.history.stage_role(
                        s.id, NodeRole(zone="z1", capacity=1 << 30))
                lm.apply_staged(None)
                while time.monotonic() < deadline:
                    if all(s.layout_manager.history.current().version == 1
                           for s in systems):
                        break
                    await asyncio.sleep(0.05)
                tabs = []
                for s, d in zip(systems, (db, db_b)):
                    repl = TableShardedReplication(
                        s, s.replication.read_quorum,
                        s.replication.write_quorum)
                    tabs.append(Table(MetaSchema(), repl,
                                      RpcHelper(s), d))
                syncers = [TableSyncer(t, interval=1e9) for t in tabs]
                t0 = time.perf_counter()
                ok = await syncers[0].sync_all_partitions()
                div_s = time.perf_counter() - t0
                healed = len(tabs[1].data.store) == keys
                t0 = time.perf_counter()
                await syncers[0].sync_all_partitions()
                conv_s = time.perf_counter() - t0
                return div_s, conv_s, ok and healed
            finally:
                for s in systems:
                    await s.stop()
                for t in tasks:
                    t.cancel()

        div_s, conv_s, sync_ok = asyncio.run(
            asyncio.wait_for(sync_round(), 300))
        r["sync_round_divergent_s"] = round(div_s, 3)
        r["sync_round_converged_s"] = round(conv_s, 3)
        r["sync_healed"] = sync_ok
        db.close()
        db_b.close()
        return r

    out: dict = {"meta_keys": keys}
    base = "/dev/shm" if os.path.isdir("/dev/shm") else None
    for engine in engines:
        tmp = tempfile.mkdtemp(prefix=f"gt_meta_{engine}_", dir=base)
        try:
            for k, v in build_and_measure(engine, tmp).items():
                out[f"meta_{engine}_{k}"] = v
        except Exception as e:  # one engine must never kill the line
            out[f"meta_{engine}_error"] = f"{type(e).__name__}: {e}"[:300]
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    if out.get("meta_lsm_insert_per_s") and out.get(
            "meta_sqlite_insert_per_s"):
        out["meta_insert_lsm_vs_sqlite"] = round(
            out["meta_lsm_insert_per_s"]
            / out["meta_sqlite_insert_per_s"], 2)
    if out.get("meta_lsm_delim_list_p99_ms") and out.get(
            "meta_sqlite_delim_list_p99_ms"):
        out["meta_delim_p99_lsm_vs_sqlite"] = round(
            out["meta_sqlite_delim_list_p99_ms"]
            / out["meta_lsm_delim_list_p99_ms"], 2)
    return out


def bench_gateway(nobj: int = 16, obj_mib: int = 2,
                  workers_list=None) -> dict:
    """Multi-process gateway scaling (ISSUE 8): s3_put/s3_get GB/s
    through a forked store + N SO_REUSEPORT workers, swept over
    `workers ∈ {1, 2, 4, cpu_count}`. `gateway_scaling_put` =
    gbps(best N) / gbps(1) — the "frontend scales with cores" number —
    plus the lease-rebalance convergence time measured against the
    real BudgetLeaseBroker under a deterministic 10:1 demand skew.

    workers=1 runs the single-process in-process frontend (the exact
    pre-gateway path), so the baseline is honest."""
    import concurrent.futures
    import json as _json
    import shutil
    import sys
    import tempfile
    import urllib.request

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tests"))
    from s3util import S3Client
    from test_s3_api import Server

    cpus = os.cpu_count() or 1
    if workers_list is None:
        workers_list = sorted({w for w in (1, 2, 4, cpus)
                               if w <= max(cpus, 2)})
    out: dict = {"gateway_cpus": cpus,
                 "gateway_workers_swept": list(workers_list)}
    size = obj_mib << 20
    data = np.random.default_rng(11).integers(
        0, 256, size, dtype=np.uint8).tobytes()
    base_dir = "/dev/shm" if os.path.isdir("/dev/shm") else None
    per: dict[int, tuple[float, float]] = {}
    for n in workers_list:
        tmp = tempfile.mkdtemp(prefix=f"gt_gw{n}_", dir=base_dir)
        srv = Server(tmp)
        with open(srv.config_path) as f:
            cfg = f.read()
        cfg = cfg.replace("block_size = 65536",
                          "block_size = 1048576")
        cfg += f"\n[gateway]\nworkers = {n}\nlease_interval_s = 0.5\n"
        with open(srv.config_path, "w") as f:
            f.write(cfg)
        try:
            with _env(GARAGE_TPU_DEVICE="off"):
                srv.start()
            srv.setup_layout_and_key()
            cli = S3Client("127.0.0.1", srv.s3_port, srv.key_id,
                           srv.secret)
            st, _, body = cli.request("PUT", "/gwbench")
            assert st == 200, body[:200]
            # cache OFF: this sweep measures the frontend + store
            # path, and the tuning POST fans out to every worker
            rq = urllib.request.Request(
                f"http://127.0.0.1:{srv.admin_port}/v1/s3/tuning",
                data=_json.dumps(
                    {"read_cache_max_bytes": 0}).encode(),
                method="POST",
                headers={"authorization": "Bearer test-admin-token"})
            urllib.request.urlopen(rq, timeout=10).read()

            def put(i):
                st, _, b = cli.request(
                    "PUT", f"/gwbench/o{i}", body=data,
                    unsigned_payload=True, timeout=60.0)
                assert st == 200, b[:200]

            def get(i):
                st, _, b = cli.request("GET", f"/gwbench/o{i}",
                                       timeout=60.0)
                assert st == 200 and len(b) == size

            put(0)  # warm
            best_put = best_get = 0.0
            threads = max(4, 2 * n)
            with concurrent.futures.ThreadPoolExecutor(threads) as pool:
                for _rep in range(2):
                    t0 = time.perf_counter()
                    list(pool.map(put, range(nobj)))
                    dt = time.perf_counter() - t0
                    best_put = max(best_put, nobj * size / dt / 1e9)
                    t0 = time.perf_counter()
                    list(pool.map(get, range(nobj)))
                    dt = time.perf_counter() - t0
                    best_get = max(best_get, nobj * size / dt / 1e9)
            per[n] = (best_put, best_get)
            out[f"s3_put_gbps_w{n}"] = round(best_put, 3)
            out[f"s3_get_gbps_w{n}"] = round(best_get, 3)
        except Exception as e:  # one worker count never kills the line
            out[f"gateway_w{n}_error"] = f"{type(e).__name__}: {e}"[:300]
        finally:
            srv.stop()
            shutil.rmtree(tmp, ignore_errors=True)
    if 1 in per and len(per) > 1:
        base_put, base_get = per[1]
        best_n = max(per, key=lambda k: per[k][0])
        out["gateway_best_workers"] = best_n
        out["gateway_scaling_put"] = round(
            per[best_n][0] / max(base_put, 1e-9), 2)
        out["gateway_scaling_get"] = round(
            max(g for _, g in per.values()) / max(base_get, 1e-9), 2)

    # lease-rebalance convergence: the broker under a deterministic
    # 10:1:1:1 demand skew (simulated renews at the production
    # interval) — rounds until the hot worker holds >= 90% of its
    # demand-proportional share
    from garage_tpu.gateway.lease import BudgetLeaseBroker

    t = [1000.0]
    broker = BudgetLeaseBroker(1000.0, min_share=0.05, ttl_s=3.0,
                               expected_workers=4,
                               clock=lambda: t[0])
    interval = 1.0
    names = [f"w{i}" for i in range(4)]
    for _ in range(5):  # settle at equal demand
        t[0] += interval
        for w in names:
            broker.renew(w, demand_rps=100.0)
    demands = {w: (1000.0 if w == "w0" else 100.0) for w in names}
    target = None
    rounds = 0
    for rounds in range(1, 31):
        t[0] += interval
        for w in names:
            broker.renew(w, demand_rps=demands[w])
        assert broker.conservation_ok
        hot = broker.granted("w0")[0] or 0.0
        # demand-proportional share (floor-adjusted) of the budget
        if target is None:
            floor = 0.05 * 250.0
            target = floor + (1000.0 - 4 * floor) * (1000.0 / 1300.0)
        if hot >= 0.9 * target:
            break
    out["lease_rebalance_convergence_s"] = round(rounds * interval, 2)
    return out


def bench_native_blake3() -> float:
    """The native host BLAKE3 kernel (b3gf.c, AVX2 8-way) — what the
    product actually hashes with on the host path."""
    from garage_tpu.native import blake3_many

    rng = np.random.default_rng(3)
    blobs = [rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
             for _ in range(8)]
    blake3_many(blobs)  # warm
    best = 0.0
    for _rep in range(3):
        t0 = time.perf_counter()
        for _ in range(4):
            blake3_many(blobs)
        dt = time.perf_counter() - t0
        best = max(best, 8 * (1 << 20) * 4 / dt / 1e9)
    return best


def bench_native_parity() -> float:
    """The HOST route of the deep-scrub detect pass
    (block/host_legs.parity_check: native GF matmul + compare) in
    logical 1 MiB blocks/s — what the product's deep scrub sustains on
    a node whose route is the host."""
    from garage_tpu.block import host_legs
    from garage_tpu.block.codec import ErasureCodec

    from garage_tpu import native

    if not native.available():
        # the numpy fallback must not masquerade under a native label
        # (same honesty rule as the blake3/jax-on-host relabeling)
        raise RuntimeError("native kernels unavailable")
    codec = ErasureCodec(10, 4, use_jax=False)
    rng = np.random.default_rng(4)
    stripes = [codec.encode(
        rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes())
        for _ in range(8)]
    host_legs.parity_check(codec, stripes)  # warm
    best = 0.0
    for _rep in range(3):
        t0 = time.perf_counter()
        for _ in range(3):
            verdicts = host_legs.parity_check(codec, stripes)
            if not all(verdicts):
                raise RuntimeError(f"healthy stripes flagged: {verdicts}")
        dt = time.perf_counter() - t0
        best = max(best, 8 * 3 / dt)
    return best


def main() -> int:
    from garage_tpu.utils.runtime import tune

    tune()
    extra: dict = {}
    failed: list[str] = []  # segments that raised: exit code non-zero

    def seg(err_key: str, fn):
        """Run one segment. A failure is recorded under its `*_error`
        key and fails the run at the end; it never kills the line."""
        try:
            return fn()
        except Exception as e:
            extra[err_key] = f"{type(e).__name__}: {e}"[:300]
            failed.append(err_key)
            return None

    # LIVE-path device proof FIRST, while this process has not touched
    # JAX: a forked server with the feeder required owns the chip, live
    # S3 PUTs batch through the accelerator, and its feeder counters
    # are scraped from its /metrics. The server is stopped before the
    # parent imports JAX below — one process for each chip. Small
    # objects (1 MiB): the segment exists to prove
    # feeder_device_items > 0 on the live path.
    assert "jax" not in sys.modules
    extra.update(seg("s3_device_error",
                     lambda: bench_s3_put(2, obj_mib=1, device=True)) or {})

    from garage_tpu.ops import jaxenv

    verdict = jaxenv.verdict()  # compile cache placed before any jit
    platform = verdict["platform"]
    if platform != "tpu":
        print(f"bench.py measures the TPU data path and JAX found "
              f"platform {platform!r}; it does not fall back to the CPU",
              file=sys.stderr)
        return 2
    import jax

    extra.update(platform=platform, device_kind=verdict["device_kind"],
                 device_count=verdict["count"])

    gbps = bench_rs_encode(jax)
    b3_e2e, b3_dev = bench_blake3(jax)
    extra["blake3_gbps"] = round(b3_e2e, 3)
    extra["blake3_device_gbps"] = round(b3_dev, 3)
    native_b3 = seg("blake3_native_error",
                    lambda: round(bench_native_blake3(), 3))
    if native_b3 is not None:
        extra["blake3_native_host_gbps"] = native_b3
    sk = seg("scrub_kernel_error",
             lambda: round(bench_scrub_kernel(jax), 1))
    if sk is not None:
        extra["scrub_kernel_blocks_per_s"] = sk
    sp = seg("scrub_parity_error",
             lambda: round(bench_native_parity(), 1))
    if sp is not None:
        extra["scrub_parity_native_host_blocks_per_s"] = sp

    nblocks = 128
    base = "/dev/shm" if os.path.isdir("/dev/shm") else None

    def run_segment(tag, device_mode, erasure, nb):
        tmp = tempfile.mkdtemp(prefix=f"gt_bench_{tag}_", dir=base)
        try:
            return asyncio.run(asyncio.wait_for(
                _put_cluster_bench(tmp, nb, device_mode, erasure), 600))
        except Exception as e:  # one segment must never kill the line
            return {"error": f"{type(e).__name__}: {e}"[:300]}
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    # main segment: erasure(4,2), feeder in mode auto. Run TWICE,
    # interleaved with the cpu-baseline segment below, and keep each
    # segment's best.
    def best_of(a: dict, b: dict) -> dict:
        if "error" in a:
            return b
        if "error" in b:
            return a
        out = dict(a)
        for k, v in b.items():
            if isinstance(v, (int, float)) and isinstance(a.get(k), (int, float)):
                out[k] = max(a[k], v)
        return out

    seg_main = run_segment("main", "auto", True, nblocks)
    cpu_seg = run_segment("cpu", "off", False, nblocks)
    seg_main = best_of(seg_main, run_segment("main2", "auto", True, nblocks))
    extra.update({k: v for k, v in seg_main.items() if k != "error"})
    if "error" in seg_main:
        extra["put_error"] = seg_main["error"]
        failed.append("put_error")

    # device-required segment: every encode batch forced onto the
    # accelerator — proves the in-process device path end to end.
    # 16 blocks keep it short.
    seg_dev = run_segment("dev", "require", True, min(nblocks, 16))
    if "error" in seg_dev:
        extra["device_put_error"] = seg_dev["error"]
        failed.append("device_put_error")
    else:
        extra["device_put_gbps"] = seg_dev["put_gbps"]
        extra["feeder_device_items"] = max(
            extra.get("feeder_device_items", 0),
            seg_dev["feeder_device_items"])
        extra["device_feeder_mbps"] = seg_dev["feeder_mbps"]

    # north-star boundary: S3 PutObject/GetObject through a real forked
    # server (HTTP + SigV4 + chunker + MD5/BLAKE3 + store), device off
    extra.update(seg("s3_put_error", lambda: bench_s3_put(16)) or {})
    # how much of the internal block path's throughput the
    # HTTP/signature frontend actually delivers
    if extra.get("s3_put_gbps") and extra.get("put_gbps"):
        extra["frontend_efficiency"] = round(
            extra["s3_put_gbps"] / extra["put_gbps"], 3)

    # qos admission control: sustained PUTs + concurrent deep scrub
    # against a tight byte budget — admitted vs shed + governor action
    extra.update(seg("qos_error", bench_qos) or {})

    # degraded-mode tail latency: one peer hung (chaos rpc_hang),
    # hedged reads on vs off
    extra.update(seg("degraded_error", bench_degraded) or {})

    # read-side device lane (ISSUE 13): degraded-GET decode +
    # scrub-rebuild through the feeder's pattern-as-data route, vs the
    # serial host baseline — the decode twin of the encode segments
    extra.update(seg("decode_error",
                     lambda: bench_decode(device_mode="auto")) or {})
    # forced-device edition: every decode batch on the accelerator
    dev = seg("device_decode_error",
              lambda: bench_decode(nblocks=8, device_mode="require"))
    if dev is not None:
        extra["device_decode_gbps"] = dev["decode_gbps"]
        extra["decode_feeder_device_items"] = max(
            extra.get("decode_feeder_device_items", 0),
            dev["decode_feeder_device_items"])
        extra["device_decode_recompiles"] = dev["decode_recompiles"]

    # zero-downtime resize: rebalance throughput vs foreground p99
    # during an add-node + drain-node transition on a 16-node
    # cluster-in-a-box (ISSUE 6)
    extra.update(seg("resize_error", bench_resize) or {})

    # metadata at scale (ISSUE 7): insert/list/sync on a many-small-keys
    # table, sqlite vs lsm. Modest key count here; the nightly soak runs
    # `bench.py bench_metadata --keys 1000000` for the full-scale line.
    extra.update(seg("metadata_error", bench_metadata) or {})

    # multi-core gateway (ISSUE 8): s3_put/get swept over worker
    # counts; gateway_scaling_put is the per-core frontend claim
    extra.update(seg("gateway_error", bench_gateway) or {})

    # cluster cache tier (ISSUE 15): cluster hot-GET throughput and
    # decode dedup (tier on vs node-local baseline), remote-hit vs
    # cold-decode latency, hint-gossip convergence, shm-vs-socket
    # forward latency
    extra.update(seg("cache_tier_error", bench_cache_tier) or {})

    # zone-aware reads (ISSUE 16): local-zone-first vs forced
    # cross-zone GET latency under an injected WAN delay, with the
    # cross-zone byte counter as the routing proof
    extra.update(seg("zone_error", bench_zone) or {})

    # CPU baseline segment: replicate-3 whole blocks, host only
    # (BASELINE.md rows 1/5: the reference's strategy on the host
    # path). Second leg of the interleave; best of both.
    cpu_seg = best_of(cpu_seg, run_segment("cpu2", "off", False, nblocks))
    if "error" in cpu_seg:
        extra["cpu_put_error"] = cpu_seg["error"]
        failed.append("cpu_put_error")
    else:
        extra["cpu_put_gbps"] = cpu_seg["put_gbps"]
        extra["cpu_put_wire_mib_per_block"] = cpu_seg.get(
            "put_wire_mib_per_block")
        extra["cpu_scrub_blocks_per_s"] = cpu_seg["scrub_blocks_per_s"]
        if extra.get("put_gbps"):
            extra["put_vs_cpu_baseline"] = round(
                extra["put_gbps"] / max(cpu_seg["put_gbps"], 1e-9), 2)
        if extra.get("scrub_blocks_per_s"):
            extra["scrub_vs_cpu_baseline"] = round(
                extra["scrub_blocks_per_s"]
                / max(cpu_seg["scrub_blocks_per_s"], 1e-9), 2)
        if extra.get("scrub_kernel_blocks_per_s"):
            # device-resident detect kernel vs the measured host
            # replicate-3 hash-scrub baseline in the SAME run
            extra["scrub_kernel_vs_cpu_baseline"] = round(
                extra["scrub_kernel_blocks_per_s"]
                / max(cpu_seg["scrub_blocks_per_s"], 1e-9), 2)

    extra["xla_compile"] = jaxenv.compile_stats()
    print(json.dumps({
        "metric": "rs_10_4_encode",
        "value": round(gbps, 3),
        "unit": f"GB/s/chip[{platform}]",
        "vs_baseline": round(gbps / 4.0, 3),
        **extra,
    }), flush=True)
    if failed:
        print(f"bench.py: {len(failed)} segment(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


def _scenarios() -> dict:
    """Standalone scenarios (nightly soak / CI gates / operator runs):
    `python bench.py <name> [options]` prints one JSON line.
    name -> (add-arguments, run-from-parsed-args)."""

    def metadata_args(ap):
        ap.add_argument("--keys", type=int, default=1_000_000)
        ap.add_argument("--engines", default="sqlite,lsm")

    def cache_tier_args(ap):
        ap.add_argument("--nblocks", type=int, default=12)
        ap.add_argument("--block-kib", type=int, default=512)
        ap.add_argument("--rounds", type=int, default=4)
        ap.add_argument("--nodes", type=int, default=6)

    def put_path_args(ap):
        ap.add_argument("--nobj", type=int, default=8)
        ap.add_argument("--obj-mib", type=int, default=6)
        ap.add_argument("--stub-gbps", default="0.02,0.08,0.04")
        ap.add_argument("--no-ingest-pool", action="store_true",
                        help="A/B control: classic copy path under "
                             "identical modelled rates")

    def zone_args(ap):
        ap.add_argument("--nblocks", type=int, default=12)
        ap.add_argument("--block-kib", type=int, default=256)
        ap.add_argument("--rounds", type=int, default=3)
        ap.add_argument("--wan-ms", type=float, default=20.0)

    def gateway_args(ap):
        ap.add_argument("--workers", default="")
        ap.add_argument("--nobj", type=int, default=16)
        ap.add_argument("--obj-mib", type=int, default=2)

    return {
        "bench_metadata": (metadata_args, lambda a: bench_metadata(
            keys=a.keys, engines=tuple(a.engines.split(",")))),
        "bench_cache_tier": (cache_tier_args, lambda a: bench_cache_tier(
            nblocks=a.nblocks, block_kib=a.block_kib, rounds=a.rounds,
            nodes=a.nodes)),
        "bench_put_path": (put_path_args, lambda a: bench_put_path(
            nobj=a.nobj, obj_mib=a.obj_mib, stub_gbps=a.stub_gbps,
            ingest_pool=not a.no_ingest_pool)),
        "bench_zone": (zone_args, lambda a: bench_zone(
            nblocks=a.nblocks, block_kib=a.block_kib, rounds=a.rounds,
            wan_ms=a.wan_ms)),
        "bench_gateway": (gateway_args, lambda a: bench_gateway(
            nobj=a.nobj, obj_mib=a.obj_mib,
            workers_list=[int(w) for w in a.workers.split(",") if w]
            or None)),
    }


def _main_cli() -> int:
    scenarios = _scenarios()
    if len(sys.argv) > 1 and sys.argv[1] in scenarios:
        import argparse

        add_args, run = scenarios[sys.argv[1]]
        ap = argparse.ArgumentParser()
        ap.add_argument("cmd")
        add_args(ap)
        print(json.dumps({"metric": sys.argv[1], **run(ap.parse_args())}),
              flush=True)
        return 0
    return main()


if __name__ == "__main__":
    sys.exit(_main_cli())
