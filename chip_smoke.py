#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that garage-tpu still starts on the chip.

    python chip_smoke.py             # on a machine with a TPU; fails without
    python chip_smoke.py rehearse    # tiny sizes on the CPU, Pallas interpreted

Drives the system's main path once, through the entry points a user
calls, at the widths of BASELINE.json config 3 — erasure(4,2), 16 MiB
multipart parts, 1 MiB blocks — and checks what comes out against
plain references. It runs its phases one after another so that only
one process needs the chip at a time, and this parent never imports
JAX:

  native   garage_tpu/native/_build/ is emptied and the host library
           must build from src/b3gf.c (the package otherwise falls back
           to pure Python without a word).
  kernels  a child that holds the chip: rs.encode, rs.parity_check,
           rs.gf_apply_batched (decode and repair), treehash.hash_fn,
           sha256.hash_fn and pallas_gf.gf_apply (compiled, not
           interpreted) against ops/rs *_np, blake3_py and hashlib, for
           erasure(4,2) at S = 524,288 and RS(10,4) at S = 131,072, at
           the item buckets the feeder launches. The largest bucket
           that fits is found by walking down from the top of the
           ladder. On several chips, one meshed encode and one hash
           batch go through the product's staging code and say where
           their arrays lived.
  served   six `python -m garage_tpu.cli.server` processes with real
           directories on disk. Node 1 owns the chip
           (GARAGE_TPU_DEVICE=require); nodes 2-6 carry
           `[tpu] enable = false` and must never load JAX. Eight
           concurrent 64 MiB multipart uploads (4 x 16 MiB parts) plus
           an aws-chunked SIGNED put in flight with them, all sent to
           node 1; GET everything back and compare SHA-256; SIGKILL two
           other nodes (one zone, so metadata keeps its quorum) and GET
           everything again (the only read that uses the parity the
           chip wrote, and the decode launch);
           restart them; run `repair scrub start` on node 1 and wait
           for a pass with zero corruptions. Pass conditions come from
           node 1's /metrics: device items > 0 for encode_put,
           hash/hash_md5, sha256, decode and parity_check; zero host
           re-runs, zero device errors, zero failed requests; and at
           least one compiled program served from the persistent cache
           the kernel phase filled (ops/jaxenv.py).

Exit code 0 and two last lines of stdout only when every phase passed
on platform "tpu": `chip_smoke summary {...}` (per-phase results,
counters, compile seconds, the largest bucket, "reduced", "claim":
null), then the verdict, one JSON object with exactly these keys, the
device as the process that held the chip reported it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

A failed run prints neither line and says why on stderr. `rehearse` is
the only way the script runs without a TPU; it is never inferred from
finding no chip, its summary says "rehearsal": true and both lines say
platform "cpu".
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
TIME_LIMIT = 1150.0  # the contract allows 1200 s, compilation included
T0 = time.monotonic()

MIB = 1 << 20

# ---- sizes ---------------------------------------------------------------
# full: the deployment's own widths. Only the object count may ever be
# cut to fit the time limit (say so under "reduced"), never the block
# size, part size or geometry.
FULL = {
    "block_size": 1 << 20, "ram_buffer_max": None,  # default: 256 MiB
    "n_uploads": 8, "parts": 4, "part_bytes": 16 * MIB,
    "signed_objects": 1, "signed_bytes": 4 * MIB, "signed_chunk": 64 * 1024,
    # (k, m, shard-length bucket): erasure(4,2) and RS(10,4) shards of a
    # 1 MiB + 1 byte packed block, rounded up as bucket_len does
    "geometries": [(4, 2, 524288), (10, 4, 131072)],
    "buckets": [1, 8, 16],
    "top_ladder": [256, 128, 64, 32],
    "hash_chunks": [(1024, [1, 8, 16, 256]), (1025, [1])],
    "sha_blocks": (2048, [1, 8, 16, 256]),
    "interpret": False,
}
# rehearsal: same code, toy sizes, CPU. Proves control flow only. The
# shapes are those a 64 KiB block_size makes the server launch, and the
# RAM buffer shrinks with the data so that the data set still is eight
# times the read cache (a quarter of the buffer), as in the full run.
TINY = {
    "block_size": 65536, "ram_buffer_max": 1 << 20,
    "n_uploads": 3, "parts": 2, "part_bytes": 320 * 1024,
    "signed_objects": 1, "signed_bytes": 256 * 1024, "signed_chunk": 64 * 1024,
    "geometries": [(4, 2, 32768), (10, 4, 8192)],
    "buckets": [1, 8],
    "top_ladder": [16],
    "hash_chunks": [(64, [1, 8])],
    "sha_blocks": (2048, [1, 8]),
    "interpret": True,
}

_procs: list[subprocess.Popen] = []


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


class Failed(Exception):
    """A phase failed; the message says which check and why."""


# ---------------------------------------------------------------------------
# phase: kernels (child process — the only code here that touches JAX)
# ---------------------------------------------------------------------------


def _timed(fn):
    """-> (result, seconds) with the device work finished."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _kernel_case(name, shape, fn, check, cases, nbytes, again=None):
    """Run one kernel at one shape: first call (compile + run), second
    call (run; `again` if it takes other operands of the same shape),
    then `check(result)` against the reference."""
    from garage_tpu.ops import jaxenv

    rec = {"kernel": name, "shape": shape, "ok": False}
    c0 = jaxenv.compile_stats()
    try:
        out, t_first = _timed(fn)
        out, t_run = _timed(again or fn)
        c1 = jaxenv.compile_stats()
        err = check(out)
        rec.update(
            ok=err is None, first_s=round(t_first, 4), run_s=round(t_run, 5),
            compile_s=round(c1["compile_seconds"] - c0["compile_seconds"], 3),
            compiles=c1["compiles"] - c0["compiles"],
            cache_hits=c1["cache_hits"] - c0["cache_hits"],
            gbps=round(nbytes / t_run / 1e9, 3))
        if err is not None:
            rec["error"] = err
    except Exception as e:  # a shape that does not compile or fit is
        # the finding this phase exists to make: record it, go on
        rec["error"] = f"{type(e).__name__}: {e}"[:600]
    cases.append(rec)
    print(f"  {name:<14} {str(shape):<26} "
          + (f"ok first {rec['first_s']:.2f}s (compile {rec['compile_s']}s,"
             f" {rec['cache_hits']} from cache) run {rec['run_s'] * 1e3:.1f}ms"
             f" {rec['gbps']} GB/s" if rec["ok"]
             else f"FAILED {rec.get('error', '')[:300]}"), flush=True)
    return rec["ok"]


def _geometry_cases(k, m, s, b, cfg, rng, cases):
    """Every GF kernel the server launches, at (b, k|k+m, s)."""
    import jax
    import numpy as np

    from garage_tpu import native
    from garage_tpu.ops import pallas_gf, rs

    data = rng.integers(0, 256, (b, k, s), dtype=np.uint8)
    pmat = rs.parity_matrix(k, m)
    # reference parity: ops/rs.encode_np (table lookup) on a sample of
    # items, the native C kernel — itself pinned to encode_np by
    # tier-1 — on all of them
    sample = sorted({0, b // 2, b - 1})
    want_np = {i: rs.encode_np(k, m, data[i]) for i in sample}
    want = np.stack([native.gf_matmul(pmat, data[i]) for i in range(b)])
    for i in sample:
        if not np.array_equal(want[i], want_np[i]):
            raise Failed("native gf_matmul disagrees with rs.encode_np")
    dev = jax.device_put(data)
    geo = f"rs({k},{m})"
    ok = True

    def same(ref):
        return lambda out: (None if np.array_equal(np.asarray(out), ref)
                            else "result differs from the reference")

    ok &= _kernel_case("rs.encode", (geo, b, s),
                       lambda: rs.encode(k, m, dev), same(want), cases,
                       data.nbytes)
    ok &= _kernel_case("pallas.gf_apply", (geo, b, s),
                       lambda: pallas_gf.gf_apply(
                           pmat, dev, interpret=cfg["interpret"]),
                       same(want), cases, data.nbytes)

    stripes = np.concatenate([data, want], axis=1)
    bad_at = b // 2
    corrupt = stripes.copy()
    corrupt[bad_at, k + m - 1, s // 3] ^= 0x40  # one bit of one parity row
    sdev, cdev = jax.device_put(stripes), jax.device_put(corrupt)
    flags = np.ones(b, dtype=bool)
    flags[bad_at] = False
    ok &= _kernel_case("rs.parity_check", (geo, b, s),
                       lambda: rs.parity_check(k, m, sdev), same(flags),
                       cases, stripes.nbytes,
                       again=lambda: rs.parity_check(k, m, cdev))
    del sdev, cdev, corrupt

    # decode and repair: the erasure pattern rides as data, so one
    # launch mixes patterns — two data shards lost, one lost, parity only
    pats = [tuple(range(2, k + 2)), tuple(range(1, k + 1)),
            tuple(range(k - 1)) + (k + m - 1,)]
    present = [pats[i % len(pats)] for i in range(b)]
    surv = np.stack([stripes[i, list(present[i])] for i in range(b)])
    for i in sample:
        if not np.array_equal(
                rs.decode_np(k, m, present[i], surv[i]), data[i]):
            raise Failed("rs.decode_np does not invert rs.encode_np")
    vdev = jax.device_put(surv)
    dmats = jax.device_put(np.stack(
        [rs.decode_bitmat_t(k, m, p) for p in present]))
    ok &= _kernel_case("gf_apply decode", (geo, b, s),
                       lambda: rs.gf_apply_batched(dmats, vdev), same(data),
                       cases, surv.nbytes)
    missing = [next(j for j in range(k + m) if j not in p) for p in present]
    rmats = jax.device_put(np.stack(
        [rs.repair_bitmat_t(k, m, p, (mi,))
         for p, mi in zip(present, missing)]))
    lost = np.stack([stripes[i, [missing[i]]] for i in range(b)])
    ok &= _kernel_case("gf_apply repair", (geo, b, s),
                       lambda: rs.gf_apply_batched(rmats, vdev), same(lost),
                       cases, surv.nbytes)
    return bool(ok)


def _hash_cases(cfg, rng, cases):
    import jax
    import numpy as np

    from garage_tpu import native
    from garage_tpu.ops import sha256, treehash

    ok = True
    for c, buckets in cfg["hash_chunks"]:
        padded = c * treehash.CHUNK_LEN
        # a c-chunk message: one byte into the last chunk is enough
        length = (c - 1) * 1024 + 1 if c % 2 else padded
        for b in buckets:
            buf = np.zeros((b, padded), dtype=np.uint8)
            buf[:, :length] = rng.integers(0, 256, (b, length),
                                           dtype=np.uint8)
            lens = np.full(b, length, dtype=np.int32)
            blobs = [buf[i, :length].tobytes() for i in range(b)]
            # treehash.blake3_py (pure Python, ~3 s per MiB) on one
            # row, the native C kernel — pinned to blake3_py by tier-1
            # — on all of them
            want = native.blake3_many(blobs)
            if treehash.blake3_py(blobs[0]) != want[0]:
                raise Failed("native blake3 disagrees with blake3_py")
            dbuf, dlens = jax.device_put(buf), jax.device_put(lens)

            def check(out, want=want, b=b):
                arr = np.ascontiguousarray(
                    np.asarray(out).astype("<u4")).view(np.uint8)
                got = [arr.reshape(b, 32)[i].tobytes() for i in range(b)]
                return None if got == want else "digest differs from blake3"

            ok &= _kernel_case(
                "blake3.hash_fn", (c, b),
                lambda: treehash.hash_fn(c)(dbuf, dlens), check, cases,
                b * length)
    npad, buckets = cfg["sha_blocks"]
    mlen = npad * sha256.BLOCK // 2  # e.g. a 64 KiB aws-chunk in 2048 blocks
    for b in buckets:
        msgs = [rng.integers(0, 256, mlen - (i % 3), dtype=np.uint8).tobytes()
                for i in range(b)]
        buf = np.zeros((b, npad * sha256.BLOCK), dtype=np.uint8)
        nbs = np.array([sha256.pad_row_into(buf[i], msgs[i])
                        for i in range(b)], dtype=np.int32)
        want = [hashlib.sha256(x).hexdigest() for x in msgs]
        dbuf, dnbs = jax.device_put(buf), jax.device_put(nbs)
        ok &= _kernel_case(
            "sha256.hash_fn", (npad, b),
            lambda: sha256.hash_fn(npad)(dbuf, dnbs),
            lambda out, want=want: (None if sha256.digests_to_hex(out) == want
                                    else "digest differs from hashlib"),
            cases, b * mlen)
    return bool(ok)


def _placement(cfg, rng):
    """Several chips: stage one meshed encode and one hash batch through
    the product's backend and say where the arrays lived."""
    import numpy as np

    from garage_tpu.block.codec import ErasureCodec
    from garage_tpu.block.device_backend import JaxDeviceBackend
    from garage_tpu.ops import rs

    k, m, s = cfg["geometries"][0]
    be = JaxDeviceBackend(codec=ErasureCodec(k, m, use_jax=False))
    n = max(be.mesh_min_items, 8)
    blocks = [rng.integers(0, 256, k * s - 3, dtype=np.uint8).tobytes()
              for _ in range(n)]

    def where(arr):
        return {"sharding": str(arr.sharding),
                "devices": sorted(d.id for d in arr.devices())}

    staged = be.stage("encode", blocks)
    handle = be.compute("encode", staged)
    parts = be.readback("encode", handle)
    for blk, got in zip(blocks, parts):
        sh = rs.split_stripe(blk, k)
        want = rs.encode_np(k, m, sh)
        if [bytes(r) for r in sh] + [bytes(r) for r in want] != got:
            raise Failed("meshed encode differs from rs.encode_np")
    out = {"mesh": dict(be._get_mesh().shape),
           "mesh_batches": be.stats["mesh_batches"],
           "encode_in": where(staged[2][3]),
           "encode_out": where(handle[2][3])}
    hb = [b[:cfg["hash_chunks"][0][0] * 1024] for b in blocks]
    hstaged = be.stage("hash", hb)
    out["hash_in"] = where(hstaged[2][1][0][2])
    return out


def phase_kernels(cfg: dict, rehearse: bool, seed: int, out_path: str) -> int:
    import numpy as np

    from garage_tpu.ops import jaxenv

    res: dict = {"ok": False, "cases": []}
    try:
        res["cache_dir"] = jaxenv.setup()
        res["device"] = jaxenv.verdict()
        plat = res["device"]["platform"]
        print(f"  device: {res['device']}  compile cache: "
              f"{res['cache_dir']}", flush=True)
        if plat != ("cpu" if rehearse else "tpu"):
            raise Failed(f"JAX found platform {plat!r}, not a TPU")
        import jax

        rng = np.random.default_rng(seed)
        ok = True
        res["largest_bucket"] = {}
        for k, m, s in cfg["geometries"]:
            for b in cfg["buckets"]:
                ok &= _geometry_cases(k, m, s, b, cfg, rng, res["cases"])
            # the top of the feeder's ladder: walk down to what fits
            for b in cfg["top_ladder"]:
                if _geometry_cases(k, m, s, b, cfg, rng, res["cases"]):
                    res["largest_bucket"][f"rs({k},{m})"] = b
                    break
            else:
                ok = False
        ok &= _hash_cases(cfg, rng, res["cases"])
        if res["device"]["count"] > 1:
            res["placement"] = _placement(cfg, rng)
            print(f"  placement: {json.dumps(res['placement'])}", flush=True)
        ms = jax.devices()[0].memory_stats() or {}
        res["memory"] = {kk: ms[kk] for kk in
                         ("bytes_limit", "peak_bytes_in_use") if kk in ms}
        res["compile"] = jaxenv.compile_stats()
        res["ok"] = bool(ok)
        if not ok:
            res["error"] = "a kernel failed below the top of the ladder"
    except Exception as e:
        res["error"] = f"{type(e).__name__}: {e}"[:1000]
    with open(out_path, "w") as f:
        json.dump(res, f)
    return 0 if res["ok"] else 3


# ---------------------------------------------------------------------------
# phase: served (runs in the parent: six servers, S3 over HTTP, no JAX)
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(url: str, token: str | None = None, timeout: float = 10.0) -> str:
    rq = urllib.request.Request(url)
    if token:
        rq.add_header("authorization", f"Bearer {token}")
    with urllib.request.urlopen(rq, timeout=timeout) as r:
        return r.read().decode()


def scrape(port: int) -> dict:
    """node /metrics -> {series-with-labels: value}."""
    out = {}
    for line in http_get(f"http://127.0.0.1:{port}/metrics").splitlines():
        if line and not line.startswith("#"):
            name, _, val = line.rpartition(" ")
            try:
                out[name] = float(val)
            except ValueError:
                pass
    return out


class Cluster:
    ADMIN_TOKEN = "chip-smoke-admin-token"

    def __init__(self, work: str, cfg: dict, rehearse: bool, env: dict,
                 seed: int):
        self.work, self.cfg, self.rehearse, self.env = work, cfg, rehearse, env
        self.seed = seed
        self.n = 6
        self.ports = {i: {"rpc": free_port(), "s3": free_port(),
                          "adm": free_port()} for i in range(1, self.n + 1)}
        self.procs: dict[int, subprocess.Popen] = {}

    def conf(self, i: int) -> str:
        return os.path.join(self.work, f"node{i}", "garage.toml")

    def node_keys(self) -> list[tuple[str, bytes]]:
        """Six (node id, private key) from the seed, ascending by id.
        Node 1 gets the smallest id on purpose: a stripe's deep-scrub
        leader is the first node of its placement and the layout lists
        a partition's nodes in id order, so with random keys node 1
        leads no stripe in two clusters out of three and the
        parity_check launch would never be reached. Node 2, killed
        later with its zone mate node 5, has the next lowest id, which
        puts data shards (not only parity) on it, so the degraded read
        has to decode."""
        from garage_tpu.net.netapp import node_key_from_bytes

        raws = [hashlib.sha256(f"chip-smoke-node/{self.seed}/{j}".encode())
                .digest() for j in range(self.n)]
        return sorted((node_key_from_bytes(r).public_key()
                       .public_bytes_raw().hex(), r) for r in raws)

    def write_configs(self) -> None:
        keys = self.node_keys()
        self.node_id = {i: keys[i - 1][0] for i in self.ports}
        for i, p in self.ports.items():
            d = os.path.join(self.work, f"node{i}")
            os.makedirs(os.path.join(d, "meta"), exist_ok=True)
            fd = os.open(os.path.join(d, "meta", "node_key"),
                         os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
            with os.fdopen(fd, "wb") as f:
                f.write(keys[i - 1][1])
            # node 1 owns the chip; in rehearsal its device platform is
            # the CPU backend, said explicitly. Nodes 2-6 are host-only.
            tpu = ("[tpu]\nenable = false\n" if i > 1 else
                   '[tpu]\nplatform = "cpu"\n' if self.rehearse else "")
            ram = (f"block_ram_buffer_max = {self.cfg['ram_buffer_max']}\n"
                   if self.cfg["ram_buffer_max"] else "")
            with open(self.conf(i), "w") as f:
                f.write(f'''metadata_dir = "{d}/meta"
data_dir = "{d}/data"
# metadata: 3 replicas, one per zone, default consistency (read and
# write quorum 2 of 3: an acknowledged write is read back). The two
# nodes killed later share a zone, so every partition keeps 2 of its 3
# replicas and stays consistent, while every block loses 2 of its 6
# shards — the full m = 2.
replication_factor = 3
erasure_coding = "4,2"
db_engine = "sqlite"
block_size = {self.cfg["block_size"]}
{ram}rpc_bind_addr = "127.0.0.1:{p["rpc"]}"
rpc_public_addr = "127.0.0.1:{p["rpc"]}"
rpc_secret = "00112233445566778899aabbccddeeff00112233445566778899aabbccddeeff"

[s3_api]
api_bind_addr = "127.0.0.1:{p["s3"]}"
s3_region = "garage"
root_domain = ".s3.garage.test"

[admin]
api_bind_addr = "127.0.0.1:{p["adm"]}"
admin_token = "{self.ADMIN_TOKEN}"

{tpu}''')

    def start(self, i: int) -> None:
        env = dict(self.env)
        if i == 1:
            env["GARAGE_TPU_DEVICE"] = "require"
        log = open(os.path.join(self.work, f"node{i}", "log"), "ab")
        p = subprocess.Popen(
            [sys.executable, "-m", "garage_tpu.cli.server", "--config",
             self.conf(i), "--log-level", "info"],
            cwd=HERE, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True)
        log.close()
        self.procs[i] = p
        _procs.append(p)

    def log_tail(self, i: int, n: int = 15) -> str:
        try:
            with open(os.path.join(self.work, f"node{i}", "log"),
                      errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def wait_up(self, i: int, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.procs[i].poll() is not None:
                raise Failed(f"node {i} exited at boot:\n{self.log_tail(i)}")
            try:
                http_get(f"http://127.0.0.1:{self.ports[i]['adm']}/health",
                         timeout=2.0)
                return
            except urllib.error.HTTPError:
                return  # it answers; "unavailable" until the layout is in
            except OSError:
                time.sleep(0.3)
        raise Failed(f"node {i} not up in {timeout:.0f}s:\n{self.log_tail(i)}")

    def cli(self, i: int, *args: str) -> str:
        r = subprocess.run(
            [sys.executable, "-m", "garage_tpu.cli.main", "--config",
             self.conf(i), *args],
            cwd=HERE, env=self.env, capture_output=True, text=True,
            timeout=120)
        if r.returncode != 0:
            raise Failed(f"cli {' '.join(args)} on node {i} failed: "
                         f"{r.stdout[-300:]} {r.stderr[-600:]}")
        return r.stdout

    def connected(self) -> int:
        try:
            return int(json.loads(http_get(
                f"http://127.0.0.1:{self.ports[1]['adm']}/v1/health",
                token=self.ADMIN_TOKEN))["connectedNodes"])
        except (OSError, ValueError, KeyError):
            return -1

    def wait_connected(self, want: int, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.connected() == want:
                return
            time.sleep(0.3)
        raise Failed(f"connectedNodes is {self.connected()}, wanted {want}")

    def loads_jax(self, i: int) -> bool:
        """Whether node i's process has mapped a JAX/XLA library."""
        with open(f"/proc/{self.procs[i].pid}/maps") as f:
            maps = f.read()
        return any(s in maps for s in ("jaxlib", "libtpu", "xla_extension"))

    def kill(self, i: int) -> None:
        p = self.procs[i]
        os.killpg(p.pid, signal.SIGKILL)
        p.wait(timeout=30)


def _field(text: str, label: str) -> str:
    for line in text.splitlines():
        if line.startswith(label):
            return line.split()[-1]
    raise Failed(f"no {label!r} in CLI output: {text[:300]}")


def _seeded(seed: int, n: int) -> bytes:
    import numpy as np

    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def phase_served(cfg: dict, rehearse: bool, seed: int, work: str,
                 env: dict) -> dict:
    cl = Cluster(work, cfg, rehearse, env, seed)
    try:
        return _serve(cl, cfg, rehearse, seed)
    except Failed as e:
        raise Failed(f"{e}\n--- node 1 log, last lines ---\n"
                     f"{cl.log_tail(1, 40)}") from None


def _serve(cl: Cluster, cfg: dict, rehearse: bool, seed: int) -> dict:
    sys.path.insert(0, os.path.join(HERE, "tests"))
    from s3util import S3Client, xml_find

    res: dict = {"steps": {}}
    failures: list[str] = []  # every request that did not succeed
    t_step = time.monotonic()

    def step(name: str) -> None:
        nonlocal t_step
        now = time.monotonic()
        res["steps"][name] = round(now - t_step, 2)
        say(f"served: {name} ({now - t_step:.1f}s)")
        t_step = now

    cl.write_configs()
    for i in range(1, cl.n + 1):
        cl.start(i)
    for i in range(2, cl.n + 1):
        cl.wait_up(i, 60)
    # node 1 takes its device verdict at boot: JAX import + chip init
    cl.wait_up(1, 240)
    step("six nodes up")

    for i in range(2, cl.n + 1):
        cl.cli(i, "connect",
               f"{cl.node_id[1]}@127.0.0.1:{cl.ports[1]['rpc']}")
    cl.wait_connected(cl.n, 60)
    for i in range(1, cl.n + 1):
        cl.cli(1, "layout", "assign", cl.node_id[i], "-z",
               f"dc{(i - 1) % 3 + 1}", "-c", "4G")
    cl.cli(1, "layout", "apply")
    key = cl.cli(1, "key", "new", "--name", "chip-smoke")
    key_id, secret = _field(key, "Key ID:"), _field(key, "Secret key:")
    cl.cli(1, "bucket", "create", "smoke")
    cl.cli(1, "bucket", "allow", "smoke", "--key", key_id, "--read",
           "--write", "--owner")
    step("layout, key, bucket")

    adm1, s3_1 = cl.ports[1]["adm"], cl.ports[1]["s3"]
    m0 = scrape(adm1)
    dev = next((k for k in m0 if k.startswith("feeder_device_count")), None)
    if dev is None:
        raise Failed("node 1 /metrics has no feeder_device_count")
    res["node1_device"] = {
        "platform": dev.split('platform="')[1].split('"')[0],
        "device_kind": dev.split('device_kind="')[1].split('"')[0],
        "count": int(m0[dev])}
    say(f"served: node 1 holds {res['node1_device']}")
    if res["node1_device"]["platform"] != ("cpu" if rehearse else "tpu"):
        raise Failed(f"node 1 runs on {res['node1_device']['platform']!r}")

    client = S3Client("127.0.0.1", s3_1, key_id, secret)
    rq_timeout = 900.0  # a first compile may sit inside any request

    def call(what: str, fn):
        """One S3 request; anything but its expected status is counted."""
        try:
            st, hdrs, body = fn()
        except Exception as e:
            failures.append(f"{what}: {type(e).__name__}: {e}")
            raise Failed(failures[-1])
        if st not in (200, 206):
            failures.append(f"{what}: HTTP {st} {body[:200]!r}")
            raise Failed(failures[-1])
        return hdrs, body

    sent: dict[str, tuple[str, int]] = {}  # key -> (sha256, size)
    done_at: dict[str, float] = {}  # key -> seconds after the PUTs began
    lock = threading.Lock()
    errors: list[BaseException] = []

    def upload(u: int) -> None:
        name = f"/smoke/mpu{u}"
        sha, size = hashlib.sha256(), 0
        _, b = call(f"create {name}", lambda: client.request(
            "POST", name, query=[("uploads", "")], timeout=rq_timeout))
        upload_id = xml_find(b, "UploadId")[0]
        etags = []
        for pn in range(1, cfg["parts"] + 1):
            part = _seeded(seed * 1000 + u * 16 + pn, cfg["part_bytes"])
            sha.update(part)
            size += len(part)
            h, _ = call(f"part {pn} of {name}", lambda: client.request(
                "PUT", name, query=[("partNumber", str(pn)),
                                    ("uploadId", upload_id)],
                body=part, unsigned_payload=True, timeout=rq_timeout))
            etags.append((pn, h["etag"].strip('"')))
        xml = "".join(f"<Part><PartNumber>{pn}</PartNumber>"
                      f'<ETag>"{e}"</ETag></Part>' for pn, e in etags)
        call(f"complete {name}", lambda: client.request(
            "POST", name, query=[("uploadId", upload_id)],
            body=f"<CompleteMultipartUpload>{xml}"
                 f"</CompleteMultipartUpload>".encode(), timeout=rq_timeout))
        with lock:
            sent[name] = (sha.hexdigest(), size)
            done_at[name] = time.monotonic() - t0

    def signed(j: int) -> None:
        name = f"/smoke/signed{j}"
        data = _seeded(seed * 1000 + 900 + j, cfg["signed_bytes"])
        cs = cfg["signed_chunk"]
        chunks = [data[o:o + cs] for o in range(0, len(data), cs)]
        call(f"signed put {name}", lambda: client.put_chunked(
            name, chunks, timeout=rq_timeout))
        with lock:
            sent[name] = (hashlib.sha256(data).hexdigest(), len(data))
            done_at[name] = time.monotonic() - t0

    def guarded(fn, *a):
        try:
            fn(*a)
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=guarded, args=(upload, u), daemon=True)
               for u in range(cfg["n_uploads"])]
    threads += [threading.Thread(target=guarded, args=(signed, j), daemon=True)
                for j in range(cfg["signed_objects"])]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise Failed(f"upload failed: {errors[0]}")
    put_bytes = sum(sz for _, sz in sent.values())
    # host clock, first XLA compiles included (node 1's compile seconds
    # are in the counters): how long the run took, not a rate to quote
    res["put"] = {
        "objects": len(sent), "bytes": put_bytes,
        "seconds": round(time.monotonic() - t0, 2),
        "multipart_done_s": round(max(
            v for k, v in done_at.items() if "mpu" in k), 2),
        "signed_done_s": round(max(
            v for k, v in done_at.items() if "signed" in k), 2)}
    step(f"PUT {len(sent)} objects, {put_bytes / MIB:.0f} MiB")

    def get_all(tag: str) -> None:
        def one(name):
            _, body = call(f"{tag} get {name}", lambda: client.request(
                "GET", name, timeout=rq_timeout))
            want, size = sent[name]
            if len(body) != size or hashlib.sha256(body).hexdigest() != want:
                failures.append(f"{tag} get {name}: bytes differ")
                raise Failed(failures[-1])

        ts = [threading.Thread(target=guarded, args=(one, n), daemon=True)
              for n in sent]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        if errors:
            raise Failed(f"{tag} GET failed: {errors[0]}")

    get_all("full")
    step("GET all, full redundancy, byte-identical")

    for i in range(2, cl.n + 1):
        if cl.loads_jax(i):
            raise Failed(f"node {i} ([tpu] enable = false) loaded JAX")
    victims = (2, 5)  # both in zone dc2 (write_configs)
    for i in victims:
        cl.kill(i)
    cl.wait_connected(cl.n - len(victims), 60)
    get_all("degraded")
    step(f"GET all with nodes {victims} SIGKILLed, byte-identical")

    for i in victims:
        cl.start(i)
    for i in victims:
        cl.wait_up(i, 60)
    cl.wait_connected(cl.n, 90)
    step("killed nodes restarted, connectedNodes = 6")

    # tranquility 0: the pass runs flat out instead of sleeping 4x its
    # own work between batches (an operator knob; same code path)
    cl.cli(1, "worker", "set", "scrub-tranquility", "0")
    t_cmd = time.time()
    cl.cli(1, "repair", "scrub", "start")
    deadline = time.monotonic() + 600
    while time.monotonic() < deadline:
        if scrape(adm1).get("block_scrub_last_completed_seconds", 0) > t_cmd:
            break
        time.sleep(1.0)
    else:
        raise Failed("scrub pass did not finish in 600 s")
    step("scrub pass finished on node 1")

    m = scrape(adm1)
    res["device_items"] = {
        k.split('op="')[1].split('"')[0]: int(v) for k, v in m.items()
        if k.startswith("feeder_device_op_items")}
    res["feeder_mbps"] = {
        k.split("{")[1].rstrip("}").replace('"', ""): v
        for k, v in m.items() if k.startswith("feeder_throughput_mbps")}
    res["stage_busy_s"] = {
        k.split('stage="')[1].split('"')[0]: v for k, v in m.items()
        if k.startswith("feeder_pipeline_busy_seconds")}
    res["counters"] = {k: m.get(k, 0) for k in (
        "feeder_device_items", "feeder_device_batches", "feeder_max_batch",
        "feeder_device_errors", "feeder_host_reruns", "feeder_recompiles",
        "feeder_mesh_batches", "feeder_xla_compile_requests",
        "feeder_xla_compiles", "feeder_xla_cache_hits",
        "feeder_xla_compile_seconds", "feeder_pad_waste_bytes",
        "feeder_device_bytes", "feeder_overlap_efficiency",
        "feeder_pipeline_wall_seconds",
        "block_scrub_corruptions", "block_scrub_deep_stripes_checked")}
    res["route"] = next((k for k in m if k.startswith("feeder_device_route")),
                        "")
    res["failed_requests"] = len(failures)
    for i in range(2, cl.n + 1):
        if cl.loads_jax(i):
            raise Failed(f"node {i} ([tpu] enable = false) loaded JAX")

    di, c = res["device_items"], res["counters"]
    checks = {
        "encode_put on device": di.get("encode_put", 0) > 0,
        "hash on device": di.get("hash", 0) + di.get("hash_md5", 0) > 0,
        "sha256 on device": di.get("sha256", 0) > 0,
        "decode on device": di.get("decode", 0) > 0,
        "parity_check on device": di.get("parity_check", 0) > 0,
        "zero host re-runs": c["feeder_host_reruns"] == 0,
        "zero device errors": c["feeder_device_errors"] == 0,
        "zero failed requests": not failures,
        "scrub found zero corruptions": c["block_scrub_corruptions"] == 0,
        "scrub parity-checked stripes":
            c["block_scrub_deep_stripes_checked"] > 0,
        "a compiled program came from the persistent cache":
            c["feeder_xla_cache_hits"] > 0,
        "meshed batches on several chips":
            res["node1_device"]["count"] == 1 or c["feeder_mesh_batches"] > 0,
    }
    res["checks"] = checks
    bad = [k for k, v in checks.items() if not v]
    if bad:
        raise Failed(f"served phase: {bad}; device_items={di} counters={c} "
                     f"failures={failures[:3]}")
    res["ok"] = True
    return res


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------


def build_native() -> dict:
    """Empty garage_tpu/native/_build/ and build the host library."""
    build = os.path.join(HERE, "garage_tpu", "native", "_build")
    shutil.rmtree(build, ignore_errors=True)
    from garage_tpu import native

    if not native.available():
        raise Failed("the native host library did not build from "
                     "garage_tpu/native/src/b3gf.c (no C compiler?)")
    built = sorted(os.listdir(build))
    if not built:
        raise Failed(f"nothing was built into {build}")
    return {"built": built, "from": "garage_tpu/native/src/b3gf.c"}


def stop_all() -> None:
    for p in _procs:
        if p.poll() is None:
            try:
                os.killpg(p.pid, signal.SIGTERM)
            except OSError:
                pass
    deadline = time.monotonic() + 10
    for p in _procs:
        try:
            p.wait(timeout=max(0.1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except OSError:
                pass
            p.wait()


def verdict_line(dev: dict) -> str:
    """The last line of stdout of a run that passed: exactly "ok" and
    "device", the device exactly "platform", "kind" and "count", as
    jaxenv.verdict() took them from jax.devices() in the process that
    held the chip. Whoever runs the smoke parses this line and refuses
    any other key; everything else goes on the summary line before."""
    return json.dumps({"ok": True, "device": {
        "platform": str(dev["platform"]), "kind": str(dev["device_kind"]),
        "count": int(dev["count"])}})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", nargs="?", choices=["rehearse"],
                    help="tiny sizes on the CPU (never inferred)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=["kernels"], help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args()
    rehearse = args.mode == "rehearse"
    cfg = TINY if rehearse else FULL

    if not os.path.isdir(os.path.join(HERE, "garage_tpu")):
        print("chip_smoke.py runs from the root of the garage-tpu checkout; "
              f"there is no garage_tpu/ in {HERE}", file=sys.stderr)
        return 2
    sys.path.insert(0, HERE)
    if args.phase == "kernels":
        return phase_kernels(cfg, rehearse, args.seed, args.out)

    def on_signal(signum, _frame):
        raise KeyboardInterrupt(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGALRM, on_signal)
    signal.alarm(int(TIME_LIMIT))

    env = dict(os.environ, PYTHONPATH=HERE, PYTHONUNBUFFERED="1")
    env.pop("GARAGE_TPU_DEVICE", None)
    env.pop("GARAGE_TPU_DEVICE_BACKEND", None)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    work = os.path.join(HERE, ".chip_smoke_work")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    out: dict = {"ok": False, "rehearsal": rehearse, "seed": args.seed,
                 "reduced": [], "claim": None}
    detail: dict = {}  # every kernel case; too long for the summary line
    if rehearse:
        out["reduced"].append("rehearsal: toy sizes on the CPU, nothing "
                              "here is a device result")
    try:
        say(f"{'REHEARSAL on cpu' if rehearse else 'chip run'}; work dir "
            f"{work}")
        out["native"] = build_native()
        say(f"native: built {out['native']['built']} from "
            f"{out['native']['from']}")

        assert "jax" not in sys.modules
        kout = os.path.join(work, "kernels.json")
        say("kernels: starting the child that holds the chip")
        t0 = time.monotonic()
        argv = [sys.executable, os.path.abspath(__file__), "--phase",
                "kernels", "--out", kout, "--seed", str(args.seed)]
        kp = subprocess.Popen(argv + (["rehearse"] if rehearse else []),
                              cwd=HERE, env=env, start_new_session=True)
        _procs.append(kp)
        rc = kp.wait()
        try:
            with open(kout) as f:
                kres = json.load(f)
        except (OSError, ValueError):
            raise Failed(f"kernel phase died (exit {rc}) with no result")
        detail["kernels"] = kres
        out["kernels"] = {
            "seconds": round(time.monotonic() - t0, 1),
            "cases": len(kres["cases"]),
            "failed": [c for c in kres["cases"] if not c["ok"]],
            "largest_bucket": kres.get("largest_bucket"),
            "compile": kres.get("compile"), "memory": kres.get("memory"),
            "placement": kres.get("placement"),
            "cache_dir": kres.get("cache_dir")}
        dev = kres.get("device")
        if not kres["ok"]:
            raise Failed(f"kernel phase: {kres.get('error')}"
                         + (f" (device: {dev})" if dev else ""))
        say(f"kernels: {len(kres['cases'])} cases ok on {dev}, largest "
            f"bucket {kres['largest_bucket']}, compile {kres['compile']}")

        assert "jax" not in sys.modules
        sres = phase_served(cfg, rehearse, args.seed, work, env)
        out["served"] = sres
        if sres["node1_device"] != dev:
            raise Failed(f"node 1 saw {sres['node1_device']}, the kernel "
                         f"phase saw {dev}")
        out["device"] = {"platform": dev["platform"],
                         "kind": dev["device_kind"], "count": dev["count"]}
        out["seconds"] = round(time.monotonic() - T0, 1)
        out["ok"] = True
    except (Failed, KeyboardInterrupt, subprocess.SubprocessError,
            OSError, AssertionError) as e:
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr)
        print(json.dumps(out, default=str)[:20000], file=sys.stderr)
        return 1
    finally:
        signal.alarm(0)
        stop_all()
        shutil.rmtree(work, ignore_errors=True)
        # the long form (every kernel case) for whoever reads the run
        # afterwards; chiprun_out/ is git-ignored
        os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
        with open(os.path.join(HERE, "chiprun_out", "chip_smoke.json"),
                  "w") as f:
            json.dump({**out, "detail": detail}, f, indent=1, default=str)
    print("chip_smoke summary " + json.dumps(out), flush=True)
    print(verdict_line(dev), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
