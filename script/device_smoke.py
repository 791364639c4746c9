#!/usr/bin/env python3
"""Stub rehearsal of the live device path (CI, deviceless boxes).

Runs the engagement gates against the STUB backend
(GARAGE_TPU_DEVICE_BACKEND=stub: real results from the host kernels,
modelled stage latencies): a forked server under
GARAGE_TPU_DEVICE=require must put live S3 PUTs through the feeder's
device route (feeder_device_items > 0), degraded GETs must engage the
batched decode route without recompiling per erasure pattern, and the
ingest path must keep the modelled pipeline fed. Every figure here is
modelled, not measured: the stub sleeps, no device runs. The proof on
the chip is `python chip_smoke.py` at the root of the repo.

Usage: GARAGE_TPU_DEVICE_BACKEND=stub python script/device_smoke.py [nobj] [obj_mib]
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def main() -> int:
    nobj = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    obj_mib = int(sys.argv[2]) if len(sys.argv) > 2 else 4

    if os.environ.get("GARAGE_TPU_DEVICE_BACKEND") != "stub":
        print("FAIL: this is the stub rehearsal; run it with "
              "GARAGE_TPU_DEVICE_BACKEND=stub. The chip run is "
              "`python chip_smoke.py`.")
        return 2

    import bench

    out = bench.bench_s3_put(nobj, obj_mib, device=True)
    print(json.dumps(out, indent=2))
    if out.get("s3_device_platform") != "stub":
        print(f"FAIL: the server ran on {out.get('s3_device_platform')!r}, "
              "not the stub")
        return 1
    if out.get("s3_feeder_device_items", 0) <= 0:
        print("FAIL: feeder_device_items == 0 — live S3 PUTs never "
              "reached the device path")
        return 1
    print("OK: live PUT path engaged the device "
          f"({out['s3_feeder_device_items']} items, overlap "
          f"{out.get('s3_feeder_overlap_efficiency', 0.0)})")

    # read-side gate (ISSUE 13): degraded GETs + rebuild waves must
    # engage the device decode route
    dec = bench.bench_decode(nblocks=4, block_kib=256,
                             device_mode="require")
    print(json.dumps(dec, indent=2))
    if dec.get("decode_feeder_device_items", 0) <= 0:
        print("FAIL: decode_feeder_device_items == 0 — degraded GETs "
              "never reached the device decode path")
        return 1
    # under the stub nothing compiles; the pattern-as-data flatness
    # of the compiled programs is pinned by tests/test_feeder_decode.py
    if dec.get("decode_recompiles", 0) > 0:
        print(f"FAIL: decode_recompiles = {dec['decode_recompiles']} "
              f"(> 0) across "
              f"{dec['decode_patterns_mixed']} erasure patterns — "
              "decode is recompiling per pattern")
        return 1
    print("OK: degraded-GET/rebuild path engaged the device "
          f"({dec['decode_feeder_device_items']} decode items, "
          f"{dec['decode_recompiles']} recompiles across "
          f"{dec['decode_patterns_mixed']} erasure patterns)")

    # wire->device gate (ISSUE 17): bench_put_path pins the STUB
    # backend with modelled rates internally (the measurement isolates
    # the FRONTEND). The frontend must keep the modelled pipeline
    # >= 80% fed and land each body byte in host RAM ~once (<= 1.1x,
    # alignment slop).
    pp = bench.bench_put_path()
    print(json.dumps(pp, indent=2))
    if pp.get("put_feeder_device_items", 0) <= 0:
        print("FAIL: put_feeder_device_items == 0 — ingest-path PUTs "
              "never reached the device path")
        return 1
    if pp.get("put_sha256_device_items", 0) <= 0:
        print("FAIL: put_sha256_device_items == 0 — signed-chunk "
              "hashing never reached the batched sha256 lane")
        return 1
    eff = pp.get("frontend_efficiency", 0.0)
    if eff < 0.8:
        print(f"FAIL: frontend_efficiency = {eff:.3f} (< 0.8) — "
              "the frontend starves the modelled device pipeline "
              f"(ceiling {pp['put_path_modeled_ceiling_gbps']} GB/s, "
              f"measured {pp['put_path_gbps']} GB/s)")
        return 1
    ratio = pp.get("put_copy_ratio", 99.0)
    if ratio > 1.1:
        print(f"FAIL: put_copy_ratio = {ratio:.2f} (> 1.1) — PUT "
              "bodies are being re-materialized between socket and "
              f"device: {pp['put_copy_bytes_by_path']}")
        return 1
    print(f"OK: wire->device gap closed (efficiency {eff:.3f}, "
          f"copy ratio {ratio:.2f}, "
          f"{pp['put_feeder_device_items']} device items)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
