"""Profile rpc_put_block end-to-end on the in-process loopback cluster.

Usage: python scripts/profile_put.py [nblocks] [--cprofile] [--mode=off]

Imports bench.py's _build_cluster so the profile measures exactly what
the bench measures: the gap between the encode kernel and the
end-to-end system number.
"""
from __future__ import annotations

import asyncio
import cProfile
import os
import pstats
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


async def run(nblocks: int, do_profile: bool, device_mode: str) -> None:
    import bench
    from garage_tpu.rpc import ReplicationMode
    from garage_tpu.utils.data import blake3sum

    tmp = tempfile.mkdtemp(prefix="gt_prof_",
                           dir="/dev/shm" if os.path.isdir("/dev/shm") else None)
    try:
        rm = ReplicationMode.parse(3, erasure="4,2")
        systems, managers, tasks = await bench._build_cluster(
            tmp, 6, rm, device_mode)
        block_len = 1 << 20
        rng = np.random.default_rng(2)
        blocks = [rng.integers(0, 256, block_len, dtype=np.uint8).tobytes()
                  for _ in range(nblocks)]
        hashes = [blake3sum(b) for b in blocks]
        for i in range(2):
            await managers[0].rpc_put_block(hashes[i], blocks[i])

        prof = cProfile.Profile() if do_profile else None
        if prof:
            prof.enable()
        t0c = time.process_time()
        dt = await bench._pump_blocks(managers[0], hashes, blocks, 2)
        dtc = time.process_time() - t0c
        if prof:
            prof.disable()
        gbps = (nblocks - 2) * block_len / dt / 1e9
        print(f"put: {nblocks-2} x 1MiB in {dt:.3f}s (cpu {dtc:.3f}s) "
              f"= {gbps:.3f} GB/s")
        print("feeder:", dict(managers[0].feeder.stats))
        print("perf:", managers[0].feeder.perf_summary())
        if prof:
            st = pstats.Stats(prof)
            st.sort_stats("cumulative").print_stats(35)
            st.sort_stats("tottime").print_stats(35)
        await bench._teardown(systems, managers, tasks)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    from garage_tpu.utils.runtime import tune

    tune()
    n = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() else 128
    mode = "off" if "--mode=off" in sys.argv else "auto"
    asyncio.run(run(n, "--cprofile" in sys.argv, mode))
