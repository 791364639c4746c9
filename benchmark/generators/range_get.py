"""Generator kind `range_get`: closed-loop ranged readers over a
preloaded data set.

Set-up preloads `preload_objects` objects of `parts_per_object` x
`part_bytes` with mpu_put's own uploader. Then `clients` readers, one
kept-alive connection each, each forever a `Range` GET of one part
chosen uniformly by the seed. Every body's SHA-256 is compared with the
part's digest, taken when its bytes were made, after the last byte is
stamped and so off the timed path.
"""

from __future__ import annotations

import hashlib
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from lib import stats
from lib.objects import PartPool, upload_object
from lib.loadgen import MIB, ClosedLoop, ms

PRODUCES = ("get_MiBps", "req_p50_ms", "first_byte_p50_ms")


class Generator(ClosedLoop):
    PRIMARY = "get"

    def __init__(self, env):
        super().__init__(env)
        self.parts_per_object = int(env.params["parts_per_object"])
        self.part_bytes = int(env.params["part_bytes"])
        self.preload_objects = int(env.params["preload_objects"])
        self.objects: list[dict] = []  # the preloaded set, sorted by key

    def prepare(self) -> None:
        pool = PartPool(self.env.seed, self.part_bytes, self.env.block_bytes)

        def load(o: int) -> None:
            client = self.env.client()
            try:
                obj = upload_object(
                    client, f"/{self.env.bucket}/data/o{o}", pool, o,
                    list(range(self.parts_per_object)), self.log.record,
                    want_sha=True)
            finally:
                client.close()
            if obj is not None:
                self.log.complete(obj)

        with ThreadPoolExecutor(max_workers=self.clients) as ex:
            list(ex.map(load, range(self.preload_objects)))
        if len(self.log.completed) != self.preload_objects:
            raise RuntimeError(f"preload failed: {self.log.failures()[:3]}")
        self.objects = sorted(self.log.completed, key=lambda o: o["key"])

    def client_loop(self, c: int, client) -> None:
        rng = np.random.default_rng([self.env.seed, 0x6E7, c])
        while not self.stopping():
            obj = self.objects[int(rng.integers(len(self.objects)))]
            pn = int(rng.integers(self.parts_per_object))
            a = pn * self.part_bytes
            try:
                r = client.request("GET", obj["key"], headers={
                    "range": f"bytes={a}-{a + self.part_bytes - 1}"})
            except Exception as e:
                self.log.record("get", e, 0, False)
                time.sleep(0.2)  # a failed exchange: do not spin on it
                continue
            ok = (r.status == 206 and len(r.body) == self.part_bytes
                  and hashlib.sha256(r.body).hexdigest()
                  == obj["sha256s"][pn])
            self.log.record("get", r, len(r.body), ok)

    def measure(self, w0: float, w1: float) -> dict:
        gets = self.log.of("get")
        inside = stats.ended_inside(gets, w0, w1)
        return {
            "metrics": {
                "get_MiBps": stats.overlap_bytes_per_s(gets, w0, w1) / MIB,
                "req_p50_ms": ms(stats.median(r.t_end - r.t_start
                                               for r in inside)),
                "first_byte_p50_ms": ms(stats.median(
                    r.t_first - r.t_start for r in inside)),
            },
            "samples": {"req_p50_ms": len(inside)},
            **self.log.counts(w0, w1),
        }

    def check(self) -> list[str]:
        """Every body was compared as it arrived; nothing more to read."""
        return list(self.log.failures())
