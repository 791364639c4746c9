"""Generator kind `mpu_put`: closed-loop multipart uploaders.

`clients` uploaders, one kept-alive connection each, each forever:
CreateMultipartUpload -> `parts_per_object` x UploadPart of
`part_bytes` -> CompleteMultipartUpload -> next key. The primary
request is UploadPart. Parameters come from the traffic file; bytes
from lib/objects.py (seeded pool, every block stamped unique).
"""

from __future__ import annotations

import time

import numpy as np

from lib import stats
from lib.objects import PartPool, read_back, upload_object
from lib.loadgen import MIB, ClosedLoop, ms

PRODUCES = ("put_MiBps", "req_p50_ms")


class Generator(ClosedLoop):
    PRIMARY = "part"

    def __init__(self, env):
        super().__init__(env)
        self.parts_per_object = int(env.params["parts_per_object"])
        self.part_bytes = int(env.params["part_bytes"])
        self.pool: PartPool | None = None

    def prepare(self) -> None:
        self.pool = PartPool(self.env.seed, self.part_bytes,
                             self.env.block_bytes)

    def client_loop(self, u: int, client) -> None:
        """An uploader lets the part it has in flight finish and leaves
        its object incomplete when the run stops."""
        seq = 0
        while not self.stopping():
            seqs = list(range(seq, seq + self.parts_per_object))
            seq += self.parts_per_object
            obj = upload_object(
                client, f"/{self.env.bucket}/u{u}/o{seqs[0]}", self.pool,
                u, seqs, self.log.record, self.stopping)
            if obj is not None:
                self.log.complete(obj)
            elif not self.stopping():
                time.sleep(0.2)  # a failed exchange: do not spin on it

    def measure(self, w0: float, w1: float) -> dict:
        parts = self.log.of("part")
        inside = stats.ended_inside(parts, w0, w1)
        return {
            "metrics": {
                "put_MiBps": stats.overlap_bytes_per_s(parts, w0, w1) / MIB,
                "req_p50_ms": ms(stats.median(r.t_end - r.t_start
                                               for r in inside)),
            },
            "samples": {"req_p50_ms": len(inside)},
            **self.log.counts(w0, w1),
        }

    def check(self) -> list[str]:
        """After the window: a seeded sample of completed objects read
        back whole and byte-identical, through the nodes and after the
        kills that the configuration's guarantees name. -> what failed."""
        bad: list[str] = []
        plan = self.env.config["guarantees"]["readback"]
        done = sorted(self.log.completed, key=lambda o: o["key"])
        if not done:
            return self.log.failures() + ["no object was completed"]
        rng = np.random.default_rng([self.env.seed, 0xC4EC])
        n = min(int(plan["sample"]), len(done))
        sample = [done[i] for i in
                  sorted(rng.choice(len(done), n, replace=False))]
        self.env.say(f"read-back: {n} of {len(done)} completed "
                     f"objects")

        def through(nodes, tag):
            for node in nodes:
                client = self.env.client(node)
                try:
                    for obj in sample:
                        if not read_back(client, obj, self.pool,
                                         self.log.record):
                            bad.append(f"{tag}: {obj['key']} through node "
                                       f"{node} differs or failed")
                finally:
                    client.close()

        through(plan["via_nodes"], "read-back")
        kill = plan.get("then_kill") or []
        if kill:
            for i in kill:
                self.env.cluster.kill(i)
            self.env.cluster.wait_connected(self.env.cluster.n - len(kill), 60)
            through(plan["via_after_kill"], f"read-back with {kill} killed")
        return self.log.failures() + bad
