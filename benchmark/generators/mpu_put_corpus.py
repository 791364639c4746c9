"""Generator kind `mpu_put_corpus`: `mpu_put` over compressible data.

The uploaders, their requests, the metrics and the read-back are
mpu_put's (generators/mpu_put.py); only the bytes differ. They come
from lib/corpus.py `CorpusPool` instead of seeded uniform bytes: each
block of the pool is of one class (uniform random bytes, Zipf text or
JSON log records), laid out by the seed in fixed shares, so every run
sends the same mix and every block is still stamped unique.

params, beside mpu_put's `clients`, `parts_per_object`, `part_bytes`:

  classes       {"random": w, "text": w, "json": w}: each class's share
                of the pool's blocks, as weights
  ratio         the zstd level-1 ratio the pool is made to have: plain
                bytes over bytes stored, a block that does not shrink
                stored plain
  ratio_within  how far the pool's own ratio, measured in prepare()
                with `zstandard` in this process, may lie from `ratio`;
                further, and the run fails before any request
"""

from __future__ import annotations

import os

from lib import manifest
from lib.corpus import CorpusPool

_base = manifest.load_module(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "generators", "mpu_put")
PRODUCES = _base.PRODUCES


class Generator(_base.Generator):

    def prepare(self) -> None:
        prm = self.env.params
        self.pool = CorpusPool(self.env.seed, self.part_bytes,
                               self.env.block_bytes, prm["classes"])
        got = self.pool.ratios()
        self.env.say("pool at zstd level 1: " + ", ".join(
            f"{c} {r:.3f}" for c, r in got.items()))
        if abs(got["all"] - float(prm["ratio"])) > float(prm["ratio_within"]):
            raise RuntimeError(
                f"the pool compresses {got['all']:.3f}:1, not "
                f"{prm['ratio']} +- {prm['ratio_within']}")
