"""Compressible data made from the seed: the parts of a cell whose
deployment keeps Garage's default compression, zstd level 1.

`CorpusPool` is `objects.PartPool` with other bytes in its parts: the
same `part(uploader, seq)` (a pool part, each block's head stamped
unique), so `objects.upload_object` and `objects.read_back` take it
as they take the random pool. Each block of the pool is one of three
classes, in the shares the traffic file gives as weights, laid out by
a permutation drawn from the seed, so every run sends the same mix:

  random  uniform bytes: never shrinks, stored plain
  text    words drawn Zipf(1.15) from a 20,000-word vocabulary
  json    JSON log records: timestamp, host, status, bytes, path

The vocabulary is one fixed language (its own constant seed): which
word is frequent, and how long it is, then moves no run's ratio. The
words drawn from it, and every record, come from the run's seed.
`ratios()` measures what zstd level 1 makes of the pool, class by
class and overall, with `zstandard` itself.
"""

from __future__ import annotations

import numpy as np

from .objects import STAMP, PartPool

CLASSES = ("random", "text", "json")
LEVEL = 1  # Garage's default (src/util/config.rs)
VOCAB_WORDS, VOCAB_SEED, ZIPF_S = 20_000, 3, 1.15
# English letter frequencies (%), a..z
LETTERS = np.array([8.2, 1.5, 2.8, 4.3, 12.7, 2.2, 2.0, 6.1, 7.0, 0.15,
                    0.77, 4.0, 2.4, 6.7, 7.5, 1.9, 0.095, 6.0, 6.3, 9.1,
                    2.8, 0.98, 2.4, 0.15, 2.0, 0.074])
HOSTS = [f"web-{i:02d}" for i in range(16)]
STATUSES = (200, 200, 200, 200, 200, 200, 201, 304, 404, 500)
PATHS = ("/api/v1/items/", "/api/v1/users/", "/api/v1/search?q=",
         "/img/", "/static/app.js", "/index.html")


def counts(weights: dict, n: int) -> dict:
    """n blocks shared out by weight, largest remainders first."""
    total = sum(weights[c] for c in CLASSES)
    exact = {c: n * weights[c] / total for c in CLASSES}
    out = {c: int(exact[c]) for c in CLASSES}
    for c in sorted(CLASSES, key=lambda c: out[c] - exact[c])[
            :n - sum(out.values())]:
        out[c] += 1
    return out


def _vocabulary():
    rng = np.random.default_rng(VOCAB_SEED)
    lens = np.minimum(rng.poisson(6.0, VOCAB_WORDS) + 1, 20)
    letters = rng.choice(26, int(lens.sum()), p=LETTERS / LETTERS.sum())
    offs = np.concatenate([[0], np.cumsum(lens)[:-1]])
    p = np.arange(1, VOCAB_WORDS + 1, dtype=np.float64) ** -ZIPF_S
    return (letters + ord("a")).astype(np.uint8), lens, offs, p / p.sum()


def text(rng, n: int, vocab) -> np.ndarray:
    """n bytes of words, each followed by a space."""
    letters, lens, offs, p = vocab
    idx = rng.choice(VOCAB_WORDS, n // 3 + 16, p=p)  # words of >= 2 bytes
    wlen = lens[idx] + 1
    ends = np.cumsum(wlen)
    keep = int(np.searchsorted(ends, n)) + 1
    idx, wlen, ends = idx[:keep], wlen[:keep], ends[:keep]
    pos = np.arange(ends[-1]) - np.repeat(ends - wlen, wlen)
    src = np.minimum(np.repeat(offs[idx], wlen) + pos, len(letters) - 1)
    space = pos == np.repeat(wlen - 1, wlen)
    return np.where(space, ord(" "), letters[src]).astype(np.uint8)[:n]


def json_records(rng, n: int) -> np.ndarray:
    """n bytes of newline-ended JSON log records, a request every 0-40
    ms from a time drawn from the seed."""
    lines, size = [], 0
    t = 1_700_000_000_000 + int(rng.integers(0, 10 ** 11))
    while size < n:
        k = 4096
        dt = rng.integers(0, 40, k)
        host = rng.integers(0, len(HOSTS), k)
        status = rng.integers(0, len(STATUSES), k)
        nbytes = rng.integers(100, 60_000, k)
        path = rng.integers(0, len(PATHS), k)
        ident = rng.integers(0, 100_000, k)
        for i in range(k):
            t += int(dt[i])
            p = PATHS[path[i]]
            if p[-1] in "/=":
                p += str(ident[i])
            s = ('{"ts":%d,"host":"%s","status":%d,"bytes":%d,"path":"%s"}\n'
                 % (t, HOSTS[host[i]], STATUSES[status[i]], nbytes[i], p))
            lines.append(s)
            size += len(s)
            if size >= n:
                break
    return np.frombuffer("".join(lines).encode()[:n], dtype=np.uint8)


class CorpusPool(PartPool):
    """`n` seeded parts of compressible blocks; every part sent is one
    of them with its blocks stamped (PartPool.part)."""

    def __init__(self, seed: int, part_bytes: int, block_bytes: int,
                 weights: dict, n: int = 4):
        if part_bytes < STAMP.size or block_bytes < STAMP.size:
            raise ValueError("part and block must hold the stamp")
        self.seed, self.part_bytes, self.block_bytes = (seed, part_bytes,
                                                        block_bytes)
        per_part = -(-part_bytes // block_bytes)
        have = counts(weights, n * per_part)
        rng = np.random.default_rng([seed, 0xC0B9])
        self.classes = [CLASSES[i] for i in rng.permutation(np.repeat(
            np.arange(len(CLASSES)), [have[c] for c in CLASSES]))]
        vocab = _vocabulary()
        self.pool = []
        for p in range(n):
            buf = np.empty(part_bytes, dtype=np.uint8)
            for bi, off in enumerate(range(0, part_bytes, block_bytes)):
                size = min(block_bytes, part_bytes - off)
                cls = self.classes[p * per_part + bi]
                if cls == "random":
                    buf[off:off + size] = rng.integers(0, 256, size,
                                                       dtype=np.uint8)
                elif cls == "text":
                    buf[off:off + size] = text(rng, size, vocab)
                else:
                    buf[off:off + size] = json_records(rng, size)
            self.pool.append(buf)

    def blocks(self):
        """(class, block) of every block of the pool, stamped as
        uploader 0 sends it (pool part p is its part p)."""
        per_part = -(-self.part_bytes // self.block_bytes)
        for p in range(len(self.pool)):
            part = self.part(0, p)
            for bi in range(per_part):
                yield (self.classes[p * per_part + bi],
                       bytes(part[bi * self.block_bytes:
                                  (bi + 1) * self.block_bytes]))

    def ratios(self) -> dict:
        """{class: plain bytes / bytes stored, ..., "all": the same over
        the whole pool}, at zstd level 1; a block whose frame is not
        smaller is stored plain, as the block manager stores it."""
        import zstandard

        cctx = zstandard.ZstdCompressor(level=LEVEL)
        plain = dict.fromkeys(CLASSES, 0)
        stored = dict.fromkeys(CLASSES, 0)
        for cls, blk in self.blocks():
            plain[cls] += len(blk)
            stored[cls] += min(len(blk), len(cctx.compress(blk)))
        out = {c: plain[c] / stored[c] for c in CLASSES if stored[c]}
        out["all"] = sum(plain.values()) / sum(stored.values())
        return out

