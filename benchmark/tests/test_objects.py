"""The data recipe and the plain reference: the same seed gives the same
bytes, every block sent is unique, the ETag is S3's md5-of-md5s; and the
span reader's self-time arithmetic."""

import hashlib
import types

import pytest
from conftest import BENCH

from lib import manifest
from lib.objects import STAMP, PartPool, multipart_etag


def test_same_seed_same_bytes_other_seed_other_bytes():
    a, b = PartPool(7, 1 << 16, 1 << 14), PartPool(7, 1 << 16, 1 << 14)
    assert a.part(3, 5) == b.part(3, 5)
    assert PartPool(8, 1 << 16, 1 << 14).part(3, 5) != a.part(3, 5)


def test_every_block_of_every_part_is_unique():
    pool, bs = PartPool(1, 1 << 16, 1 << 14), 1 << 14
    blocks = set()
    for u in range(3):
        for seq in range(9):  # more parts than the pool holds
            p = pool.part(u, seq)
            assert len(p) == 1 << 16
            for off in range(0, len(p), bs):
                blocks.add(hashlib.sha256(p[off:off + bs]).digest())
    assert len(blocks) == 3 * 9 * 4
    magic, seed, u, seq, bi = STAMP.unpack_from(pool.part(2, 7), 3 * bs)
    assert (seed, u, seq, bi) == (1, 2, 7, 3)


def test_a_pool_too_small_for_the_stamp_is_refused():
    with pytest.raises(ValueError):
        PartPool(1, 16, 16)


def test_multipart_etag_is_md5_of_the_parts_md5s():
    parts = [b"a" * 10, b"b" * 20]
    md5s = [hashlib.md5(p).hexdigest() for p in parts]
    want = hashlib.md5(b"".join(hashlib.md5(p).digest() for p in parts))
    assert multipart_etag(md5s) == want.hexdigest() + "-2"


def test_span_self_share_is_time_not_covered_by_direct_children():
    spans = [
        {"span": "r1", "parent": None, "name": "http.request", "start_us": 0, "dur_us": 100},
        {"span": "c1", "parent": "r1", "name": "s3.put.block", "start_us": 10, "dur_us": 40},
        {"span": "c2", "parent": "r1", "name": "s3.put.block", "start_us": 30, "dur_us": 40},
        {"span": "g1", "parent": "c1", "name": "block.put", "start_us": 80, "dur_us": 15},
        {"span": "r2", "parent": None, "name": "http.request", "start_us": 0, "dur_us": 100},
    ]
    rd = manifest.load_module(BENCH, "readers", "span_self_share")
    ctx = types.SimpleNamespace(window_spans=lambda: spans)
    # r1: children cover 10..70 = 60 of 100; r2: none of 100 -> 140 / 200
    assert rd.read({"root": "http.request"}, ctx) == pytest.approx(70.0)
    assert rd.read({"root": "no.such.span"}, ctx) is None
