"""The recipe of ec42-put-zstd's data (lib/corpus.py): each class at its
own zstd level-1 ratio, the pool at the ratio the traffic file asks
for, the same bytes for the same seed, every block stamped unique; and
the configuration the same cluster as ec42-6n3z."""

import hashlib
import json
import os

import pytest
from conftest import BENCH

from lib.corpus import CLASSES, CorpusPool, counts
from lib.objects import MAGIC, STAMP

MIB = 1 << 20
with open(os.path.join(BENCH, "traffic", "put-mp16x8-zstd.json")) as f:
    PARAMS = json.load(f)["params"]


@pytest.fixture(scope="module")
def pool():
    """Four 4 MiB parts of 1 MiB blocks: 16 blocks, the 1:5:2 mix
    exactly, each block the size the cell sends."""
    return CorpusPool(2 ** 31 + 77, 4 * MIB, MIB, PARAMS["classes"])


def test_the_mix_is_the_traffic_files(pool):
    assert counts(PARAMS["classes"], 16) == {"random": 2, "text": 10,
                                             "json": 4}
    assert sorted(pool.classes) == sorted(
        c for c, n in counts(PARAMS["classes"], 16).items() for _ in range(n))
    assert counts({"random": 1, "text": 5, "json": 2}, 20) == {
        "random": 3, "text": 12, "json": 5}


def test_each_class_and_the_pool_at_their_ratios(pool):
    got = pool.ratios()
    assert got["random"] == 1.0  # stored plain
    assert 3.3 < got["text"] < 3.7
    assert 5.8 < got["json"] < 6.7
    assert abs(got["all"] - PARAMS["ratio"]) <= PARAMS["ratio_within"]


def test_same_seed_same_bytes_other_seed_other_bytes(pool):
    again = CorpusPool(2 ** 31 + 77, 4 * MIB, MIB, PARAMS["classes"])
    assert again.classes == pool.classes
    assert again.part(5, 9) == pool.part(5, 9)
    other = CorpusPool(2 ** 31 + 78, 4 * MIB, MIB, PARAMS["classes"])
    assert other.part(5, 9) != pool.part(5, 9)


def test_every_block_is_stamped_and_unique(pool):
    seen = set()
    for u in range(2):
        for seq in range(6):  # more parts than the pool holds
            p = pool.part(u, seq)
            for bi in range(4):
                blk = bytes(p[bi * MIB:(bi + 1) * MIB])
                assert STAMP.unpack_from(blk) == (MAGIC, pool.seed, u,
                                                  seq, bi)
                seen.add(hashlib.sha256(blk).digest())
    assert len(seen) == 2 * 6 * 4


def test_a_rehearsal_pool_is_within_its_own_band():
    """The configuration's toy sizes: 64 KiB blocks compress less, so
    its `rehearse` block states its own ratio."""
    with open(os.path.join(BENCH, "configs", "ec42-6n3z-zstd.json")) as f:
        cfg = json.load(f)
    prm = dict(PARAMS, **cfg["rehearse"]["traffic"])
    small = CorpusPool(3, prm["part_bytes"],
                       cfg["rehearse"]["toml"]["block_size"], prm["classes"])
    assert set(small.classes) == set(CLASSES)
    assert abs(small.ratios()["all"] - prm["ratio"]) <= prm["ratio_within"]


def test_the_cluster_is_ec42_6n3z():
    with open(os.path.join(BENCH, "configs", "ec42-6n3z.json")) as f:
        base = json.load(f)
    with open(os.path.join(BENCH, "configs", "ec42-6n3z-zstd.json")) as f:
        cfg = json.load(f)
    for key in ("nodes", "zones", "capacity", "toml", "chip_node",
                "s3_node", "guarantees"):
        assert cfg[key] == base[key], key
    assert cfg["rehearse"]["toml"] == base["rehearse"]["toml"]
    assert "compression" not in cfg["toml"]  # the default, zstd level 1
    assert set(cfg["reduced"]) == set(base["reduced"]) | {"corpus"}
