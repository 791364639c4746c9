"""Sharded data-plane steps (parallel/mesh.py) on the 8-device CPU mesh.

VERDICT r3 weak-item 6: make_put_step/make_scrub_step/make_repair_step
were exercised only by the driver's dryrun. These tests pin:
- sharded-vs-single-device equivalence for RS(4,2) and the flagship
  RS(10,4) across (dp, tp) in {(8,1), (4,2), (2,4)}
- the shard-S fallback when tp does not divide n = k+m
- corruption detection through the sharded scrub step
- the tp-does-not-divide-S error path
"""

from __future__ import annotations

import numpy as np
import pytest

from garage_tpu.ops import rs, treehash
from garage_tpu.parallel.mesh import (
    _layouts,
    data_plane_mesh,
    make_put_step,
    make_repair_step,
    make_scrub_step,
)

SHAPES = [(4, 2), (10, 4)]
GRIDS = [(8, 1), (4, 2), (2, 4)]
S = 2048


def _mesh(dp: int, tp: int):
    import jax

    assert len(jax.devices()) >= dp * tp, "conftest must provide 8 devices"
    return data_plane_mesh(dp * tp, tp=tp)


def _host_reference(data: np.ndarray, k: int, m: int):
    """Single-host numpy/py reference for the put step."""
    parity = np.stack([rs.encode_np(k, m, data[i])
                       for i in range(data.shape[0])])
    allsh = np.concatenate([data, parity], axis=1)
    hashes = np.stack([
        np.stack([np.frombuffer(treehash.blake3_py(allsh[i, j].tobytes()),
                                dtype=np.uint8)
                  for j in range(k + m)])
        for i in range(allsh.shape[0])
    ])
    return parity, allsh, hashes


@pytest.mark.parametrize("dp,tp", GRIDS)
@pytest.mark.parametrize("k,m", SHAPES)
def test_put_step_sharded_matches_host(k, m, dp, tp):
    mesh = _mesh(dp, tp)
    batch = dp * 2
    rng = np.random.default_rng(k * 100 + tp)
    data = rng.integers(0, 256, size=(batch, k, S), dtype=np.uint8)
    put = make_put_step(mesh, k, m, S)
    parity, hashes = put(data)
    ref_parity, _, ref_hashes = _host_reference(data, k, m)
    np.testing.assert_array_equal(np.asarray(parity), ref_parity)
    np.testing.assert_array_equal(np.asarray(hashes), ref_hashes)


@pytest.mark.parametrize("shard_len", [1024, 3 * 1024, 5 * 1024])
def test_put_step_digests_at_odd_chunk_counts(shard_len):
    """hash_rows composed inside the sharded step where its tree has no
    level (one chunk), one carried node (three) and a node carried over
    two levels (five): the digests are the reference's."""
    k, m = 4, 2
    mesh = _mesh(4, 2)
    rng = np.random.default_rng(shard_len)
    data = rng.integers(0, 256, size=(8, k, shard_len), dtype=np.uint8)
    parity, hashes = make_put_step(mesh, k, m, shard_len)(data)
    ref_parity, _, ref_hashes = _host_reference(data, k, m)
    np.testing.assert_array_equal(np.asarray(parity), ref_parity)
    np.testing.assert_array_equal(np.asarray(hashes), ref_hashes)


@pytest.mark.parametrize("dp,tp", [(4, 2), (2, 4)])
@pytest.mark.parametrize("k,m", SHAPES)
def test_scrub_step_detects_injected_corruption(k, m, dp, tp):
    mesh = _mesh(dp, tp)
    batch = dp * 2
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=(batch, k, S), dtype=np.uint8)
    put = make_put_step(mesh, k, m, S)
    parity, hashes = put(data)
    shards = np.concatenate([data, np.asarray(parity)], axis=1)

    scrub = make_scrub_step(mesh, k, m, S)
    bad, count = scrub(shards, np.asarray(hashes))
    assert int(count) == 0
    assert not np.asarray(bad).any()

    # flip one byte in a data shard and one in a parity shard
    shards2 = shards.copy()
    shards2[1, 0, 100] ^= 0xFF
    shards2[2, k + 1, 5] ^= 0x01
    bad2, count2 = scrub(shards2, np.asarray(hashes))
    bad2 = np.asarray(bad2)
    assert bad2[1, 0] and bad2[2, k + 1]
    assert int(count2) == 2


@pytest.mark.parametrize("dp,tp", GRIDS)
def test_repair_step_rebuilds_missing(dp, tp):
    k, m = 10, 4
    mesh = _mesh(dp, tp)
    batch = dp * 2
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, size=(batch, k, S), dtype=np.uint8)
    parity = np.stack([rs.encode_np(k, m, data[i]) for i in range(batch)])
    shards = np.concatenate([data, parity], axis=1)
    present = (0, 1, 2, 3, 4, 6, 7, 8, 9, 12)
    missing = (5, 10, 13)
    repair = make_repair_step(mesh, k, m, present, missing, S)
    rebuilt, rhashes = repair(shards[:, list(present), :])
    np.testing.assert_array_equal(np.asarray(rebuilt),
                                  shards[:, list(missing), :])
    for j, mi in enumerate(missing):
        assert bytes(np.asarray(rhashes)[0, j]) == \
            treehash.blake3_py(shards[0, mi].tobytes())


def test_repair_step_shares_one_program_across_patterns():
    """ISSUE 20 / GL14 regression: make_repair_step was lru_cache'd per
    (present, missing) pattern — C(n,k) compiled programs. The repair
    matrix now rides as a tensor operand through a shape-keyed apply:
    same-size patterns must share ONE cache entry and stay correct."""
    from garage_tpu.parallel.mesh import _repair_apply_step

    k, m = 4, 2
    mesh = _mesh(4, 2)
    batch = 8
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, size=(batch, k, S), dtype=np.uint8)
    parity = np.stack([rs.encode_np(k, m, data[i]) for i in range(batch)])
    shards = np.concatenate([data, parity], axis=1)
    patterns = [((0, 1, 2, 4), (3,)), ((1, 2, 3, 5), (0,)),
                ((0, 2, 3, 4), (1,))]
    _repair_apply_step.cache_clear()
    for present, missing in patterns:
        repair = make_repair_step(mesh, k, m, present, missing, S)
        rebuilt, _ = repair(shards[:, list(present), :])
        np.testing.assert_array_equal(np.asarray(rebuilt),
                                      shards[:, list(missing), :])
    assert _repair_apply_step.cache_info().currsize == 1


def test_layout_fallback_when_tp_does_not_divide_n():
    mesh = _mesh(2, 4)
    # n = 14, tp = 4: whole-shard layout must fall back to sharding S
    _, shards_sh, n_sharded = _layouts(mesh, 14, S)
    assert not n_sharded
    # n = 6, tp = 2 on a fresh mesh: n axis sharded
    mesh2 = _mesh(4, 2)
    _, _, n_sharded2 = _layouts(mesh2, 6, S)
    assert n_sharded2


def test_tp_must_divide_shard_len():
    mesh = _mesh(2, 4)
    with pytest.raises(ValueError, match="divide shard_len"):
        _layouts(mesh, 6, 1023 * 3)  # 3069 % 4 != 0
    with pytest.raises(ValueError):
        data_plane_mesh(8, tp=3)  # 3 does not divide 8 devices
