"""Device-routed read path (ISSUE 13): batched RS decode & repair
through the staged pipeline with pattern-as-data GF kernels.

Same deviceless discipline as test_feeder_pipeline.py: the jax
backend's "device" is the cpu platform (conftest pins JAX_PLATFORMS=cpu)
— the staging/padding/grouping and the pattern-as-data compile behavior
are under test, not the silicon — and the stub backend covers the
watchdog and live-gate semantics.
"""

from __future__ import annotations

import asyncio
import itertools
import os

import numpy as np
import pytest

from garage_tpu.block.codec import ErasureCodec
from garage_tpu.block.device_backend import StubDeviceBackend
from garage_tpu.block.feeder import DeviceFeeder, _Item
from garage_tpu.ops import rs


def run(coro):
    return asyncio.run(coro)


def _stripe(codec, block: bytes):
    return codec.encode(block)


# ---------------------------------------------------------------------------
# byte-parity: device decode/repair == decode_np across ALL erasure
# patterns and across shard-length buckets
# ---------------------------------------------------------------------------


def test_decode_byte_parity_all_patterns_and_buckets():
    """Every C(k+m, k) present-set, at two block sizes landing in
    different shard-length pad buckets, decoded through the staged jax
    route in ONE batch — results byte-identical to decode_np +
    join_stripe (pad rows and length padding sliced away)."""
    k, m = 4, 2
    codec = ErasureCodec(k, m, use_jax=False)
    f = DeviceFeeder(codec=codec, mode="require", max_batch=256)
    f._device_ok = True
    rng = np.random.default_rng(13)
    patterns = list(itertools.combinations(range(k + m), k))
    assert len(patterns) == 15

    async def go():
        items, want = [], []
        for blen in (3_000, 300_000):  # distinct bucket_len buckets
            block = rng.integers(0, 256, blen, dtype=np.uint8).tobytes()
            stripe = _stripe(codec, block)
            for present in patterns:
                shards = [stripe[i] for i in present]
                items.append((present, shards, blen))
                st = np.stack([np.frombuffer(s, dtype=np.uint8)
                               for s in shards])
                want.append(rs.join_stripe(
                    rs.decode_np(k, m, present, st), blen))
        batch = [_Item("decode", it, asyncio.get_running_loop()
                       .create_future()) for it in items]
        res = await f._run_batch_staged(batch)
        for got, exp, it in zip(res, want, items):
            assert not isinstance(got, BaseException), (it[0], got)
            assert got == exp, f"pattern {it[0]} len {it[2]}"
        assert f.stats["decode_device_items"] == len(items)
        assert f.stats["pad_waste_bytes"] > 0
        await f.stop()

    run(go())


def test_repair_byte_parity_mixed_missing_sizes():
    """Repair through the staged route rebuilds the exact missing
    shard bytes for 1- and 2-missing patterns in one batch (grouped by
    output row count internally) — vs the repair_np reference."""
    k, m = 4, 2
    codec = ErasureCodec(k, m, use_jax=False)
    f = DeviceFeeder(codec=codec, mode="require", max_batch=256)
    f._device_ok = True
    rng = np.random.default_rng(17)
    block = rng.integers(0, 256, 65_000, dtype=np.uint8).tobytes()
    stripe = _stripe(codec, block)
    full = np.stack([np.frombuffer(s, dtype=np.uint8) for s in stripe])

    items = []
    for missing in [(0,), (3,), (5,), (0, 1), (2, 5), (4, 5)]:
        present = tuple(i for i in range(k + m) if i not in missing)[:k]
        items.append((present, missing, [stripe[i] for i in present]))

    async def go():
        batch = [_Item("repair", it, asyncio.get_running_loop()
                       .create_future()) for it in items]
        res = await f._run_batch_staged(batch)
        for (present, missing, _s), got in zip(items, res):
            assert not isinstance(got, BaseException), (missing, got)
            assert sorted(got) == sorted(missing)
            for mi in missing:
                assert got[mi] == bytes(full[mi]), (present, missing, mi)
        await f.stop()

    run(go())


# ---------------------------------------------------------------------------
# recompile stability: the pattern is DATA, not a trace constant
# ---------------------------------------------------------------------------


def test_recompiles_flat_across_mixed_erasure_patterns():
    """>= 8 distinct erasure patterns through the staged decode route,
    one batch per pattern at identical shapes: feeder_recompiles moves
    once for the first shape and NEVER again — and the per-pattern
    constant-matrix jit cache (rs._jit_apply, the pre-ISSUE-13 leak)
    gains no entries at all."""
    k, m = 4, 2
    codec = ErasureCodec(k, m, use_jax=False)
    f = DeviceFeeder(codec=codec, mode="require", max_batch=16)
    f._device_ok = True
    rng = np.random.default_rng(23)
    block = rng.integers(0, 256, 40_000, dtype=np.uint8).tobytes()
    stripe = _stripe(codec, block)
    patterns = list(itertools.combinations(range(k + m), k))[:9]
    assert len(patterns) >= 8
    leak_cache_before = rs._jit_apply.cache_info().currsize

    async def go():
        rc_after_first = None
        for present in patterns:
            shards = [stripe[i] for i in present]
            batch = [_Item("decode", (present, shards, len(block)),
                           asyncio.get_running_loop().create_future())
                     for _ in range(4)]
            res = await f._run_batch_staged(batch)
            st = np.stack([np.frombuffer(s, dtype=np.uint8)
                           for s in shards])
            want = rs.join_stripe(rs.decode_np(k, m, present, st),
                                  len(block))
            assert all(r == want for r in res), present
            if rc_after_first is None:
                rc_after_first = f.stats["recompiles"]
        assert f.stats["recompiles"] == rc_after_first, \
            "a new erasure pattern caused a recompile"
        assert f.stats["decode_device_items"] == 4 * len(patterns)
        await f.stop()

    run(go())
    assert rs._jit_apply.cache_info().currsize == leak_cache_before, \
        "per-pattern constant-matrix jit entries leaked"


def test_rs_decode_repair_share_one_jit_across_patterns():
    """The ops-level decode/repair entry points themselves no longer
    grow a jit cache entry per pattern (the f"dec{k},{m},{present}"
    keys): every pattern rides the single pattern-as-data kernel."""
    k, m = 4, 2
    rng = np.random.default_rng(29)
    data = rng.integers(0, 256, (k, 64), dtype=np.uint8)
    stripe = np.concatenate([data, np.asarray(rs.encode(k, m, data))])
    before = rs._jit_apply.cache_info().currsize
    for present in itertools.combinations(range(k + m), k):
        got = np.asarray(rs.decode(k, m, present, stripe[list(present)]))
        assert np.array_equal(got, data)
    missing = (0, 5)
    present = (1, 2, 3, 4)
    got = np.asarray(rs.repair(k, m, present, missing,
                               stripe[list(present)]))
    assert np.array_equal(got, stripe[list(missing)])
    assert rs._jit_apply.cache_info().currsize == before


# ---------------------------------------------------------------------------
# watchdog: depth-2 decode hang -> host re-run, every future resolves
# ---------------------------------------------------------------------------


def test_decode_hang_reruns_host_every_future_resolves(monkeypatch):
    """mode="auto", injected device hang with decode batches in flight
    at depth 2: every caller gets the CORRECT packed bytes via the host
    re-run, no future is lost, and the device path is shut — the
    read-side edition of the pipeline hang test."""
    # conftest exports GARAGE_TPU_DEVICE=off (auto would become off)
    monkeypatch.delenv("GARAGE_TPU_DEVICE", raising=False)
    k, m = 4, 2
    codec = ErasureCodec(k, m, use_jax=False)
    stub = StubDeviceBackend(codec, fixed_s=0.01)
    stub.hang_stage = "compute"
    f = DeviceFeeder(codec=codec, mode="auto", max_batch=2,
                     backend=stub)
    f.batch_timeout = 1.0
    rng = np.random.default_rng(31)
    blocks = [rng.integers(0, 256, 20_000 + i, dtype=np.uint8).tobytes()
              for i in range(4)]
    present = (1, 2, 3, 4)  # degraded: shard 0 lost

    async def go():
        jobs = []
        for b in blocks:
            stripe = codec.encode(b)
            jobs.append(f.decode(present, [stripe[i] for i in present],
                                 len(b)))
        outs = await asyncio.gather(*jobs)
        dev_ok = f._device_ok
        await f.stop()
        return outs, dev_ok

    outs, dev_ok = run(go())
    for b, got in zip(blocks, outs):
        st = np.stack([np.frombuffer(s, dtype=np.uint8)
                       for s in codec.encode(b)])
        want = rs.join_stripe(
            rs.decode_np(k, m, present, st[list(present)]), len(b))
        assert got == want
    assert dev_ok is False
    assert f.stats["decode_device_items"] == 0
    assert f.stats["host_reruns"] >= 1


# ---------------------------------------------------------------------------
# stub live gate: degraded GETs through a real cluster engage the
# device decode route
# ---------------------------------------------------------------------------


def test_degraded_get_stub_live_gate(tmp_path, monkeypatch):
    """GARAGE_TPU_DEVICE=require + stub backend on a real 6-node
    erasure cluster: a degraded GET (one systematic shard destroyed)
    must route its decode through the device path —
    feeder_decode_device_items > 0, the CI shape of the read-side
    engagement gate."""
    from test_block import make_block_cluster, stop_all
    from garage_tpu.utils.data import blake2sum

    monkeypatch.setenv("GARAGE_TPU_DEVICE", "require")
    monkeypatch.setenv("GARAGE_TPU_DEVICE_BACKEND", "stub")

    async def main():
        net, systems, managers, tasks = await make_block_cluster(
            tmp_path, n=6, rf=3, erasure=(4, 2))
        try:
            data = os.urandom(200_000)
            h = blake2sum(data)
            await managers[0].rpc_put_block(h, data)
            for _ in range(100):
                held = sorted(i for mg in managers
                              for i in mg.local_parts(h))
                if held == [0, 1, 2, 3, 4, 5]:
                    break
                await asyncio.sleep(0.02)
            # destroy a systematic shard so the GET really decodes
            victim = next(mg for mg in managers
                          if 0 in mg.local_parts(h))
            victim.delete_local(h)
            reader = managers[1]
            reader.cache.clear()
            got = await reader.rpc_get_block(h, cacheable=False)
            assert got == data
            fs = reader.feeder.stats
            assert fs["decode_items"] >= 1
            assert fs["decode_device_items"] >= 1, fs
        finally:
            await stop_all(systems, tasks)

    run(asyncio.wait_for(main(), 120))


# ---------------------------------------------------------------------------
# deep-scrub gather fan-out is windowed
# ---------------------------------------------------------------------------


def test_deep_scrub_gather_window_bounded():
    """gather_bounded keeps at most `window` stripe gathers in flight
    (repair.py:258 used to fan out the whole leader set at once) and
    returns results in item order."""
    from garage_tpu.block.repair import gather_bounded

    live = 0
    peak = 0

    async def fake_gather(h, placement):
        nonlocal live, peak
        live += 1
        peak = max(peak, live)
        await asyncio.sleep(0.01)
        live -= 1
        return (h, placement)

    items = [(i, f"p{i}") for i in range(23)]

    async def go():
        return await gather_bounded(fake_gather, items, 4)

    out = run(go())
    assert out == items  # order preserved
    assert peak <= 4, f"window exceeded: {peak}"
    assert peak >= 2  # it did actually run concurrently


def test_malformed_decode_item_fails_its_caller_only():
    """Unequal shard lengths are rejected BEFORE the queue: the bad
    caller gets ValueError, batch-mates are unaffected (an in-batch
    failure would poison the whole op group)."""
    k, m = 4, 2
    codec = ErasureCodec(k, m, use_jax=False)
    f = DeviceFeeder(codec=codec, mode="off", max_batch=8)

    async def go():
        block = os.urandom(10_000)
        stripe = codec.encode(block)
        present = (1, 2, 3, 4)
        bad_shards = [stripe[1], stripe[2][:100], stripe[3], stripe[4]]
        with pytest.raises(ValueError):
            await f.decode(present, bad_shards, len(block))
        good = await f.decode(present,
                              [stripe[i] for i in present], len(block))
        st = np.stack([np.frombuffer(stripe[i], dtype=np.uint8)
                       for i in present])
        assert good == rs.join_stripe(
            rs.decode_np(k, m, present, st), len(block))
        await f.stop()

    run(go())


# ---------------------------------------------------------------------------
# the gather, counted once a gather (ISSUE 37): block_gather_seconds,
# block_gather_fetches, the block.gather span's attrs; rs_decode_patterns
# ---------------------------------------------------------------------------

# case -> (systematic holders stopped, parity holders stopped,
#          fetches by result, waves, outcome)
GATHERS = {
    "all-up": (0, 0, {"ok": 4}, 1, "ok"),
    "m-down": (2, 0, {"ok": 4, "refused": 2}, 2, "ok"),
    "short": (2, 1, {"ok": 3, "refused": 3}, 2, "short"),
}


@pytest.mark.parametrize("case", GATHERS)
def test_gather_series_and_span(tmp_path, case):
    """One gather at (4,2) with every holder up, with m down, and one
    that comes back short: the histogram moves once under its outcome,
    the fetch counter once a fetch under what became of it, and the
    span says how many fetches, in how many waves, of how many holders
    up — under the names the three metric files read."""
    import json
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from clusterbox import ClusterBox

    from garage_tpu.block.codec import shard_nodes_of
    from garage_tpu.utils import tracing
    from garage_tpu.utils.error import MissingBlock
    from garage_tpu.utils.metrics import registry

    data_down, parity_down, want_fetches, want_waves, outcome = GATHERS[case]
    k, m = 4, 2
    reg = registry()

    def counts():
        return ({o: reg.totals("block_gather_seconds", outcome=o)[0]
                 for o in ("ok", "short")},
                {r: reg.totals("block_gather_fetches", result=r)[0]
                 for r in ("ok", "refused", "failed", "cancelled")})

    async def main():
        box = await ClusterBox(tmp_path, n=k + m, rf=3, erasure=(k, m),
                               block_size=20_000).start()
        try:
            reader = box.nodes[0]
            mgr = reader.manager
            data = np.random.default_rng(37).integers(
                0, 256, 20_000, dtype=np.uint8).tobytes()
            h = await mgr.hash_block(data)
            await mgr.rpc_put_block(h, data)
            placement = shard_nodes_of(
                reader.system.layout_helper.current(), h, k + m)
            by_id = {nd.id: nd for nd in box.nodes}
            systematic = [by_id[x] for x in placement[:k] if x != reader.id]
            parity = [by_id[x] for x in placement[k:] if x != reader.id]
            dead = systematic[:data_down] + parity[:parity_down]
            await box.wait(
                lambda: box.resync_backlog() == 0 and not any(
                    nd.manager.cache_tier._insert_inflight
                    for nd in box.live()),
                15, "the box quiet")
            for nd in dead:
                await box.stop_node(nd)
            await box.wait(
                lambda: not any(reader.system.is_up(nd.id) for nd in dead),
                15, "the reader sees the dead holders down")
            before = counts()
            tracing.tracer.enabled = True
            tracing.tracer.ring.clear()
            try:
                try:
                    got = await mgr.rpc_get_block(h, cacheable=False)
                except MissingBlock:
                    got = None
                spans = [r for r in tracing.tracer.ring
                         if r["name"] == "block.gather"]
            finally:
                tracing.tracer.enabled = False
                tracing.tracer.ring.clear()
            return got == data, before, counts(), spans
        finally:
            await box.stop()

    same, before, after, spans = run(asyncio.wait_for(main(), 120))
    assert same == (outcome == "ok")
    gathers = {o: after[0][o] - before[0][o] for o in after[0]}
    fetches = {r: after[1][r] - before[1][r] for r in after[1]}
    assert gathers == {"ok": int(outcome == "ok"),
                       "short": int(outcome == "short")}
    assert {r: n for r, n in fetches.items() if n} == want_fetches
    (span,) = spans
    assert span["attrs"] == {
        "hash": span["attrs"]["hash"], "parts": k,
        "fetches": sum(want_fetches.values()), "waves": want_waves,
        "holders_up": k + m - data_down - parity_down}
    # what the three metric files read is what the registry renders
    rendered = {line.split("{")[0].split(" ")[0] for line in reg.render()}
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in ("gather_ms", "fetches_per_block", "gathered_share"):
        with open(os.path.join(root, "benchmark", "layer_metrics",
                               f"{name}.json")) as f:
            params = json.load(f)["params"]
        series = {t["series"] for side in ("num", "den")
                  for t in params[side]}
        assert series - {"cache_hits", "cache_misses"} <= rendered, name


def test_rs_decode_patterns_counts_the_present_sets_launched():
    """The gauge is the size of decode_bitmat_t's cache, which keeps
    one expansion a present-set for the life of the process: after a
    batch of five stripes under three present-sets it reads three."""
    k, m = 4, 2
    codec = ErasureCodec(k, m, use_jax=False)
    f = DeviceFeeder(codec=codec, mode="require")
    f._device_ok = True
    block = np.random.default_rng(3).integers(
        0, 256, 5_000, dtype=np.uint8).tobytes()
    stripe = _stripe(codec, block)
    sets = [(0, 1, 2, 4), (1, 2, 3, 5), (0, 2, 4, 5)]

    async def go():
        rs.decode_bitmat_t.cache_clear()
        assert f.stats["decode_patterns"] == 0
        items = [(p, [stripe[i] for i in p], len(block))
                 for p in sets + sets[:2]]
        try:
            got = await f._run_batch_staged(
                [_Item("decode", it, None) for it in items])
        finally:
            await f.stop()
        assert got == [block] * 5
        assert f.stats["decode_patterns"] == 3 \
            == rs.decode_bitmat_t.cache_info().currsize

    run(go())
