"""One rule for when a call may be cut short (ISSUE 36): a call keeps
its caller's flat timeout when no live node could be asked in its
place (`RpcHelper.has_spare`), and is tightened to the peer's observed
latency only while one could. Asked at each launch (ISSUE 37), over the
nodes that can still answer then, at two sites: `try_call_many`, which
names in the node's log who failed a quorum it refuses, and the block
manager's `_gather_parts`, which names there the first error of a
gather from a holder that is up.
"""

from __future__ import annotations

import asyncio
import logging
import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from clusterbox import ClusterBox  # noqa: E402
from test_rpc import (  # noqa: E402
    _wait,
    apply_flat_layout,
    make_cluster,
    stop_cluster,
)

from garage_tpu.block.codec import shard_nodes_of  # noqa: E402
from garage_tpu.chaos import FaultSpec, arm, disarm  # noqa: E402
from garage_tpu.net.message import PRIO_NORMAL  # noqa: E402
from garage_tpu.net.peering import PeerHealthTracker  # noqa: E402
from garage_tpu.rpc import RequestStrategy, RpcHelper  # noqa: E402
from garage_tpu.utils.error import MissingBlock, QuorumError  # noqa: E402
from garage_tpu.utils.metrics import registry  # noqa: E402

BLOCK = 20_000
PEER = b"\x07" * 32


def run(coro, timeout=120.0):
    return asyncio.run(asyncio.wait_for(coro, timeout))


# ---- the gather: erasure, in-process cluster -------------------------------

# case -> (erasure, holders down, what is done to one live holder)
GATHERS = {
    "ec42-2down-slow": ((4, 2), 2, "slow"),
    "ec104-4down-slow": ((10, 4), 4, "slow"),
    "ec42-allup-error": ((4, 2), 0, "rpc_error"),
    "ec104-allup-error": ((10, 4), 0, "rpc_error"),
    "ec42-2down-error": ((4, 2), 2, "rpc_error"),
    "ec104-4down-error": ((10, 4), 4, "rpc_error"),
    "ec42-2down-noshard": ((4, 2), 2, "no_shard"),
    "ec104-4down-noshard": ((10, 4), 4, "no_shard"),
}
SLOW_S = 1.2  # past the adaptive floor (1 s), far inside the flat 60 s


@pytest.mark.parametrize("case", GATHERS)
def test_gather_with_and_without_a_holder_to_spare(tmp_path, caplog, case):
    (k, m), down, what = GATHERS[case]
    n = k + m
    caplog.set_level(logging.DEBUG, logger="garage_tpu.block")

    async def main():
        box = await ClusterBox(tmp_path, n=n, rf=3, erasure=(k, m),
                               block_size=BLOCK).start()
        try:
            reader = box.nodes[0]
            mgr = reader.manager
            data = np.random.default_rng(n).integers(
                0, 256, BLOCK, dtype=np.uint8).tobytes()
            h = await mgr.hash_block(data)
            await mgr.rpc_put_block(h, data)
            placement = shard_nodes_of(
                reader.system.layout_helper.current(), h, n)
            by_id = {nd.id: nd for nd in box.nodes}
            remote = [by_id[x] for x in placement if x != reader.id]
            # the victim holds a systematic shard, so that with every
            # holder up the gather asks it; the dead are the last ones
            victim, dead = remote[0], remote[len(remote) - down:]
            assert placement.index(victim.id) < k
            for nd in dead:
                await box.stop_node(nd)
            await box.wait(
                lambda: not any(reader.system.is_up(nd.id) for nd in dead),
                15, "the reader sees the dead holders down")
            assert mgr.rpc.has_spare(placement, k) == (down < m)
            # nothing else of the box may meet the fault: the put's
            # write-through to the cache tier runs in the background
            await box.wait(
                lambda: box.resync_backlog() == 0 and not any(
                    nd.manager.cache_tier._insert_inflight
                    for nd in box.live()),
                15, "the box quiet")
            if what == "no_shard":
                victim.manager.delete_local(h)
            if what == "slow":
                # the reader knows the victim as a 20 ms peer, and the
                # victim is then silent for longer than that allows
                for _ in range(8):
                    reader.system.peering.health.record_success(
                        victim.id, 0.02)
                ep = victim.manager.endpoint
                handler = ep._handler

                async def late(frm, payload, stream):
                    await asyncio.sleep(SLOW_S)
                    return await handler(frm, payload, stream)

                ep.set_handler(late)
            fault = FaultSpec(kind="rpc_error", peer=victim.id.hex()[:16],
                              endpoint="garage_tpu/block", count=1)
            if what == "rpc_error":
                arm(seed=36).add(fault)
            try:
                try:
                    got = await mgr.rpc_get_block(h, cacheable=False)
                except MissingBlock:
                    got = None
            finally:
                disarm()
            return (got == data, got is None, fault.fired,
                    victim.id[:4].hex())
        finally:
            await box.stop()

    same, missing, fired, victim = run(main())
    warnings = [r.getMessage() for r in caplog.records
                if r.levelno == logging.WARNING
                and "shard fetch part=" in r.getMessage()]
    # (the dead holders' refusals are errors too, logged at debug)
    if what == "slow":
        # a second late, not cut
        assert same and not warnings
    elif what == "no_shard":
        # an answer is not an error; the stripe is short, as before
        assert missing and not warnings
    else:
        # with a holder to spare the error costs one more fetch, from
        # the spare; with none the GET fails, and the log says why
        assert fired == 1
        assert same if down < m else missing
        assert len(warnings) == 1, warnings
        assert f"from {victim} failed: RpcError" in warnings[0]


# ---- the single call --------------------------------------------------------

class _Endpoint:
    path = "test/spare"

    def __init__(self):
        self.timeouts = []

    async def call(self, node, payload, prio, stream=None, timeout=None):
        self.timeouts.append(timeout)
        return {"ok": True}, None


@pytest.mark.parametrize("adaptive, handed", [
    (None, 1.0), (True, 1.0), (False, 60.0)])
def test_tracked_call_keeps_the_flat_timeout_when_told(adaptive, handed):
    """A peer with >= 8 samples at 20 ms sits at the adaptive floor of
    1 s; `adaptive_timeout=False` hands the caller's own value on, and
    the call is recorded in the peer's health either way."""
    ht = PeerHealthTracker()
    for _ in range(8):
        ht.record_success(PEER, 0.02)
    helper = RpcHelper(types.SimpleNamespace(
        netapp=None, peering=types.SimpleNamespace(health=ht)))
    ep = _Endpoint()
    kw = {} if adaptive is None else {"adaptive_timeout": adaptive}

    async def main():
        assert await helper.call(ep, PEER, {}, PRIO_NORMAL, timeout=60.0,
                                 **kw) == {"ok": True}
        await helper._tracked_call(ep, PEER, {}, PRIO_NORMAL, 60.0, **kw)

    run(main())
    assert ep.timeouts == [handed, handed]
    assert ht.peers[PEER].samples == 10
    # a `system` without is_up (this stub) counts as spare
    assert helper.has_spare([PEER], 1)


# case -> (copies partitioned away, what the live remote copies do)
QUORUMS = {
    "three-up": (0, {}),
    "one-down": (1, {}),
    "one-cut-one-answered": (0, {1: "late"}),
    "one-cut-one-broken": (0, {1: "late", 2: "broken"}),
}
LATE_S = 1.3  # past the tightened 1 s, far inside the flat 30 s


@pytest.mark.parametrize("case", QUORUMS)
def test_try_call_many_asks_the_rule_at_each_launch(tmp_path, caplog, case):
    """Quorum 2 of 3, every peer known as a 20 ms one. Three copies up:
    both calls sit at their peer's tightened 1 s. One copy down: both
    live ones are needed and keep the strategy's flat 30 s. Three up
    and the second asked is silent: it is cut at 1 s, and the third is
    then the last that can be asked, so its launch is flat. That one
    broken too: the quorum is refused, and the node says once who
    failed it and how."""
    copies_down, faults = QUORUMS[case]
    caplog.set_level(logging.WARNING, logger="garage_tpu.rpc.helper")

    async def main():
        net, systems, tasks = await make_cluster(tmp_path, 3)
        try:
            apply_flat_layout(systems)
            me = systems[0]
            nodes = [s.id for s in systems]
            for s in systems:
                for peer in systems:
                    for _ in range(8):
                        s.peering.health.record_success(peer.id, 0.02)
            helper = RpcHelper(me)
            order = helper.request_order(nodes)
            assert order[0] == me.id
            by_id = {s.id: s for s in systems}

            def handler(what):
                async def h(frm, payload, stream):
                    if what == "late":
                        await asyncio.sleep(LATE_S)
                    if what == "broken":
                        raise ValueError("no such row")
                    return {}
                return h

            for i, node in enumerate(order):
                by_id[node].netapp.endpoint("test/spare").set_handler(
                    handler(faults.get(i)))
            if copies_down:
                net.partition(systems[0].id, systems[2].id)
                net.partition(systems[1].id, systems[2].id)
                await _wait(lambda: not me.is_up(systems[2].id), 15)
            assert helper.has_spare(nodes, 2) == (not copies_down)
            ep = me.netapp.endpoint("test/spare")
            seen = []
            real = ep.call

            async def call(node, payload, prio, stream=None, timeout=None):
                seen.append(timeout)
                return await real(node, payload, prio, stream=stream,
                                  timeout=timeout)

            ep.call = call
            try:
                got = len(await helper.try_call_many(
                    ep, nodes, {},
                    RequestStrategy(quorum=2, timeout=30.0, hedge=False)))
            except QuorumError as e:
                got = e
            return seen, got, [n.hex()[:8] for n in order]
        finally:
            await stop_cluster(systems, tasks)

    seen, got, order = run(main())
    warnings = [r.getMessage() for r in caplog.records
                if "quorum 2 refused" in r.getMessage()]
    if case == "one-cut-one-broken":
        assert seen == [1.0, 1.0, 30.0]
        assert isinstance(got, QuorumError) and "1/3 ok" in str(got)
        assert len(warnings) == 1, warnings
        assert warnings[0].startswith(
            "test/spare: quorum 2 refused with 1 answer(s); ")
        assert f"{order[1]} (up, tightened timeout): TimeoutError" \
            in warnings[0]
        assert f"{order[2]} (up, flat timeout): RpcError: " in warnings[0]
        assert "no such row" in warnings[0]
        return
    assert got == 2 and not warnings
    assert seen == {"three-up": [1.0, 1.0], "one-down": [30.0, 30.0],
                    "one-cut-one-answered": [1.0, 1.0, 30.0]}[case]


def test_gather_whose_spares_are_used_up_launches_flat(tmp_path):
    """Fourteen holders up, four of the first ten fail their fetch: the
    first wave is tightened (fourteen up, ten wanted), and by the last
    launch everybody left is needed, so it keeps the flat 60 s."""
    k, m = 10, 4

    async def main():
        box = await ClusterBox(tmp_path, n=k + m, rf=3, erasure=(k, m),
                               block_size=BLOCK).start()
        try:
            reader = box.nodes[0]
            mgr = reader.manager
            data = np.random.default_rng(37).integers(
                0, 256, BLOCK, dtype=np.uint8).tobytes()
            h = await mgr.hash_block(data)
            await mgr.rpc_put_block(h, data)
            placement = shard_nodes_of(
                reader.system.layout_helper.current(), h, k + m)
            await box.wait(
                lambda: box.resync_backlog() == 0 and not any(
                    nd.manager.cache_tier._insert_inflight
                    for nd in box.live()),
                15, "the box quiet")
            for node in placement:
                for _ in range(8):
                    reader.system.peering.health.record_success(node, 0.02)
            victims = [n for n in placement[:k] if n != reader.id][:m]
            chaos = arm(seed=37)
            for v in victims:
                chaos.add(FaultSpec(kind="rpc_error", peer=v.hex()[:16],
                                    endpoint="garage_tpu/block", count=1))
            asked = []
            real = mgr.endpoint.call

            async def call(node, payload, prio, stream=None, timeout=None):
                asked.append((placement.index(node), timeout))
                return await real(node, payload, prio, stream=stream,
                                  timeout=timeout)

            mgr.endpoint.call = call
            before = registry().totals("block_gather_fetches")[0]
            try:
                got = await mgr.rpc_get_block(h, cacheable=False)
            finally:
                disarm()
            return (got == data, asked,
                    registry().totals("block_gather_fetches")[0] - before)
        finally:
            await box.stop()

    same, asked, fetches = run(main())
    assert same and fetches == k + m
    # remote fetches in launch order: (index in the placement, timeout)
    # (tightened is max(1 s, 4 x p99) of the peer: over 1 s where the
    # suite's own load made the put's writes slow)
    first_wave = [t for i, t in asked if i < k]
    assert first_wave and max(first_wave) < 60.0
    assert len(asked) == k + m - 1 and asked[-1][1] == 60.0
