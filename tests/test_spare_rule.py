"""One rule for when a call may be cut short (ISSUE 36): a call keeps
its caller's flat timeout when no live node could be asked in its
place (`RpcHelper.has_spare`), and is tightened to the peer's observed
latency only while one could. Read at two sites: `try_call_many` and
the block manager's `_gather_parts`, which also names in the node's log
the first error of a gather from a holder that is up.
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
from garage_tpu.utils.error import MissingBlock  # noqa: E402

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


@pytest.mark.parametrize("copies_down", [0, 1])
def test_try_call_many_is_adaptive_only_with_a_copy_to_spare(
        tmp_path, copies_down):
    """Quorum 2 of 3: three copies up, every call sits at its peer's
    adaptive value; one copy down, both live ones are needed and keep
    the strategy's flat timeout."""
    async def main():
        net, systems, tasks = await make_cluster(tmp_path, 3)
        try:
            apply_flat_layout(systems)

            async def h(frm, payload, stream):
                return {}

            for s in systems:
                s.netapp.endpoint("test/spare").set_handler(h)
                for peer in systems:
                    for _ in range(8):
                        s.peering.health.record_success(peer.id, 0.02)
            me = systems[0]
            nodes = [s.id for s in systems]
            if copies_down:
                net.partition(systems[0].id, systems[2].id)
                net.partition(systems[1].id, systems[2].id)
                await _wait(lambda: not me.is_up(systems[2].id), 15)
            helper = RpcHelper(me)
            assert helper.has_spare(nodes, 2) == (not copies_down)
            ep = me.netapp.endpoint("test/spare")
            seen = []
            real = ep.call

            async def call(node, payload, prio, stream=None, timeout=None):
                seen.append(timeout)
                return await real(node, payload, prio, stream=stream,
                                  timeout=timeout)

            ep.call = call
            got = await helper.try_call_many(
                ep, nodes, {}, RequestStrategy(quorum=2, timeout=30.0))
            assert len(got) == 2
            return seen
        finally:
            await stop_cluster(systems, tasks)

    seen = run(main())
    assert seen == [30.0 if copies_down else 1.0] * 2
