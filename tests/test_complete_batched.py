"""CompleteMultipartUpload writes the final version's block refs as one
batched quorum write (ISSUE 28): `block_ref_table.insert_many`, one RPC
a holder, where it used to await one `insert` a block.

Held here to the serial path as its plain reference: the rows that
`insert`, called a block at a time by the test, leaves on every node.
Over erasure(10,4) on fourteen nodes, erasure(4,2) on six and
replicate-3 on three, on the box of test_ec104.py (20,000-byte blocks,
S3 through real frontends). The block_ref table is written to every
shard holder of a block with the metadata write quorum of 2, so its
write set is 14, 6 or 3 wide.
"""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_ec104 import BUCKET, ZONES, Box, parts_of, run  # noqa: E402

from garage_tpu.chaos import FaultSpec, arm, disarm  # noqa: E402
from garage_tpu.model.s3.block_ref_table import BlockRef  # noqa: E402
from garage_tpu.utils.data import gen_uuid  # noqa: E402

MODES = {
    "erasure-10-4": dict(n=14, erasure=(10, 4), zones=ZONES),
    "erasure-4-2": dict(n=6, erasure=(4, 2),
                        zones=["dc1", "dc2", "dc3"] * 2),
    "replicate-3": dict(n=3, erasure=None, zones=None),
}
BLOCKS = 9  # of parts_of(): three parts of three blocks


def mode_box(tmp_path, mode: str) -> Box:
    return Box(tmp_path, **MODES[mode])


async def final_version(b: Box, name: str):
    """-> (uuid of the object's completed version, its block hashes in
    (part, offset) order)."""
    g = b.box.nodes[0].garage
    obj = await g.object_table.get(b.bucket_id, name.encode())
    uuid = obj.versions[-1].uuid
    version = await g.version_table.get(uuid, b"")
    return uuid, [h for _k, (h, _s) in version.blocks.items()]


def ref_rows(b: Box, version: bytes) -> dict[int, list]:
    """node index -> the block_ref rows it stores for `version`, as
    sorted (block hash, deleted), over the live nodes."""
    out = {}
    for i, nd in enumerate(b.box.nodes):
        if not nd.alive:
            continue
        data = nd.garage.block_ref_table.data
        rows = [data.decode_stored(raw) for _k, raw in data.store.iter()]
        out[i] = sorted((e.block, e.deleted.value) for e in rows
                        if e.version == version)
    return out


async def wait_for_rows(b: Box, hashes: list[bytes], *versions: bytes):
    """Every live node stores a live ref of every block for each of
    `versions`: the stragglers of a quorum write finish behind it."""
    want = sorted((h, False) for h in hashes)
    await b.box.wait(
        lambda: all(rows == want for v in versions
                    for rows in ref_rows(b, v).values()),
        20, "every live holder has every ref")


class RefWrites:
    """The block_ref RPCs node 0 sends that carry a live ref (the
    tombstones of the part versions' refs, which follow a Complete, are
    not its writes): (destination, live entries) a call."""

    def __init__(self, b: Box):
        self.table = b.box.nodes[0].garage.block_ref_table
        self.calls: list[tuple[bytes, int]] = []

    def __enter__(self) -> "RefWrites":
        ep, self._call = self.table.endpoint, self.table.endpoint.call

        async def counted(node, payload, *a, **kw):
            if isinstance(payload, dict) and payload.get("op") == "update":
                live = sum(
                    1 for raw in payload["entries"]
                    if not self.table.schema.decode_entry(raw).is_tombstone())
                if live:
                    self.calls.append((node, live))
            return await self._call(node, payload, *a, **kw)

        ep.call = counted
        return self

    def __exit__(self, *exc) -> None:
        del self.table.endpoint.call  # the class's method again


@pytest.mark.parametrize("mode", sorted(MODES))
def test_rows_equal_the_serial_path_with_one_rpc_a_holder(tmp_path, mode):
    """After Complete every node stores, for the final version, the rows
    that one `insert` a block gives for a version of the same blocks;
    the object reads back; and the Complete sent each holder ONE
    block_ref RPC carrying all of its refs, where the serial path sends
    one a block a holder."""
    parts = parts_of(2800)
    width = MODES[mode]["n"]  # every node of these boxes holds a shard

    async def main():
        async with mode_box(tmp_path, mode) as b:
            upload_id, etags = await b.begin(0, "o", parts)
            with RefWrites(b) as batched:
                st, body = await b.complete(0, "o", upload_id, etags)
            assert st == 200, body
            uuid, hashes = await final_version(b, "o")
            assert len(hashes) == len(set(hashes)) == BLOCKS
            # the plain reference: the same refs, one insert at a time
            table = b.box.nodes[0].garage.block_ref_table
            serial_uuid = gen_uuid()
            with RefWrites(b) as serial:
                for h in hashes:
                    await table.insert(BlockRef.new(h, serial_uuid))
            await wait_for_rows(b, hashes, uuid, serial_uuid)
            assert ref_rows(b, uuid) == ref_rows(b, serial_uuid)
            assert len(ref_rows(b, uuid)) == width
            # one RPC a holder, each with all nine refs
            assert sorted(batched.calls) == sorted(
                (nd.id, BLOCKS) for nd in b.box.nodes)
            assert len(serial.calls) == BLOCKS * width
            for node in (0, 1):
                st, _, got = await b.request(node, "GET", f"/{BUCKET}/o")
                assert st == 200 and got == b"".join(parts)

    run(main())


@pytest.mark.parametrize("mode", sorted(MODES))
def test_a_holder_down_and_a_missed_quorum(tmp_path, mode):
    """Same quorum, same failure as the serial path. With one holder
    down the ref write still has its quorum and the Complete is
    acknowledged. When the ref write misses its quorum (every block_ref
    RPC fails: with a quorum of 2 of 14 no two crashes do that on
    erasure(10,4)) the Complete answers 503, the object is not visible,
    and the same Complete asked again once the RPCs go through succeeds
    and leaves the same rows."""
    parts = parts_of(2810)
    whole = b"".join(parts)

    async def main():
        async with mode_box(tmp_path, mode) as b:
            first = await b.begin(0, "a", parts)
            second = await b.begin(0, "c", parts_of(2820))
            await b.stop_nodes([2])
            st, body = await b.complete(0, "a", *first)
            assert st == 200, body
            uuid, hashes = await final_version(b, "a")
            await wait_for_rows(b, hashes, uuid)
            st, _, got = await b.request(0, "GET", f"/{BUCKET}/a")
            assert st == 200 and got == whole

            path = b.box.nodes[0].garage.block_ref_table.endpoint.path
            arm(seed=28).add(FaultSpec(kind="rpc_error", endpoint=path))
            try:
                st, body = await b.complete(0, "c", *second)
            finally:
                disarm()
            assert st == 503, (st, body)
            st, _, _ = await b.request(0, "GET", f"/{BUCKET}/c")
            assert st == 404  # not completed, not visible
            st, body = await b.complete(0, "c", *second)
            assert st == 200, body
            uuid, hashes = await final_version(b, "c")
            assert len(hashes) == BLOCKS
            await wait_for_rows(b, hashes, uuid)
            st, _, got = await b.request(0, "GET", f"/{BUCKET}/c")
            assert st == 200 and got == b"".join(parts_of(2820))

    run(main())


def test_two_of_three_down_then_one_back(tmp_path):
    """replicate-3, where a ref's partition has three holders: with two
    of them down the Complete answers an error, and after one returns
    the same Complete succeeds."""
    parts = parts_of(2830)

    async def main():
        async with mode_box(tmp_path, "replicate-3") as b:
            upload = await b.begin(0, "o", parts)
            await b.stop_nodes([1, 2])
            st, body = await b.complete(0, "o", *upload)
            assert st >= 500, (st, body)
            await b.box.restart_node(b.box.nodes[1])
            await b.box.wait(
                lambda: len(b.box.nodes[0].garage.netapp.conns) == 1,
                20, "node 1 is back")
            st, body = await b.complete(0, "o", *upload)
            assert st == 200, body
            st, _, got = await b.request(0, "GET", f"/{BUCKET}/o")
            assert st == 200 and got == b"".join(parts)

    run(main())
