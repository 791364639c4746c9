"""BlockCodec: the replicate-N / erasure(k,m) plugin boundary.

This is the north-star extension point (SURVEY.md §2.11 item 8,
BASELINE.md): the reference only replicates whole blocks
(rpc/replication_mode.rs); here the block data path is generic over a
codec that turns one block into `width` placed parts and back.

- ReplicateCodec(n): every part IS the whole block (the reference's
  behavior); any 1 part reconstructs.
- ErasureCodec(k, m): parts are Reed-Solomon GF(2^8) shards computed by
  the TPU data plane (ops/rs.py — Cauchy matrix, bit-matmul
  formulation); any k of k+m reconstruct. Writes are durable once
  `write_quorum` parts land; scrub can verify parity instead of
  re-reading every replica.

Shard placement uses the ring: part i of a block in partition p goes to
the i-th distinct node walking the ring from p (`shard_nodes_of`) — so
erasure width may exceed the metadata replication factor.
"""

from __future__ import annotations

import numpy as np

from ..ops import rs
from ..utils.data import content_hash_matches
from ..utils.error import MissingBlock


class BlockCodec:
    """width parts per block; any `read_need` reconstruct."""

    width: int
    read_need: int
    write_quorum: int

    def encode(self, data: bytes) -> list[bytes]:
        raise NotImplementedError

    def decode(self, parts: dict[int, bytes], plain_len: int) -> bytes:
        """parts: {part_index: bytes}, at least read_need of them."""
        raise NotImplementedError

    def parity_ok(self, parts: dict[int, bytes], hash32: bytes) -> bool:
        """Scrub check: do these parts reconstruct the block?"""
        raise NotImplementedError


class ReplicateCodec(BlockCodec):
    def __init__(self, n: int, write_quorum: int | None = None):
        self.width = n
        self.read_need = 1
        self.write_quorum = write_quorum if write_quorum is not None \
            else max(1, n // 2 + 1)

    def encode(self, data: bytes) -> list[bytes]:
        return [data] * self.width

    def decode(self, parts, plain_len):
        for _, b in sorted(parts.items()):
            return b
        raise MissingBlock(b"")

    def parity_ok(self, parts, hash32):
        return any(content_hash_matches(b, hash32) for b in parts.values())


class ErasureCodec(BlockCodec):
    """RS(k, m) striping; the math runs through ops/rs (jax on TPU,
    numpy fallback for tiny/offline use)."""

    def __init__(self, k: int, m: int, write_quorum: int | None = None,
                 use_jax: bool | None = None):
        self.k, self.m = k, m
        self.width = k + m
        self.read_need = k
        # durable-against-m-failures default (replication_mode.py):
        self.write_quorum = write_quorum if write_quorum is not None \
            else min(k + (m + 1) // 2, k + m)
        self._use_jax = use_jax

    def _jax_ok(self) -> bool:
        if self._use_jax is None:
            try:
                import jax  # noqa: F401

                self._use_jax = True
            except Exception:
                self._use_jax = False
        return self._use_jax

    def encode(self, data: bytes) -> list[bytes]:
        shards = rs.split_stripe(data, self.k)  # (k, slen) uint8, padded
        if self._jax_ok():
            parity = np.asarray(rs.encode(self.k, self.m, shards[None])[0])
        else:
            parity = rs.encode_np(self.k, self.m, shards)
        return [bytes(s) for s in shards] + [bytes(p) for p in parity]

    def encode_batch(self, blocks: list[bytes]) -> list[list[bytes]]:
        """Batched TPU path: encode many equal-ish blocks in one XLA
        call (pads to the longest; the per-part framing keeps true
        lengths). This is where MXU batching pays (BASELINE.md)."""
        if not blocks:
            return []
        slens = [rs.shard_len(len(b), self.k) for b in blocks]
        smax = max(slens)
        batch = np.zeros((len(blocks), self.k, smax), dtype=np.uint8)
        for i, b in enumerate(blocks):
            sh = rs.split_stripe(b, self.k)
            batch[i, :, : sh.shape[1]] = sh
        if self._jax_ok():
            parity = np.asarray(rs.encode(self.k, self.m, batch))
        else:
            parity = np.stack(
                [rs.encode_np(self.k, self.m, batch[i]) for i in range(len(blocks))]
            )
        out = []
        for i, b in enumerate(blocks):
            sl = slens[i]
            out.append(
                [bytes(batch[i, j, :sl]) for j in range(self.k)]
                + [bytes(parity[i, j, :sl]) for j in range(self.m)]
            )
        return out

    def decode(self, parts: dict[int, bytes], plain_len: int) -> bytes:
        """HOST-ONLY single-stripe decode (numpy). The device route is
        the feeder's batched `decode` op (BlockManager._decode_parts):
        a synchronous per-block device round-trip here would block the
        CALLER's thread on the device — and the old `_jax_ok` branch
        also jitted one XLA program per erasure pattern (the unbounded
        `dec{k},{m},{present}` cache). Callers that can batch go
        through the feeder; everyone else gets the numpy path."""
        if len(parts) < self.k:
            raise MissingBlock(b"")
        idx = tuple(sorted(parts.keys())[: self.k])
        shards = np.stack(
            [np.frombuffer(parts[i], dtype=np.uint8) for i in idx]
        )
        if all(i < self.k for i in idx):
            data = shards  # all-systematic fast path: no math needed
        else:
            data = rs.decode_np(self.k, self.m, idx, shards)
        return rs.join_stripe(data, plain_len)

    def repair_parts(self, parts: dict[int, bytes],
                     missing: tuple[int, ...]) -> dict[int, bytes]:
        """Recompute lost shards from any k present ones. Host-only,
        one precomposed repair-matrix matmul per stripe (same rule as
        decode: the batched device route is feeder.repair)."""
        idx = tuple(sorted(parts.keys())[: self.k])
        shards = np.stack(
            [np.frombuffer(parts[i], dtype=np.uint8) for i in idx]
        )
        out = rs.repair_np(self.k, self.m, idx, tuple(missing), shards)
        return {mi: bytes(out[j]) for j, mi in enumerate(missing)}

    def parity_ok(self, parts: dict[int, bytes], hash32: bytes) -> bool:
        """All width parts present and mutually consistent: systematic
        shards re-encode to the stored parity."""
        if len(parts) < self.k:
            return False
        try:
            data = np.stack(
                [np.frombuffer(parts[i], dtype=np.uint8) for i in range(self.k)]
            )
        except KeyError:
            # missing a systematic shard: decode then compare what exists
            try:
                idx = tuple(sorted(parts.keys())[: self.k])
                shards = np.stack(
                    [np.frombuffer(parts[i], dtype=np.uint8) for i in idx]
                )
                data = rs.decode_np(self.k, self.m, idx, shards)
            except Exception:
                return False
        parity = rs.encode_np(self.k, self.m, data)
        for i, p in parts.items():
            if i >= self.k and bytes(parity[i - self.k]) != p:
                return False
            if i < self.k and bytes(data[i]) != p:
                return False
        return True


def shard_nodes_of(layout_version, hash32: bytes, width: int) -> list[bytes]:
    """`width` distinct nodes for a block's parts: the ring nodes of its
    partition, then of successive partitions, dedup'd, in order. For
    replicate-n this equals nodes_of (width == rf). Deterministic given
    a layout version, so every node computes the same placement."""
    from ..rpc.layout.version import N_PARTITIONS, partition_of

    p0 = partition_of(hash32)
    out: list[bytes] = []
    for off in range(N_PARTITIONS):
        for n in layout_version.nodes_of((p0 + off) % N_PARTITIONS):
            if n not in out:
                out.append(n)
                if len(out) == width:
                    return out
    return out  # cluster smaller than width: best effort
