"""The block math on the host: one function an op, each of (codec, items).

What the DeviceFeeder runs when a batch's route is the host (mode "off",
no device, a device leg re-run under "auto"), and what the stub device
backend computes its results with. Native kernels (garage_tpu/native)
when they built, numpy otherwise — and never JAX: a host leg may be the
re-run of a device leg that just failed or hung, and `codec.encode`
would re-enter the backend it exists to avoid.

Item formats, the feeder's own:
  hash          bytes
  hash_md5      (md5acc, bytes)
  verify        (hash32, bytes)
  sha256        one message: a buffer or a span list
  encode        packed block bytes
  encode_put    (prefix, data), or an ingest lease
  parity_check  [k data + m parity shard payloads]
  decode        (present, shards, plain_len)
  repair        (present, missing, shards)
"""

from __future__ import annotations

import numpy as np


def verify_matches(digs: list, items: list) -> list[bool]:
    """Per-item content-hash verdicts; one copy of the match rule
    (digest equality, legacy-algo fallback) for the inline fast path,
    the host legs and both device backends alike."""
    from ..utils.data import content_hash_matches

    return [dg == h or content_hash_matches(d, h)
            for dg, (h, d) in zip(digs, items)]


def _native_or_none():
    """The optional native kernel module, or None — one copy of the
    guarded import the legs share."""
    try:
        from .. import native

        if native.available():
            return native
    except Exception:
        # lint: ignore[GL05] native backend optional; numpy path handles it
        pass
    return None


def run(codec, op: str, blobs: list) -> list:
    """One op group on the host -> one result an item, in order."""
    if op == "hash":
        return content_hashes(blobs)
    if op == "hash_md5":
        from .. import native

        return native.b3_md5_many(list(blobs))
    if op == "sha256":
        from ..ops import sha256 as _sha

        return [_sha.sha256_hex_py(b) for b in blobs]
    if op == "verify":
        return verify_matches(content_hashes([b for _, b in blobs]), blobs)
    if op == "encode":
        return encode(codec, blobs)
    if op == "encode_put":
        return encode_put(codec, blobs)
    if op == "parity_check":
        return parity_check(codec, blobs)
    if op == "decode":
        return decode(codec, blobs)
    if op == "repair":
        return repair(codec, blobs)
    raise RuntimeError(f"unknown feeder op {op!r}")


def content_hashes(blobs: list[bytes]) -> list[bytes]:
    from ..utils import data as _data

    if _data._content_algo != "blake3":
        return [_data.content_hash(b) for b in blobs]
    try:
        from .. import native

        if native.available():
            return native.blake3_many(blobs)
    except Exception:
        # lint: ignore[GL05] native backend optional; pure-python fallback follows
        pass
    return [_data.blake3sum(b) for b in blobs]


def encode_put(codec, items: list) -> list[list]:
    """items = [(prefix, data)] or ingest leases (scheme byte + body
    resident in one pool buffer); like `encode` but each part is a
    complete shard payload (pack_shard framing, crc32c). With the
    native kernel this is the PUT hot path: split + parity + crc +
    headers in one GIL-released call a block."""
    from .manager import pack_shard

    try:
        from .. import native

        if native.available():
            from ..ops import rs

            pmat = rs.parity_matrix(codec.k, codec.m)
            out = []
            for it in items:
                if hasattr(it, "stripe"):
                    out.append(native.rs_encode_packed(
                        it.view(), codec.k, codec.m, pmat,
                        prefix=bytes([it.buf[0]])))
                else:
                    out.append(native.rs_encode_packed(
                        it[1], codec.k, codec.m, pmat, prefix=it[0]))
            return out
    except Exception:
        # lint: ignore[GL05] native backend optional; encode() fallback follows
        pass
    # without native: `encode` is the single source of truth, wrapped
    # with pack_shard. Leases materialize here — this fallback is off
    # the perf path, and `encode` wants plain byte blocks.
    blocks = [bytes(it.buf[:it.total_len]) if hasattr(it, "total_len")
              else it[0] + it[1] for it in items]
    return [[pack_shard(pp, len(b)) for pp in parts]
            for b, parts in zip(blocks, encode(codec, blocks))]


def encode(codec, blocks: list[bytes]) -> list[list[bytes]]:
    from ..ops import rs

    try:
        from .. import native

        if native.available():
            out = []
            for b in blocks:
                shards = rs.split_stripe(b, codec.k)
                parity = native.gf_matmul(
                    rs.parity_matrix(codec.k, codec.m), shards)
                out.append([bytes(s) for s in shards]
                           + [bytes(p) for p in parity])
            return out
    except Exception:
        # lint: ignore[GL05] native backend optional; numpy fallback follows
        pass
    # last resort: pure numpy — NEVER codec.encode here, whose JAX
    # path would re-enter the possibly-dead backend a host leg exists
    # to avoid
    out = []
    for b in blocks:
        shards = rs.split_stripe(b, codec.k)
        parity = rs.encode_np(codec.k, codec.m, shards)
        out.append([bytes(s) for s in shards]
                   + [bytes(p) for p in parity])
    return out


def parity_check(codec, stripes: list[list[bytes]]) -> list[bool]:
    """stripes = [[k data + m parity shard payloads]] -> per-stripe
    consistency verdicts: native GF matmul per stripe, numpy as last
    resort — same no-JAX rule as `encode`."""
    from ..ops import rs

    k, m = codec.k, codec.m
    pmat = rs.parity_matrix(k, m)
    native_mod = _native_or_none()
    out = []
    for s in stripes:
        data = np.stack(
            [np.frombuffer(b, dtype=np.uint8) for b in s[:k]])
        parity = (native_mod.gf_matmul(pmat, data)
                  if native_mod is not None
                  else rs.encode_np(k, m, data))
        out.append(all(bytes(parity[j]) == bytes(s[k + j])
                       for j in range(m)))
    return out


def decode(codec, items: list[tuple]) -> list[bytes]:
    """items = [(present, shards, plain_len)] -> packed block bytes per
    item: native GF matmul per stripe, numpy as last resort."""
    from ..ops import rs

    k, m = codec.k, codec.m
    native_mod = _native_or_none()
    out = []
    for present, shards, plain_len in items:
        present = tuple(present)
        st = np.stack([np.frombuffer(s, dtype=np.uint8)
                       for s in shards])
        if all(i < k for i in present):
            data = st  # all-systematic: no math needed
        elif native_mod is not None:
            data = native_mod.gf_matmul(
                rs.decode_matrix(k, m, present), st)
        else:
            data = rs.decode_np(k, m, present, st)
        out.append(rs.join_stripe(data, plain_len))
    return out


def repair(codec, items: list[tuple]) -> list[dict]:
    """items = [(present, missing, shards)] -> {missing_index: payload}
    per item (the resync/scrub rebuild op)."""
    from ..ops import rs

    k, m = codec.k, codec.m
    native_mod = _native_or_none()
    out = []
    for present, missing, shards in items:
        present, missing = tuple(present), tuple(missing)
        st = np.stack([np.frombuffer(s, dtype=np.uint8)
                       for s in shards])
        rows = (native_mod.gf_matmul(
                    rs.repair_matrix(k, m, present, missing), st)
                if native_mod is not None
                else rs.repair_np(k, m, present, missing, st))
        out.append({mi: bytes(rows[j])
                    for j, mi in enumerate(missing)})
    return out
