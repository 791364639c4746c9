"""Plain reference for an erasure(k, m) block, independent of the served
path AND of the program's own host arithmetic: nothing here imports
`garage_tpu.ops.rs` or `gf256`. The field, the generator, the k-way
split with its zero tail, encode and decode are written out below from
the definition (GF(2^8) modulo x^8+x^4+x^3+x^2+1, systematic generator
= identity over the Cauchy matrix 1 / (i xor (m + j))), with a multiply
table built by shift-and-add instead of exp/log tables and decode by
elimination on the shard rows instead of a matrix inverse. The content
hash is the pure-Python BLAKE3 (`treehash.blake3_py`), the shard file's
checksum zlib's or the pure-Python CRC32C.

    plain block -> content hash, the k+m shard payloads in shard-index
                   order (index i goes to the i-th node `shard_nodes_of`
                   gives), the packed length
    any k shards -> the plain block
    a stored stripe -> its scheme, checked against the plain block

A block stored compressed (scheme byte 2) is checked by decompressing
its frame with `zstandard` itself, so the reference does not depend on
the compressor's exact bytes.
"""

from __future__ import annotations

import functools
import zlib

import numpy as np
import zstandard

from garage_tpu import native
from garage_tpu.ops import treehash

SCHEME_PLAIN = 0  # DataBlock's header byte of an uncompressed block
SCHEME_ZSTD = 2  # ... and of a block stored as a zstd frame
POLY = 0x11D


def _mul_table() -> np.ndarray:
    """MUL[a, b] = a*b in GF(2^8): carry-less multiply, reduced by POLY
    a bit at a time."""
    a = np.arange(256, dtype=np.uint16)[:, None]
    b = np.arange(256, dtype=np.uint16)[None, :]
    out = np.zeros((256, 256), dtype=np.uint16)
    for _ in range(8):
        out ^= np.where(b & 1, a, 0).astype(np.uint16)
        b = b >> 1
        a = a << 1
        a = np.where(a & 0x100, a ^ POLY, a)
    return out.astype(np.uint8)


MUL = _mul_table()
INV = np.zeros(256, dtype=np.uint8)  # INV[0] stays 0 and is never read
INV[1:] = [int(np.nonzero(MUL[a] == 1)[0][0]) for a in range(1, 256)]


@functools.lru_cache(maxsize=None)
def generator(k: int, m: int) -> np.ndarray:
    """(k+m, k): row i < k is the i-th unit row, row k+i holds
    1 / (i xor (m + j)) at column j."""
    g = np.zeros((k + m, k), dtype=np.uint8)
    for i in range(k):
        g[i, i] = 1
    for i in range(m):
        for j in range(k):
            g[k + i, j] = INV[i ^ (m + j)]
    return g


def split(packed: bytes, k: int) -> np.ndarray:
    """The packed block row-major in k rows of ceil(len / k) bytes, the
    last row's tail zero."""
    n = -(-len(packed) // k)
    padded = packed + bytes(k * n - len(packed))
    return np.frombuffer(padded, dtype=np.uint8).reshape(k, n)


def encode(k: int, m: int, data: np.ndarray) -> np.ndarray:
    """data (k, n) -> parity (m, n)."""
    g = generator(k, m)
    parity = np.zeros((m, data.shape[1]), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            parity[i] ^= MUL[g[k + i, j]][data[j]]
    return parity


def decode(k: int, m: int, present, shards: np.ndarray) -> np.ndarray:
    """Solve G[present] . data = shards for data (k, n) by Gauss-Jordan
    elimination carried out on the shard rows themselves."""
    a = generator(k, m)[list(present)].copy()
    rows = np.array(shards, dtype=np.uint8)
    for col in range(k):
        piv = next(r for r in range(col, k) if a[r, col])
        if piv != col:
            a[[col, piv]] = a[[piv, col]]
            rows[[col, piv]] = rows[[piv, col]]
        inv = INV[a[col, col]]
        a[col], rows[col] = MUL[inv][a[col]], MUL[inv][rows[col]]
        for r in range(k):
            c = a[r, col]
            if r != col and c:
                a[r] ^= MUL[c][a[col]]
                rows[r] ^= MUL[c][rows[col]]
    return rows


def reference_stripe(block: bytes, k: int, m: int):
    """-> (content hash, [k+m shard payloads], packed length). The
    stripe is the packed block (scheme byte + body) split row-major k
    ways with a zero tail, parity rows after the data rows."""
    packed = bytes([SCHEME_PLAIN]) + block
    data = split(packed, k)
    rows = np.concatenate([data, encode(k, m, data)])
    return (treehash.blake3_py(block), [r.tobytes() for r in rows],
            len(packed))


def reference_block(present, shards, k: int, m: int,
                    packed_len: int) -> bytes:
    """Any k shard payloads (ascending `present` indices) -> the plain
    block."""
    assert len(present) == len(shards) == k
    rows = np.stack([np.frombuffer(s, dtype=np.uint8) for s in shards])
    packed = decode(k, m, present, rows).reshape(-1)[:packed_len].tobytes()
    return plain_of(packed)


def plain_of(packed: bytes) -> bytes:
    """A packed block (scheme byte + payload) -> the plain block: the
    payload itself, or what zstandard makes of its frame."""
    if packed[0] == SCHEME_ZSTD:
        return zstandard.ZstdDecompressor().decompress(packed[1:])
    assert packed[0] == SCHEME_PLAIN
    return packed[1:]


def check_stored_stripe(block: bytes, payloads: list[bytes], k: int,
                        m: int, packed_len: int) -> int:
    """The k+m shard payloads a PUT of `block` stored, in shard-index
    order: the data shards joined and trimmed to the packed length are
    the scheme byte and a payload that gives `block` back, and the
    parity shards are the reference's encode of those data shards.
    -> the scheme."""
    assert len(payloads) == k + m
    rows = np.stack([np.frombuffer(p, dtype=np.uint8) for p in payloads])
    assert np.array_equal(rows[k:], encode(k, m, rows[:k]))
    packed = rows[:k].reshape(-1)[:packed_len].tobytes()
    assert not rows[:k].reshape(-1)[packed_len:].any()  # the zero tail
    assert plain_of(packed) == block
    return packed[0]


def parse_shard_file(raw: bytes):
    """A shard file as it lies on disk -> (payload, packed length), its
    checksum verified here: 4 bytes of magic naming the CRC flavour, the
    packed length (8, big endian), the CRC (4), the payload."""
    magic, packed_len = raw[:4], int.from_bytes(raw[4:12], "big")
    ck, payload = int.from_bytes(raw[12:16], "big"), raw[16:]
    if magic == b"GTS2":
        assert native.crc32c_py(payload) == ck
    else:
        assert magic == b"GTS3" and zlib.crc32(payload) == ck
    return payload, packed_len
