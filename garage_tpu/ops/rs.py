"""Cauchy-Reed-Solomon (k, m) erasure codec, batched on TPU.

Construction: systematic generator G (n x k, n = k + m) = [I_k ; C] with
C the m x k Cauchy matrix C[i, j] = 1 / (x_i + y_j), x_i = i,
y_j = m + j over GF(2^8). Every square submatrix of a Cauchy matrix is
nonsingular, so any k of the n shards reconstruct the stripe (MDS).

Shapes: a *stripe* is (k, shard_len) bytes of data producing (m,
shard_len) parity; all ops take arbitrary leading batch dims so a whole
batch of 1-16 MiB blocks is one MXU matmul (see gf256.bit_matmul_apply).
Decode/repair matrices depend on *which* shards survive; they are built
host-side per erasure pattern (k x k inversion, microseconds) and
cached — but on device they travel as DATA (gf_apply_batched /
gf256.bit_matmul_apply_batched), so one compiled XLA program serves
every pattern; only the encode/parity constants are baked into traces.

This is the math behind the `erasure(k, m)` replication mode — the north
star's addition at the reference's plugin boundary
(src/rpc/replication_mode.rs:8-20, which only offers replicate-N).
"""

from __future__ import annotations

import functools

import numpy as np

from . import gf256


@functools.lru_cache(maxsize=None)
def generator_matrix(k: int, m: int) -> np.ndarray:
    """(k+m, k) systematic generator over GF(2^8): identity over Cauchy."""
    if k < 1 or m < 0 or k + m > 256:
        raise ValueError(f"need 1 <= k, 0 <= m, k+m <= 256; got k={k} m={m}")
    x = np.arange(m, dtype=np.uint8)[:, None]  # parity row ids
    y = np.arange(m, m + k, dtype=np.uint8)[None, :]  # data col ids
    cauchy = gf256.gf_inv(x ^ y)
    return np.concatenate([np.eye(k, dtype=np.uint8), cauchy], axis=0)


@functools.lru_cache(maxsize=None)
def parity_matrix(k: int, m: int) -> np.ndarray:
    """(m, k) Cauchy part of the generator."""
    return np.ascontiguousarray(generator_matrix(k, m)[k:])


@functools.lru_cache(maxsize=None)
def decode_matrix(k: int, m: int, present: tuple[int, ...]) -> np.ndarray:
    """(k, k) matrix mapping k surviving shards (rows `present` of G,
    ascending) back to the k data shards."""
    if len(present) != k:
        raise ValueError(f"need exactly k={k} shard indices, got {len(present)}")
    sub = generator_matrix(k, m)[list(present)]
    return gf256.gf_inv_matrix(sub)


@functools.lru_cache(maxsize=None)
def repair_matrix(
    k: int, m: int, present: tuple[int, ...], missing: tuple[int, ...]
) -> np.ndarray:
    """(len(missing), k) matrix rebuilding the `missing` shards directly
    from the k `present` ones (data and parity alike)."""
    g = generator_matrix(k, m)
    return gf256.gf_matmul(g[list(missing)], decode_matrix(k, m, present))


@functools.lru_cache(maxsize=None)
def decode_bitmat_t(k: int, m: int, present: tuple[int, ...]) -> np.ndarray:
    """(8k, 8k) int8 transposed bit-expansion of decode_matrix — the
    per-item DATA operand of the pattern-as-data batched kernel
    (gf_apply_batched). Host-side and lru-cached like the matrix
    itself: the inversion plus expansion is microseconds, and caching
    keys on the pattern tuple so a busy mixed-pattern read path builds
    each expansion once."""
    return gf256.bitmat_t_for(decode_matrix(k, m, present))


@functools.lru_cache(maxsize=None)
def repair_bitmat_t(k: int, m: int, present: tuple[int, ...],
                    missing: tuple[int, ...]) -> np.ndarray:
    """(8k, 8·len(missing)) int8 transposed bit-expansion of
    repair_matrix, for the batched repair launch."""
    return gf256.bitmat_t_for(repair_matrix(k, m, present, missing))


# ---------------------------------------------------------------------------
# Device (JAX) paths — jitted per (k, m[, pattern]); batched over stripes
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _jit_apply(key, matrix_bytes, rows: int, cols: int):
    """One jitted bit-matmul per distinct GF matrix. `key` keeps cache
    entries readable; the matrix travels as bytes to stay hashable."""
    import jax

    mat = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(rows, cols)
    bitmat_t = gf256.bitmat_t_for(mat)

    @jax.jit
    def apply(x):
        return gf256.bit_matmul_apply(bitmat_t, x)

    return apply


def _try_pallas(mat: np.ndarray, x):
    """Fused Pallas kernel (ops/pallas_gf.py), opt-in via
    GARAGE_TPU_PALLAS=1 on a TPU; -> None where it does not apply
    (flag unset, another platform, a shard length it cannot tile) and
    the XLA bit-matmul runs instead. Once it applies, a kernel that
    fails to compile or run raises: whoever asked for the Pallas path
    must not be handed an XLA result under its name."""
    import os

    if not os.environ.get("GARAGE_TPU_PALLAS"):
        return None
    shape = getattr(x, "shape", ())
    if len(shape) < 2:
        return None
    n = shape[-1]
    if n % 256 or n < 256:
        return None
    from . import pallas_gf

    if not pallas_gf.available():
        return None
    x3 = x.reshape((-1,) + tuple(shape[-2:]))
    out = pallas_gf.gf_apply(mat, x3)
    return out.reshape(tuple(shape[:-2]) + (mat.shape[0], n))


def _apply(tag: str, mat: np.ndarray, x):
    out = _try_pallas(mat, x)
    if out is not None:
        return out
    fn = _jit_apply((tag, mat.shape), mat.tobytes(), *mat.shape)
    return fn(x)


@functools.lru_cache(maxsize=None)
def _jit_gf_apply_batched():
    """THE pattern-as-data kernel: one jitted batched GF apply for all
    erasure patterns. The per-item bit-matrices are a tensor operand,
    so jit keys on SHAPES only — (batch bucket, k, rows, shard-len
    bucket) — never on which shards survived. One compiled program per
    shape serves every present-set; the feeder's pad-bucket ladder
    keeps the shape set finite."""
    import jax

    @jax.jit
    def apply(bitmats_t, x):
        return gf256.bit_matmul_apply_batched(bitmats_t, x)

    return apply


def gf_apply_batched(bitmats_t, shards):
    """Per-stripe GF maps, batched: bitmats_t (B, 8s, 8r) int8 +
    shards (B, s, n) uint8 -> (B, r, n) uint8 on device."""
    return _jit_gf_apply_batched()(bitmats_t, shards)


def _apply_pattern(bitmat_t: np.ndarray, x):
    """Apply ONE pattern's bit-matrix to a (..., s, n) batch through
    the pattern-as-data kernel (matrix broadcast over the batch). The
    predecessor jitted per pattern (`f"dec{k},{m},{present}"` keys):
    every distinct erasure pattern grew the jit cache and paid a fresh
    XLA compile — unbounded across C(k+m, k) patterns."""
    shape = tuple(x.shape)
    x3 = x.reshape((-1,) + shape[-2:])
    mats = np.ascontiguousarray(
        np.broadcast_to(bitmat_t, (x3.shape[0],) + bitmat_t.shape))
    out = gf_apply_batched(mats, x3)
    return out.reshape(shape[:-2] + tuple(out.shape[-2:]))


def encode(k: int, m: int, data):
    """data (..., k, n) uint8 -> parity (..., m, n) uint8 on device."""
    return _apply(f"enc{k},{m}", parity_matrix(k, m), data)


def decode(k: int, m: int, present: tuple[int, ...], shards):
    """shards (..., k, n) = surviving shard rows in ascending-index order
    -> data (..., k, n). Pattern-as-data: every present-set shares one
    compiled program per shape (the constant-matrix form leaked a jit
    cache entry + compile per pattern)."""
    return _apply_pattern(decode_bitmat_t(k, m, tuple(present)), shards)


def repair(k: int, m: int, present: tuple[int, ...], missing: tuple[int, ...], shards):
    """shards (..., k, n) -> rebuilt missing shards (..., len(missing), n).
    Pattern-as-data like decode."""
    return _apply_pattern(
        repair_bitmat_t(k, m, tuple(present), tuple(missing)), shards)


@functools.lru_cache(maxsize=None)
def _jit_parity_check(k: int, m: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chk(stripes):
        p2 = encode(k, m, stripes[:, :k, :])
        return jnp.all(p2 == stripes[:, k:, :], axis=(1, 2))

    return chk


def parity_check(k: int, m: int, stripes):
    """stripes (B, k+m, n) uint8 -> (B,) bool: stored parity equals
    parity re-derived from the data shards — ONE fused device pass (the
    scrub detect kernel). A corrupt *data* shard flips every re-derived
    parity row (each parity is a function of all k data shards); a
    corrupt *parity* row differs only in itself — either way at least
    one row mismatches, so any single corruption is detected, but
    localization needs the decode sweep in repair.py. Zero-padding
    stripes to a common n is safe: the code is linear, so zero data
    rows encode to zero parity rows."""
    return _jit_parity_check(k, m)(stripes)


# ---------------------------------------------------------------------------
# Host (numpy) reference + small-input fallback
# ---------------------------------------------------------------------------


def encode_np(k: int, m: int, data: np.ndarray) -> np.ndarray:
    """Table-lookup reference: data (k, n) -> parity (m, n)."""
    return gf256.gf_matmul(parity_matrix(k, m), np.asarray(data, dtype=np.uint8))


def decode_np(k: int, m: int, present: tuple[int, ...], shards: np.ndarray) -> np.ndarray:
    return gf256.gf_matmul(decode_matrix(k, m, present), np.asarray(shards, dtype=np.uint8))


def repair_np(k: int, m: int, present: tuple[int, ...],
              missing: tuple[int, ...], shards: np.ndarray) -> np.ndarray:
    """Host reference: rebuild the `missing` rows directly from the k
    `present` ones (one matmul by the precomposed repair matrix)."""
    return gf256.gf_matmul(repair_matrix(k, m, present, missing),
                           np.asarray(shards, dtype=np.uint8))


# ---------------------------------------------------------------------------
# Stripe layout helpers (byte-level, host)
# ---------------------------------------------------------------------------


def shard_len(block_len: int, k: int) -> int:
    return (block_len + k - 1) // k


def split_stripe(data: bytes, k: int) -> np.ndarray:
    """bytes -> (k, shard_len) uint8, zero-padded. Original length is
    metadata the block layer stores alongside (block/codec.py)."""
    n = shard_len(len(data), k)
    buf = np.zeros(k * n, dtype=np.uint8)
    buf[: len(data)] = np.frombuffer(data, dtype=np.uint8)
    return buf.reshape(k, n)


def join_stripe(shards: np.ndarray, block_len: int) -> bytes:
    return np.asarray(shards, dtype=np.uint8).reshape(-1)[:block_len].tobytes()
