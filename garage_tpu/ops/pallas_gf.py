"""Pallas TPU kernel for GF(2^8) matrix application (RS encode/decode).

The XLA path (gf256.bit_matmul_apply) materializes the 8x bit expansion
in HBM: a 1 MiB block becomes 8 MiB of int8 bit-planes before the
matmul, and the packed result round-trips again — HBM traffic is ~9x
the payload. This kernel fuses unpack -> matmul -> parity -> pack
inside VMEM, so HBM sees only the raw bytes in (k rows) and out
(m rows) per tile.

Layout per grid step (b, s):
  data tile  (k, T) u8   -> bits (8k, T) i8 (bit j of symbol s at row
                            s*8+j, matching gf256.expand_bitmatrix)
  bitmat     (8m, 8k) i8 (constant, VMEM-resident)
  acc        (8m, T) i32 = bitmat @ bits   [MXU]
  parity     (m, T) u8   = pack(acc & 1)

Compiled on TPU only (chip_smoke.py's kernel phase checks it there
against the numpy reference); CPU tests run it in interpreter mode
(tests/test_rs.py). The product launches the XLA path unless
GARAGE_TPU_PALLAS is set (ops/rs._try_pallas).
"""

from __future__ import annotations

import functools

import numpy as np

from . import gf256

LANE_TILE = 2048  # bytes of each shard processed per grid step


def _kernel(mat_ref, x_ref, o_ref, *, k: int, m: int):
    """Mosaic-friendly formulation: no narrow-dtype 3-D intermediates.
    Bit rows are built by concatenating 8 shifted copies along the
    sublane axis (row order j*k + s); the COLUMN permutation that maps
    this order back to the canonical s*8 + j layout is pre-applied to
    the constant matrix on the host (_mat_bits_jk)."""
    import jax
    import jax.numpy as jnp

    x = x_ref[...].astype(jnp.int32)  # (k, T)
    # f32 matmul: sums are <= 8k <= 2048, so f32 is exact. libtpu
    # 0.0.34's Mosaic also compiles this dot_general with bf16 operands
    # (f32 accumulation) and with int8 operands (int32 accumulation),
    # both exact on the chip (PERF.md, PR 21); which operand type the
    # product should launch is ROADMAP S7's question, not settled here
    bits = jnp.concatenate(
        [((x >> j) & 1).astype(jnp.float32) for j in range(8)],
        axis=0)  # (8k, T), row j*k+s
    acc = jax.lax.dot_general(
        mat_ref[...].astype(jnp.float32), bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (8m, T), row i*8 + bit
    t = x.shape[1]
    # pack: weight each bit row by 1 << (row % 8), sum groups of 8 rows
    row_w = jnp.tile(1 << jnp.arange(8, dtype=jnp.int32), m)[:, None]
    weighted = ((acc.astype(jnp.int32) & 1) * row_w).reshape(m, 8, t)
    o_ref[...] = weighted.sum(axis=1).astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _build(k: int, rows: int, shard_len: int, batch: int,
           interpret: bool):
    """Jitted pallas_call applying an (rows x k) GF matrix to
    (batch, k, shard_len) uint8."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    for tile in (LANE_TILE, 1024, 512, 256, 128):
        if tile <= shard_len and shard_len % tile == 0:
            break
    else:
        raise ValueError(f"shard_len {shard_len} has no lane tile")
    grid = (batch, shard_len // tile)

    call = pl.pallas_call(
        functools.partial(_kernel, k=k, m=rows),
        out_shape=jax.ShapeDtypeStruct((batch, rows, shard_len),
                                       jnp.uint8),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * rows, 8 * k), lambda b, s: (0, 0)),
            pl.BlockSpec((None, k, tile), lambda b, s: (b, 0, s)),
        ],
        out_specs=pl.BlockSpec((None, rows, tile), lambda b, s: (b, 0, s)),
        interpret=interpret,
    )

    @jax.jit
    def apply(mat_bits, x):
        return call(mat_bits, x)

    return apply


@functools.lru_cache(maxsize=None)
def _mat_bits_jk(mat_bytes: bytes, rows: int, k: int) -> np.ndarray:
    """expand_bitmatrix with columns permuted from canonical s*8+j to
    the kernel's concatenation order j*k+s."""
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(rows, k)
    exp = gf256.expand_bitmatrix(mat)  # (8r, 8k), col s*8+j
    perm = np.empty(8 * k, dtype=np.int64)
    for j in range(8):
        for s in range(k):
            perm[j * k + s] = s * 8 + j
    return np.ascontiguousarray(exp[:, perm]).astype(np.int8)


def gf_apply(mat: np.ndarray, data, interpret: bool = False):
    """Apply a GF(2^8) matrix (rows, k) to data (B, k, S) uint8 ->
    (B, rows, S) uint8 on device via the fused Pallas kernel."""
    import jax.numpy as jnp

    rows, k = mat.shape
    b, k2, s = data.shape
    if k2 != k:
        raise ValueError(f"matrix {mat.shape} does not match data "
                         f"{data.shape}")
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    mat_bits = jnp.asarray(_mat_bits_jk(mat.tobytes(), rows, k))
    fn = _build(k, rows, s, b, interpret)
    return fn(mat_bits, data)


def encode(k: int, m: int, data, interpret: bool = False):
    """RS parity via the Pallas kernel: (B, k, S) -> (B, m, S)."""
    from . import rs

    return gf_apply(rs.parity_matrix(k, m), data, interpret=interpret)


def available() -> bool:
    """The compiled kernel needs a TPU (elsewhere it only interprets)."""
    from . import jaxenv

    return jaxenv.verdict()["platform"] == "tpu"
