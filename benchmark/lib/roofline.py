"""What a kernel must move and compute, from shapes alone, and the
least time the chip could take for it.

The byte and operation counts are what the *algorithm* needs for the
items it was given (the block as the server packs it, split k ways),
not what a launch happened to pad it to: padding a launch to a bucket
makes the kernel's time per useful byte worse, and the roofline share
is where that shows.

Peaks are the published ones, keyed by `device_kind` as JAX reports
it. A kind that is not in the table is an error, never a default.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture): per chip
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9,
                "source": 'Google Cloud documentation, "TPU v5e"'},
}

PACK_HEADER = 1  # DataBlock.pack: one tag byte before the (raw) payload


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmark/lib/roofline.py with its "
                       f"source") from None


def shard_len(block_bytes: int, k: int) -> int:
    """Bytes per shard of one packed block split k ways."""
    return -(-(block_bytes + PACK_HEADER) // k)


def rs_encode(block_bytes: int, k: int, m: int) -> tuple[int, int]:
    """(HBM bytes, int8 operations) to encode one block: read k data
    shards, write m parity shards; as a GF(2) bit-matmul an (8m x 8k)
    matrix per byte column, a multiply and an add per element."""
    s = shard_len(block_bytes, k)
    return (k + m) * s, 2 * (8 * m) * (8 * k) * s


def rs_decode(block_bytes: int, k: int, m: int) -> tuple[int, int]:
    """(HBM bytes, int8 operations) to decode one block from k
    surviving shards: read k, write the k data shards back."""
    s = shard_len(block_bytes, k)
    return 2 * k * s, 2 * (8 * k) * (8 * k) * s


def blake3(block_bytes: int, k: int = 0, m: int = 0) -> tuple[int, int]:
    """(HBM bytes, operations) to hash one block: read it once, write
    32 bytes. BLAKE3 is 32-bit integer work on the vector unit, for
    which the published table has no peak: the count is 0 and the
    bound is memory, so the share is against the HBM roofline alone."""
    return block_bytes + 32, 0


FUNCTIONS = {"rs_encode": rs_encode, "rs_decode": rs_decode, "blake3": blake3}


def least_seconds(fn: str, items: float, block_bytes: int, k: int, m: int,
                  device_kind: str) -> tuple[float, str]:
    """-> (the least seconds the chip could take for `items` blocks,
    which roof binds: "hbm" or "int8")."""
    pk = peaks(device_kind)
    nbytes, ops = FUNCTIONS[fn](block_bytes, k, m)
    t_mem = items * nbytes / pk["hbm_bytes_per_s"]
    t_ops = items * ops / pk["int8_ops"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "int8")
