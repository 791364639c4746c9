"""ops — the TPU data plane.

This package is the reason this framework exists: the reference (Garage,
Rust) does all block math — content hashing (src/util/data.rs:124), zstd
compression (src/block/block.rs:85), and has NO erasure coding at all — on
CPU, one block at a time. Here the block data path is batched math on TPU:

  gf256.py    GF(2^8) arithmetic + the GF(2) bit-matrix formulation that
              turns erasure coding into int8 matmuls on the MXU
  rs.py       Cauchy-Reed-Solomon (k, m) codec: encode / decode / repair,
              batched over stripes (the `erasure(k,m)` replication mode
              the north star adds next to the reference's replicate-N,
              plugged in at src/rpc/replication_mode.rs:8)
  treehash.py BLAKE3 tree hashing in JAX: 1 MiB block = 1024 chunks
              compressed in parallel on the VPU (replaces the reference's
              sequential blake2 block hash, src/block/manager.rs:554)
  sha256.py   batched SHA-256 of SigV4 chunk payloads, lanes across
              concurrent PUT streams
  pallas_gf.py fused Pallas TPU kernel for GF(2^8) matrix application:
              unpack -> MXU matmul -> pack entirely in VMEM, so HBM sees
              only the raw bytes (the XLA path's 8x bit expansion stays
              on chip); opt-in via GARAGE_TPU_PALLAS, the XLA path is
              the default
  jaxenv.py   the process's device verdict, compile-cache placement and
              compile counters
"""
