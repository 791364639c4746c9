"""BLAKE3 tree hashing: pure-Python reference + batched JAX implementation.

The reference hashes every block with sequential blake2
(src/util/data.rs:124-132, verified on every read at
src/block/manager.rs:554-609) — one core, one block at a time. BLAKE3's
chunk tree is the TPU-native choice: a 1 MiB block is 1024 independent
1 KiB chunks (VPU-parallel), merged by a 10-level binary parent tree.
Scrub/verify of a whole batch of blocks becomes one jitted program.

Layout of the JAX path: messages are padded to a static chunk count C;
byte *lengths* stay traced, so one compiled program serves every block
whose size lands in the same chunk count (tail blocks don't recompile).
Batching is lane-major (batch = trailing vector axis, see the section
comment above _compress_lanes): all B*C chunks are lanes of one 16-step
lax.scan over block positions, the parent tree is a second scan with
one step a level over one array of chaining values, and each step of
either runs the 7 rounds as an inner scan. The device program is two
copies of the compression function plus the message-word load, ~500
StableHLO ops for any B and C.

The pure-Python implementation is the test oracle (checked against the
published empty-input vector) and the host fallback for small inputs.
"""

from __future__ import annotations

import functools

import numpy as np

IV = (
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
)

MSG_PERMUTATION = (2, 6, 3, 10, 7, 0, 4, 13, 1, 11, 12, 5, 9, 14, 15, 8)

CHUNK_START = 1 << 0
CHUNK_END = 1 << 1
PARENT = 1 << 2
ROOT = 1 << 3

CHUNK_LEN = 1024
BLOCK_LEN = 64
BLOCKS_PER_CHUNK = CHUNK_LEN // BLOCK_LEN  # 16


@functools.lru_cache(maxsize=None)
def _schedules() -> tuple[tuple[int, ...], ...]:
    """Message-word index schedule per round (permutation pre-applied)."""
    idx = list(range(16))
    out = [tuple(idx)]
    for _ in range(6):
        idx = [idx[p] for p in MSG_PERMUTATION]
        out.append(tuple(idx))
    return tuple(out)


# ---------------------------------------------------------------------------
# Pure-Python reference
# ---------------------------------------------------------------------------

_M32 = 0xFFFFFFFF


def _rotr(x: int, n: int) -> int:
    return ((x >> n) | (x << (32 - n))) & _M32


def _g(v, a, b, c, d, mx, my):
    v[a] = (v[a] + v[b] + mx) & _M32
    v[d] = _rotr(v[d] ^ v[a], 16)
    v[c] = (v[c] + v[d]) & _M32
    v[b] = _rotr(v[b] ^ v[c], 12)
    v[a] = (v[a] + v[b] + my) & _M32
    v[d] = _rotr(v[d] ^ v[a], 8)
    v[c] = (v[c] + v[d]) & _M32
    v[b] = _rotr(v[b] ^ v[c], 7)


def compress_py(h, m, counter: int, block_len: int, flags: int) -> list[int]:
    """One blake3 compression; returns the 8-word chaining value."""
    v = list(h) + list(IV[:4]) + [
        counter & _M32, (counter >> 32) & _M32, block_len, flags,
    ]
    for sched in _schedules():
        _g(v, 0, 4, 8, 12, m[sched[0]], m[sched[1]])
        _g(v, 1, 5, 9, 13, m[sched[2]], m[sched[3]])
        _g(v, 2, 6, 10, 14, m[sched[4]], m[sched[5]])
        _g(v, 3, 7, 11, 15, m[sched[6]], m[sched[7]])
        _g(v, 0, 5, 10, 15, m[sched[8]], m[sched[9]])
        _g(v, 1, 6, 11, 12, m[sched[10]], m[sched[11]])
        _g(v, 2, 7, 8, 13, m[sched[12]], m[sched[13]])
        _g(v, 3, 4, 9, 14, m[sched[14]], m[sched[15]])
    return [v[i] ^ v[i + 8] for i in range(8)]


def _words(block: bytes) -> list[int]:
    block = block.ljust(BLOCK_LEN, b"\x00")
    return [int.from_bytes(block[4 * i : 4 * i + 4], "little") for i in range(16)]


def _chunk_cv_py(chunk: bytes, counter: int, root: bool) -> list[int]:
    n_blocks = max(1, (len(chunk) + BLOCK_LEN - 1) // BLOCK_LEN)
    cv = list(IV)
    for b in range(n_blocks):
        piece = chunk[b * BLOCK_LEN : (b + 1) * BLOCK_LEN]
        flags = (CHUNK_START if b == 0 else 0) | (
            (CHUNK_END | (ROOT if root else 0)) if b == n_blocks - 1 else 0
        )
        cv = compress_py(cv, _words(piece), counter, len(piece), flags)
    return cv


def _parent_cv_py(left, right, root: bool) -> list[int]:
    m = list(left) + list(right)
    return compress_py(list(IV), m, 0, BLOCK_LEN, PARENT | (ROOT if root else 0))


def blake3_py(data: bytes) -> bytes:
    """Reference blake3 (default 32-byte digest)."""
    chunks = [data[i : i + CHUNK_LEN] for i in range(0, len(data), CHUNK_LEN)] or [b""]
    if len(chunks) == 1:
        cv = _chunk_cv_py(chunks[0], 0, root=True)
        return b"".join(w.to_bytes(4, "little") for w in cv)
    cvs = [_chunk_cv_py(c, i, root=False) for i, c in enumerate(chunks)]
    # Pairwise merge with odd tail carried — reproduces the spec tree
    # (left subtree = largest power of two < n) level by level.
    while len(cvs) > 2:
        nxt = [_parent_cv_py(cvs[i], cvs[i + 1], False) for i in range(0, len(cvs) - 1, 2)]
        if len(cvs) % 2:
            nxt.append(cvs[-1])
        cvs = nxt
    root = _parent_cv_py(cvs[0], cvs[1], root=True)
    return b"".join(w.to_bytes(4, "little") for w in root)


# ---------------------------------------------------------------------------
# JAX batched implementation — lane-major
# ---------------------------------------------------------------------------
#
# Batch layout: every independent hash unit (chunk of a row, then parent
# node of a tree level) is a *lane* — the trailing axis of every array,
# lane = row * C + chunk. State is (8, L), messages (16, L). The program
# holds two copies of the compression function, the chunk scan's and the
# tree scan's, whatever B and C are: its size is independent of the
# batch (a vmap formulation made XLA:CPU compile time explode
# superlinearly in B) and grows with log2(C) by two scalars a level. The
# size is what a launch, a trace, a build and a load from the compile
# cache all pay for, so tests/test_treehash.py holds it.


@functools.lru_cache(maxsize=None)
def _round_words() -> np.ndarray:
    """(7, 4, 4) message-word indices: for each round the x words and
    the y words of the four column G's, then of the four diagonal G's."""
    s = np.array(_schedules(), dtype=np.int32)
    return np.stack([s[:, 0:8:2], s[:, 1:8:2], s[:, 8:16:2], s[:, 9:16:2]],
                    axis=1)


def _compress_lanes(h, m, counter, block_len, flags):
    """h (8, L), m (16, L), counter/block_len/flags (L,) or scalar u32
    -> (8, L). All ops lane-vectorized.

    The state is its four row groups a, b, c, d = v[0:4], v[4:8],
    v[8:12], v[12:16], each (4, L). A round is one G on the four
    columns at once, a roll of b, c, d by one, two, three rows, one G on
    the four diagonals, and the roll back. The message is permuted once,
    outside the rounds, and each round takes its sixteen words as the
    scan's xs.

    The 7 rounds run as a lax.scan: unrolled, the ~450 interdependent
    u32 ops send XLA:CPU's backend into multi-minute compiles for any
    lane count >= 4; the scan form compiles in seconds everywhere.
    """
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32
    lanes = h.shape[1]

    def row(x):
        return jnp.broadcast_to(jnp.asarray(x, u32), (lanes,))

    iv = jnp.broadcast_to(jnp.asarray(IV[:4], u32)[:, None], (4, lanes))
    tail = jnp.stack([row(counter), row(0), row(block_len), row(flags)])
    words = m[_round_words()]  # (7, 4, 4, L)

    def rotr(x, n):
        return (x >> u32(n)) | (x << u32(32 - n))

    def g(a, b, c, d, mx, my):
        a = a + b + mx
        d = rotr(d ^ a, 16)
        c = c + d
        b = rotr(b ^ c, 12)
        a = a + b + my
        d = rotr(d ^ a, 8)
        c = c + d
        b = rotr(b ^ c, 7)
        return a, b, c, d

    def round_body(v, mr):
        a, b, c, d = g(*v, mr[0], mr[1])
        b, c, d = jnp.roll(b, -1, 0), jnp.roll(c, -2, 0), jnp.roll(d, -3, 0)
        a, b, c, d = g(a, b, c, d, mr[2], mr[3])
        return (a, jnp.roll(b, 1, 0), jnp.roll(c, 2, 0), jnp.roll(d, 3, 0)), None

    (a, b, c, d), _ = jax.lax.scan(round_body, (h[0:4], h[4:8], iv, tail), words)
    return jnp.concatenate([a ^ c, b ^ d])


def _message_words(msgs):
    """(B, C*1024) u8 -> (16, 16, B*C) u32: block position, word, lane.

    One chunk a row, transposed so that chunks are lanes and the four
    bytes of a word lie four rows apart in one lane. The shape that
    comes to mind first, reshape(b, c, 16, 16, 4) of the u8 array and a
    five-axis transpose, has a minor dimension of 4: on the v5e it took
    the compiler 43-53 s of a 58 s build and was 3 to 10 ms of every
    launch (PERF.md, PR 26).
    """
    import jax.numpy as jnp

    u32 = jnp.uint32
    x = msgs.reshape(-1, CHUNK_LEN).T.astype(u32).reshape(CHUNK_LEN // 4, 4, -1)
    words = x[:, 0] | (x[:, 1] << 8) | (x[:, 2] << 16) | (x[:, 3] << 24)
    return words.reshape(BLOCKS_PER_CHUNK, 16, -1)


def hash_rows(msgs, lengths, n_chunks: int):
    """Traceable batched hash: (B, n_chunks*1024) u8 + (B,) i32 -> (B, 8) u32.

    Precondition (caller-enforced, like hash_batch_jax does): every row's
    length must span exactly n_chunks, i.e. n_chunks_for(length) ==
    n_chunks, and bytes past `length` must be zero — otherwise the digest
    is silently wrong (phantom all-zero chunks enter the tree).

    Composable inside larger jitted programs (parallel/ data-plane steps);
    _hash_fn below is the standalone jitted wrapper. All B*C chunks hash
    as lanes of one 16-step lax.scan over block positions. The parent
    tree is a second scan, one step a level, over one (8, B, 2*W) array
    of chaining values, W = ceil(C/2): a step merges the array's W pairs
    in the lanes of one compression, keeps the parents of the pairs the
    level really has and carries the odd node, so every level has the
    shape of the first and the finished lanes are masked, not sliced off.
    """
    import jax
    import jax.numpy as jnp

    u32 = jnp.uint32
    b = msgs.shape[0]
    c = n_chunks
    n = b * c
    words = _message_words(msgs)

    chunk = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32), (b, c))
    counters = chunk.astype(u32).reshape(n)
    chunk_lens = jnp.clip(
        lengths[:, None] - chunk * CHUNK_LEN, 0, CHUNK_LEN
    ).reshape(n)
    n_blocks = jnp.maximum(1, (chunk_lens + (BLOCK_LEN - 1)) // BLOCK_LEN)

    pos = jnp.arange(BLOCKS_PER_CHUNK, dtype=jnp.int32)[:, None]  # (block, 1)
    block_lens = jnp.clip(
        chunk_lens[None, :] - pos * BLOCK_LEN, 0, BLOCK_LEN
    ).astype(u32)  # (block, B*C)
    is_end = pos == (n_blocks - 1)[None, :]
    flags = (
        jnp.where(pos == 0, u32(CHUNK_START), u32(0))
        | jnp.where(is_end, u32(CHUNK_END | (ROOT if c == 1 else 0)), u32(0))
    )
    active = pos < n_blocks[None, :]

    def step(cv, xs):
        m, blen, flg, act = xs
        new_cv = _compress_lanes(cv, m, counters, blen, flg)
        return jnp.where(act, new_cv, cv), None

    iv = jnp.asarray(IV, dtype=u32)[:, None]
    cv, _ = jax.lax.scan(
        step, jnp.broadcast_to(iv, (8, n)), (words, block_lens, flags, active)
    )  # (8, B*C)

    if c == 1:
        return cv.T  # (B, 8)

    # Parent tree: pairwise merge with the odd tail carried, as in
    # blake3_py. Level sizes c, ceil(c/2), ... , 2; the merge of the
    # last two is the root.
    sizes = [c]
    while sizes[-1] > 2:
        sizes.append((sizes[-1] + 1) // 2)
    w = (c + 1) // 2
    level = cv.reshape(8, b, c)
    if c % 2:
        level = jnp.concatenate([level, jnp.zeros((8, b, 1), u32)], axis=2)
    pair = jnp.arange(w, dtype=jnp.int32)
    spare = jnp.zeros((8, b, w), u32)
    iv_w = jnp.broadcast_to(iv, (8, b * w))

    def merge(level, xs):
        size, flg = xs
        left = level[:, :, 0::2]
        m = jnp.concatenate([left, level[:, :, 1::2]]).reshape(16, b * w)
        parents = _compress_lanes(iv_w, m, 0, BLOCK_LEN, flg).reshape(8, b, w)
        # pairs past size // 2 do not exist at this level: their lanes
        # keep `left`, which at size // 2 is the odd node carried up
        nxt = jnp.where(pair < size // 2, parents, left)
        return jnp.concatenate([nxt, spare], axis=2), None

    level, _ = jax.lax.scan(
        merge, level,
        (jnp.asarray(sizes, jnp.int32),
         jnp.asarray([PARENT | (ROOT if s == 2 else 0) for s in sizes], u32)),
    )
    return level[:, :, 0].T  # (B, 8)


@functools.lru_cache(maxsize=None)
def _hash_fn(n_chunks: int):
    """Jitted (B, n_chunks*1024) u8 + (B,) i32 lengths -> (B, 8) u32."""
    import jax

    return jax.jit(functools.partial(hash_rows, n_chunks=n_chunks))


def hash_fn(n_chunks: int):
    """Public handle on the per-chunk-count jitted hasher: the staged
    device backend (block/device_backend.py) launches it in its compute
    stage and reads the result back in a separate d2h stage, so the two
    can overlap across pipelined batches (hash_batch_jax fuses launch
    and readback, which serializes the pipeline)."""
    return _hash_fn(n_chunks)


def n_chunks_for(length: int) -> int:
    return max(1, (length + CHUNK_LEN - 1) // CHUNK_LEN)


def hash_batch_jax(msgs: np.ndarray, lengths: np.ndarray) -> np.ndarray:
    """msgs (B, C*1024) uint8 zero-padded, lengths (B,) -> (B, 32) uint8.

    All messages must share the chunk count C = msgs.shape[1] // 1024.
    """
    b, padded = msgs.shape
    if padded % CHUNK_LEN:
        raise ValueError(f"padded length {padded} not a chunk multiple")
    lengths = np.asarray(lengths, dtype=np.int32)
    c = padded // CHUNK_LEN
    if any(n_chunks_for(int(n)) != c for n in lengths):
        raise ValueError(f"all lengths must span exactly {c} chunks")
    cvs = _hash_fn(c)(msgs, lengths)
    # ascontiguousarray: device transfers can return a transposed layout
    # whose last axis is not contiguous, which .view(uint8) rejects
    out = np.ascontiguousarray(np.asarray(cvs).astype("<u4"))
    return out.view(np.uint8).reshape(b, 32)


def blake3_many(blobs: list[bytes]) -> list[bytes]:
    """Hash many byte strings, batching same-chunk-count groups on device."""
    out: list[bytes | None] = [None] * len(blobs)
    groups: dict[int, list[int]] = {}
    for i, blob in enumerate(blobs):
        groups.setdefault(n_chunks_for(len(blob)), []).append(i)
    for n_chunks, idxs in groups.items():
        padded = n_chunks * CHUNK_LEN
        buf = np.zeros((len(idxs), padded), dtype=np.uint8)
        lengths = np.empty(len(idxs), dtype=np.int32)
        for row, i in enumerate(idxs):
            arr = np.frombuffer(blobs[i], dtype=np.uint8)
            buf[row, : arr.size] = arr
            lengths[row] = arr.size
        digests = hash_batch_jax(buf, lengths)
        for row, i in enumerate(idxs):
            out[i] = digests[row].tobytes()
    return out  # type: ignore[return-value]


def blake3(data: bytes) -> bytes:
    """Single-input convenience (host reference path)."""
    return blake3_py(data)
