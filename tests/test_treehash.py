"""BLAKE3 tree hash tests (ops/treehash.py)."""

import numpy as np
import pytest

from garage_tpu.ops import treehash

# Published blake3 test vector: hash of the empty input.
EMPTY_B3 = "af1349b9f5f9a1a6a0404dea36dcc9499bcb25c9adc112b7cc9a93cae41f3262"


def vector_input(n: int) -> bytes:
    """The official blake3 test-vector input pattern: bytes i % 251."""
    return bytes(i % 251 for i in range(n))


class TestPythonReference:
    def test_empty_vector(self):
        assert treehash.blake3_py(b"").hex() == EMPTY_B3

    def test_deterministic_and_distinct(self):
        a = treehash.blake3_py(b"hello")
        assert a == treehash.blake3_py(b"hello")
        assert a != treehash.blake3_py(b"hellp")
        assert len(a) == 32

    def test_chunk_boundaries_distinct(self):
        # Different lengths straddling chunk/block boundaries all distinct
        seen = set()
        for n in (0, 1, 63, 64, 65, 1023, 1024, 1025, 2048, 3072):
            seen.add(treehash.blake3_py(vector_input(n)))
        assert len(seen) == 10


class TestJaxMatchesReference:
    @pytest.mark.parametrize(
        "n",
        [0, 1, 31, 64, 65, 128, 1023, 1024, 1025, 2047, 2048, 2049,
         3 * 1024, 5 * 1024 + 7, 8 * 1024, 16 * 1024 + 1],
    )
    def test_lengths(self, n):
        data = vector_input(n)
        got = treehash.blake3_many([data])[0]
        assert got.hex() == treehash.blake3_py(data).hex(), f"len={n}"

    def test_batch_mixed_lengths(self):
        blobs = [vector_input(n) for n in (0, 10, 1024, 1500, 1500, 4096, 100)]
        got = treehash.blake3_many(blobs)
        want = [treehash.blake3_py(b) for b in blobs]
        assert [g.hex() for g in got] == [w.hex() for w in want]

    def test_batch_same_chunkcount_shares_program(self):
        # 1500 and 2000 bytes are both 2 chunks — one device call
        before = treehash._hash_fn.cache_info().currsize
        treehash.blake3_many([vector_input(1500), vector_input(2000)])
        after = treehash._hash_fn.cache_info().currsize
        assert after <= before + 1

    def test_hash_batch_jax_shape(self):
        msgs = np.zeros((3, 2048), dtype=np.uint8)
        out = treehash.hash_batch_jax(msgs, np.array([1025, 1500, 2048]))
        assert out.shape == (3, 32)
        assert out[2].tobytes().hex() == treehash.blake3_py(bytes(2048)).hex()

    def test_hash_batch_jax_rejects_wrong_chunk_count(self):
        msgs = np.zeros((1, 2048), dtype=np.uint8)
        with pytest.raises(ValueError):
            treehash.hash_batch_jax(msgs, np.array([0]))


def _ragged_batch(c: int, b: int):
    """(b, c*1024) zero-padded rows whose lengths all span exactly c
    chunks: row 0 full, the others cut anywhere inside the last chunk
    (inside the only chunk, down to empty, when c == 1)."""
    rng = np.random.default_rng(c * 31 + b)
    lo = (c - 1) * treehash.CHUNK_LEN + 1 if c > 1 else 0
    lengths = rng.integers(lo, c * treehash.CHUNK_LEN + 1, size=b)
    lengths[0] = c * treehash.CHUNK_LEN
    buf = np.zeros((b, c * treehash.CHUNK_LEN), dtype=np.uint8)
    for i, n in enumerate(lengths):
        buf[i, :n] = rng.integers(0, 256, size=n, dtype=np.uint8)
    return buf, lengths.astype(np.int32)


class TestDeviceProgram:
    """The jitted program itself (hash_fn), every tree shape: one chunk
    (no tree), powers of two, odd tails carried at one level or several
    (3, 5, 13, 1023), and the 1 MiB block the servers launch."""

    @pytest.mark.parametrize("b", [1, 3])
    @pytest.mark.parametrize("c", [1, 2, 3, 5, 8, 13, 64, 1023, 1024])
    def test_digests_equal_reference(self, c, b):
        buf, lengths = _ragged_batch(c, b)
        cvs = np.asarray(treehash.hash_fn(c)(buf, lengths)).astype("<u4")
        got = [np.ascontiguousarray(cvs[i]).tobytes() for i in range(b)]
        # the pure-Python oracle takes ~2 s a MiB: at the two large
        # chunk counts only row 0 goes through it, the other rows
        # through the host's digest (native C where it built, else the
        # same oracle)
        from garage_tpu.utils.data import blake3sum

        for i, n in enumerate(lengths):
            ref = treehash.blake3_py if (c <= 64 or i == 0) else blake3sum
            assert got[i] == ref(buf[i, :n].tobytes()), f"c={c} b={b} row={i}"

    @staticmethod
    def _lowered_ops(c: int, b: int) -> int:
        import jax

        text = treehash.hash_fn(c).lower(
            jax.ShapeDtypeStruct((b, c * treehash.CHUNK_LEN), np.uint8),
            jax.ShapeDtypeStruct((b,), np.int32)).as_text()
        return text.count("stablehlo.")

    def test_program_size_guard(self):
        """The program's size is an invariant (DEVICE_PATH.md "The
        BLAKE3 program"): independent of the batch, and of the chunk
        count but for two scalars a level. Every trace, build, load and
        launch pays for each op: a tree unrolled over per-chunk slices
        was 9,729 of them at C = 1024 (484 when this was written)."""
        at_1024 = self._lowered_ops(1024, 1)
        assert at_1024 <= 1000
        assert self._lowered_ops(1024, 8) == at_1024
        # the two levels between C = 256 and C = 1024 are two more
        # steps of the tree's scan, not two more copies of its body
        assert abs(at_1024 - self._lowered_ops(256, 1)) <= 8
