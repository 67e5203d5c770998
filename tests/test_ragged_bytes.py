"""ragged_bytes primitives vs numpy oracles, and padded-vs-scatter
mixed-row-encode parity (the dual-implementation cross-check pattern,
reference row_conversion.cpp:43-60)."""

import numpy as np
import pytest

import spark_rapids_jni_tpu  # noqa: F401
import jax.numpy as jnp

from spark_rapids_jni_tpu.ops.ragged_bytes import (
    assemble_rows,
    byte_rotate_left,
    byte_shift_right,
    overlap_tiles,
    padded_extract,
)


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


def test_overlap_tiles(rng):
    buf = rng.integers(0, 255, 1000, dtype=np.uint8)
    t = np.asarray(overlap_tiles(jnp.asarray(buf), 32, 64))
    assert t.shape == ((1000 + 31) // 32, 64)
    padded = np.zeros(t.shape[0] * 32 + 64, np.uint8)
    padded[:1000] = buf
    for w in range(t.shape[0]):
        np.testing.assert_array_equal(t[w], padded[w * 32 : w * 32 + 64])


@pytest.mark.parametrize("w", [8, 32, 128, 256])
def test_byte_rotate_left(rng, w):
    x = rng.integers(0, 255, (40, w), dtype=np.uint8)
    sh = rng.integers(0, w, 40)
    got = np.asarray(byte_rotate_left(jnp.asarray(x), jnp.asarray(sh, jnp.int32)))
    for r in range(40):
        np.testing.assert_array_equal(got[r], np.roll(x[r], -int(sh[r])))


@pytest.mark.parametrize("w", [8, 64, 256])
def test_byte_shift_right(rng, w):
    x = rng.integers(0, 255, (40, w), dtype=np.uint8)
    sh = rng.integers(0, w + 16, 40)  # amounts past W must clear the row
    got = np.asarray(byte_shift_right(jnp.asarray(x), jnp.asarray(sh, jnp.int32)))
    for r in range(40):
        want = np.zeros(w, np.uint8)
        s = int(sh[r])
        if s < w:
            want[s:] = x[r, : w - s]
        np.testing.assert_array_equal(got[r], want)


@pytest.mark.parametrize("max_len", [1, 7, 32, 100])
def test_padded_extract(rng, max_len):
    pool = rng.integers(0, 255, 5000, dtype=np.uint8)
    starts = np.sort(rng.integers(0, 4900, 64)).astype(np.int64)
    got = np.asarray(padded_extract(jnp.asarray(pool), jnp.asarray(starts), max_len))
    padded = np.concatenate([pool, np.zeros(max_len + 512, np.uint8)])
    for r in range(64):
        np.testing.assert_array_equal(
            got[r, :max_len], padded[starts[r] : starts[r] + max_len]
        )


@pytest.mark.parametrize("min_row,spread", [(8, 24), (16, 300), (136, 128)])
def test_assemble_rows(rng, min_row, spread):
    n = 50
    sizes = (min_row + rng.integers(0, spread // 8 + 1, n) * 8).astype(np.int64)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    total = int(offsets[-1])
    s = int(sizes.max())
    rp = np.zeros((n, s), np.uint8)
    for r in range(n):
        rp[r, : sizes[r]] = rng.integers(1, 255, sizes[r])
    rp4 = rp if rp.shape[1] % 4 == 0 else np.pad(rp, ((0, 0), (0, 4 - rp.shape[1] % 4)))
    rp32 = rp4.reshape(n, -1, 4).view(np.uint32)[:, :, 0]
    got = np.asarray(
        assemble_rows(
            jnp.asarray(rp32),
            jnp.asarray(sizes),
            jnp.asarray(offsets),
            total,
            min_row,
        )
    )
    want = np.concatenate([rp[r, : sizes[r]] for r in range(n)])
    np.testing.assert_array_equal(got, want)


def test_pallas_kernels_interpret_parity(rng):
    """The Pallas epilogue kernels (TPU hot path) must agree with the
    plain-jnp fallbacks — exercised through the Pallas interpreter so
    the kernel bodies run hermetically on CPU."""
    from spark_rapids_jni_tpu.ops.ragged_bytes import (
        _asm_epilogue,
        rotl_take,
        var_accumulate,
    )

    n = 700  # not a multiple of the 512-row kernel block
    x = jnp.asarray(rng.integers(0, 255, (n, 64), dtype=np.uint8))
    sh = jnp.asarray(rng.integers(0, 64, n), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(rotl_take(x, sh, 32, interpret=True)),
        np.asarray(byte_rotate_left(x, sh))[:, :32],
    )

    p1 = jnp.asarray(rng.integers(0, 255, (n, 16), dtype=np.uint8))
    p2 = jnp.asarray(rng.integers(0, 255, (n, 32), dtype=np.uint8))
    s1 = jnp.asarray(rng.integers(0, 40, n), jnp.int32)
    s2 = jnp.asarray(rng.integers(0, 60, n), jnp.int32)
    # fallback uses +, kernel uses |: compare with disjoint placements
    # per row (the contract)
    s2d = s1 + 16  # p1 is 16 wide -> never overlaps
    got = np.asarray(var_accumulate((p1, p2), (s1, s2d), 96, interpret=True))
    want = np.asarray(var_accumulate((p1, p2), (s1, s2d), 96))
    np.testing.assert_array_equal(got, want)

    g = 32
    a0 = jnp.asarray(rng.integers(0, 2**31, (n, g // 4)).astype(np.uint32))
    a1 = jnp.asarray(rng.integers(0, 2**31, (n, g // 4)).astype(np.uint32))
    c0 = jnp.asarray(rng.integers(0, 2**31, (n, g // 4)).astype(np.uint32))
    pmod = jnp.asarray(rng.integers(0, g // 8, n) * 8, jnp.int32)
    delta = jnp.asarray(rng.integers(0, g // 8 + 1, n) * 8, jnp.int32)
    alen = jnp.asarray(rng.integers(0, g // 8 + 1, n) * 8, jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(_asm_epilogue(a0, a1, c0, pmod, delta, alen, g, interpret=True)),
        np.asarray(_asm_epilogue(a0, a1, c0, pmod, delta, alen, g)),
    )


def test_padded_vs_scatter_encode_parity(rng):
    """Byte-exact agreement of the padded fast path with the scatter
    fallback on a mixed table (both against the reference layout)."""
    from spark_rapids_jni_tpu.columnar import Column, Table
    from spark_rapids_jni_tpu.columnar import dtype as dt
    from spark_rapids_jni_tpu.ops import row_conversion as rc

    n = 257
    words = ["", "a", "spark", "tpu-native", "x" * 31, "yz"]
    tbl = Table(
        [
            Column(dt.INT32, data=jnp.asarray(rng.integers(-100, 100, n), jnp.int32)),
            Column.from_pylist([words[i % len(words)] for i in range(n)], dt.STRING),
            Column(dt.INT64, data=jnp.asarray(rng.integers(-(2**40), 2**40, n), jnp.int64)),
            Column.from_pylist(
                [None if i % 7 == 0 else words[(i * 3) % len(words)] for i in range(n)],
                dt.STRING,
            ),
            Column(dt.INT16, data=jnp.asarray(rng.integers(-999, 999, n), jnp.int16)),
        ],
        ["a", "s1", "b", "s2", "c"],
    )
    layout = rc.compute_row_layout(tbl.dtypes())
    cols = tbl.columns
    lens_total = jnp.zeros((n,), jnp.int64)
    for i in layout.variable_cols:
        offs = cols[i].offsets
        lens_total = lens_total + (offs[1:] - offs[:-1]).astype(jnp.int64)
    sizes = np.asarray(
        (lens_total + layout.fixed_end + 7) // 8 * 8, dtype=np.int64
    )
    offsets = jnp.asarray(np.concatenate([[0], np.cumsum(sizes)]))
    total = int(np.sum(sizes))
    maxlens = tuple(cols[i].max_char_len for i in layout.variable_cols)
    maxvar = max(rc._round_up(int(sizes.max()) - layout.fixed_end, 64), 8)
    fast = np.asarray(
        rc._jit_encode_strings_fused(layout, tuple(cols), offsets, total, maxlens, maxvar)
    )
    slow = np.asarray(rc._to_rows_strings(layout, cols, offsets[:-1], total))
    np.testing.assert_array_equal(fast, slow)


# ---------------------------------------------------------------------------
# ragged_compact: the word-granular decode compaction (round 4)
# ---------------------------------------------------------------------------

from spark_rapids_jni_tpu.ops.ragged_bytes import flat_u8_to_u32, ragged_compact


class TestRaggedCompact:
    def _oracle(self, pool, base, lens):
        out = [pool[b : b + ln] for b, ln in zip(base, lens)]
        return np.concatenate(out) if out else np.zeros((0,), np.uint8)

    def _run(self, pool, base, lens):
        offs = np.zeros(len(base) + 1, np.int64)
        np.cumsum(lens, out=offs[1:])
        got = ragged_compact(
            jnp.asarray(pool), jnp.asarray(base, jnp.int64), jnp.asarray(offs), int(offs[-1])
        )
        want = self._oracle(pool, np.asarray(base), np.asarray(lens))
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_simple(self):
        pool = np.arange(64, dtype=np.uint8)
        self._run(pool, [0, 10, 30], [5, 8, 20])

    def test_zero_length_rows(self):
        pool = np.arange(64, dtype=np.uint8)
        self._run(pool, [0, 3, 3, 3, 20], [3, 0, 0, 5, 9])

    def test_all_zero(self):
        pool = np.arange(16, dtype=np.uint8)
        self._run(pool, [0, 4, 8], [0, 0, 0])

    def test_tiny_rows_within_words(self):
        # many 1-3 byte rows: multiple head chunks share output words
        r = np.random.default_rng(3)
        lens = r.integers(0, 4, 50)
        base = np.cumsum(np.concatenate([[0], lens[:-1] + r.integers(0, 5, 49)]))
        pool = r.integers(0, 256, int(base[-1]) + 16).astype(np.uint8)
        self._run(pool, base, lens)

    def test_word_straddles(self):
        pool = np.arange(200, dtype=np.uint8)
        self._run(pool, [1, 9, 33, 77], [7, 13, 21, 40])

    def test_aligned_and_unaligned_mix(self):
        r = np.random.default_rng(11)
        for _trial in range(10):
            n = int(r.integers(1, 80))
            lens = r.integers(0, 40, n)
            gaps = r.integers(0, 9, n)
            base = np.cumsum(np.concatenate([[0], (lens + gaps)[:-1]]))
            pool = r.integers(0, 256, int(base[-1] + lens[-1]) + 16).astype(np.uint8)
            self._run(pool, base, lens)

    def test_large_random(self):
        r = np.random.default_rng(42)
        n = 5000
        lens = r.integers(0, 64, n)
        gaps = r.integers(0, 16, n)
        base = np.cumsum(np.concatenate([[0], (lens + gaps)[:-1]]))
        pool = r.integers(0, 256, int(base[-1] + lens[-1]) + 16).astype(np.uint8)
        self._run(pool, base, lens)

    def test_single_giant_row(self):
        r = np.random.default_rng(5)
        pool = r.integers(0, 256, 100_000).astype(np.uint8)
        self._run(pool, [17], [99_000])

    def test_flat_u8_to_u32(self):
        b = np.arange(32, dtype=np.uint8)
        np.testing.assert_array_equal(
            np.asarray(flat_u8_to_u32(jnp.asarray(b))), b.view(np.uint32)
        )
