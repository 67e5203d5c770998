"""Exact f64 accumulation on integer-only datapaths (ops/f64acc).

Oracles: math.fsum (correctly rounded exact sum) and Fraction (exact
rational mean) — the strongest available references. Within the 224-bit
window (addends within 2^108 of the group max) the accumulator must be
BIT-IDENTICAL to the correctly rounded exact result; across wider
exponent spans the documented bound is < 2^-107 relative to the largest
addend, asserted as <= 1e-15 relative here.
"""

import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu.ops import f64acc
from spark_rapids_jni_tpu.ops.f64acc import (
    DD,
    dd_from_any,
    dd_from_f64bits,
    dd_to_f64bits,
    segment_mean_f64bits,
    segment_sum_f64bits,
)


def _bits(vals) -> jnp.ndarray:
    return jnp.asarray(np.asarray(vals, np.float64).view(np.uint64))


def _vals(bits) -> np.ndarray:
    return np.asarray(bits, np.uint64).view(np.float64)


def _sum_one(vals):
    b = _bits(vals)
    seg = jnp.zeros((len(vals),), jnp.int32)
    return _vals(segment_sum_f64bits(b, seg, 1))[0]


def exact_sum(vals) -> float:
    return math.fsum([float(v) for v in vals])


class TestExactSum:
    def test_simple(self):
        assert _sum_one([1.0, 2.0, 3.5]) == 6.5

    def test_bit_identical_small_span(self, rng):
        # exponents within the window -> must equal fsum bit-for-bit
        for trial in range(20):
            n = int(rng.integers(1, 200))
            exps = rng.uniform(-30, 30, n)
            vals = rng.standard_normal(n) * (10.0 ** exps)
            got = _sum_one(vals)
            want = exact_sum(vals)
            assert math.isfinite(want)
            assert got == want, f"trial {trial}: {got!r} != {want!r}"

    def test_wide_span_relative_bound(self, rng):
        for trial in range(10):
            n = int(rng.integers(2, 100))
            exps = rng.uniform(-290, 290, n)
            vals = rng.standard_normal(n) * (10.0 ** exps)
            got = _sum_one(vals)
            want = exact_sum(vals)
            assert got == pytest.approx(want, rel=1e-15)

    def test_rounding_tie_to_even(self):
        # 2^53 + 1 is exactly halfway; nearest-even keeps 2^53
        assert _sum_one([2.0**53, 1.0]) == 2.0**53
        # any dust below the tie breaks it upward
        assert _sum_one([2.0**53, 1.0, 2.0**-40]) == 2.0**53 + 2
        # odd mantissa neighbor: tie rounds AWAY to the even 2^53+4? no:
        # 2^53+3 is halfway between +2 and +4; +4 has even mantissa
        assert _sum_one([2.0**53 + 2, 1.0]) == 2.0**53 + 4

    def test_exact_cancellation(self):
        assert _sum_one([1e20, -1e20, 3.5]) == 3.5
        assert _sum_one([1.0, -1.0]) == 0.0
        # sign of a clean negative sum
        assert _sum_one([-2.5, -3.25]) == -5.75

    def test_kahan_killer_inside_window(self):
        # big addends cancel, dust survives: naive f64 returns 0.0 here
        # (1e30 absorbs the 1.0s); the windowed accumulator is exact
        # because 1.0 sits ~100 bits below 1e30 — inside the 108-bit
        # window. We BEAT the f64 oracle.
        vals = [1.0, 1e30, 1.0, -1e30] * 1000
        assert np.sum(np.asarray(vals)) == 0.0  # the f64 oracle's failure
        assert _sum_one(vals) == 2000.0

    def test_kahan_killer_beyond_window(self):
        # beyond the window (1e100 is ~332 bits above 1.0) the dust is
        # dropped — EXACTLY like every f64 accumulator (np.sum, Spark,
        # cudf all return 0.0; only arbitrary-precision fsum sees 2000).
        # The contract: error never exceeds the f64 oracle's own.
        vals = [1.0, 1e100, 1.0, -1e100] * 1000
        assert np.sum(np.asarray(vals)) == 0.0
        assert _sum_one(vals) == 0.0

    def test_subnormal_inputs(self):
        tiny = 5e-324
        assert _sum_one([tiny] * 7) == 7 * tiny
        assert _sum_one([tiny, -tiny]) == 0.0

    def test_subnormal_result_rounding(self):
        # sum lands in the subnormal range with a rounding decision
        a = 2.0**-1060
        b = 2.0**-1074
        got = _sum_one([a, -a / 2, b])
        want = exact_sum([a, -a / 2, b])
        assert got == want

    def test_overflow_to_inf(self):
        assert _sum_one([1.7e308, 1.7e308]) == math.inf
        assert _sum_one([-1.7e308, -1.7e308]) == -math.inf
        # near-max but finite
        assert _sum_one([1.7e308, 0.5e308]) == pytest.approx(2.2e308, rel=1e-15)

    def test_inf_nan_propagation(self):
        assert _sum_one([math.inf, 1.0]) == math.inf
        assert _sum_one([-math.inf, 1e308]) == -math.inf
        assert math.isnan(_sum_one([math.inf, -math.inf]))
        assert math.isnan(_sum_one([math.nan, 1.0]))

    def test_segments_and_validity(self, rng):
        vals = rng.standard_normal(64) * (10.0 ** rng.uniform(-10, 10, 64))
        seg = jnp.asarray(rng.integers(0, 5, 64), jnp.int32)
        valid = jnp.asarray(rng.random(64) < 0.7)
        out = _vals(segment_sum_f64bits(_bits(vals), seg, 5, valid=jnp.asarray(valid)))
        segs = np.asarray(seg)
        vm = np.asarray(valid)
        for g in range(5):
            want = exact_sum(vals[(segs == g) & vm])
            assert out[g] == want

    def test_empty_segment_is_zero(self):
        out = _vals(segment_sum_f64bits(_bits([1.0]), jnp.zeros((1,), jnp.int32), 3))
        assert out[0] == 1.0 and out[1] == 0.0 and out[2] == 0.0

    @pytest.mark.parametrize("num_segments", [1, 3, 16, 17])
    def test_zero_rows_any_group_count(self, num_segments):
        # regression (ADVICE r4): 0 rows with 1 <= G <= 16 crashed the
        # small-G masked path with a zero-size jnp.max
        empty_bits = jnp.zeros((0,), jnp.uint64)
        empty_seg = jnp.zeros((0,), jnp.int32)
        out = _vals(segment_sum_f64bits(empty_bits, empty_seg, num_segments))
        assert out.shape == (num_segments,) and (out == 0.0).all()
        mean, cnt = segment_mean_f64bits(empty_bits, empty_seg, num_segments)
        assert _vals(mean).shape == (num_segments,)
        assert (np.asarray(cnt) == 0).all()

    def test_large_n_exactness(self, rng):
        # adversarial magnitudes at scale: 100k values across 25 decades
        n = 100_000
        vals = rng.standard_normal(n) * (10.0 ** rng.uniform(-12, 13, n))
        got = _sum_one(vals)
        assert got == exact_sum(vals)


class TestExactMean:
    def _mean_one(self, vals, valid=None):
        b = _bits(vals)
        seg = jnp.zeros((len(vals),), jnp.int32)
        out, cnt = segment_mean_f64bits(
            b, seg, 1, valid=None if valid is None else jnp.asarray(valid)
        )
        return _vals(out)[0], int(cnt[0])

    def test_simple(self):
        got, cnt = self._mean_one([1.0, 2.0, 4.0])
        assert cnt == 3
        assert got == float(Fraction(7, 3))

    def test_correctly_rounded_mean(self, rng):
        for trial in range(10):
            n = int(rng.integers(1, 50))
            vals = rng.standard_normal(n) * (10.0 ** rng.uniform(-20, 20, n))
            got, cnt = self._mean_one(vals)
            exact = sum(Fraction(float(v)) for v in vals) / n
            assert cnt == n
            assert got == float(exact), f"trial {trial}"

    def test_mean_with_validity(self):
        got, cnt = self._mean_one([10.0, 999.0, 20.0], valid=[True, False, True])
        assert cnt == 2 and got == 15.0

    def test_mean_nonterminating(self):
        # 1/3 in binary never terminates: full sticky path
        got, _ = self._mean_one([1.0, 0.0, 0.0])
        assert got == float(Fraction(1, 3))


class TestAdd2:
    def _check(self, av, bv):
        from spark_rapids_jni_tpu.ops.f64acc import add2_f64bits

        a = np.asarray(av, np.float64)
        b = np.asarray(bv, np.float64)
        got = np.asarray(add2_f64bits(jnp.asarray(a.view(np.uint64)),
                                      jnp.asarray(b.view(np.uint64))))
        want = (a + b).view(np.uint64)
        # two documented sign-bit deviations: zero results carry +0
        # (like the windowed accumulator) and NaN results are the
        # canonical quiet NaN (sign/payload of NaN is unobservable)
        gz = got & np.uint64(0x7FFFFFFFFFFFFFFF)
        wz = want & np.uint64(0x7FFFFFFFFFFFFFFF)
        zero = (gz == 0) & (wz == 0)
        is_nan = np.isnan(a + b) & np.isnan(got.view(np.float64))
        norm = zero | is_nan
        np.testing.assert_array_equal(np.where(norm, gz, got), np.where(norm, wz, want))

    def test_random_pairs_match_hardware(self, rng):
        n = 200_000
        a = rng.standard_normal(n) * (10.0 ** rng.uniform(-300, 300, n))
        b = rng.standard_normal(n) * (10.0 ** rng.uniform(-300, 300, n))
        self._check(a, b)

    def test_near_cancellation(self, rng):
        n = 50_000
        a = rng.standard_normal(n) * (10.0 ** rng.uniform(-10, 10, n))
        ulps = rng.integers(-8, 9, n)
        b = -(np.frombuffer((a.view(np.int64) + ulps).tobytes(), np.float64).copy())
        self._check(a, b)

    def test_guard_boundary_gaps(self, rng):
        # exponent gaps straddling the 8-bit guard: 0..70, both signs
        n = 20_000
        a = rng.standard_normal(n)
        gap = rng.integers(0, 71, n)
        b = np.ldexp(rng.standard_normal(n), -gap.astype(np.int64))
        self._check(a, b)
        self._check(a, -b)

    def test_ties_and_exact_halves(self):
        # construct exact round-to-even ties: 1 + 2^-53 etc.
        a = np.array([1.0, 1.0, 1.5, -1.0, 2.0**52, 2.0**52])
        b = np.array([2.0**-53, 2.0**-52, 2.0**-53, -(2.0**-53), 0.5, 1.5])
        self._check(a, b)

    def test_specials_and_subnormals(self):
        tiny = np.float64(5e-324)
        a = np.array([np.inf, -np.inf, np.inf, np.nan, tiny, -tiny, 1e308, 0.0])
        b = np.array([1.0, 1.0, -np.inf, 1.0, tiny, tiny, 1e308, -0.0])
        self._check(a, b)

    def test_dd_roundtrip_still_exact(self, rng):
        from spark_rapids_jni_tpu.ops.f64acc import dd_to_f64bits

        # f32-representable pairs roundtrip bit-exactly through dd
        hi = rng.standard_normal(10_000).astype(np.float32)
        lo = (rng.standard_normal(10_000) * 1e-9).astype(np.float32)
        want = hi.astype(np.float64) + lo.astype(np.float64)
        got = np.asarray(dd_to_f64bits(DD(jnp.asarray(hi), jnp.asarray(lo))))
        np.testing.assert_array_equal(got, want.view(np.uint64))


class TestMxuPathIdentity:
    def test_mxu_matches_payload_bits(self, rng, monkeypatch):
        # the int8-MXU contraction and the i64 payload reduction must
        # produce the SAME bits on every input, including non-finite
        # mixes and invalid rows
        from spark_rapids_jni_tpu.ops import f64acc

        n = 4096
        vals = rng.standard_normal(n) * (10.0 ** rng.uniform(-18, 18, n))
        vals[rng.random(n) < 0.01] = np.inf
        vals[rng.random(n) < 0.01] = -np.inf
        vals[rng.random(n) < 0.01] = np.nan
        vals[rng.random(n) < 0.01] = -np.nan
        b = _bits(vals)
        seg = jnp.asarray(rng.integers(0, 9, n), jnp.int32)
        valid = jnp.asarray(rng.random(n) < 0.8)
        mxu = segment_sum_f64bits(b, seg, 9, valid=valid)
        monkeypatch.setattr(f64acc, "_MXU_ONEHOT_BUDGET", -1)
        payload = segment_sum_f64bits(b, seg, 9, valid=valid)
        assert np.array_equal(np.asarray(mxu), np.asarray(payload))
        mean_m, cnt_m = segment_mean_f64bits(b, seg, 9, valid=valid)
        monkeypatch.undo()
        monkeypatch.setattr(f64acc, "_MXU_ONEHOT_BUDGET", -1)
        mean_p, cnt_p = segment_mean_f64bits(b, seg, 9, valid=valid)
        assert np.array_equal(np.asarray(mean_m), np.asarray(mean_p))
        assert np.array_equal(np.asarray(cnt_m), np.asarray(cnt_p))

    @pytest.mark.parametrize("budget,barriers", [(1 << 28, 1), (-1, 0)])
    def test_mxu_branch_hands_its_limb_sums_on_behind_a_barrier(self, monkeypatch, budget, barriers):
        # ROADMAP F1 / ISSUE 29: in ONE program the TPU compiler folds the
        # nibble recombination into the carry propagation and drops
        # carries; the barrier is the mend (the CPU cannot see its
        # effect, so the trace is what is held). The payload branch,
        # which the mesh programs take, has none and stays as it was.
        monkeypatch.setattr(f64acc, "_MXU_ONEHOT_BUDGET", budget)
        jaxpr = jax.make_jaxpr(lambda b, s: segment_sum_f64bits(b, s, 3))(
            jnp.zeros((64,), jnp.uint64), jnp.zeros((64,), jnp.int32))
        assert str(jaxpr).count("optimization_barrier") == barriers

    def test_mxu_chunking_exact(self, rng, monkeypatch):
        # force multi-chunk matmuls and check against the payload path
        from spark_rapids_jni_tpu.ops import f64acc

        monkeypatch.setattr(f64acc, "_MXU_CHUNK", 1000)
        n = 2500
        vals = rng.standard_normal(n) * (10.0 ** rng.uniform(-10, 10, n))
        b = _bits(vals)
        seg = jnp.asarray(rng.integers(0, 3, n), jnp.int32)
        got = _vals(segment_sum_f64bits(b, seg, 3))
        for g in range(3):
            assert got[g] == exact_sum(vals[np.asarray(seg) == g])


class TestCrossBackendContract:
    def test_jit_matches_eager(self, rng):
        import jax

        vals = rng.standard_normal(256) * (10.0 ** rng.uniform(-15, 15, 256))
        b = _bits(vals)
        seg = jnp.asarray(rng.integers(0, 7, 256), jnp.int32)
        eager = segment_sum_f64bits(b, seg, 7)
        jitted = jax.jit(lambda bb, ss: segment_sum_f64bits(bb, ss, 7))(b, seg)
        assert np.array_equal(np.asarray(eager), np.asarray(jitted))


class TestDD:
    def test_roundtrip_precision(self, rng):
        # full dd precision holds while the RESIDUAL stays f32-normal,
        # i.e. |x| >~ 4e-31 (2^-101); the generator stays inside that
        vals = rng.standard_normal(1000) * (10.0 ** rng.uniform(-28, 28, 1000))
        dd = dd_from_f64bits(_bits(vals))
        recon = np.asarray(dd.hi, np.float64) + np.asarray(dd.lo, np.float64)
        rel = np.abs(recon - vals) / np.abs(vals)
        assert rel.max() <= 2.0**-47

    def test_roundtrip_bits(self, rng):
        # f64 -> dd -> f64 keeps ~48 mantissa bits
        vals = rng.standard_normal(500) * (10.0 ** rng.uniform(-28, 28, 500))
        dd = dd_from_f64bits(_bits(vals))
        back = _vals(dd_to_f64bits(dd))
        rel = np.abs(back - vals) / np.abs(vals)
        assert rel.max() <= 2.0**-47

    def test_tiny_values_flush_gracefully(self, rng):
        # below ~4e-31 the residual flushes (f32 subnormal floor): dd
        # degrades to plain-f32 precision (2^-24), never worse — the
        # same loss profile as the f32 path it replaces
        vals = rng.standard_normal(200) * (10.0 ** rng.uniform(-35, -31, 200))
        vals = np.where(np.abs(vals) < 1.2e-38, 1e-35, vals)  # stay f32-normal
        dd = dd_from_f64bits(_bits(vals))
        recon = np.asarray(dd.hi, np.float64) + np.asarray(dd.lo, np.float64)
        rel = np.abs(recon - vals) / np.abs(vals)
        assert rel.max() <= 2.0**-23
        # below the f32 floor the whole value flushes — same as the old
        # plain-f32 expression path (bitutils._f64_bits_to_f32 contract)
        sub = dd_from_f64bits(_bits([7e-39]))
        assert float(sub.hi[0]) == 0.0 and float(sub.lo[0]) == 0.0

    def test_exact_f32_values_roundtrip_exactly(self, rng):
        vals = rng.standard_normal(100).astype(np.float32).astype(np.float64)
        dd = dd_from_f64bits(_bits(vals))
        assert np.all(np.asarray(dd.lo) == 0)
        assert np.array_equal(_vals(dd_to_f64bits(dd)), vals)

    def test_mul_precision(self, rng):
        a = rng.standard_normal(500) * (10.0 ** rng.uniform(-15, 15, 500))
        b = rng.standard_normal(500) * (10.0 ** rng.uniform(-15, 15, 500))
        da, db = dd_from_f64bits(_bits(a)), dd_from_f64bits(_bits(b))
        got = _vals(dd_to_f64bits(da * db))
        want = a * b
        rel = np.abs(got - want) / np.abs(want)
        assert rel.max() <= 1e-13

    def test_add_sub_precision(self, rng):
        a = rng.standard_normal(500) * (10.0 ** rng.uniform(-10, 10, 500))
        b = rng.standard_normal(500) * (10.0 ** rng.uniform(-10, 10, 500))
        da, db = dd_from_f64bits(_bits(a)), dd_from_f64bits(_bits(b))
        got = _vals(dd_to_f64bits(da + db))
        want = a + b
        nz = want != 0
        rel = np.abs(got[nz] - want[nz]) / np.abs(want[nz])
        assert rel.max() <= 1e-12

    def test_div_precision(self, rng):
        a = rng.standard_normal(500) * (10.0 ** rng.uniform(-10, 10, 500))
        b = rng.standard_normal(500) * (10.0 ** rng.uniform(-10, 10, 500))
        b = np.where(np.abs(b) < 1e-30, 1.0, b)
        da, db = dd_from_f64bits(_bits(a)), dd_from_f64bits(_bits(b))
        got = _vals(dd_to_f64bits(da / db))
        want = a / b
        rel = np.abs(got - want) / np.abs(want)
        assert rel.max() <= 1e-13

    def test_q1_expression_shape(self, rng):
        # price * (1 - disc) * (1 + tax): the q1 money kernel, dd vs f64
        price = rng.uniform(900, 105_000, 2000)
        disc = rng.uniform(0, 0.1, 2000)
        tax = rng.uniform(0, 0.08, 2000)
        dp = dd_from_f64bits(_bits(price))
        dd_res = dp * (1.0 - dd_from_f64bits(_bits(disc))) * (
            1.0 + dd_from_f64bits(_bits(tax))
        )
        got = _vals(dd_to_f64bits(dd_res))
        want = price * (1 - disc) * (1 + tax)
        rel = np.abs(got - want) / np.abs(want)
        assert rel.max() <= 1e-13

    def test_comparisons(self):
        a = dd_from_any(jnp.asarray([1.0, 2.0, 3.0], jnp.float32))
        b = dd_from_any(2.0)
        assert np.asarray(a < b).tolist() == [True, False, False]
        assert np.asarray(a <= b).tolist() == [True, True, False]
        assert np.asarray(a > b).tolist() == [False, False, True]
        assert np.asarray(a == b).tolist() == [False, True, False]

    def test_comparison_uses_lo(self):
        # values equal in hi but differing in lo must order correctly
        one_plus = 1.0 + 2.0**-40
        a = dd_from_f64bits(_bits([one_plus]))
        b = dd_from_f64bits(_bits([1.0]))
        assert bool(np.asarray(a > b)[0])
        assert not bool(np.asarray(a == b)[0])

    def test_scalar_promotion(self):
        a = dd_from_any(jnp.asarray([1.5, 2.5], jnp.float32))
        s = a + 0.1  # 0.1 splits exactly on host into hi+lo
        got = _vals(dd_to_f64bits(s))
        want = np.asarray([1.5, 2.5]) + np.float64(np.float32(0.1)) + (
            0.1 - np.float64(np.float32(0.1))
        )
        assert got == pytest.approx(want.tolist(), rel=1e-14)

    def test_mod(self, rng):
        # C fmod semantics (Spark %)
        a = rng.standard_normal(300) * (10.0 ** rng.uniform(-3, 6, 300))
        b = rng.standard_normal(300) * (10.0 ** rng.uniform(-3, 6, 300))
        b = np.where(np.abs(b) < 1e-30, 1.5, b)
        da, db = dd_from_f64bits(_bits(a)), dd_from_f64bits(_bits(b))
        got = _vals(dd_to_f64bits(da % db))
        want = np.fmod(a, b)
        # |r| < |b| and sign follows a; value within dd precision of fmod
        # (near-multiple boundaries can flip the quotient by 1 -> compare
        # against both adjacent remainders)
        alt = np.where(want >= 0, want - np.abs(b), want + np.abs(b))
        err = np.minimum(np.abs(got - want), np.abs(got - alt))
        # documented dd fmod bound: error ~ |a| * 2^-48 (the quotient's
        # dd rounding scaled back by b), asserted with headroom
        assert (err <= np.abs(a) * 2.0**-40 + 1e-300).all()
        exact = np.fmod(np.asarray([7.0, -7.0, 7.5, 100.0]), np.asarray([2.0, 2.0, 0.5, 3.0]))
        g2 = _vals(dd_to_f64bits(
            dd_from_f64bits(_bits([7.0, -7.0, 7.5, 100.0]))
            % dd_from_f64bits(_bits([2.0, 2.0, 0.5, 3.0]))
        ))
        np.testing.assert_allclose(g2, exact, atol=1e-12)


class TestBoundedDomainF64:
    def test_groupby_sum_bounded_f64_bits(self, rng):
        from spark_rapids_jni_tpu.ops.aggregate import groupby_sum_bounded

        vals = rng.standard_normal(2000) * (10.0 ** rng.uniform(-10, 10, 2000))
        keys = jnp.asarray(rng.integers(-1, 8, 2000), jnp.int64)  # -1 = dropped
        sums, counts = groupby_sum_bounded(keys, _bits(vals), 8, f64_bits=True)
        kh = np.asarray(keys)
        for g in range(8):
            want = exact_sum(vals[kh == g])
            assert _vals(sums)[g] == want
            assert int(counts[g]) == int((kh == g).sum())

    def test_f64_bits_requires_u64(self):
        import pytest as _pytest

        from spark_rapids_jni_tpu.ops.aggregate import groupby_sum_bounded

        with _pytest.raises(ValueError):
            groupby_sum_bounded(
                jnp.zeros((4,), jnp.int64), jnp.zeros((4,), jnp.float32), 2, f64_bits=True
            )
