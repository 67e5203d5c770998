"""Exact float64 accumulation + double-f32 arithmetic on integer-only
datapaths (TPU v5e has no f64 ALU, and the design keeps f64 values as
IEEE bit patterns in u64 lanes so that no f64 bitcast or f64 arithmetic
is ever asked of the device).

The reference sums doubles in real f64 on device (cudf segment reduce;
SURVEY §2.8), so Spark ``sum(double)`` semantics require f64-accurate
accumulation. This module delivers that WITHOUT an f64 datapath:

**Exact windowed integer accumulation** (``segment_sum_f64bits``):
each FLOAT64 value (stored as IEEE-754 bits in uint64 lanes — see
bitutils) is decomposed into sign/exponent/53-bit mantissa with pure
integer ops (exact on TPU), aligned to the per-group maximum exponent
inside a 224-bit fixed-point window (7 x u32 limbs), and segment-summed
limb-wise in int64 (exact: every per-limb partial stays < 2^63 for up to
2^31 rows). A carry-propagate + round-to-nearest-even pass rebuilds the
IEEE bits. Values more than ~108 bits below the group maximum fall off
the window — an error < 2^-107 relative to the largest element, i.e.
strictly tighter than one f64 ulp of any achievable result, so the
returned sum is the correctly rounded f64 of the exact real sum in all
practical regimes (and far more accurate than sequential f64 addition,
whose error grows with N). The same bits come back on every backend —
CPU and TPU agree bit-for-bit.

**Exact mean**: the 224-bit limb sum is divided by the count with a
restoring bit-at-a-time long division (compare/subtract only — the
emulated 64-bit integer divide never enters the program), the remainder
folds into the sticky bit, and the quotient rounds through the same
nearest-even path.

**Double-f32 ("dd") arithmetic** for the expression tier: values carried
as an unevaluated (hi, lo) f32 pair with |lo| <= ulp(hi)/2, giving
~2^-48 relative error for +,-,*,/ — vs 2^-24 for the plain-f32
approximation it replaces. Error-free transforms (2Sum, Dekker split
2Prod) use only IEEE f32 add/mul, both exact on the TPU VPU. dd covers
the f32 exponent range (|x| in ~[1e-38, 3e38]); magnitudes outside it
saturate exactly as the old f32 path did. dd -> f64-bits conversion is
exact: each half widens losslessly to f64 bits and the pair goes through
the windowed accumulator (n=2), rounding once.

IEEE edges: +/-inf and NaN propagate via per-group flags (inf + -inf =
NaN); subnormal inputs accumulate exactly (they are just e_eff=1
mantissas); subnormal RESULTS round correctly into the f64 subnormal
encoding. The single knowingly dropped edge: a group whose every addend
is -0.0 returns +0.0 (IEEE says -0.0); no aggregation consumer observes
the sign of zero.

Reference parity: cudf groupby SUM/MEAN on FLOAT64
(/root/reference 's engine tier via the linked cudf, SURVEY §2.8);
exactness target pinned by VERDICT r3 item 5.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

__all__ = [
    "segment_sum_f64bits",
    "segment_mean_f64bits",
    "i64_to_f64bits",
    "mean_i64_div",
    "div_f64bits_by_int",
    "DD",
    "dd_from_f64bits",
    "dd_to_f64bits",
    "dd_from_any",
]

_U64 = jnp.uint64
_U32 = jnp.uint32
_I64 = jnp.int64
_I32 = jnp.int32

LIMBS = 7  # 224-bit window
# Window anchoring: the mantissa MSB (bit 52 of the 53-bit mantissa) of a
# max-exponent element sits at window bit 160, i.e. the mantissa LSB at
# bit 108; window bit 0 weighs 2^(E - 1183) where E is the group's max
# biased effective exponent. 64 headroom bits (160..223) keep the signed
# sum of up to 2^31 elements inside the window.
_ANCHOR_LSB = 108


def _u64(x) -> jnp.ndarray:
    return jnp.asarray(x, _U64)


def _decompose(bits: jnp.ndarray):
    """IEEE-754 double bits -> (negative, e_eff, mantissa, is_nan, is_pinf, is_ninf).

    e_eff is the *effective* biased exponent: subnormals (e=0) read as
    e_eff=1 with no implicit bit — which makes value = m * 2^(e_eff-1075)
    uniformly true for every finite double, subnormals included.
    """
    neg = (bits >> _u64(63)) != 0
    e = ((bits >> _u64(52)) & _u64(0x7FF)).astype(_I32)
    frac = bits & _u64((1 << 52) - 1)
    is_nan = (e == 0x7FF) & (frac != 0)
    is_inf = (e == 0x7FF) & (frac == 0)
    mant = jnp.where(e == 0, frac, frac | _u64(1 << 52))
    e_eff = jnp.where(e == 0, 1, e)
    finite = e != 0x7FF
    mant = jnp.where(finite, mant, _u64(0))
    e_eff = jnp.where(finite, e_eff, 1)
    return neg, e_eff, mant, is_nan, is_inf & ~neg, is_inf & neg


def _element_limbs(mant: jnp.ndarray, shift: jnp.ndarray) -> list:
    """Per-element limb values: bits [32k, 32k+32) of mant << (108 - shift).

    shift = E[group] - e_elem >= 0. Returns LIMBS arrays of uint32.
    All shift amounts are clamped into [0, 63] with where-guards (XLA
    shifts >= bit width are undefined).
    """
    out = []
    m32 = (mant & _u64(0xFFFFFFFF)).astype(_U64)
    for k in range(LIMBS):
        # t = bit offset into mant of this limb's LSB
        t = _I32(32 * k - _ANCHOR_LSB) + shift.astype(_I32)
        pos = jnp.clip(t, 0, 63).astype(_U64)
        neg_sh = jnp.clip(-t, 0, 31).astype(_U64)
        right = (mant >> pos) & _u64(0xFFFFFFFF)
        left = (m32 << neg_sh) & _u64(0xFFFFFFFF)
        limb = jnp.where(t >= 0, right, left)
        # mantissas are <= 64 bits (53 for doubles; up to 63 for the
        # integer-mean dividend) — t >= 64 reads past any of them
        limb = jnp.where((t >= 64) | (t <= -32), _u64(0), limb)
        out.append(limb.astype(_U32))
    return out


class _GroupSum(NamedTuple):
    """Exact per-group sum in windowed fixed point, pre-rounding."""

    limbs: jnp.ndarray  # [G, LIMBS] int64 signed limb partial sums
    emax: jnp.ndarray  # [G] int32 group max effective biased exponent
    has_nan: jnp.ndarray  # [G] bool
    has_pinf: jnp.ndarray
    has_ninf: jnp.ndarray
    any_live: jnp.ndarray  # [G] bool: the group holds a valid, present row


# one-hot bytes per group x row the MXU path may materialize (256 MB)
_MXU_ONEHOT_BUDGET = 1 << 28
# rows per matmul chunk: |signed nibble partial| <= 15 * chunk must stay
# inside s32 (2^31); 2^26 rows leaves 32x headroom
_MXU_CHUNK = 1 << 26


def _accumulate_mxu(
    neg, e_eff, mant, is_nan, is_pinf, is_ninf, live, emax, any_live, seg, num_segments
) -> _GroupSum:
    """Per-group limb reduction as a signed one-hot int8 MXU contraction.

    The round-4 payload formulation ([N, LIMBS+3] int64 stacked per
    element, segment-summed) was per-element ALU/relayout-bound: ~0.34 s
    per fused-q1 iteration at 1M rows. Here the
    reduction rides the systolic array instead: each 32-bit limb splits
    into 8 nibble planes (values 0..15, int8), planes stack row-major as
    B [8*LIMBS+3, N], and a signed one-hot A [G, N] (+1/-1 by element
    sign, 0 for dead rows) contracts over N in one s8 x s8 -> s32
    dot_general. Nibble partial sums recombine into the exact signed
    224-bit window limbs in int64 at [G] scale — bit-identical to the
    payload path, at matmul bandwidth.

    Exactness bound: every per-group nibble partial is <= 15 * chunk
    rows in magnitude; chunking at 2^26 rows keeps it under 2^30, well
    inside the s32 accumulator. Non-finite rows carry zero limbs and a
    forced +1 sign so the nan/pinf/ninf indicator planes cannot cancel
    between +NaN and -NaN payload signs.
    """
    n = mant.shape[0]
    shift = emax[seg] - e_eff  # >= 0 for live rows
    limbs = _element_limbs(mant, shift)
    nonfinite = is_nan | is_pinf | is_ninf
    sgn8 = jnp.where(
        live, jnp.where(nonfinite | ~neg, jnp.int8(1), jnp.int8(-1)), jnp.int8(0)
    )
    planes = []
    for limb in limbs:
        for j in range(8):
            planes.append(((limb >> _U32(4 * j)) & _U32(0xF)).astype(jnp.int8))
    planes.append(is_nan.astype(jnp.int8))
    planes.append(is_pinf.astype(jnp.int8))
    planes.append(is_ninf.astype(jnp.int8))
    b = jnp.stack(planes, axis=0)  # [8*LIMBS+3, N] — rows contiguous
    onehot = (seg[None, :] == jnp.arange(num_segments, dtype=seg.dtype)[:, None])
    a = jnp.where(onehot, sgn8[None, :], jnp.int8(0))  # [G, N]
    acc = None
    for start in range(0, max(n, 1), _MXU_CHUNK):
        stop = min(start + _MXU_CHUNK, n)
        s = lax.dot_general(
            a[:, start:stop],
            b[:, start:stop],
            (((1,), (1,)), ((), ())),
            preferred_element_type=_I32,
        ).astype(_I64)
        acc = s if acc is None else acc + s
    # recombine nibble sums into signed 32-bit-limb partials (int64 at
    # [G, LIMBS] scale — tiny)
    limb_sums = []
    for k in range(LIMBS):
        t = jnp.zeros((num_segments,), _I64)
        for j in range(8):
            t = t + (acc[:, 8 * k + j] << _I64(4 * j))
        limb_sums.append(t)
    # The barrier is what keeps a JITTED caller exact on the TPU (ISSUE
    # 29, ROADMAP F1): with the shift-and-add recombination above and
    # the carry propagation that follows in one program, the TPU
    # compiler folds the two and drops carries on some data — one lane
    # of 16 of 348 q1-shaped sums off by a few units of one nibble
    # plane, q6's sum by -1.0 and -2049.125 on F1's two seeds — while
    # the contraction's own output is right. Behind the barrier the
    # limb sums are opaque values, as they are when every step is its
    # own program (0 of 348 wrong; benchmarks/calls/pr29_bisect.py).
    return _GroupSum(
        *lax.optimization_barrier(
            (
                jnp.stack(limb_sums, axis=-1),
                emax,
                acc[:, 8 * LIMBS] > 0,
                acc[:, 8 * LIMBS + 1] > 0,
                acc[:, 8 * LIMBS + 2] > 0,
            )
        ),
        any_live,
    )


def _accumulate(bits, valid, seg, num_segments) -> _GroupSum:
    if num_segments == 0 or bits.shape[0] == 0:
        # zero groups (fully filtered batch) or zero rows with live
        # groups: every group sums to +0.0. The small-G masked path
        # below would jnp.max over a zero-size array, which errors.
        z64 = jnp.zeros((num_segments, LIMBS), _I64)
        zb = jnp.zeros((num_segments,), bool)
        return _GroupSum(z64, jnp.ones((num_segments,), _I32), zb, zb, zb, zb)
    neg, e_eff, mant, is_nan, is_pinf, is_ninf = _decompose(bits)
    if valid is not None:
        live = valid
    else:
        live = jnp.ones(bits.shape, bool)
    is_nan = is_nan & live
    is_pinf = is_pinf & live
    is_ninf = is_ninf & live

    e_live = jnp.where(live, e_eff, 0)
    # TPU scatters cost ~40 ns per ELEMENT (payload lanes included): at
    # 1M rows the 10-lane scatter alone is ~0.4 s. For small group
    # counts — the fused-pipeline regime (q1 has 6 groups, a global sum
    # 1) — G masked bandwidth-bound reductions are orders of magnitude
    # cheaper than one scatter pass. e_eff is at least 1 on every row, so
    # the maxima before their clamp are > 0 exactly where the group holds
    # a live row: that is ``any_live``, and no pass over the rows asks it.
    small = num_segments <= 16
    if small:
        emax = jnp.stack(
            [jnp.max(jnp.where(seg == g, e_live, 0)) for g in range(num_segments)]
        )
    else:
        emax = jax.ops.segment_max(e_live, seg, num_segments=num_segments)
    any_live = emax > 0
    emax = jnp.maximum(emax, 1)  # empty / all-invalid groups: any base works

    if num_segments * bits.shape[0] <= _MXU_ONEHOT_BUDGET:
        # hot path (round 5): signed one-hot int8 MXU contraction —
        # bit-identical to the payload reduction below, at matmul
        # bandwidth instead of per-element i64 ALU
        return _accumulate_mxu(
            neg, e_eff, mant, is_nan, is_pinf, is_ninf, live, emax, any_live, seg,
            num_segments,
        )

    shift = emax[seg] - e_eff  # >= 0 for live rows
    limbs = _element_limbs(mant, shift)
    sgn = jnp.where(neg, _I64(-1), _I64(1))
    sgn = jnp.where(live, sgn, _I64(0))
    # ONE vectorized [N, LIMBS+3] payload (fallback when the one-hot
    # would blow the budget). Measured on chip at the q6 axis (1M rows):
    # payload scatter 0.42 s/iter, payload + small-G masked reduction
    # 0.34 s/iter, flat per-lane masked reductions 2.4 s/iter (XLA
    # re-materializes the shared decompose per lane).
    payload = jnp.stack(
        [l.astype(_I64) * sgn for l in limbs]
        + [is_nan.astype(_I64), is_pinf.astype(_I64), is_ninf.astype(_I64)],
        axis=-1,
    )
    if small:
        acc = jnp.stack(
            [
                jnp.sum(jnp.where((seg == g)[:, None], payload, _I64(0)), axis=0)
                for g in range(num_segments)
            ]
        )
    else:
        acc = jax.ops.segment_sum(payload, seg, num_segments=num_segments)
    return _GroupSum(
        acc[..., :LIMBS],
        emax,
        acc[..., LIMBS] > 0,
        acc[..., LIMBS + 1] > 0,
        acc[..., LIMBS + 2] > 0,
        any_live,
    )


def _carry_normalize(acc: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[G, LIMBS] signed int64 partials -> (negative [G], mag [G, LIMBS] u32).

    Carry-propagates into a two's-complement limb string, then converts
    to sign-magnitude (the window headroom guarantees the value fits)."""
    out = []
    carry = jnp.zeros(acc.shape[:-1], _I64)
    for k in range(LIMBS):
        t = acc[..., k] + carry
        out.append((t & _I64(0xFFFFFFFF)).astype(_U32))
        carry = t >> _I64(32)  # arithmetic shift: sign-correct
    negative = carry < 0
    # two's complement -> magnitude: invert + 1 with a ripple carry
    mag = []
    add = jnp.where(negative, _U64(1), _U64(0))
    for k in range(LIMBS):
        limb = jnp.where(negative, ~out[k], out[k]).astype(_U64)
        t = limb + add
        mag.append((t & _u64(0xFFFFFFFF)).astype(_U32))
        add = t >> _u64(32)
    return negative, jnp.stack(mag, axis=-1)


def _clz32(x: jnp.ndarray) -> jnp.ndarray:
    """count leading zeros of a u32 (x != 0 -> 0..31; x == 0 -> 32)."""
    n = jnp.full(x.shape, 32, _I32)
    f = x
    # classic binary clz: n tracks 32 - bits consumed
    for shift in (16, 8, 4, 2, 1):
        big = f >= (_U32(1) << _U32(shift))
        n = jnp.where(big, n - shift, n)
        f = jnp.where(big, f >> _U32(shift), f)
    return jnp.where(x == 0, 32, n - 1)  # x>=1 consumed one sentinel bit


def _msb_pos(mag: jnp.ndarray) -> jnp.ndarray:
    """[G, LIMBS] u32 magnitude -> [G] int32 highest set bit (-1 if zero)."""
    best = jnp.full(mag.shape[:-1], -1, _I32)
    for k in range(LIMBS):
        limb = mag[..., k]
        pos = 32 * k + 31 - _clz32(limb)
        best = jnp.where(limb != 0, pos, best)
    return best


def _extract_bits(mag: jnp.ndarray, start: jnp.ndarray, width: int) -> jnp.ndarray:
    """bits [start, start+width) of the limb string as u64 (width <= 62).

    start may be any int32 >= 0 (bits above the window read as 0).
    Funnel-shifts out of the three aligned u64 words."""
    words = []
    for w in range((LIMBS + 1) // 2):
        lo = mag[..., 2 * w].astype(_U64)
        hi = (
            mag[..., 2 * w + 1].astype(_U64)
            if 2 * w + 1 < LIMBS
            else jnp.zeros_like(lo)
        )
        words.append(lo | (hi << _u64(32)))
    nwords = len(words)
    idx = (start >> 6).astype(_I32)
    r = (start & 63).astype(_U64)
    res = jnp.zeros(mag.shape[:-1], _U64)
    for w in range(nwords):
        cur = words[w]
        nxt = words[w + 1] if w + 1 < nwords else jnp.zeros_like(cur)
        # (cur >> r) | (nxt << (64 - r)), r == 0 handled without UB
        lo_part = cur >> r
        hi_part = jnp.where(r == 0, _u64(0), nxt << (_u64(64) - jnp.maximum(r, _u64(1))))
        res = jnp.where(idx == w, lo_part | hi_part, res)
    return res & _u64((1 << width) - 1)


def _sticky_below(mag: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """any bit of the limb string strictly below bit `pos` set? [G] bool."""
    sticky = jnp.zeros(mag.shape[:-1], bool)
    for k in range(LIMBS):
        limb = mag[..., k]
        # bits of limb k occupy [32k, 32k+32)
        full = pos >= 32 * (k + 1)
        partial = (pos > 32 * k) & ~full
        nbits = jnp.clip(pos - 32 * k, 0, 32)
        mask = jnp.where(
            nbits >= 32, _U32(0xFFFFFFFF), (_U32(1) << nbits.astype(_U32)) - _U32(1)
        )
        sticky = sticky | (full & (limb != 0)) | (partial & ((limb & mask) != 0))
    return sticky


def _round_to_bits(
    negative, mag, emax, has_nan, has_pinf, has_ninf, extra_sticky=None
) -> jnp.ndarray:
    """Windowed sign-magnitude -> IEEE-754 double bits, nearest-even."""
    B = _msb_pos(mag)
    # shift q: result value = keep53 * 2^(q + emax - 1183); the subnormal
    # boundary forces q >= 109 - emax (so the quotient aligns with the
    # f64 subnormal LSB 2^-1074 exactly when the exponent bottoms out)
    q = jnp.maximum(B - 52, 109 - emax)
    q_pos = jnp.maximum(q, 0)

    # rounding path (q > 0): keep = bits [q, q+53), guard = bit q-1,
    # sticky = bits below q-1 (plus the division remainder, if any)
    keep_r = _extract_bits(mag, q_pos.astype(_I32), 53)
    guard_start = jnp.maximum(q_pos - 1, 0).astype(_I32)
    guard = jnp.where(
        q_pos > 0, _extract_bits(mag, guard_start, 1), _u64(0)
    )
    sticky = _sticky_below(mag, jnp.maximum(q_pos - 1, 0)) & (q_pos > 0)
    if extra_sticky is not None:
        sticky = sticky | extra_sticky
    round_up = (guard == 1) & (sticky | ((keep_r & _u64(1)) == 1))
    keep_r = keep_r + round_up.astype(_U64)

    # exact path (q <= 0): the whole magnitude fits below bit 53 —
    # left-shift it into place (B <= 52 implies it lives in word 0).
    # A division remainder here (sub-window-bit resolution while the
    # result wants finer ulps) only arises after >108-bit cancellation,
    # i.e. already below the window's documented noise floor — the
    # sticky is ignorable by construction on this branch.
    w0 = mag[..., 0].astype(_U64) | (mag[..., 1].astype(_U64) << _u64(32))
    keep_e = w0 << jnp.clip(-q, 0, 63).astype(_U64)

    keep = jnp.where(q > 0, keep_r, keep_e)
    # mantissa overflow from rounding: 2^53 -> 2^52, exponent +1
    ovf = keep >> _u64(53) != 0
    keep = jnp.where(ovf, keep >> _u64(1), keep)
    q = q + ovf.astype(_I32)

    subnormal = (B + emax) < 161  # biased exponent would be <= 0
    biased = jnp.clip(q + emax - 108, 0, 0x7FF).astype(_U64)
    frac = keep & _u64((1 << 52) - 1)
    # subnormal encoding: exp field 0, keep53 <= 2^52; a rounding carry
    # into bit 52 lands exactly on biased-exponent 1 — IEEE's layout
    # makes the transition seamless
    bits = jnp.where(
        subnormal, keep, (biased << _u64(52)) | frac
    )
    overflow = (~subnormal) & (q + emax - 108 >= 0x7FF)
    bits = jnp.where(overflow, _u64(0x7FF) << _u64(52), bits)
    zero = _msb_pos(mag) < 0
    bits = jnp.where(zero, _u64(0), bits)
    sign = jnp.where(negative & ~zero, _u64(1) << _u64(63), _u64(0))
    bits = bits | sign

    inf_bits = _u64(0x7FF) << _u64(52)
    bits = jnp.where(has_pinf & ~has_ninf, inf_bits, bits)
    bits = jnp.where(has_ninf & ~has_pinf, inf_bits | (_u64(1) << _u64(63)), bits)
    is_nan = has_nan | (has_pinf & has_ninf)
    bits = jnp.where(is_nan, inf_bits | _u64(1 << 51), bits)
    return bits


def segment_sum_f64bits(
    bits: jnp.ndarray,
    seg: jnp.ndarray,
    num_segments: int,
    valid: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Exact per-segment SUM of FLOAT64 bit-stored values.

    Returns [num_segments] uint64 IEEE bits: the f64 nearest-even
    rounding of the exact real sum (window error < 2^-107 of the largest
    addend — below any representable ulp). Integer-only: identical bits
    on CPU and TPU. Invalid rows (valid=False) contribute nothing.
    """
    return _segment_sum(bits, seg, num_segments, valid)[0]


def _segment_sum(bits, seg, num_segments, valid):
    """``segment_sum_f64bits`` and, beside it, [G] bool: the group holds a
    valid row (read off the accumulation's exponent maxima)."""
    gs = _accumulate(bits, valid, seg, num_segments)
    negative, mag = _carry_normalize(gs.limbs)
    out = _round_to_bits(negative, mag, gs.emax, gs.has_nan, gs.has_pinf, gs.has_ninf)
    return out, gs.any_live


def _limb_divide(mag: jnp.ndarray, cnt: jnp.ndarray):
    """Restoring long division of the 224-bit magnitude by cnt (< 2^31).

    Returns (quotient [G, LIMBS] u32, remainder-nonzero [G] bool). No
    64-bit hardware divide anywhere: the magnitude is exploded into an
    MSB-first bit matrix, a 224-step lax.scan shifts each bit into a
    per-group int64 remainder with one compare/subtract, and the scanned
    quotient bits pack back into limbs. G is a group count — small — so
    the serial scan is cheap."""
    G = mag.shape[0]
    total_bits = 32 * LIMBS
    cnt64 = jnp.maximum(cnt.astype(_I64), 1)
    shifts = jnp.arange(32, dtype=_U32)
    # [G, LIMBS*32] bits, LSB-first within the whole window
    bits_lsb = ((mag[..., None] >> shifts[None, None, :]) & _U32(1)).reshape(G, total_bits)
    xs = bits_lsb[:, ::-1].T.astype(_I64)  # [224, G], MSB first

    def step(r, b):
        r = (r << 1) | b
        ge = r >= cnt64
        return jnp.where(ge, r - cnt64, r), ge

    # carry seeds from a VARYING operand (cnt) so the scan type-checks
    # under shard_map's varying-manual-axes tracking; plain zeros would
    # start unvarying and mismatch the carry output
    rem, qbits = lax.scan(step, cnt64 * 0, xs)
    qb = qbits.T[:, ::-1].reshape(G, LIMBS, 32)  # LSB-first again
    weights = _u64(1) << jnp.arange(32, dtype=_U64)
    q = (qb.astype(_U64) * weights[None, None, :]).sum(axis=-1).astype(_U32)
    return q, rem != 0


def segment_mean_f64bits(
    bits: jnp.ndarray,
    seg: jnp.ndarray,
    num_segments: int,
    valid: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact per-segment MEAN of FLOAT64 bit-stored values.

    The 224-bit exact sum divides by the valid count via binary long
    division; the remainder folds into the sticky bit, so the result is
    the f64 nearest-even rounding of (exact sum / count). Returns
    (mean_bits [G] u64, count [G] i64)."""
    out, cnt, _ = _segment_mean(bits, seg, num_segments, valid)
    return out, cnt


def _segment_mean(bits, seg, num_segments, valid):
    """``segment_mean_f64bits``' (bits, count) and, beside them, [G] bool:
    the group holds a valid row (read off the exponent maxima)."""
    gs = _accumulate(bits, valid, seg, num_segments)
    live = valid if valid is not None else jnp.ones(bits.shape, bool)
    if num_segments == 0:
        cnt = jnp.zeros((0,), _I64)
    elif num_segments <= 16:  # masked reductions beat the scatter class
        cnt = jnp.stack(
            [
                jnp.sum(jnp.where(seg == g, live, False).astype(_I64))
                for g in range(num_segments)
            ]
        )
    else:
        cnt = jax.ops.segment_sum(live.astype(_I64), seg, num_segments=num_segments)
    negative, mag = _carry_normalize(gs.limbs)
    q, rem = _limb_divide(mag, cnt)
    out = _round_to_bits(
        negative, q, gs.emax, gs.has_nan, gs.has_pinf, gs.has_ninf, extra_sticky=rem
    )
    return out, cnt, gs.any_live


def u64_to_f64bits(x: jnp.ndarray) -> jnp.ndarray:
    """uint64 -> IEEE-754 double bits, nearest-even (exact < 2^53)."""
    return _abs64_to_f64bits(x.astype(_U64), jnp.zeros(x.shape, bool))


def i64_to_f64bits(x: jnp.ndarray) -> jnp.ndarray:
    """int64 -> IEEE-754 double bits, nearest-even (exact for |x| < 2^53).

    Integer-only, for materializing exact integer aggregates into
    FLOAT64 columns on the f64-less tier."""
    neg = x < 0
    return _abs64_to_f64bits(jnp.where(neg, -x, x).astype(_U64), neg)


def _abs64_to_f64bits(a: jnp.ndarray, neg: jnp.ndarray) -> jnp.ndarray:
    msb = jnp.zeros(a.shape, _I32)
    v = a
    for shift in (32, 16, 8, 4, 2, 1):
        big = v >= (_u64(1) << _u64(shift))
        msb = jnp.where(big, msb + shift, msb)
        v = jnp.where(big, v >> _u64(shift), v)
    sh = jnp.maximum(msb - 52, 0)
    shc = jnp.clip(sh, 0, 63).astype(_U64)
    keep = a >> shc
    dropped = a & ((_u64(1) << shc) - _u64(1))
    half = jnp.where(sh > 0, _u64(1) << jnp.clip(sh - 1, 0, 63).astype(_U64), _u64(0))
    round_up = (sh > 0) & ((dropped > half) | ((dropped == half) & ((keep & _u64(1)) == 1)))
    keep = keep + round_up.astype(_U64)
    carry = keep >> _u64(53) != 0
    keep = jnp.where(carry, keep >> _u64(1), keep)
    up = jnp.clip(52 - msb, 0, 63)
    mant = jnp.where(sh > 0, keep, keep << up.astype(_U64))
    # normalized mantissa MSB sits at bit 52; value exponent = msb (+1
    # when rounding carried out of the mantissa)
    biased = (msb + carry.astype(_I32) + 1023).astype(_U64)
    bits = (biased << _u64(52)) | (mant & _u64((1 << 52) - 1))
    bits = jnp.where(a == 0, _u64(0), bits)
    bits = bits | jnp.where(neg, _u64(1) << _u64(63), _u64(0))
    return bits


def mean_i64_div(sums: jnp.ndarray, cnt: jnp.ndarray, unsigned: bool = False) -> jnp.ndarray:
    """Exact f64 mean of integer aggregates: |sums| rides the window
    shifted up to the mantissa anchor (bit 108, via _element_limbs with
    shift 0), so the long division yields 108 FRACTIONAL quotient bits
    below the integer point before the shared nearest-even rounding.
    E = 1075 makes window bit 108 weigh 2^0. [G] i64 / [G] i64 -> u64.
    ``unsigned=True`` reads ``sums`` as uint64 magnitudes (UINT64
    aggregates whose two's-complement sum bits exceed 2^63)."""
    if unsigned:
        neg = jnp.zeros(sums.shape, bool)
        a = sums.astype(_U64)
    else:
        neg = sums < 0
        a = jnp.where(neg, -sums, sums).astype(_U64)
    e = jnp.full(sums.shape, 1075, _I32)
    mag = jnp.stack(_element_limbs(a, jnp.zeros_like(e)), axis=-1)
    q, rem = _limb_divide(mag, cnt)
    false = jnp.zeros(sums.shape, bool)
    return _round_to_bits(neg, q, e, false, false, false, extra_sticky=rem)


def div_f64bits_by_int(bits: jnp.ndarray, cnt: jnp.ndarray) -> jnp.ndarray:
    """Correctly rounded f64 division of bit-stored doubles by positive
    ints (< 2^31): mean recombination (partial sum / merged count).

    The mantissa rides the window at its own exponent (shift 0), the
    limb divider produces 161 quotient bits + remainder-sticky, and the
    shared rounding path emits the bits. Integer-only."""
    neg, e_eff, mant, is_nan, is_pinf, is_ninf = _decompose(bits)
    limbs = _element_limbs(mant, jnp.zeros_like(e_eff))
    mag = jnp.stack(limbs, axis=-1)
    q, rem = _limb_divide(mag, cnt)
    return _round_to_bits(neg, q, e_eff, is_nan, is_pinf, is_ninf, extra_sticky=rem)


# ---------------------------------------------------------------------------
# double-f32 ("dd") arithmetic for the expression tier
# ---------------------------------------------------------------------------


def _as_computed(x):
    """``x`` as the float32 it was rounded to, behind a select on itself
    that no algebraic rewrite looks through (a NaN stays a NaN)."""
    return jnp.where(x == x, x, jnp.float32(jnp.nan))


def _two_sum(a, b):
    """Knuth 2Sum: s + e == a + b exactly (IEEE f32 add only)."""
    s = a + b
    bb = s - a
    e = (a - (s - bb)) + (b - bb)
    return s, e


def _rounded(x):
    """A float32 product as it is ROUNDED, and nothing wider, for the add
    or subtract it feeds. Inside one fused program a compiler may contract
    a multiply into the add behind it (an FMA keeps the product exact),
    which the error-free transformations below and ``DD``'s cross terms do
    not survive: a contracted ``hi * o.lo + ...`` rounds once where the
    chain launched one ``jnp`` call at a time rounds twice. XLA:CPU always
    allows the contraction (with the dd branch forced onto the CPU, 48% of
    a traced product's low halves differed from the eager ones); the TPU
    compiler did not contract (ISSUE 33, ``benchmarks/calls/pr33_bits.py``:
    0 of 1 M lanes with this fence taken out), so on the chip it is a
    guard, not a mend. The product passes a select on itself (no compiler
    contracts through it; a NaN stays a NaN: what mends the CPU) and an
    ``optimization_barrier`` (a fusion boundary where the compiler honours
    it, as the TPU's does; XLA:CPU drops it before it fuses), so that a
    traced chain computes what the eager one does, lane for lane."""
    return lax.optimization_barrier(_as_computed(x))


def _split(a):
    """Dekker split: a == hi + lo with 12-bit halves (f32: 2^12+1)."""
    c = _rounded(jnp.float32(4097.0) * a)
    hi = c - (c - a)
    return hi, a - hi


def _two_prod(a, b):
    """p + e == a * b exactly, via Dekker splitting (no FMA dependence)."""
    p = _rounded(a * b)
    ah, al = _split(a)
    bh, bl = _split(b)
    # the four partial products are exact in float32 (12-bit halves):
    # contracted or not, they round the same
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return p, e


class DD(NamedTuple):
    """Unevaluated f32 pair: value = hi + lo, |lo| <= ulp(hi)/2.

    Carried by the expression tier for FLOAT64 columns on backends
    without an f64 datapath; ~2^-48 relative error per operation.
    Comparison operators compare (hi, lo) — exact on the dd values.
    """

    hi: jnp.ndarray
    lo: jnp.ndarray

    # -- arithmetic ---------------------------------------------------------
    def __add__(self, o):
        o = dd_from_any(o)
        s, e = _two_sum(self.hi, o.hi)
        e = e + self.lo + o.lo
        hi, lo = _two_sum(s, e)
        return DD(hi, lo)

    __radd__ = __add__

    def __neg__(self):
        return DD(-self.hi, -self.lo)

    def __sub__(self, o):
        return self + (-dd_from_any(o))

    def __rsub__(self, o):
        return dd_from_any(o) + (-self)

    def __mul__(self, o):
        o = dd_from_any(o)
        p, e = _two_prod(self.hi, o.hi)
        e = e + _rounded(self.hi * o.lo) + _rounded(self.lo * o.hi)
        hi, lo = _two_sum(p, e)
        return DD(hi, lo)

    __rmul__ = __mul__

    def __truediv__(self, o):
        o = dd_from_any(o)
        q1 = self.hi / o.hi
        # r = self - q1 * o, evaluated in dd
        p, e = _two_prod(q1, o.hi)
        r = self + DD(-p, -e - _rounded(q1 * o.lo))
        q2 = (r.hi + r.lo) / o.hi
        hi, lo = _two_sum(q1, q2)
        return DD(hi, lo)

    def __rtruediv__(self, o):
        return dd_from_any(o) / self

    def __mod__(self, o):
        # C fmod semantics (Spark %): r = a - trunc(a/b) * b, result
        # carries a's sign with |r| < |b|. Error bound ~ |a| * 2^-48
        # (the dd quotient's rounding scaled back by b) — large
        # quotients lose low bits, like any non-iterative fmod.
        # trunc of a dd value: truncate hi; only when hi is already
        # integral can lo still carry a fractional part that moves the
        # integer part (hi int, lo < 0).
        o = dd_from_any(o)
        q = self / o
        t = q.trunc()
        r = self - t * o
        # one correction step absorbs the dd division's ulp-level error
        babs = DD(jnp.abs(o.hi), jnp.where(o.hi < 0, -o.lo, o.lo))
        r_neg_wrong = (r.hi < 0) & (self.hi >= 0)
        r_pos_wrong = (r.hi > 0) & (self.hi < 0)
        r = DD(
            jnp.where(r_neg_wrong, (r + babs).hi, jnp.where(r_pos_wrong, (r - babs).hi, r.hi)),
            jnp.where(r_neg_wrong, (r + babs).lo, jnp.where(r_pos_wrong, (r - babs).lo, r.lo)),
        )
        too_big = jnp.abs(r.hi) >= jnp.abs(o.hi)
        sgn = jnp.where(r.hi < 0, jnp.float32(-1), jnp.float32(1))
        shrunk = r - DD(sgn * babs.hi, sgn * babs.lo)
        return DD(jnp.where(too_big, shrunk.hi, r.hi), jnp.where(too_big, shrunk.lo, r.lo))

    def __rmod__(self, o):
        return dd_from_any(o) % self

    # -- comparisons (lexicographic on the normalized pair) -----------------
    def __lt__(self, o):
        o = dd_from_any(o)
        return (self.hi < o.hi) | ((self.hi == o.hi) & (self.lo < o.lo))

    def __le__(self, o):
        o = dd_from_any(o)
        return (self.hi < o.hi) | ((self.hi == o.hi) & (self.lo <= o.lo))

    def __gt__(self, o):
        o = dd_from_any(o)
        return (o.hi < self.hi) | ((self.hi == o.hi) & (o.lo < self.lo))

    def __ge__(self, o):
        o = dd_from_any(o)
        return (o.hi < self.hi) | ((self.hi == o.hi) & (o.lo <= self.lo))

    def __eq__(self, o):  # noqa: A003 — SQL equality, not identity
        o = dd_from_any(o)
        return (self.hi == o.hi) & (self.lo == o.lo)

    def __ne__(self, o):
        return ~(self == o)

    __hash__ = None

    @property
    def shape(self):
        return self.hi.shape

    def trunc(self) -> "DD":
        """Truncate the PAIR VALUE toward zero (not the halves
        separately): when hi is already integral, a fractional lo of
        the opposite sign pulls the value past the integer, so the
        truncation steps hi by one."""
        t_hi = jnp.trunc(self.hi)
        t_lo = jnp.where(t_hi == self.hi, jnp.trunc(self.lo), jnp.float32(0))
        frac_lo = (t_hi == self.hi) & (self.lo != t_lo)
        adj = jnp.where(
            frac_lo & (self.hi > 0) & (self.lo < 0), jnp.float32(-1), jnp.float32(0)
        )
        adj = adj + jnp.where(
            frac_lo & (self.hi < 0) & (self.lo > 0), jnp.float32(1), jnp.float32(0)
        )
        return DD(t_hi, t_lo + adj)

    def astype(self, dtype):
        """Narrowing view for casts out of FLOAT64."""
        if jnp.issubdtype(dtype, jnp.integer):
            # truncate the pair value first (per-half truncation casts
            # 2.9999999999 to 3, not 2), then split across both halves
            # to keep ~48-bit integers exact
            t = self.trunc()
            return t.hi.astype(dtype) + t.lo.astype(dtype)
        return self.hi.astype(dtype)


def dd_from_any(x) -> DD:
    """Promote a scalar / f32 array / DD to DD.

    Python floats split exactly on the host (real f64 there); f32 arrays
    carry lo = 0 (exact)."""
    if isinstance(x, DD):
        return x
    if isinstance(x, (int, float)):
        import numpy as np

        hi = np.float32(x)
        lo = np.float32(float(x) - float(hi))
        return DD(jnp.float32(hi), jnp.float32(lo))
    arr = jnp.asarray(x)
    if jnp.issubdtype(arr.dtype, jnp.integer):
        # exact 2-term split of wide ints: hi holds the top 24 bits, the
        # integer residual (computed exactly in int64) rounds into lo —
        # ~48-bit coverage, vs 24 for a bare f32 cast
        wide = arr.astype(_I64)
        hi = wide.astype(jnp.float32)
        lo = (wide - hi.astype(_I64)).astype(jnp.float32)
        return DD(hi, lo)
    if arr.dtype != jnp.float32:
        arr = arr.astype(jnp.float32)
    return DD(arr, jnp.zeros_like(arr))


def dd_from_f64bits(bits: jnp.ndarray) -> DD:
    """FLOAT64 bit storage -> dd: hi = round-f32(x) (bitutils' integer
    construction), lo = round-f32(x - hi).

    The residual x - hi is computed EXACTLY in the integer domain (both
    mantissas aligned at x's scale) and then rounded to 24 bits, nearest
    even — the pair captures ~48 of f64's 53 mantissa bits (relative
    representation error <= 2^-49; a 2x(f32) pair cannot do better).
    |x| beyond f32 range saturates hi to +/-inf (same loss as the plain
    f32 path this replaces); residuals under the f32 normal floor flush
    to 0."""
    from .bitutils import _f64_bits_to_f32

    hi = _f64_bits_to_f32(bits)
    neg, e_eff, mant, is_nan, is_pinf, is_ninf = _decompose(bits)
    hb = lax.bitcast_convert_type(hi, _U32)
    he = ((hb >> _U32(23)) & _U32(0xFF)).astype(_I32)
    hfrac = (hb & _U32((1 << 23) - 1)).astype(_U64)
    hmant = jnp.where(he == 0, hfrac, hfrac | _u64(1 << 23))
    he_eff = jnp.where(he == 0, 1, he).astype(_I32)
    # |hi| = hmant * 2^(he_eff - 150); express at x's scale 2^(e_eff - 1075):
    # sigma ~ 29 (30 after a rounding carry); hmant << sigma fits u64
    sigma = (he_eff - 150) - (e_eff - 1075)
    hmant_scaled = hmant << jnp.clip(sigma, 0, 40).astype(_U64)
    r = mant.astype(_I64) - hmant_scaled.astype(_I64)  # exact, |r| <= 2^29
    # residual of the SIGNED value x - hi = sign(x) * r * 2^(e_eff-1075)
    r_neg = r < 0
    lo_neg = neg != r_neg
    ra = jnp.where(r_neg, -r, r).astype(_U64)

    # highest set bit of ra (ra < 2^40)
    msb = jnp.zeros(ra.shape, _I32)
    v = ra
    for shift in (32, 16, 8, 4, 2, 1):
        big = v >= (_u64(1) << _u64(shift))
        msb = jnp.where(big, msb + shift, msb)
        v = jnp.where(big, v >> _u64(shift), v)

    # round ra to 24 bits, nearest even (residuals carry up to 29
    # significant bits — the unavoidable f64 -> 2xf32 truncation)
    sh = jnp.maximum(msb - 23, 0)
    shc = jnp.clip(sh, 0, 63).astype(_U64)
    keep = ra >> shc
    rem_mask = (_u64(1) << shc) - _u64(1)
    dropped = ra & rem_mask
    half = jnp.where(sh > 0, _u64(1) << jnp.clip(sh - 1, 0, 63).astype(_U64), _u64(0))
    round_up = (sh > 0) & (
        (dropped > half) | ((dropped == half) & ((keep & _u64(1)) == 1))
    )
    keep = keep + round_up.astype(_U64)
    carry = keep >> _u64(24) != 0
    keep = jnp.where(carry, keep >> _u64(1), keep)
    sh = sh + carry.astype(_I32)
    # msb after rounding, at ra's scale: rounded residuals are 24-bit
    # normalized (msb 23 + sh); short ones (sh == 0) keep their true msb
    msb_r = jnp.where(sh > 0, 23 + sh, msb)

    lo_exp = msb_r + (e_eff - 1075) + 127  # biased f32 exponent of the residual
    # left-align short residuals to the 24-bit mantissa position
    up = jnp.clip(23 - msb, 0, 63)
    m24 = jnp.where(sh > 0, keep, keep << up.astype(_U64))
    lo_bits = (
        jnp.clip(lo_exp, 1, 254).astype(_U32) << _U32(23)
    ) | (m24.astype(_U32) & _U32((1 << 23) - 1))
    lo_sign = jnp.where(lo_neg, _U32(0x80000000), _U32(0))
    lo = lax.bitcast_convert_type(lo_bits | lo_sign, jnp.float32)
    lo = jnp.where((ra == 0) | (lo_exp < 1) | (lo_exp > 254), jnp.float32(0), lo)
    lo = jnp.where(is_nan | is_pinf | is_ninf | (he == 0xFF), jnp.float32(0), lo)
    return DD(hi, lo)


def add2_f64bits(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Correctly rounded f64 sum of two bit-stored doubles, ELEMENTWISE.

    The windowed accumulator with one segment per element is a scatter
    over [2N] rows — measured ~0.34 s/iter at 1M rows inside the fused
    pipelines (the round-4 flagship regression).
    A two-addend sum needs no window at all: align the smaller mantissa
    into an 8-bit guard extension of the larger (61 bits total, flat
    u64 lanes), fold bits beyond the guard into a sticky (for effective
    subtraction the floor correction R-1 keeps the value bracketed:
    gap >= guard implies at most one bit of cancellation, so the
    rounding position stays above the guard LSB and the sticky is
    exact), then round to nearest-even with the shared subnormal /
    overflow handling. Pure elementwise integer ops — bit-identical on
    every backend, verified against real-f64 hardware addition on the
    CPU tier (tests).
    """
    GUARD = 8
    neg_a, e_a, m_a, nan_a, pinf_a, ninf_a = _decompose(a)
    neg_b, e_b, m_b, nan_b, pinf_b, ninf_b = _decompose(b)

    a_big = (e_a > e_b) | ((e_a == e_b) & (m_a >= m_b))
    e_big = jnp.where(a_big, e_a, e_b)
    m_big = jnp.where(a_big, m_a, m_b)
    neg_big = jnp.where(a_big, neg_a, neg_b)
    e_sm = jnp.where(a_big, e_b, e_a)
    m_sm = jnp.where(a_big, m_b, m_a)
    neg_sm = jnp.where(a_big, neg_b, neg_a)

    gap = e_big - e_sm  # >= 0
    big = m_big << _u64(GUARD)  # <= 61 bits
    sh_r = jnp.clip(gap - GUARD, 0, 63).astype(_U64)
    sh_l = jnp.clip(GUARD - gap, 0, GUARD).astype(_U64)
    aligned = jnp.where(gap >= GUARD, m_sm >> sh_r, m_sm << sh_l)
    dropped = jnp.where(gap >= GUARD, m_sm & ((_u64(1) << sh_r) - _u64(1)), _u64(0))
    sticky = dropped != 0

    same_sign = neg_big == neg_sm
    r = jnp.where(same_sign, big + aligned, big - aligned)
    # effective subtraction with dropped bits: true value is r - frac,
    # frac in (0,1) guard-LSB units -> floor is r-1 with sticky kept
    r = jnp.where(~same_sign & sticky, r - _u64(1), r)

    # highest set bit of r (<= 61)
    p = jnp.zeros(r.shape, _I32)
    v = r
    for shift in (32, 16, 8, 4, 2, 1):
        bigger = v >= (_u64(1) << _u64(shift))
        p = jnp.where(bigger, p + shift, p)
        v = jnp.where(bigger, v >> _u64(shift), v)

    # drop q bits to land a 53-bit mantissa; the subnormal floor pins
    # E_res = e_big - GUARD + q >= 1
    q = jnp.maximum(p - 52, 1 + GUARD - e_big)
    q_pos = jnp.clip(q, 0, 63).astype(_U64)
    keep_r = r >> q_pos
    gmask = (_u64(1) << q_pos) - _u64(1)
    low = r & gmask
    half = jnp.where(q > 0, _u64(1) << jnp.clip(q - 1, 0, 63).astype(_U64), _u64(0))
    round_up = (q > 0) & (
        (low > half) | ((low == half) & (sticky | ((keep_r & _u64(1)) == 1)))
    )
    keep_r = keep_r + round_up.astype(_U64)
    keep_l = r << jnp.clip(-q, 0, 63).astype(_U64)
    keep = jnp.where(q > 0, keep_r, keep_l)
    ovf = keep >> _u64(53) != 0
    keep = jnp.where(ovf, keep >> _u64(1), keep)
    q = q + ovf.astype(_I32)

    e_res = e_big - GUARD + q
    subnormal = keep < _u64(1 << 52)
    biased = jnp.clip(e_res, 0, 0x7FF).astype(_U64)
    bits = jnp.where(
        subnormal, keep, (biased << _u64(52)) | (keep & _u64((1 << 52) - 1))
    )
    inf_bits = _u64(0x7FF) << _u64(52)
    bits = jnp.where((~subnormal) & (e_res >= 0x7FF), inf_bits, bits)
    zero = r == 0
    bits = jnp.where(zero, _u64(0), bits)
    bits = bits | jnp.where(neg_big & ~zero, _u64(1) << _u64(63), _u64(0))

    # IEEE specials: NaN dominates; inf +/- finite = inf; inf - inf = NaN
    has_pinf = pinf_a | pinf_b
    has_ninf = ninf_a | ninf_b
    bits = jnp.where(has_pinf & ~has_ninf, inf_bits, bits)
    bits = jnp.where(has_ninf & ~has_pinf, inf_bits | (_u64(1) << _u64(63)), bits)
    bits = jnp.where(nan_a | nan_b | (has_pinf & has_ninf), inf_bits | _u64(1 << 51), bits)
    return bits


def dd_to_f64bits(x: DD) -> jnp.ndarray:
    """dd -> FLOAT64 bits, exactly: widen each half losslessly to f64
    bits and round their exact pair-sum once through the elementwise
    two-addend adder."""
    from .bitutils import _f32_to_f64_bits

    return add2_f64bits(_f32_to_f64_bits(x.hi), _f32_to_f64_bits(x.lo))
