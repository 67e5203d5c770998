"""Pallas TPU kernel tier for hot ops (ISSUE 13).

Residents:

- the shuffle partitioner — murmur3(key) pmod P fused in one VMEM pass,
- the bounded-domain GROUP BY SUM MXU kernels (one-hot / outer-product),
- the PAGED HASH JOIN build/probe pair (``build_paged_table`` /
  ``pallas_probe_paged``): the Ragged-Paged-Attention page discipline
  (arxiv 2604.15464) applied to equi-joins — build partitions keys into
  fixed 128-slot pages with contiguous overflow chaining, probe streams
  the probe side through the VMEM-resident page table in one fused pass
  emitting per-row match ranges,
- the FUSED RAGGED DECODE kernel (``pallas_ragged_compact``): the
  Mosaic escalation for the 1M x 155 decode axis —
  offset walk (owner resolution), windowed byte gather, boundary
  masking, and head merge in ONE pass over a scalar-prefetched pool
  window, replacing the XLA formulation's three N-row scatter passes
  and per-column HBM intermediates.

Every kernel keeps an interpret-mode path (``interpret=True``) so the
hermetic CPU test tier exercises the same kernel bodies, and every
caller dispatches through ``kernel_tier_mode``. A shape outside a
kernel's caps (a ``None`` return) selects the XLA formulation; an
exception from a kernel propagates — a refusal by the chip's compiler
must be seen (see utils/dispatch.note_tier for the tier observability).

Bit-exactness: the partitioner matches ops/hashing.murmur3_raw, the
join pair matches ops/join.join_gather_maps, the decode kernel matches
ops/ragged_bytes.ragged_compact (tests cross-check all three in
interpret mode on CPU).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import knobs, tracing

_VMEM = pltpu.VMEM

__all__ = [
    "pallas_partition_map",
    "pallas_groupby_sum_bounded",
    "pallas_groupby_sum_outer",
    "on_tpu",
    "kernel_tier_mode",
    "PagedHashTable",
    "build_paged_table",
    "pallas_probe_paged",
    "pallas_decode_probe",
    "pallas_ragged_compact",
]

_LANES = 128
_BLOCK_ROWS = 512  # 512x128 u32 block = 256KB/input plane in VMEM


def _pow2_ceil(v: int) -> int:
    p = 1
    while p < v:
        p *= 2
    return p


# Memoized backend probe (the memory.device_memory_budget pattern): it
# sits on the per-dispatch hot path of every tiered op, and
# ``jax.default_backend()`` re-walks the backend registry on every
# call. The backend cannot change within a process, so one probe is
# sound; ``_reset_probe_cache`` is the test hook.
_ON_TPU: "bool | None" = None


def on_tpu() -> bool:
    """True when the default jax backend is a real TPU (memoized)."""
    global _ON_TPU
    if _ON_TPU is None:
        _ON_TPU = jax.default_backend() == "tpu"
    return _ON_TPU


def _reset_probe_cache() -> None:
    global _ON_TPU
    _ON_TPU = None


def kernel_tier_mode(knob_name: str) -> str:
    """Per-op kernel-tier dispatch decision, shared by every tiered op.

    Returns ``"tpu"`` (compiled kernels), ``"interpret"`` (forced
    through the Pallas interpreter off-TPU — the hermetic CI posture,
    ``SRJT_PALLAS_INTERPRET=1``), or ``""`` (XLA formulation). The
    per-op knob (``SRJT_PALLAS_JOIN`` / ``SRJT_PALLAS_DECODE``) is read
    LIVE (the knob-registry test/operator contract); the backend probe
    is memoized."""
    if not knobs.get_bool(knob_name):
        return ""
    if on_tpu():
        return "tpu"
    if knobs.get_bool("SRJT_PALLAS_INTERPRET"):
        return "interpret"
    return ""


def _mix_k(k):
    k = k * jnp.uint32(0xCC9E2D51)
    k = (k << jnp.uint32(15)) | (k >> jnp.uint32(17))
    return k * jnp.uint32(0x1B873593)


def _mix_h(h, k):
    h = h ^ _mix_k(k)
    h = (h << jnp.uint32(13)) | (h >> jnp.uint32(19))
    return h * jnp.uint32(5) + jnp.uint32(0xE6546B64)


def _fmix(h):
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> jnp.uint32(16))


def _partition_kernel_2word(lo_ref, hi_ref, out_ref, *, num_partitions: int):
    h = jnp.full(lo_ref.shape, 42, jnp.uint32)
    h = _mix_h(h, lo_ref[:])
    h = _mix_h(h, hi_ref[:])
    h = _fmix(h ^ jnp.uint32(8))
    signed = h.astype(jnp.int32)
    m = signed % jnp.int32(num_partitions)
    out_ref[:] = jnp.where(m < 0, m + num_partitions, m)


def _partition_kernel_1word(w_ref, out_ref, *, num_partitions: int):
    h = jnp.full(w_ref.shape, 42, jnp.uint32)
    h = _mix_h(h, w_ref[:])
    h = _fmix(h ^ jnp.uint32(4))
    signed = h.astype(jnp.int32)
    m = signed % jnp.int32(num_partitions)
    out_ref[:] = jnp.where(m < 0, m + num_partitions, m)


def _pad_to_tiles(plane: jnp.ndarray) -> jnp.ndarray:
    """[N] u32 -> [rows, 128] u32 with rows a multiple of _BLOCK_ROWS."""
    n = plane.shape[0]
    rows = max((n + _LANES - 1) // _LANES, 1)
    rows = (rows + _BLOCK_ROWS - 1) // _BLOCK_ROWS * _BLOCK_ROWS
    padded = jnp.zeros((rows * _LANES,), jnp.uint32).at[:n].set(plane)
    return padded.reshape(rows, _LANES)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _run(planes, num_partitions: int, interpret: bool):
    two = len(planes) == 2
    rows = planes[0].shape[0]
    grid = (rows // _BLOCK_ROWS,)
    # index map returns must be uniformly i32: with jax_enable_x64 the
    # bare literal 0 traces as i64 and Mosaic fails to legalize the
    # mixed-width return
    spec = pl.BlockSpec(
        (_BLOCK_ROWS, _LANES),
        lambda i: (i, jnp.int32(0)),
        memory_space=_VMEM if not interpret else None,
    )
    kernel = (
        functools.partial(_partition_kernel_2word, num_partitions=num_partitions)
        if two
        else functools.partial(_partition_kernel_1word, num_partitions=num_partitions)
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        grid=grid,
        in_specs=[spec] * len(planes),
        out_specs=spec,
        interpret=interpret,
    )(*planes)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _partition_map_impl(keys, num_partitions: int, interpret: bool):
    from jax import lax

    n = keys.shape[0]
    if keys.dtype.itemsize == 8:
        u = lax.bitcast_convert_type(keys, jnp.uint32)  # [N, 2]
        planes = (_pad_to_tiles(u[:, 0]), _pad_to_tiles(u[:, 1]))
    else:
        signed = keys.astype(jnp.int32)
        planes = (_pad_to_tiles(lax.bitcast_convert_type(signed, jnp.uint32)),)
    out = _run(planes, num_partitions, interpret)
    return out.reshape(-1)[:n]


def pallas_partition_map(
    keys: jnp.ndarray, num_partitions: int, interpret: bool = False
) -> jnp.ndarray:
    """[N] int32/int64 keys -> [N] int32 partition ids, bit-exact with
    hash_partition_map on a single int column.

    interpret=True runs the kernel in the Pallas interpreter (hermetic
    CPU testing); on TPU leave it False for the compiled kernel. The
    whole path (lane split, tile pad, kernel, unpad) is one compiled
    program — eager prep dispatches would dominate on remote backends.
    """
    if keys.dtype.itemsize not in (4, 8):
        raise ValueError(f"pallas_partition_map supports 4/8-byte keys, got {keys.dtype}")
    return _partition_map_impl(keys, int(num_partitions), bool(interpret))


# ---------------------------------------------------------------------------
# bounded-domain GROUP BY SUM on the MXU
# ---------------------------------------------------------------------------
#
# TPUs have no fast scatter: jax.ops.segment_sum over 1M rows costs ~7ms
# (XLA serializes the scatter-add), and an XLA one-hot matmul pays K*N*4
# bytes of HBM traffic just materializing the one-hot. This kernel builds
# each one-hot tile IN VMEM and contracts it on the MXU immediately —
# the one-hot never touches HBM.
#
# Measured (v5e, 1M rows x 4096 keys): ~matches the scatter path
# (~150 Mrows/s) rather than beating it — the [1, 256] x [256, K]
# contraction is a matvec (M=1), which uses 1/128 of the MXU, and
# Precision.HIGHEST (needed for f32-exact sums) triples the passes.
# Next step when this op matters: batch 128 row-chunks into one
# [128, 256] x [256, K] block-diagonal contraction per grid step, or
# specialize K <= 128 where a full-width matmul applies.

_GB_CHUNK = 256  # columns of each (8, 256) row block; one-hot tile [256, K]
_GB_SUBLANES = 8  # TPU block sublane quantum


def _groupby_kernel(k_ref, v_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    kpad = out_ref.shape[1]
    cols = jax.lax.broadcasted_iota(jnp.int32, (_GB_CHUNK, kpad), 1)
    # static unroll over the 8 sublanes: each [256, Kpad] one-hot tile
    # lives only in VMEM/registers; rows with out-of-domain keys (incl.
    # the padding sentinel) match no column and vanish
    for s in range(_GB_SUBLANES):
        oh = (k_ref[s, :].reshape(-1, 1) == cols).astype(jnp.float32)
        # HIGHEST: the MXU's default single-pass bf16 loses ~3 decimal
        # digits; the 3-pass f32 emulation keeps sums exact to f32 ulp
        dot = jax.lax.dot_general(
            v_ref[s, :].reshape(1, -1),
            oh,
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        # accumulate straight into the revisited output block: no
        # scratch buffer, so interpret mode needs no TPU plugin
        out_ref[s : s + 1, :] += dot


@functools.partial(jax.jit, static_argnums=(2, 3))
def _groupby_impl(keys, vals, num_keys: int, interpret: bool):
    n = keys.shape[0]
    kpad = max((num_keys + _LANES - 1) // _LANES * _LANES, _LANES)
    step_rows = _GB_SUBLANES * _GB_CHUNK
    m = max((n + step_rows - 1) // step_rows, 1)  # grid=(0,) never runs
    total = m * step_rows
    # domain check BEFORE any narrowing cast: int64 keys >= 2^32 must
    # drop, not wrap into the valid domain
    in_domain = (keys >= 0) & (keys < num_keys)
    keys32 = jnp.where(in_domain, keys, -1).astype(jnp.int32)
    # pad with an out-of-domain sentinel so padding rows sum nowhere
    kp = jnp.full((total,), -1, jnp.int32).at[:n].set(keys32)
    vp = jnp.zeros((total,), jnp.float32).at[:n].set(vals.astype(jnp.float32))
    kp = kp.reshape(m * _GB_SUBLANES, _GB_CHUNK)
    vp = vp.reshape(m * _GB_SUBLANES, _GB_CHUNK)

    row_spec = pl.BlockSpec(
        (_GB_SUBLANES, _GB_CHUNK),
        lambda i: (i, jnp.int32(0)),
        memory_space=_VMEM if not interpret else None,
    )
    out_spec = pl.BlockSpec(
        (_GB_SUBLANES, kpad),
        lambda i: (jnp.int32(0), jnp.int32(0)),
        memory_space=_VMEM if not interpret else None,
    )
    out = pl.pallas_call(
        _groupby_kernel,
        out_shape=jax.ShapeDtypeStruct((_GB_SUBLANES, kpad), jnp.float32),
        grid=(m,),
        in_specs=[row_spec, row_spec],
        out_specs=out_spec,
        interpret=interpret,
    )(kp, vp)
    # 8 sublane partial accumulators -> final sums
    return jnp.sum(out, axis=0)[:num_keys]


# ---------------------------------------------------------------------------
# outer-product GROUP BY SUM: full-width MXU formulation
# ---------------------------------------------------------------------------
#
# The kernel above is a matvec (M=1) and wastes 127/128 of the MXU.
# This one restores the M dimension with the histogram outer-product
# decomposition: write key = hi*128 + lo, then
#
#   sums[hi, lo]   = sum_i vals[i] * OH_hi[i, hi] * OH_lo[i, lo]
#   counts[hi, lo] = sum_i           OH_hi[i, hi] * OH_lo[i, lo]
#
# i.e. ONE [4H, NT] x [NT, 128] matmul per row block:
#   lhs = [A1 | A2 | A3 | C] with A_k = v_k-weighted hi-one-hot and C
#   the unweighted hi-one-hot, rhs = lo-one-hot. v is split into three
#   bf16 limbs (v = v1+v2+v3 captures all 24 f32 mantissa bits), and
#   the rhs one-hot is exactly representable in bf16, so each MXU
#   product is exact and the f32 accumulator gives segment_sum-class
#   accuracy — at single-pass bf16 speed, with H=32 (num_keys=4096)
#   filling the MXU's M dimension (4H=128).
#
# Both one-hots live only in VMEM; HBM traffic is just keys+vals.

_OUTER_NT_MAX = 8192  # rows contracted per grid step (the dot's K dim).
# The transposed build keeps one [4H, NT] lhs, one [128, NT] rhs and one
# [H, NT] cmp tile live — (5H + 128) * 2 bytes per row — so NT scales
# down as the key domain grows. v5e-measured (1M rows, chained): K=4096
# NT 2048/4096/8192 -> 4.1/5.2/6.7 Grows/s; K=16384 NT=8192 -> 1.75
# Grows/s; K=65536 NT=2048 -> 0.38 Grows/s (scatter: 0.15).
_OUTER_VMEM_BUDGET = 13_000_000  # bytes of live kernel tiles that fit


def _outer_nt(H: int) -> int:
    per_row = (5 * H + _LANES) * 2
    nt = _OUTER_VMEM_BUDGET // per_row
    p = 512
    while p * 2 <= min(nt, _OUTER_NT_MAX):
        p *= 2
    return p


def _outer_kernel(k_ref, v_ref, out_ref, *, H: int):
    """One full-width MXU contraction per grid step, everything built in
    the keys' NATIVE row orientation.

    The round-2 kernel spent its time on layout, not math: each sublane
    step paid a [NT] -> [NT, 1] lane->sublane relayout to build one-hots
    and a lane-axis concatenate to assemble the lhs, then issued a small
    dot. Here keys arrive as a [1, NT] row; both one-hots broadcast that
    row across SUBLANES (free) against a dim-0 iota, the limb concat
    stacks along sublanes (tile-aligned), and the dot contracts both
    operands on their last dim — lhsT [4H, NT] x rhsT [128, NT] ->
    [4H, 128] — which the MXU consumes directly.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    k = k_ref[0]  # [1, NT] i32 (pre-mapped to [0, H*128) + trash H*128)
    v = v_ref[0]  # [1, NT] f32
    nt = k.shape[1]

    hi = k >> 7
    lo = k & 127
    iota_h = jax.lax.broadcasted_iota(jnp.int32, (H, nt), 0)
    iota_l = jax.lax.broadcasted_iota(jnp.int32, (_LANES, nt), 0)
    # single bool->bf16 consumer, then multiplies: Mosaic rejects the
    # multi-consumer broadcast i1 relayout a where-chain needs, and
    # one-hot products are exact either way (factors are 0/1)
    cmp = (jnp.broadcast_to(hi, (H, nt)) == iota_h).astype(jnp.bfloat16)  # [H, NT]
    rhsT = (jnp.broadcast_to(lo, (_LANES, nt)) == iota_l).astype(jnp.bfloat16)  # [128, NT]

    # v = v1 + v2 + v3 in bf16 limbs captures all 24 f32 mantissa bits;
    # each limb and each one-hot entry is exactly representable in bf16,
    # so every MXU product is exact and the f32 accumulator gives
    # segment_sum-class accuracy at single-pass bf16 speed.
    v1 = v.astype(jnp.bfloat16)
    r1 = v - v1.astype(jnp.float32)
    v2 = r1.astype(jnp.bfloat16)
    v3 = (r1 - v2.astype(jnp.float32)).astype(jnp.bfloat16)
    lhsT = jnp.concatenate(
        [cmp * v1, cmp * v2, cmp * v3, cmp],
        axis=0,
    )  # [4H, NT] — sublane-axis concat: tile stacking, no relayout
    out_ref[...] += jax.lax.dot_general(
        lhsT, rhsT, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [4H, 128]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _outer_impl(keys, vals, num_keys: int, interpret: bool):
    n = keys.shape[0]
    H = max((num_keys + _LANES - 1) // _LANES, 1)  # ceil(num_keys/128)
    # out-of-domain/padding rows map to hi == H: outside the hi-one-hot,
    # so they match no column and vanish (no in-matrix trash slot, which
    # would force a 128-misaligned H)
    trash = H * _LANES
    in_domain = (keys >= 0) & (keys < num_keys)
    seg = jnp.where(in_domain, keys, trash).astype(jnp.int32)

    nt = _outer_nt(H)
    g = max((n + nt - 1) // nt, 1)
    total = g * nt
    # [g, 1, NT]: blocks index the leading dim; the trailing (1, NT)
    # equals the array's own trailing dims (the tiling rule Mosaic
    # requires for non-(8,128)-divisible blocks)
    kp = jnp.full((total,), trash, jnp.int32).at[:n].set(seg).reshape(g, 1, nt)
    vp = (
        jnp.zeros((total,), jnp.float32)
        .at[:n]
        .set(vals.astype(jnp.float32))
        .reshape(g, 1, nt)
    )

    row_spec = pl.BlockSpec(
        (1, 1, nt),
        lambda i: (i, jnp.int32(0), jnp.int32(0)),
        memory_space=_VMEM if not interpret else None,
    )
    out_spec = pl.BlockSpec(
        (4 * H, _LANES),
        lambda i: (jnp.int32(0), jnp.int32(0)),
        memory_space=_VMEM if not interpret else None,
    )
    out = pl.pallas_call(
        functools.partial(_outer_kernel, H=H),
        out_shape=jax.ShapeDtypeStruct((4 * H, _LANES), jnp.float32),
        grid=(g,),
        in_specs=[row_spec, row_spec],
        out_specs=out_spec,
        interpret=interpret,
    )(kp, vp)
    sums = (out[:H] + out[H : 2 * H] + out[2 * H : 3 * H]).reshape(H * _LANES)[:num_keys]
    counts = out[3 * H :].reshape(H * _LANES)[:num_keys].astype(jnp.int64)
    return sums, counts


def pallas_groupby_sum_outer(
    keys: jnp.ndarray, vals: jnp.ndarray, num_keys: int, interpret: bool = False
):
    """GROUP BY SUM + COUNT over a bounded key domain [0, num_keys) as a
    full-width MXU outer-product contraction. float32 sums, exact
    int64-safe counts (f32 accumulator: exact below 2^24 rows/key).

    Returns (sums[num_keys] f32, counts[num_keys] i64); out-of-domain
    keys are dropped. num_keys <= 65536: the contraction length NT
    scales down as H grows (see _outer_nt) and past H=512 the one-hot
    work amplification (2*4H*128 FLOPs/row) loses to the scatter path.
    """
    if num_keys > 65536:
        raise ValueError("pallas_groupby_sum_outer supports num_keys <= 65536")
    return _outer_impl(keys, vals, int(num_keys), bool(interpret))


def pallas_groupby_sum_bounded(
    keys: jnp.ndarray, vals: jnp.ndarray, num_keys: int, interpret: bool = False
) -> jnp.ndarray:
    """GROUP BY SUM over a bounded key domain [0, num_keys), one-hot
    matmul on the MXU with VMEM-resident tiles. float32 sums.

    Matches ops.aggregate.groupby_sum_bounded's sums (float path) for
    in-domain keys; out-of-domain keys are dropped.
    """
    if num_keys > 4096:
        raise ValueError("pallas_groupby_sum_bounded supports num_keys <= 4096 (VMEM tile)")
    return _groupby_impl(keys, vals, int(num_keys), bool(interpret))


# ---------------------------------------------------------------------------
# paged hash-table JOIN build/probe (the RPA page discipline)
# ---------------------------------------------------------------------------
#
# XLA has no device hash table, so ops/join.py's formulation sorts the
# CONCATENATED key tables (nl + nr rows, multi-pass) per join. Ragged
# Paged Attention's answer to ragged lookups on TPU is fixed-size
# on-chip pages with overflow chaining; applied to an equi-join:
#
# BUILD (XLA prep, build-side scale only): bucket = mix(key) & (B-1);
# build rows sort by (bucket, key) — two stable single-key argsorts,
# not the probe-side multi-column sort — and fill fixed 128-slot pages
# allocated CONTIGUOUSLY per bucket, so a bucket's overflow chain is
# page_first[b] .. page_first[b] + chain_len[b) (the chain pointer is
# the implicit +1). Because slots within a bucket are (key, row)-
# sorted, a probe's matches are one CONTIGUOUS slot range.
#
# PROBE (the Pallas kernel): the whole page table lives in VMEM as u8
# LIMB PLANES in bf16 ([nlimb * n_pages, 128]; 0..255 and the empty
# sentinel 320 are bf16-exact, so one-hot MXU products are exact). Per
# (probe block, chain step) the kernel builds the [BLK, n_pages] page
# one-hot, gathers the chain page's limbs with nlimb MXU contractions,
# and accumulates per-row counts of slots with key < probe (lt) and
# key == probe (eq) via a lexicographic limb compare — so each probe
# row leaves the kernel with its match range [start[bucket] + lt,
# start[bucket] + lt + eq) over the page-sorted build order, and the
# shared join expansion emits gather maps BIT-IDENTICAL to the XLA
# formulation (stable sorts tie-break equal keys by original row on
# both paths).
#
# Work shape: one chain step costs nlimb [BLK, n_pages] x [n_pages,
# 128] bf16 matmuls — the one-hot gather's N_probe x n_pages work
# amplification means the tier targets DIMENSION-TABLE builds (the
# TPC-DS star shape): n_pages is capped, and pathological skew (every
# key in one bucket) stays correct but pays chain_len grid steps.

_PJ_PAGE = _LANES  # slots per page: one lane row
_PJ_BLK = 256  # probe rows per grid step
_PJ_MAX_BUILD = 1 << 16  # build rows the page table will hold
_PJ_MAX_PAGES = 2048  # VMEM cap: 8 limb planes x 2048 pages x 128 x 2B = 4MB
_PJ_BUCKET_TARGET = 64  # average build rows per bucket
_PJ_MAX_BUCKETS = 2048
_PJ_EMPTY = 320.0  # empty-slot sentinel limb: > any u8 limb, bf16-exact


class PagedHashTable(NamedTuple):
    """Build-side page table (see the module comment for the layout)."""

    limbs: jnp.ndarray  # [nlimb * n_pages, 128] bf16 u8-limb planes, MS limb first
    meta: jnp.ndarray  # [B] i64 packed (page_first << 44 | chain_len << 24 | slot_start)
    r_order: jnp.ndarray  # [nm] i32: page-sorted rank -> original build row
    num_buckets: int
    n_pages: int
    nlimb: int
    c_max: int  # longest overflow chain, rounded up to a power of two
    nm: int  # matchable (non-null) build rows


def _order_map_u(keys: jnp.ndarray) -> jnp.ndarray:
    """[N] integer keys -> order-preserving unsigned words (u32 for
    widths <= 4, u64 for 8): unsigned compare in limb space must agree
    with the key dtype's native order."""
    dt_ = keys.dtype
    signed = jnp.issubdtype(dt_, jnp.signedinteger)
    if dt_.itemsize < 4:
        keys = keys.astype(jnp.int32 if signed else jnp.uint32)
        dt_ = keys.dtype
    if dt_.itemsize == 4:
        u = lax.bitcast_convert_type(keys, jnp.uint32)
        return u ^ jnp.uint32(0x80000000) if signed else u
    u = lax.bitcast_convert_type(keys, jnp.uint64)
    return u ^ jnp.uint64(1 << 63) if signed else u


def _bucket_of(u: jnp.ndarray, num_buckets: int) -> jnp.ndarray:
    """[N] order words -> [N] i32 bucket ids in [0, B). Identical on
    the build and probe sides by construction (same function)."""
    if u.dtype == jnp.uint64:
        lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
        h = _fmix(lo ^ _fmix(hi))
    else:
        h = _fmix(u)
    return (h & jnp.uint32(num_buckets - 1)).astype(jnp.int32)


def _limb_val(u: jnp.ndarray, l: int, nlimb: int) -> jnp.ndarray:
    """Most-significant-first u8 limb ``l`` of the order words."""
    sh = 8 * (nlimb - 1 - l)
    one = jnp.uint64(sh) if u.dtype == jnp.uint64 else jnp.uint32(sh)
    mask = jnp.uint64(0xFF) if u.dtype == jnp.uint64 else jnp.uint32(0xFF)
    return (u >> one) & mask


def build_paged_table(
    keys: jnp.ndarray, valid: Optional[jnp.ndarray] = None
) -> Optional[PagedHashTable]:
    """Partition build-side keys into fixed 128-slot pages with
    contiguous overflow chaining. Returns None when the build side is
    empty, all-null, or over the page-table caps — the caller's signal
    to keep the XLA formulation (selection by shape). Eager-context
    only (ONE stacked host sync: matchable rows, page count, longest
    chain)."""
    n = int(keys.shape[0])
    if n == 0 or n > _PJ_MAX_BUILD:
        return None
    u = _order_map_u(keys)
    nlimb = 8 if u.dtype == jnp.uint64 else 4
    # bucket sizing uses n (nm is still on-device here): at most one
    # doubling of oversize when the build side is null-heavy — empty
    # buckets cost a metadata row, never a page
    num_buckets = 16
    while num_buckets * _PJ_BUCKET_TARGET < n and num_buckets < _PJ_MAX_BUCKETS:
        num_buckets *= 2
    bucket = _bucket_of(u, num_buckets)
    if valid is not None:
        # null build keys never match: park them past the last bucket
        bucket = jnp.where(valid, bucket, jnp.int32(num_buckets))
    # (bucket, key, row) total order from two stable argsorts: sort by
    # key first, then stably by bucket — equal (bucket, key) ties keep
    # original row order, the property the bit-identity proof needs
    perm1 = jnp.argsort(u, stable=True).astype(jnp.int32)
    perm = perm1[jnp.argsort(bucket[perm1], stable=True)].astype(jnp.int32)
    bs_full = bucket[perm]  # nulls parked at bucket B sort LAST, so
    # per-bucket counts over the full array already exclude them

    bids = jnp.arange(num_buckets, dtype=jnp.int32)
    starts = jnp.searchsorted(bs_full, bids, side="left").astype(jnp.int32)
    ends = jnp.searchsorted(bs_full, bids, side="right").astype(jnp.int32)
    cnt = ends - starts
    pages_b = (cnt + _PJ_PAGE - 1) // _PJ_PAGE
    page_first = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(pages_b, dtype=jnp.int32)]
    )
    # ONE stacked host sync for every scalar the build needs (matchable
    # rows, table allocation size, longest chain) — three separate
    # pulls would be three host↔device round trips
    nm_dev = (
        jnp.int32(n) if valid is None else jnp.sum(valid, dtype=jnp.int32)
    )
    nm, n_pages, c_max = (
        int(x)
        for x in np.asarray(tracing.device_wait(
            jnp.stack([nm_dev, page_first[-1], jnp.max(pages_b)]), "paged_table"))
    )
    if nm == 0 or n_pages == 0 or n_pages > _PJ_MAX_PAGES:
        return None
    cp = _pow2_ceil(max(c_max, 1))  # pow2 chain grid keeps the probe
    # compile cache stable
    r_order = perm[:nm]
    bs = bs_full[:nm]
    u_sorted = u[perm][:nm]

    rank = jnp.arange(nm, dtype=jnp.int32) - starts[bs]
    slot = (page_first[bs] + rank // _PJ_PAGE) * _PJ_PAGE + rank % _PJ_PAGE
    planes = []
    for l in range(nlimb):
        init = _PJ_EMPTY if l == 0 else 0.0
        plane = (
            jnp.full((n_pages * _PJ_PAGE,), init, jnp.bfloat16)
            .at[slot]
            .set(_limb_val(u_sorted, l, nlimb).astype(jnp.bfloat16))
        )
        planes.append(plane.reshape(n_pages, _PJ_PAGE))
    limbs = jnp.concatenate(planes, axis=0)
    meta = (
        (page_first[:num_buckets].astype(jnp.int64) << 44)
        | (pages_b.astype(jnp.int64) << 24)
        | starts.astype(jnp.int64)
    )
    return PagedHashTable(limbs, meta, r_order, num_buckets, n_pages, nlimb, cp, nm)


def _probe_kernel(fp_ref, cl_ref, *rest, n_pages: int, nlimb: int, blk: int):
    pls = rest[:nlimb]
    tab_ref = rest[nlimb]
    o_ref = rest[nlimb + 1]
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        o_ref[:] = jnp.zeros_like(o_ref)

    fp = fp_ref[0].reshape(-1, 1)  # [1, BLK] -> [BLK, 1] (the _scal relayout)
    cl = cl_ref[0].reshape(-1, 1)
    pid = fp + c
    iota_p = lax.broadcasted_iota(jnp.int32, (blk, n_pages), 1)
    vmask = c < cl  # [BLK, 1]: rows whose chain still has a page at step c
    # single bool->bf16 consumer (the _outer_kernel Mosaic discipline);
    # one-hot entries are 0/1 and limbs <= 320, all bf16-exact, and each
    # one-hot row selects at most one page, so every MXU product and the
    # length-n_pages sum are exact in any precision
    oh = ((pid == iota_p) & vmask).astype(jnp.bfloat16)
    one = jnp.float32(1)
    zero = jnp.float32(0)
    lt = eq = None
    for l in range(nlimb):
        tl = tab_ref[l * n_pages : (l + 1) * n_pages, :]
        gl = lax.dot_general(
            oh, tl, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BLK, 128]: chain page l-limbs per probe row
        pv = pls[l][0].reshape(-1, 1)  # [BLK, 1] f32 probe limb
        ltk = jnp.where(gl < pv, one, zero)
        eqk = jnp.where(gl == pv, one, zero)
        if l == 0:
            lt, eq = ltk, eqk
        else:
            lt = lt + eq * ltk  # lexicographic: strictly-less at limb l
            eq = eq * eqk  # breaks any earlier all-equal prefix
    # invalid chain steps gathered all-zero limbs, which can spuriously
    # equal an all-zero probe key: mask by chain validity before summing
    lt_n = jnp.sum(jnp.where(vmask, lt, zero), axis=1, keepdims=True)
    eq_n = jnp.sum(jnp.where(vmask, eq, zero), axis=1, keepdims=True)
    upd = jnp.concatenate(
        [lt_n.reshape(1, 1, -1), eq_n.reshape(1, 1, -1)], axis=1
    )  # [1, 2, BLK]
    o_ref[...] += upd


@tracing.launches
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8))
def _probe_impl(
    u, lvalid, limbs, meta, num_buckets: int, n_pages: int, nlimb: int,
    c_grid: int, interpret: bool,
):
    n = u.shape[0]
    bucket = jnp.clip(_bucket_of(u, num_buckets), 0, num_buckets - 1)
    m = meta[bucket]  # ONE [N]-from-[B] element gather for all three fields
    fp = (m >> 44).astype(jnp.int32)
    cl = ((m >> 24) & 0xFFFFF).astype(jnp.int32)
    st = (m & 0xFFFFFF).astype(jnp.int32)
    cl = jnp.where(lvalid, cl, 0)  # null probe keys visit no pages

    g = max((n + _PJ_BLK - 1) // _PJ_BLK, 1)
    total = g * _PJ_BLK

    def pack_i(a):
        return (
            jnp.zeros((total,), jnp.int32).at[:n].set(a).reshape(g, 1, _PJ_BLK)
        )

    def pack_f(a):
        return (
            jnp.zeros((total,), jnp.float32).at[:n].set(a).reshape(g, 1, _PJ_BLK)
        )

    limb_ops = [
        pack_f(_limb_val(u, l, nlimb).astype(jnp.float32)) for l in range(nlimb)
    ]
    scal_spec = pl.BlockSpec(
        (1, 1, _PJ_BLK),
        lambda i, c: (i, jnp.int32(0), jnp.int32(0)),
        memory_space=_VMEM if not interpret else None,
    )
    tab_spec = pl.BlockSpec(
        (nlimb * n_pages, _PJ_PAGE),
        lambda i, c: (jnp.int32(0), jnp.int32(0)),
        memory_space=_VMEM if not interpret else None,
    )
    out_spec = pl.BlockSpec(
        (1, 2, _PJ_BLK),
        lambda i, c: (i, jnp.int32(0), jnp.int32(0)),
        memory_space=_VMEM if not interpret else None,
    )
    out = pl.pallas_call(
        functools.partial(
            _probe_kernel, n_pages=n_pages, nlimb=nlimb, blk=_PJ_BLK
        ),
        out_shape=jax.ShapeDtypeStruct((g, 2, _PJ_BLK), jnp.float32),
        grid=(g, c_grid),
        in_specs=[scal_spec] * (2 + nlimb) + [tab_spec],
        out_specs=out_spec,
        interpret=interpret,
    )(pack_i(fp), pack_i(cl), *limb_ops, limbs)
    lt = out[:, 0, :].reshape(-1)[:n].astype(jnp.int32)
    eq = out[:, 1, :].reshape(-1)[:n].astype(jnp.int32)
    return st + lt, eq


def pallas_probe_paged(
    keys: jnp.ndarray,
    valid: Optional[jnp.ndarray],
    table: PagedHashTable,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Stream probe keys through the page table: one fused pass per
    chain step. Returns ``(lo, eq)`` — probe row i matches build ranks
    ``r_order[lo[i] : lo[i] + eq[i]]`` (page-sorted order; equal keys
    keep original build-row order, matching the XLA join's stable
    argsort)."""
    u = _order_map_u(keys)
    nlimb = 8 if u.dtype == jnp.uint64 else 4
    if nlimb != table.nlimb:
        raise ValueError("probe key width does not match the build table")
    lvalid = (
        jnp.ones(keys.shape, bool) if valid is None else valid.astype(bool)
    )
    return _probe_impl(
        u, lvalid, table.limbs, table.meta, table.num_buckets, table.n_pages,
        table.nlimb, table.c_max, bool(interpret),
    )


# ---------------------------------------------------------------------------
# fused ragged DECODE (ragged_compact as one Mosaic kernel)
# ---------------------------------------------------------------------------
#
# ops/ragged_bytes.ragged_compact is the pure-XLA floor of the 1M x 155
# mixed decode axis: per string column it pays THREE N-row scatter passes (~40 ns/element each: the
# owner shift c_w, the boundary mask nb, the head-chunk add) plus two
# element gathers per output word, materializing every stage in HBM.
# This kernel is the escalation: per OUTPUT BLOCK of
# _PD_BLKW u32 words it holds the overlapping ROW WINDOW's metadata and
# a scalar-prefetched two-block POOL WINDOW in VMEM and resolves
# everything on-chip —
#
# - OWNER (the offset walk): c_w[w] = max c_row over window rows with
#   wfirst <= w — a dense masked max over [row_chunk, BLKW] tiles
#   (brute-force compare beats an HBM scatter; the owner row of every
#   word in the block provably lies inside the window),
# - BOUNDARY: nb[w] = min in-word boundary position, same dense min,
# - HEAD: sub-word head chunks of rows starting in the block, dense
#   masked sum (disjoint byte lanes by the dense-offsets contract),
# - FETCH: source words via two in-window dynamic gathers + a 4-way
#   funnel select (constant u32 shifts: no in-kernel i32<->u32
#   conversion, the Mosaic recursion hazard ragged_bytes documents).
#
# The pool window rides pltpu.PrefetchScalarGridSpec: block g fetches
# pool blocks [b_g, b_g + 2) of WINW words each, b_g data-dependent via
# the scalar-prefetched block vector — the RPA paged-fetch shape. WINW
# and the row-window size RW are probed per call (G-scale reduces, one
# host sync — or batched by the caller via ``hint``); inputs whose
# windows exceed the VMEM caps return None and the caller keeps the
# XLA formulation. Zero-length rows (null strings' validity) own no
# bytes and are masked out of all three resolutions.

_PD_BLKW = 512  # output u32 words per grid step (2 KB of output bytes)
_PD_ROW_CHUNK = 128  # row-window rows reduced per unrolled step
_PD_MAX_RW = 1024  # row-window cap (VMEM: [128, 512] i32 tiles per step)
_PD_MAX_WIN = 1 << 17  # pool-window cap in words (2 x 512 KB blocks in VMEM)
_PD_BIG = 0x3FFFFFFF  # parked word index: matches no real output word


@functools.partial(jax.jit, static_argnums=(2,))
def pallas_decode_probe(base, offs, total: int):
    """Static-shape probe for ``pallas_ragged_compact``: [2] i32 of
    (max rows overlapping any output block, max pool-window words any
    block needs). G-scale reduces only; callers batch several columns'
    probes into one host sync."""
    n = base.shape[0]
    nw = (total + 3) // 4
    g = max((nw + _PD_BLKW - 1) // _PD_BLKW, 1)
    w0 = jnp.arange(g, dtype=jnp.int64) * (_PD_BLKW * 4)
    rfirst = jnp.clip(
        jnp.searchsorted(offs[1:], w0, side="right"), 0, n - 1
    ).astype(jnp.int32)
    rlast = jnp.clip(
        jnp.searchsorted(offs[:-1], w0 + 4 * _PD_BLKW - 1, side="right") - 1,
        0, n - 1,
    ).astype(jnp.int32)
    rlast = jnp.maximum(rlast, rfirst)
    rw = jnp.max(rlast - rfirst + 1)
    b_rf = base[rfirst]
    wl = jnp.clip(b_rf - 4, 0, None) >> 2
    c_rl = base[rlast] - offs[rlast]
    span = ((c_rl + w0 + 4 * _PD_BLKW + 8) >> 2) - wl + 2
    return jnp.stack([rw.astype(jnp.int32), jnp.max(span).astype(jnp.int32)])


def _pd_kernel(
    bvec_ref, cr_ref, wf_ref, bw_ref, bp_ref, hw_ref, hc_ref, p0_ref, p1_ref,
    o_ref, *, blkw: int, rw: int, winw: int, rc_chunk: int,
):
    g = pl.program_id(0)
    wb = bvec_ref[g] * winw
    w = g * blkw + lax.broadcasted_iota(jnp.int32, (1, blkw), 1)
    crm = cr_ref[:]
    wfm = wf_ref[:]
    bwm = bw_ref[:]
    bpm = bp_ref[:]
    hwm = hw_ref[:]
    hcm = hc_ref[:]
    acc_c = jnp.zeros((1, blkw), jnp.int32)
    acc_nb = jnp.full((1, blkw), 4, jnp.int32)
    acc_h = jnp.zeros((1, blkw), jnp.uint32)
    # chunked row reduction (the _vacc_kernel VMEM discipline: each
    # [rc_chunk, blkw] tile's temps die before the next chunk)
    for k in range(rw // rc_chunk):
        sl = slice(k * rc_chunk, (k + 1) * rc_chunk)
        wfk = wfm[:, sl].reshape(-1, 1)  # [RC, 1] (the _scal relayout)
        crk = crm[:, sl].reshape(-1, 1)
        acc_c = jnp.maximum(
            acc_c,
            jnp.max(jnp.where(wfk <= w, crk, 0), axis=0, keepdims=True),
        )
        bwk = bwm[:, sl].reshape(-1, 1)
        bpk = bpm[:, sl].reshape(-1, 1)
        acc_nb = jnp.minimum(
            acc_nb,
            jnp.min(jnp.where(bwk == w, bpk, 4), axis=0, keepdims=True),
        )
        hwk = hwm[:, sl].reshape(-1, 1)
        hck = hcm[:, sl].reshape(-1, 1)
        acc_h = acc_h + jnp.sum(
            jnp.where(hwk == w, hck, jnp.uint32(0)),
            axis=0, keepdims=True, dtype=jnp.uint32,  # x64 would promote
        )
    s = acc_c + w * 4  # owner source byte address per output word
    lw = jnp.clip((s >> 2) - wb, 0, 2 * winw - 2)
    w2 = jnp.concatenate([p0_ref[:], p1_ref[:]], axis=1)  # [1, 2*WINW]
    g0 = jnp.take_along_axis(w2, lw, axis=1)
    g1 = jnp.take_along_axis(w2, lw + 1, axis=1)
    # 4-way funnel select on constant u32 shifts: no i32<->u32 astype
    # in-kernel (the Mosaic convert-lowering recursion ragged_bytes hit)
    c1 = (g0 >> jnp.uint32(8)) | (g1 << jnp.uint32(24))
    c2 = (g0 >> jnp.uint32(16)) | (g1 << jnp.uint32(16))
    c3 = (g0 >> jnp.uint32(24)) | (g1 << jnp.uint32(8))
    rbsel = s & 3
    word = jnp.where(
        rbsel == 0, g0, jnp.where(rbsel == 1, c1, jnp.where(rbsel == 2, c2, c3))
    )
    keep = jnp.where(
        acc_nb >= 4,
        ~jnp.uint32(0),
        jnp.where(
            acc_nb == 1,
            jnp.uint32(0xFF),
            jnp.where(acc_nb == 2, jnp.uint32(0xFFFF), jnp.uint32(0xFFFFFF)),
        ),
    )
    o_ref[:] = (word & keep) | acc_h


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _pd_impl(
    pool32, base, offs, total: int, plen: int, rw: int, winw: int,
    interpret: bool,
):
    from .ragged_bytes import _funnel_u32, u32_rows_to_u8_flat

    n = base.shape[0]
    nw = (total + 3) // 4
    g = max((nw + _PD_BLKW - 1) // _PD_BLKW, 1)
    pw = pool32.shape[0]
    pb = pw // winw + 2
    pool2d = (
        jnp.zeros((pb * winw,), jnp.uint32).at[:pw].set(pool32).reshape(pb, winw)
    )

    w0 = jnp.arange(g, dtype=jnp.int64) * (_PD_BLKW * 4)
    rfirst = jnp.clip(
        jnp.searchsorted(offs[1:], w0, side="right"), 0, n - 1
    ).astype(jnp.int32)
    ridx = rfirst[:, None] + jnp.arange(rw, dtype=jnp.int32)[None, :]
    inb = ridx < n
    rc = jnp.clip(ridx, 0, n - 1)
    o_r = offs[rc].astype(jnp.int32)  # addresses < 2^31 (cudf size_type)
    e_r = offs[rc + 1].astype(jnp.int32)
    b_r = base[rc].astype(jnp.int32)
    valid = inb & (e_r > o_r)
    cr = jnp.where(valid, b_r - o_r, 0)
    wf = jnp.where(valid, (o_r + 3) >> 2, _PD_BIG)
    bpos = e_r & 3
    bw = jnp.where(inb & (bpos > 0), e_r >> 2, _PD_BIG)
    bp = bpos
    xa = (o_r + 3) & ~jnp.int32(3)
    chunk = jnp.clip(jnp.minimum(e_r, xa) - o_r, 0, 3)
    has = valid & (chunk > 0)
    hsrc = _funnel_u32(pool32, jnp.clip(b_r, 0, plen))
    hmask = (jnp.uint32(1) << (chunk.astype(jnp.uint32) * 8)) - jnp.uint32(1)
    hc = jnp.where(
        has,
        (hsrc & hmask) << ((o_r & 3).astype(jnp.uint32) * 8),
        jnp.uint32(0),
    )
    hw = jnp.where(has, o_r >> 2, _PD_BIG)

    b_rf = base[rfirst].astype(jnp.int32)
    wl = jnp.clip(b_rf - 4, 0, None) >> 2
    bvec = jnp.clip(wl // winw, 0, pb - 2).astype(jnp.int32)

    def _meta_spec():
        return pl.BlockSpec(
            (1, rw),
            lambda i, b: (i, jnp.int32(0)),
            memory_space=_VMEM if not interpret else None,
        )

    def _pool_spec(step: int):
        return pl.BlockSpec(
            (1, winw),
            lambda i, b, _s=step: (b[i] + _s, jnp.int32(0)),
            memory_space=_VMEM if not interpret else None,
        )

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[_meta_spec() for _ in range(6)]
        + [_pool_spec(0), _pool_spec(1)],
        out_specs=pl.BlockSpec(
            (1, _PD_BLKW),
            lambda i, b: (i, jnp.int32(0)),
            memory_space=_VMEM if not interpret else None,
        ),
    )
    out = pl.pallas_call(
        functools.partial(
            _pd_kernel, blkw=_PD_BLKW, rw=rw, winw=winw,
            rc_chunk=min(rw, _PD_ROW_CHUNK),
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((g, _PD_BLKW), jnp.uint32),
        interpret=interpret,
    )(bvec, cr, wf, bw, bp, hw, hc, pool2d, pool2d)
    return u32_rows_to_u8_flat(out)[:total]


def pallas_ragged_compact(
    pool: jnp.ndarray,
    base: jnp.ndarray,
    offs: jnp.ndarray,
    total: int,
    pool32: jnp.ndarray = None,
    interpret: bool = False,
    hint=None,
):
    """Fused-kernel twin of ``ops.ragged_bytes.ragged_compact`` (same
    contract: dense ``offs``, nondecreasing non-overlapping ``base``,
    addresses < 2^31). Returns the [total] u8 blob BIT-IDENTICAL to the
    XLA formulation, or None when the probed row/pool windows exceed
    the VMEM caps — the caller's keep-XLA signal. ``hint`` short-cuts
    the probe with precomputed (rw_max, span_max) so multi-column
    callers pay ONE host sync for all columns. Eager-context only."""
    total = int(total)
    n = int(base.shape[0])
    if total == 0 or n == 0:
        return jnp.zeros((0,), jnp.uint8)
    if hint is None:
        rw_max, span_max = (
            int(x) for x in np.asarray(pallas_decode_probe(base, offs, total))
        )
    else:
        rw_max, span_max = int(hint[0]), int(hint[1])
    rw = _pow2_ceil(max(rw_max, 8))
    winw = _pow2_ceil(max(span_max, _LANES))
    if rw > _PD_MAX_RW or winw > _PD_MAX_WIN:
        return None
    if pool32 is None:
        from .ragged_bytes import build_pool32

        pool32 = build_pool32(pool)
    return _pd_impl(
        pool32, base, offs, total, int(pool.shape[0]), rw, winw, bool(interpret)
    )
