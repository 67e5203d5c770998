"""Ragged byte movement as REGULAR array ops — the TPU answer to the
reference's warp-per-row memcpy kernels (row_conversion.cu:827-874).

XLA:TPU's per-ELEMENT irregular u8 gather/scatter is the slow access
class on this hardware, and ROW-granular gathers (``jnp.take(pool2d,
idx, axis=0)`` with monotonic indices) the fast one. The rates this memo
used to quote for them (~0.005 GB/s; 71.6 s for the mixed transcode at
155-col x 1M; ~29 GB/s at 128-byte rows; ~109 GB/s for the windowed
two-tile form) were read BEFORE the benchmark existed, in process, on
benchmarks/microbench.py's table (four fixed-width types and a STRING of
1-32 bytes at every tenth column), not on the reference's: read them as
claims. What PR 34 measured on the chip, through the sidecar, on the
reference's own table (155 columns over its ten-type list, 15 STRING of
0-32 bytes, 1 Mi rows; 0.85 GB in, 1.17 GB of rows out): the whole
encode is ONE program of 637-639 ms of device time (3.2 GB/s of the
request's bytes, 0.39% of the HBM roofline): ``assemble_rows``' chunked
loop 161 ms (its three two-tile gathers 121 ms), the two 64-bit
``cummax`` scans over its 4,587,520 tile indices 147 ms, the
``_vacc_kernel`` 57 ms, the fifteen ``_rotl_take_kernel`` 55 ms
(PERF.md section 5 has the table). So every ragged access here is
decomposed into

1. an axis-0 gather of fixed-width OVERLAPPING tiles (stride s, width
   2s: any s-aligned window of length <= s+1 lands in ONE tile), and
2. a per-row byte ROTATE/SHIFT done arithmetically on u32 lanes —
   log2(W) conditional lane rolls plus an elementwise per-row sub-word
   shift — all regular VPU ops XLA fuses.

No Pallas needed: the formulation is pure jnp, so the hermetic CPU test
tier runs the exact code the chip runs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM = pltpu.VMEM

__all__ = [
    "overlap_tiles",
    "byte_rotate_left",
    "byte_shift_right",
    "padded_extract",
    "assemble_rows",
    "expand_u32_planes",
    "pack_u8_planes",
    "u32_rows_to_u8_flat",
    "flat_u8_to_u32",
    "build_pool32",
    "ragged_compact",
    "ragged_compact_tiered",
]


from .pallas_kernels import on_tpu as _on_tpu  # noqa: E402  (memoized probe)


def _use_pallas() -> bool:
    # memoized probe (pallas_kernels): this gate sits on every ragged
    # helper's hot path and jax.default_backend() re-walks the backend
    # registry per call (ISSUE 13 satellite)
    return _on_tpu()


def _pow2_ceil(v: int) -> int:
    p = 1
    while p < v:
        p *= 2
    return p


def overlap_tiles(buf: jnp.ndarray, stride: int, width: int) -> jnp.ndarray:
    """[L] u8 -> [ceil(L/stride), width] where row w = buf[w*stride :
    w*stride + width] (zero padded past the end). width must be a
    multiple of stride; rows overlap so that any stride-aligned window
    of width-stride+... <= width bytes is contained in one row."""
    if width % stride != 0:
        raise ValueError("width must be a multiple of stride")
    n = buf.shape[0]
    rows = max((n + stride - 1) // stride, 1)
    padded = jnp.zeros((rows * stride + width,), jnp.uint8).at[:n].set(buf)
    parts = [
        padded[k * stride : (rows + k) * stride].reshape(rows, stride)
        for k in range(width // stride)
    ]
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _as_u32(x: jnp.ndarray) -> jnp.ndarray:
    n, w = x.shape
    return lax.bitcast_convert_type(x.reshape(n, w // 4, 4), jnp.uint32)


def _as_u8(x32: jnp.ndarray) -> jnp.ndarray:
    n, lanes = x32.shape
    return lax.bitcast_convert_type(x32, jnp.uint8).reshape(n, lanes * 4)


def _rotl_u32(x32: jnp.ndarray, sl: jnp.ndarray, rb: jnp.ndarray) -> jnp.ndarray:
    """Per-row byte rotate-left of [B, L] u32 lanes. sl [B, 1] i32 lane
    count in [0, L); rb [B, 1] u32 sub-word shift in BITS (0/8/16/24).
    Log2(L) conditional lane rolls + one elementwise sub-word combine —
    runs entirely in registers inside a Pallas kernel. No dtype
    conversions inside: Mosaic's convert-lowering recurses to a Python
    RecursionError on in-kernel i32<->u32 astype (observed), so callers
    precompute both operand dtypes."""
    w = x32.shape[1]
    k = 1
    while k < w:
        rolled = jnp.concatenate([x32[:, k:], x32[:, :k]], axis=1)
        x32 = jnp.where((sl & k) != 0, rolled, x32)
        k *= 2
    nxt = jnp.concatenate([x32[:, 1:], x32[:, :1]], axis=1)
    combined = (x32 >> rb) | (nxt << (jnp.uint32(32) - rb))
    return jnp.where(rb == jnp.uint32(0), x32, combined)


def _shr_u32(x32: jnp.ndarray, sl: jnp.ndarray, rb: jnp.ndarray) -> jnp.ndarray:
    """Per-row byte shift-right (zero fill) of [B, L] u32 lanes. sl
    [B, 1] i32 lane count (>= L clears the row); rb [B, 1] u32 sub-word
    shift in bits. Same no-conversion discipline as _rotl_u32."""
    n, lanes = x32.shape
    ls = jnp.minimum(sl, lanes)
    k = 1
    while k < lanes:
        shifted = jnp.concatenate(
            [jnp.zeros((n, min(k, lanes)), jnp.uint32), x32[:, : max(lanes - k, 0)]], axis=1
        )
        x32 = jnp.where((ls & k) != 0, shifted, x32)
        k *= 2
    x32 = jnp.where(ls >= lanes, jnp.uint32(0), x32)
    prv = jnp.concatenate([jnp.zeros((n, 1), jnp.uint32), x32[:, :-1]], axis=1)
    combined = (x32 << rb) | (prv >> (jnp.uint32(32) - rb))
    return jnp.where(rb == jnp.uint32(0), x32, combined)


def _split_shift(sh_bytes: jnp.ndarray):
    """[N] (or [N, 1]) byte shift -> ([N, 1] i32 lane count, [N, 1] u32
    sub-word bit count): the operand pair _rotl_u32/_shr_u32 take, in
    their final dtypes so no conversion happens inside a kernel."""
    sh = sh_bytes.astype(jnp.int32)[:, None] if sh_bytes.ndim == 1 else sh_bytes.astype(jnp.int32)
    return sh // 4, ((sh % 4) * 8).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# u32 <-> u8 tile relayout (Pallas sublane bitcast)
# ---------------------------------------------------------------------------
#
# A u32 array and its byte stream have IDENTICAL linear content; XLA:TPU
# still charges a full elementwise conversion with a 32x tile-padded
# [..., 4] u8 temp for the dtype change (u32 tiles are (8, 128), u8
# tiles (32, 128)). Mosaic's `tpu.bitcast` reinterprets a vreg across
# SUBLANES — u32 [P, N] -> u8 [4P, N] with byte k of word (p, n) at row
# (4p + k, n) — so the whole relayout is one streaming kernel: one HBM
# read, one write, no padded temp. Composed with the (fast, ~1.5 TB/s)
# u8 transpose this replaces the lax.map chunked converter that ran the
# 212-col encode axis at 34 GB/s (round-3 profile: 48 of 50.8 ms).
#
# NOTE Mosaic fragility (all verified on v5e): block index_maps MUST use
# jnp.int32 constants (a plain Python `0` crashes the compiler), and
# neither strided lane refs (pl.Slice(stride=4)) nor in-kernel
# swapaxes/reshape rearranges compile — the sublane bitcast is the one
# shape this Mosaic lowers reliably.

_XP_LBLK = 512  # lanes per grid step


def _expand_kernel(x_ref, o_ref):
    o_ref[:] = pltpu.bitcast(x_ref[:], jnp.uint8)


def _pack_kernel(x_ref, o_ref):
    o_ref[:] = pltpu.bitcast(x_ref[:], jnp.uint32)


def _plane_lblk(p: int) -> int:
    # bound the (P, lblk) u32 + (4P, lblk) u8 blocks to ~4 MB of VMEM
    lblk = _XP_LBLK
    while lblk > 128 and p * lblk * 8 > (4 << 20):
        lblk //= 2
    return lblk


def expand_u32_planes(x32: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """u32 [P, N] -> u8 [4P, N] where row 4p+k holds byte k (LE) of
    plane p. Pallas on TPU; jnp fallback elsewhere."""
    p, n = x32.shape
    if not (_use_pallas() or interpret):
        by = lax.bitcast_convert_type(x32, jnp.uint8)  # [P, N, 4]
        return by.transpose(0, 2, 1).reshape(4 * p, n)
    lblk = _plane_lblk(p)
    cols = (n + lblk - 1) // lblk * lblk
    xp = jnp.pad(x32, ((0, 0), (0, cols - n))) if cols != n else x32
    out = pl.pallas_call(
        _expand_kernel,
        out_shape=jax.ShapeDtypeStruct((4 * p, cols), jnp.uint8),
        grid=(cols // lblk,),
        in_specs=[pl.BlockSpec((p, lblk), lambda i: (jnp.int32(0), i),
                               memory_space=_VMEM if not interpret else None)],
        out_specs=pl.BlockSpec((4 * p, lblk), lambda i: (jnp.int32(0), i),
                               memory_space=_VMEM if not interpret else None),
        interpret=interpret,
    )(xp)
    return out[:, :n] if cols != n else out


def pack_u8_planes(x8: jnp.ndarray, interpret: bool = False) -> jnp.ndarray:
    """u8 [4P, N] -> u32 [P, N]: inverse of expand_u32_planes."""
    p4, n = x8.shape
    p = p4 // 4
    if not (_use_pallas() or interpret):
        by = x8.reshape(p, 4, n).transpose(0, 2, 1)  # [P, N, 4]
        return lax.bitcast_convert_type(by, jnp.uint32)
    lblk = _plane_lblk(p)
    cols = (n + lblk - 1) // lblk * lblk
    xp = jnp.pad(x8, ((0, 0), (0, cols - n))) if cols != n else x8
    out = pl.pallas_call(
        _pack_kernel,
        out_shape=jax.ShapeDtypeStruct((p, cols), jnp.uint32),
        grid=(cols // lblk,),
        in_specs=[pl.BlockSpec((4 * p, lblk), lambda i: (jnp.int32(0), i),
                               memory_space=_VMEM if not interpret else None)],
        out_specs=pl.BlockSpec((p, lblk), lambda i: (jnp.int32(0), i),
                               memory_space=_VMEM if not interpret else None),
        interpret=interpret,
    )(xp)
    return out[:, :n] if cols != n else out


def u32_rows_to_u8_flat(x32: jnp.ndarray) -> jnp.ndarray:
    """[R, L] u32 -> [R * 4L] u8 little-endian bytes.

    TPU: transpose -> sublane-expand kernel -> transpose back — three
    streaming passes (~7 ms at 1M x 196 vs 48 ms for the chunked
    converter below). Elsewhere: lax.map row blocks — the u32->u8
    bitcast materializes a [..., L, 4] u8 whose tiled layout pads the
    4-lane minor dim 32x, so converting a GB-scale array in one op is a
    40+ GB allocation (observed); per-block the padded temp is bounded
    to ~70 MB."""
    r, lanes = x32.shape
    if _use_pallas() and r >= 8 and lanes >= 1:
        by = expand_u32_planes(x32.T)  # [4L, R]
        return by.T.reshape(-1)
    nbt = max(1, (1 << 19) // max(lanes, 1))
    rows = (r + nbt - 1) // nbt * nbt
    xp = _pad_rows(x32, rows)

    def block(xb):
        return lax.bitcast_convert_type(xb, jnp.uint8).reshape(nbt, lanes * 4)

    out = lax.map(block, xp.reshape(rows // nbt, nbt, lanes))
    return out.reshape(-1)[: r * lanes * 4]


def byte_rotate_left(x: jnp.ndarray, shift_bytes: jnp.ndarray) -> jnp.ndarray:
    """Rotate each row of [N, W] u8 left by a per-row byte count in
    [0, W). W must be a multiple of 4 (u32 lanes; pow2 W keeps the roll
    ladder minimal). Little-endian lane order matches byte order."""
    sl, rb = _split_shift(shift_bytes)
    return _as_u8(_rotl_u32(_as_u32(x), sl, rb))


def byte_shift_right(x: jnp.ndarray, shift_bytes: jnp.ndarray) -> jnp.ndarray:
    """Shift each row of [N, W] u8 right by a per-row byte count >= 0,
    zero-filling on the left (amounts >= W clear the row). W must be a
    multiple of 4."""
    sl, rb = _split_shift(jnp.minimum(shift_bytes.astype(jnp.int64), x.shape[1]))
    return _as_u8(_shr_u32(_as_u32(x), sl, rb))


# ---------------------------------------------------------------------------
# Pallas epilogue kernels
# ---------------------------------------------------------------------------
#
# The u32 shift ladders are correct as plain XLA but each conditional
# roll materializes a full-width HLO temp: 35 GB of temps (OOM) unfused,
# or ~7 HBM passes fused — measured seconds per call at the 1M-row
# mixed axis. Inside a Pallas kernel the whole ladder runs in
# VMEM/registers: one HBM read + one write per tile.

_PK_BLK = 512  # rows per grid step


def _rows_spec(blk: int, lanes: int, interpret: bool):
    return pl.BlockSpec(
        (blk, lanes),
        lambda i: (i, jnp.int32(0)),
        memory_space=_VMEM if not interpret else None,
    )


def _scal_spec(blk: int, interpret: bool):
    """Per-row scalars travel LANE-PACKED as [G, 1, blk]: a [N, 1] i32
    operand's T(8,128) HBM layout pads the single lane to 128 (a 128x
    expansion — 512 MB per scalar at N=1M, observed OOM); lane-packing
    stores them dense and the kernel reshapes one [1, blk] row to
    [blk, 1] (a cheap in-VMEM relayout, verified lowering)."""
    return pl.BlockSpec(
        (1, 1, blk),
        lambda i: (i, jnp.int32(0), jnp.int32(0)),
        memory_space=_VMEM if not interpret else None,
    )


def _pack_scalar(a: jnp.ndarray, blk: int, rows: int) -> jnp.ndarray:
    return _pad_rows(a, rows).reshape(rows // blk, 1, blk)


def _scal(ref) -> jnp.ndarray:
    return ref[0].reshape(-1, 1)  # [1, blk] -> [blk, 1]


def _pad_rows(a: jnp.ndarray, rows: int) -> jnp.ndarray:
    if a.shape[0] == rows:
        return a
    return jnp.pad(a, ((0, rows - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def _rotl_take_kernel(sl_ref, rb_ref, x_ref, o_ref, *, out_lanes: int):
    o_ref[:] = _rotl_u32(x_ref[:], _scal(sl_ref), _scal(rb_ref))[:, :out_lanes]


def rotl_take(
    x: jnp.ndarray, shift_bytes: jnp.ndarray, out_w: int, interpret: bool = False
) -> jnp.ndarray:
    """byte_rotate_left(x, sh)[:, :out_w] — Pallas on TPU (ladder in
    VMEM), plain-jnp fallback elsewhere. out_w % 4 == 0. interpret=True
    forces the kernel through the Pallas interpreter (hermetic CPU
    testing of the kernel body)."""
    if not (_use_pallas() or interpret):
        return byte_rotate_left(x, shift_bytes)[:, :out_w]
    n, w = x.shape
    rows = max((n + _PK_BLK - 1) // _PK_BLK * _PK_BLK, _PK_BLK)
    x32 = _as_u32(_pad_rows(x, rows))
    sl, rb = _split_shift(shift_bytes.astype(jnp.int32))
    out32 = pl.pallas_call(
        functools.partial(_rotl_take_kernel, out_lanes=out_w // 4),
        out_shape=jax.ShapeDtypeStruct((rows, out_w // 4), jnp.uint32),
        grid=(rows // _PK_BLK,),
        in_specs=[_scal_spec(_PK_BLK, interpret)] * 2
        + [_rows_spec(_PK_BLK, w // 4, interpret)],
        out_specs=_rows_spec(_PK_BLK, out_w // 4, interpret),
        interpret=interpret,
    )(
        _pack_scalar(sl[:, 0], _PK_BLK, rows),
        _pack_scalar(rb[:, 0], _PK_BLK, rows),
        x32,
    )
    return _as_u8(out32)[:n]


def rotl_take32(
    x32: jnp.ndarray, shift_bytes: jnp.ndarray, out_w: int, interpret: bool = False
) -> jnp.ndarray:
    """rotl_take for a u32-lane input [N, W/4]: byte rotate-left by
    shift_bytes, keep out_w bytes, return [N, out_w] u8. Same kernel as
    rotl_take minus the [N, W]-u8 -> u32 conversion (which pads ~4x at
    GB scale)."""
    n, w4 = x32.shape
    if not (_use_pallas() or interpret):
        return byte_rotate_left(_as_u8(x32), shift_bytes)[:, :out_w]
    rows = max((n + _PK_BLK - 1) // _PK_BLK * _PK_BLK, _PK_BLK)
    sl, rb = _split_shift(shift_bytes.astype(jnp.int32))
    out32 = pl.pallas_call(
        functools.partial(_rotl_take_kernel, out_lanes=out_w // 4),
        out_shape=jax.ShapeDtypeStruct((rows, out_w // 4), jnp.uint32),
        grid=(rows // _PK_BLK,),
        in_specs=[_scal_spec(_PK_BLK, interpret)] * 2
        + [_rows_spec(_PK_BLK, w4, interpret)],
        out_specs=_rows_spec(_PK_BLK, out_w // 4, interpret),
        interpret=interpret,
    )(
        _pack_scalar(sl[:, 0], _PK_BLK, rows),
        _pack_scalar(rb[:, 0], _PK_BLK, rows),
        _pad_rows(x32, rows),
    )
    return _as_u8(out32)[:n]


def _vacc_kernel(*refs, lane_offs: tuple, out_lanes: int):
    """Accumulate the packed string matrices into the variable section:
    refs = (sl_0..sl_{K-1}, rb_0..rb_{K-1}, packed_p, out); column k's
    lanes live at lane_offs[k]:lane_offs[k+1] of packed_p.

    Accumulates THROUGH the output ref, not an SSA chain: with a chained
    `v = v | shr(...)` Mosaic's stack estimate keeps every column's
    ladder live at once (21.9 MB > the 16 MB scoped-vmem limit at 16
    cols); read-modify-write frees each column's temps before the
    next."""
    num_cols = len(lane_offs) - 1
    pp_ref = refs[-2]
    o_ref = refs[-1]
    o_ref[:] = jnp.zeros((o_ref.shape[0], out_lanes), jnp.uint32)
    for k in range(num_cols):
        sl = _scal(refs[k])
        rb = _scal(refs[num_cols + k])
        p32 = pp_ref[:, lane_offs[k] : lane_offs[k + 1]]
        if p32.shape[1] < out_lanes:
            zero = jnp.zeros((p32.shape[0], out_lanes - p32.shape[1]), jnp.uint32)
            p32 = jnp.concatenate([p32, zero], axis=1)
        o_ref[:] |= _shr_u32(p32, sl, rb)  # strings are disjoint per row


def var_accumulate(p_mats, shifts, maxvar: int, interpret: bool = False) -> jnp.ndarray:
    """Sum_k byte_shift_right(pad(p_k, maxvar), s_k), returned as
    [N, maxvar/4] u32 lanes — Pallas on TPU, jnp fallback elsewhere.
    p_k widths % 4 == 0; maxvar % 4 == 0."""
    n = p_mats[0].shape[0]
    if not (_use_pallas() or interpret):
        v = jnp.zeros((n, maxvar), jnp.uint8)
        for p, s in zip(p_mats, shifts):
            if p.shape[1] < maxvar:
                p = jnp.pad(p, ((0, 0), (0, maxvar - p.shape[1])))
            v = v + byte_shift_right(p, s)
        return _as_u32(v)
    # block rows scale inversely with the section width (the ladder's
    # live VMEM intermediates are [blk, >=128-lane] tiles)
    blk = _PK_BLK
    while blk > 32 and blk * maxvar > 64 * 1792:
        blk //= 2
    rows = max((n + blk - 1) // blk * blk, blk)
    k = len(p_mats)
    packed_args = []
    for sarr in shifts:
        sl, rb = _split_shift(sarr.astype(jnp.int32))
        packed_args.append((sl[:, 0], rb[:, 0]))
    # ONE packed u8 matrix, lanes padded to a 128 multiple: sixteen
    # separate [N, 8-lane] u32 operands tile-pad 16x each (480 MB a
    # piece at N=1M, observed OOM)
    lane_offs = [0]
    for p in p_mats:
        lane_offs.append(lane_offs[-1] + p.shape[1] // 4)
    pad_lanes = (lane_offs[-1] + 127) // 128 * 128 - lane_offs[-1]
    pieces = [_pad_rows(p, rows) for p in p_mats]
    if pad_lanes:
        pieces.append(jnp.zeros((rows, pad_lanes * 4), jnp.uint8))
    packed = _as_u32(jnp.concatenate(pieces, axis=1))
    out32 = pl.pallas_call(
        functools.partial(
            _vacc_kernel, lane_offs=tuple(lane_offs), out_lanes=maxvar // 4
        ),
        out_shape=jax.ShapeDtypeStruct((rows, maxvar // 4), jnp.uint32),
        grid=(rows // blk,),
        in_specs=[_scal_spec(blk, interpret)] * (2 * k)
        + [_rows_spec(blk, packed.shape[1], interpret)],
        out_specs=_rows_spec(blk, maxvar // 4, interpret),
        interpret=interpret,
    )(
        *[_pack_scalar(sl, blk, rows) for sl, _ in packed_args],
        *[_pack_scalar(rb, blk, rows) for _, rb in packed_args],
        packed,
    )
    return out32[:n]


def _asm_kernel(psl_ref, prb_ref, dsl_ref, drb_ref, al_ref, a0_ref, a1_ref, c0_ref, o_ref, *, g_lanes: int):
    ga = jnp.concatenate([a0_ref[:], a1_ref[:]], axis=1)  # VMEM concat
    rot_a = _rotl_u32(ga, _scal(psl_ref), _scal(prb_ref))[:, :g_lanes]
    rot_c = _shr_u32(c0_ref[:], _scal(dsl_ref), _scal(drb_ref))
    lane_byte = jax.lax.broadcasted_iota(jnp.int32, (1, g_lanes), 1) * 4
    o_ref[:] = jnp.where(lane_byte < _scal(al_ref), rot_a, rot_c)


def _asm_epilogue(a0, a1, c0, pmod, delta, alen, g_tile: int, interpret: bool = False) -> jnp.ndarray:
    """Combine the gathered u32 sources into final dst tiles: rotate the
    in-row window (two adjacent tiles, concatenated in VMEM), right-
    shift the next-row head, select at the 8-aligned row boundary."""
    t = a0.shape[0]
    g4 = g_tile // 4
    if not (_use_pallas() or interpret):
        ga = _as_u8(jnp.concatenate([a0, a1], axis=1))
        rot_a = byte_rotate_left(ga, pmod)[:, :g_tile]
        rot_c = byte_shift_right(_as_u8(c0), delta)
        take_a = jnp.arange(g_tile, dtype=jnp.int32)[None, :] < alen[:, None]
        return _as_u32(jnp.where(take_a, rot_a, rot_c))
    rows = max((t + _PK_BLK - 1) // _PK_BLK * _PK_BLK, _PK_BLK)
    psl, prb = _split_shift(pmod.astype(jnp.int32))
    dsl, drb = _split_shift(delta.astype(jnp.int32))
    return pl.pallas_call(
        functools.partial(_asm_kernel, g_lanes=g4),
        out_shape=jax.ShapeDtypeStruct((rows, g4), jnp.uint32),
        grid=(rows // _PK_BLK,),
        in_specs=[_scal_spec(_PK_BLK, interpret)] * 5
        + [_rows_spec(_PK_BLK, g4, interpret)] * 3,
        out_specs=_rows_spec(_PK_BLK, g4, interpret),
        interpret=interpret,
    )(
        _pack_scalar(psl[:, 0], _PK_BLK, rows),
        _pack_scalar(prb[:, 0], _PK_BLK, rows),
        _pack_scalar(dsl[:, 0], _PK_BLK, rows),
        _pack_scalar(drb[:, 0], _PK_BLK, rows),
        _pack_scalar(alen.astype(jnp.int32), _PK_BLK, rows),
        _pad_rows(a0, rows),
        _pad_rows(a1, rows),
        _pad_rows(c0, rows),
    )[:t]


def overlap_tiles_u32(buf: jnp.ndarray, stride: int, width: int) -> jnp.ndarray:
    """overlap_tiles emitting u32 LANES: [ceil(L/stride), width/4] u32
    where row w covers buf bytes [w*stride, w*stride + width). stride
    and width must be multiples of 4. The whole relayout happens on the
    FLAT buffer (flat_u8_to_u32) — a [N, width]-u8 tile matrix
    converted to u32 per element pads ~4x at GB scale and OOMed the
    compile at the 1Mx155 mixed-decode axis (two 7.6 GB temps; round-5
    finding)."""
    if width % stride != 0 or stride % 4 != 0:
        raise ValueError("width must be a multiple of stride; stride of 4")
    n = buf.shape[0]
    rows = max((n + stride - 1) // stride, 1)
    padded = jnp.zeros((rows * stride + width,), jnp.uint8).at[:n].set(buf)
    p32 = flat_u8_to_u32(padded)
    s4 = stride // 4
    parts = [
        p32[k * s4 : (rows + k) * s4].reshape(rows, s4) for k in range(width // stride)
    ]
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def padded_extract(pool: jnp.ndarray, starts: jnp.ndarray, max_len: int) -> jnp.ndarray:
    """[N] windows of up to ``max_len`` bytes at arbitrary byte offsets
    ``starts`` in ``pool`` -> [N, W] u8 (W = pow2 >= max_len) where row
    r's bytes 0..max_len are pool[starts[r] : starts[r]+max_len].
    Bytes past max_len are tile garbage — callers mask by true length.

    One overlapping-tile gather + one per-row rotate: stride s =
    pow2_ceil(max_len), width 2s, so window [starts % s, starts % s +
    max_len) always lies inside the gathered row (s - 1 + max_len < 2s).
    The tiles live in u32 lanes end to end (overlap_tiles_u32): the row
    gather feeds the rotate kernel directly, with no per-element u8->u32
    conversion at [N, 2s] scale.
    """
    if max_len < 1:
        return jnp.zeros((starts.shape[0], 4), jnp.uint8)
    stride = max(_pow2_ceil(max_len), 4)
    # u32-lane tiles only at wide strides: s/4 >= 128 lanes keeps the
    # tile matrix unpadded. At short strides (string extracts) the u32
    # minor dim would pad up to 16x, while the u8 path's convert temp
    # is proportionally tiny — the OOM it guards against is a
    # wide-stride (row-blob) phenomenon.
    if _use_pallas() and stride >= 512:
        tiles32 = overlap_tiles_u32(pool, stride, 2 * stride)
        idx = (starts // stride).astype(jnp.int32)
        g32 = jnp.take(tiles32, idx, axis=0)  # [N, 2s/4] u32
        return rotl_take32(g32, (starts % stride).astype(jnp.int32), stride)
    tiles = overlap_tiles(pool, stride, 2 * stride)
    idx = (starts // stride).astype(jnp.int32)
    g = jnp.take(tiles, idx, axis=0)  # [N, 2s]
    return rotl_take(g, (starts % stride).astype(jnp.int32), stride)


def flat_u8_to_u32(buf: jnp.ndarray) -> jnp.ndarray:
    """[L] u8 (L % 4 == 0) -> [L/4] u32 little-endian words.

    TPU: the decode twin of u32_rows_to_u8_flat — transpose ->
    sublane-pack kernel -> transpose, three streaming passes over a
    free [R, 512] view. Both the naive [L/4, 4]-view bitcast AND a
    [L/4, 4] transpose charge a 32x tile-padded temp (measured: a
    1.3 GB blob tried to allocate 43 GB and OOMed the compile).
    Elsewhere the view bitcast is free."""
    n4 = buf.shape[0] // 4
    if _use_pallas() and n4 >= 128:
        lanes = 512
        rows = (buf.shape[0] + lanes - 1) // lanes
        padded = (
            jnp.zeros((rows * lanes,), jnp.uint8).at[: buf.shape[0]].set(buf)
            if rows * lanes != buf.shape[0]
            else buf
        )
        m = padded.reshape(rows, lanes).T  # [512, R]: byte b of row r
        packed = pack_u8_planes(m)  # [128, R]: LE word j of row r
        return packed.T.reshape(-1)[:n4]
    return lax.bitcast_convert_type(buf.reshape(n4, 4), jnp.uint32)


def _funnel_u64(pool64: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """u64 little-endian word containing pool bytes [s, s+8) for each
    byte address s (pool64 must extend one word past any s): two
    monotone element gathers + a byte funnel shift."""
    q = (s >> 3).astype(jnp.int32)
    g0 = pool64[q]
    g1 = pool64[q + 1]
    rb = ((s & 7) * 8).astype(jnp.uint64)
    hi = jnp.where(rb == 0, jnp.uint64(0), g1 << (jnp.uint64(64) - jnp.maximum(rb, jnp.uint64(1))))
    return (g0 >> rb) | hi


def build_pool32(pool: jnp.ndarray) -> jnp.ndarray:
    """[L] u8 -> flat little-endian u32 word view, padded two words past
    the end (the funnel's q+1 read). Build ONCE per pool and share
    across every ragged_compact over it — the relayout walks the whole
    pool (a GB-scale blob when decoding rows), and 16 string columns
    rebuilding it dominated the first on-chip measurement."""
    plen = int(pool.shape[0])
    pwords = (plen + 4) // 4 + 2
    pool_pad = jnp.zeros((pwords * 4,), jnp.uint8).at[:plen].set(pool)
    return flat_u8_to_u32(pool_pad)


def _funnel_u32(p32: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """u32 little-endian word containing pool bytes [s, s+4) for each
    byte address s (p32 must extend one word past any s): two monotone
    element gathers + a byte funnel shift. All flat 1-D — any 2-D view
    with a tiny minor dim tile-pads 32-64x on TPU (measured 43 GB and
    64 GB compile-time OOMs from [N,4]-u8 / [N,2]-u32 views)."""
    q = (s >> 2).astype(jnp.int32)
    g0 = p32[q]
    g1 = p32[q + 1]
    rb = ((s & 3) * 8).astype(jnp.uint32)
    hi = jnp.where(
        rb == 0, jnp.uint32(0), g1 << (jnp.uint32(32) - jnp.maximum(rb, jnp.uint32(1)))
    )
    return (g0 >> rb) | hi


def ragged_compact(
    pool: jnp.ndarray,
    base: jnp.ndarray,
    offs: jnp.ndarray,
    total: int,
    pool32: jnp.ndarray = None,
) -> jnp.ndarray:
    """Dense ragged gather: out[offs[r] + j] = pool[base[r] + j] for
    j < offs[r+1] - offs[r] — the reference's warp-per-row memcpy
    (row_conversion.cu:1141 copy_strings_from_rows) as REGULAR ops.

    ``offs`` [N+1] must be dense (cumsum of lengths); ``base`` [N] must
    be nondecreasing over rows with nonzero length AND source rows must
    not overlap in row order (base[r+1] >= base[r] + len[r]) — true for
    every row-blob layout (a row contains its own strings) and for
    padded matrices (base = r*W, len <= W). Both i64, addresses < 2^31
    (cudf size_type discipline). The no-overlap form makes
    c = base - offs[r] nondecreasing, so ONE packed scatter-max
    ((c << 31) | end_offset) + one cummax resolves the whole
    owner/source mapping.

    Formulation (the decode twin of assemble_rows): per-element u8
    gathers cost ~8 ns/ELEMENT regardless of width (round-3 memo), so
    the unit of movement is the u32 WORD — 2 gathers + a funnel shift
    per 4 output bytes (~4 ns/byte). Because dst is DENSE, each output
    word splits between one OWNER row (the last row whose span covers
    the word's first byte — computed wholesale by the scatter + cummax
    forward-fill trick) and the sub-word HEAD chunks of later rows
    (<= 3 bytes each, disjoint byte lanes, scatter-ADDed). Pure jnp: the
    hermetic CPU tier runs the exact code the chip runs. Everything
    stays FLAT 1-D (see _funnel_u32 on why).
    """
    n = base.shape[0]
    if total == 0 or n == 0:
        return jnp.zeros((0,), jnp.uint8)
    lens = offs[1:] - offs[:-1]
    nw = (total + 3) // 4 + 1

    if pool32 is None:
        pool32 = build_pool32(pool)
    plen = int(pool.shape[0])

    # Owner-row resolution, all in 32-bit lanes (i64 scans on the
    # emulated-64 datapath cost ~2x):
    # - c_w: the owner's src-minus-dst shift, scatter-MAX of the
    #   nondecreasing c = base - offs[r] at each row's first owned word
    #   + cummax forward-fill (s = c_w + 4w addresses the source).
    # - nb_w: valid bytes of word w before the next row takes over =
    #   scatter-MIN of in-word boundary positions (dense dst: the
    #   owner's bytes always end at the FIRST content start inside the
    #   word; word-aligned boundaries need no mask). The final end
    #   (total) joins as a sentinel boundary.
    nonzero = lens > 0
    wfirst = ((offs[:-1] + 3) >> 2).astype(jnp.int32)
    widx = jnp.where(nonzero, wfirst, nw)  # park zero rows off the end
    c_row = (base - offs[:-1]).astype(jnp.int32)  # nondecreasing, >= 0
    c_w = lax.cummax(
        jnp.zeros((nw + 1,), jnp.int32).at[widx].max(c_row, mode="drop")[:nw]
    )

    # every boundary (row starts AND the final total) is an entry of offs
    bpos = (offs & 3).astype(jnp.uint32)
    bword = (offs >> 2).astype(jnp.int32)
    bidx = jnp.where(bpos > 0, bword, nw)  # aligned boundaries: no mask
    nb = (
        jnp.full((nw + 1,), 4, jnp.uint32).at[bidx].min(bpos, mode="drop")[:nw]
    )

    w0 = jnp.arange(nw, dtype=jnp.int64) * 4
    s = jnp.clip(c_w.astype(jnp.int64) + w0, 0, plen)
    cand = _funnel_u32(pool32, s)
    keep = jnp.where(
        nb >= 4, ~jnp.uint32(0), (jnp.uint32(1) << (nb * 8)) - jnp.uint32(1)
    )
    words = cand & keep

    # head chunks: bytes [offs[r], min(offs[r+1], align4up(offs[r])))
    # of each row land in its start word at byte offset offs[r] % 4 —
    # disjoint lanes across rows, so scatter-add composes them
    x = offs[:-1]
    xa = (x + 3) & ~jnp.int64(3)
    chunk = jnp.clip(jnp.minimum(offs[1:], xa) - x, 0, 3).astype(jnp.uint32)
    has = nonzero & (chunk > 0)
    hsrc = _funnel_u32(pool32, jnp.clip(base, 0, plen))
    hmask = (jnp.uint32(1) << (chunk * 8)) - jnp.uint32(1)
    contrib = (hsrc & hmask) << ((x & 3).astype(jnp.uint32) * 8)
    hidx = jnp.where(has, (x >> 2).astype(jnp.int32), nw)
    words = (
        jnp.concatenate([words, jnp.zeros((1,), jnp.uint32)])
        .at[hidx]
        .add(jnp.where(has, contrib, jnp.uint32(0)), mode="drop")[:nw]
    )

    # flat u32 words -> u8 stream via the sublane-expand path (a direct
    # u32 -> u8 bitcast charges the 32x padded temp)
    lanes = 512
    rows = (nw + lanes - 1) // lanes
    w32p = jnp.zeros((rows * lanes,), jnp.uint32).at[:nw].set(words)
    return u32_rows_to_u8_flat(w32p.reshape(rows, lanes))[:total]


def ragged_compact_tiered(
    pool: jnp.ndarray,
    base: jnp.ndarray,
    offs: jnp.ndarray,
    total: int,
    pool32: jnp.ndarray = None,
) -> jnp.ndarray:
    """EAGER kernel-tier dispatcher for ``ragged_compact`` (ISSUE 13):
    the fused Pallas decode kernel when ``SRJT_PALLAS_DECODE`` arms and
    the probed windows fit (pallas_kernels.pallas_ragged_compact), the
    XLA formulation otherwise — bit-identical either way; an exception
    from the kernel propagates. Host-syncs the window probe,
    so inside-jit callers (the fused multi-column decode program) keep
    calling ``ragged_compact`` directly; row_conversion batches its
    per-column probes through the ``hint`` path instead."""
    from ..utils.dispatch import note_tier
    from .pallas_kernels import kernel_tier_mode, pallas_ragged_compact

    mode = kernel_tier_mode("SRJT_PALLAS_DECODE")
    if mode and int(total) > 0:
        # a kernel exception propagates: None is selection by shape
        out = pallas_ragged_compact(
            pool, base, offs, int(total), pool32=pool32,
            interpret=mode == "interpret",
        )
        if out is not None:
            note_tier("pallas", "ragged_compact")
            return out
    note_tier("xla", "ragged_compact")
    return ragged_compact(pool, base, offs, int(total), pool32=pool32)


_ASSEMBLE_BLOCK_TILES = 1 << 16  # dst tiles per lax.map block when the
# blob is too large for the single-pass form (bounds per-block temps)
_ASSEMBLE_SINGLE_PASS_BYTES = 768 * (1 << 20)  # single-pass gather cap:
# the three [T, G] gather buffers coexist (3x blob bytes, ~2.3 GB at
# the cap) — fine on 16 GB HBM; above it the lax.map path bounds them.
# Round-3 note: the old 256 MB cap pushed the 1M-row mixed axis
# (537 MB blob) onto 33 SEQUENTIAL map blocks for no memory benefit.


def assemble_rows(
    rp_parts,  # [N, *] u32 lane parts concatenated logically (fixed |
    # var | implicit zero pad): rows are byte sequences in little-endian
    # u32 lanes, bytes >= size_r zero
    sizes: jnp.ndarray,  # [N] int64, 8-aligned true row sizes
    offsets: jnp.ndarray,  # [N+1] int64 dst offsets (cumsum of sizes)
    total: int,  # offsets[-1], static
    min_row_size: int,  # static lower bound on sizes (>= 8, 8-aligned)
) -> jnp.ndarray:
    """Compact padded rows into the exact 8-aligned ragged blob (u8).

    Dst-centric at tile granularity G = pow2 <= min_row_size (so a dst
    tile straddles at most 2 rows): tile t takes G bytes at in-row
    offset p from row r (two adjacent-tile u32 gathers from the free
    reshape view, concatenated in VMEM; at the reference's 155-col x 1M
    axis the three gathers of a request's 4.6 M tiles take 121 ms on
    the chip, PR 34) and bytes past row r's end come from row r+1's head (third
    gather + zero-filling right shift). All gather indices are
    monotonic. Everything stays in u32 lanes: u8<->u32 bitcasts of 2-D
    arrays are real tiled-layout relayouts, paid once at the final 1-D
    blob view."""
    parts = rp_parts if isinstance(rp_parts, (tuple, list)) else (rp_parts,)
    n = parts[0].shape[0]
    s4 = sum(p.shape[1] for p in parts)
    g_tile = min(_pow2_ceil(min_row_size + 1) // 2, 256)
    g_tile = max(g_tile, 8)
    g4 = g_tile // 4
    # pad S so any in-row window [p, p+2G) with p < size_r stays inside
    # the row's padded span, and keep G | S' so the flat reshape view's
    # tiles never mix two rows
    s_pad4 = (s4 + g4 - 1) // g4 * g4 + 2 * g4
    rp = jnp.concatenate(
        list(parts) + [jnp.zeros((n, s_pad4 - s4), jnp.uint32)], axis=1
    )
    tiles = rp.reshape(n * (s_pad4 // g4), g4)  # free view
    s_pad = s_pad4 * 4

    t_total = (total + g_tile - 1) // g_tile
    single = t_total * g_tile <= _ASSEMBLE_SINGLE_PASS_BYTES
    nbt = t_total if single else _ASSEMBLE_BLOCK_TILES
    nblk = (t_total + nbt - 1) // nbt

    # Per-tile source indices via scatter + forward-fill scan, NOT
    # searchsorted + offsets[r]: searchsorted lowers to ~log2(N) rounds
    # of element gathers and each offsets[r]/sizes[r] is an element
    # gather — the ~0.005 GB/s access class, seconds at 5M tiles.
    # Tile t's owner is max r with D_r <= t*G, i.e. r owns tiles
    # ceil(D_r/G) .. ceil(D_{r+1}/G)-1; row sizes >= G make those
    # first-owned tiles strictly increasing, so scattering each row's
    # (r, D_r, D_{r+1}) into tile ceil(D_r/G) and forward-filling
    # (cummax of monotone values) yields r_t and both offsets for ALL
    # tiles in one scatter + one scan.
    tt = nblk * nbt
    start_tile = ((offsets[:-1] + g_tile - 1) // g_tile).astype(jnp.int32)
    r_fill = (
        jnp.full((tt,), -1, jnp.int32)
        .at[start_tile]
        .max(jnp.arange(n, dtype=jnp.int32), mode="drop")
    )
    d_fill = (
        jnp.full((tt,), jnp.int64(0))
        .at[start_tile]
        .max(offsets[:-1], mode="drop")
    )
    dn_fill = (
        jnp.full((tt,), jnp.int64(0))
        .at[start_tile]
        .max(offsets[1:], mode="drop")
    )
    r = jnp.maximum(lax.cummax(r_fill), 0)
    d_r = lax.cummax(d_fill)  # offsets[r] (monotone in r)
    d_next = lax.cummax(dn_fill)  # offsets[r + 1]

    t0 = jnp.arange(tt, dtype=jnp.int64) * g_tile
    p = jnp.clip(t0 - d_r, 0, s_pad - 2 * g_tile)
    src_a = ((r.astype(jnp.int64) * s_pad + p) // g_tile).astype(jnp.int32)
    r_next = jnp.minimum(r + 1, n - 1)
    src_c = (r_next.astype(jnp.int64) * (s_pad // g_tile)).astype(jnp.int32)
    pmod = (p % g_tile).astype(jnp.int32)
    delta = jnp.clip(d_next - t0, 0, g_tile).astype(jnp.int32)
    alen = jnp.clip(d_next - d_r - p, 0, g_tile).astype(jnp.int32)

    def block(args):
        s_a, s_c, pm, dl, al = args
        a0 = jnp.take(tiles, s_a, axis=0)
        a1 = jnp.take(tiles, s_a + 1, axis=0)
        c0 = jnp.take(tiles, s_c, axis=0)
        return _asm_epilogue(a0, a1, c0, pm, dl, al, g_tile)

    if single:
        out = block((src_a, src_c, pmod, delta, alen))
    else:
        xs = tuple(v.reshape(nblk, nbt) for v in (src_a, src_c, pmod, delta, alen))
        out = lax.map(block, xs)  # [nblk, nbt, g4]
    return u32_rows_to_u8_flat(out.reshape(-1, out.shape[-1]))[:total]
