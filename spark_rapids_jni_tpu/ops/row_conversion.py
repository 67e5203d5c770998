"""JCUDF row <-> columnar transcode — the flagship op family.

Behavioral parity with reference src/main/cpp/src/row_conversion.cu
(format doc: reference RowConversion.java:44-117; layout computation:
row_conversion.cu compute_column_information :1340-1378; string writes
:827-874; validity bit order :404-407):

- each row is laid out like a C struct: every fixed-width column aligned
  to its own size; STRING/LIST columns occupy an 8-byte
  ``{offset:u32, len:u32}`` slot aligned to 4 bytes,
- validity bytes follow the last column with no extra padding; bit
  ``col % 8`` of byte ``col / 8`` is set when the value is VALID,
- variable-width (string) character data follows the validity bytes;
  the u32 ``offset`` written in the slot is relative to the row start,
- every row is padded to a multiple of 8 bytes (JCUDF_ROW_ALIGNMENT),
- output is one or more LIST<INT8> columns, each holding at most 2 GiB
  (cudf ``size_type`` discipline, row_conversion.cu:67,100-105).

TPU-first design notes (NOT a kernel translation):

- The CUDA code moves bytes with warp-cooperative shared-memory tiles
  because GPU global memory wants coalesced 128B transactions. On TPU,
  XLA owns layout: we express the transcode as pure array ops
  (bitcast -> concat -> pad for fixed rows; scatter/gather with
  searchsorted row binning for ragged string rows) and let XLA fuse the
  whole thing into a handful of HBM-bandwidth-bound loops.
- All shapes are static per (schema, num_rows, total_bytes): jit caches
  one executable per size class.
- The 2 GiB batch split is host metadata (the reference also computes it
  with host synchronizations, row_conversion.cu:1465-1543).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..columnar import Column, Table
from ..columnar import dtype as dt
from ..columnar.dtype import DType, TypeId
from ..utils import metrics, tracing
from ..utils.dispatch import op_boundary
from . import bitutils

__all__ = [
    "RowLayout",
    "compute_row_layout",
    "convert_to_rows",
    "convert_from_rows",
    "convert_from_rows_grouped",
    "GroupedRows",
    "convert_to_rows_fixed_width_optimized",
    "convert_from_rows_fixed_width_optimized",
]

JCUDF_ROW_ALIGNMENT = 8
MAX_BATCH_BYTES = (1 << 31) - 1  # cudf size_type limit per LIST<INT8> batch
MAX_ROW_SIZE_OPTIMIZED = 1024  # RowConversion.java:115-116
MAX_COLS_OPTIMIZED = 100  # RowConversion.java:27-34


def _round_up(v: int, align: int) -> int:
    return (v + align - 1) // align * align


@dataclasses.dataclass(frozen=True)
class RowLayout:
    """Static per-schema row layout (hashable: used as a jit static arg)."""

    col_starts: Tuple[int, ...]  # byte offset of each column's slot
    col_sizes: Tuple[int, ...]  # slot width (8 for compound columns)
    validity_offset: int  # first validity byte
    fixed_end: int  # validity_offset + validity bytes
    variable_cols: Tuple[int, ...]  # indices of STRING columns, in order
    row_size_fixed: int  # aligned row size when no variable data

    @property
    def num_columns(self) -> int:
        return len(self.col_starts)


def compute_row_layout(dtypes: Sequence[DType]) -> RowLayout:
    """Mirror of compute_column_information (row_conversion.cu:1340-1378)."""
    starts: List[int] = []
    sizes: List[int] = []
    variable: List[int] = []
    off = 0
    for i, d in enumerate(dtypes):
        if d.is_compound:
            if d.id != TypeId.STRING:
                raise ValueError(f"only STRING compound columns supported in rows, got {d!r}")
            size, align = 8, 4  # {offset:u32, len:u32}
            variable.append(i)
        elif d.is_fixed_width:
            size = d.size_bytes
            align = size
        else:
            raise ValueError(f"unsupported dtype in row conversion: {d!r}")
        off = _round_up(off, align)
        starts.append(off)
        sizes.append(size)
        off += size
    validity_offset = off
    fixed_end = off + (len(list(dtypes)) + 7) // 8
    return RowLayout(
        col_starts=tuple(starts),
        col_sizes=tuple(sizes),
        validity_offset=validity_offset,
        fixed_end=fixed_end,
        variable_cols=tuple(variable),
        row_size_fixed=_round_up(fixed_end, JCUDF_ROW_ALIGNMENT),
    )


# ---------------------------------------------------------------------------
# byte views
# ---------------------------------------------------------------------------


def _unpack_validity(vbytes: jnp.ndarray, num_cols: int) -> jnp.ndarray:
    """[N, nbytes] uint8 -> [N, num_cols] bool."""
    bits = (vbytes[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)[None, None, :]) & 1
    return bits.reshape(vbytes.shape[0], -1)[:, :num_cols].astype(bool)


# ---------------------------------------------------------------------------
# fixed section assembly (shared by the fixed-only and string paths)
# ---------------------------------------------------------------------------


def _entry_plan(layout: RowLayout, dtypes: Sequence[DType]):
    """Static grouping plan: each column becomes scalar 'entries' of one
    storage dtype (DECIMAL128 -> 4 u32 limbs, STRING slot -> 2 u32s,
    others -> 1 entry). Entries group by dtype so the device program
    stacks each group ONCE — op count scales with the number of distinct
    widths, not the number of columns (the 212-column reference bench
    axis compiles flat).

    Returns (group_order, entries) where entries[i] is a list of
    (dtype_key, byte_offset_in_row) per entry of column i, in entry
    order, and group_order is the dict of dtype_key -> next free index
    (i.e. final group sizes) built in first-seen order.
    """
    groups: dict = {}
    entries: List[List[Tuple[str, int, int]]] = []  # (key, slot_index, row_byte)
    for i, d in enumerate(dtypes):
        start = layout.col_starts[i]
        col_entries = []
        if d.id == TypeId.STRING:
            for sub in range(2):  # offset, length
                idx = groups.setdefault("u4", 0)
                groups["u4"] += 1
                col_entries.append(("u4", idx, start + 4 * sub))
        elif d.id == TypeId.DECIMAL128:
            for limb in range(4):
                idx = groups.setdefault("u4", 0)
                groups["u4"] += 1
                col_entries.append(("u4", idx, start + 4 * limb))
        else:
            key = f"w{d.size_bytes}_{jnp.dtype(d.jnp_dtype).name}"
            idx = groups.setdefault(key, 0)
            groups[key] += 1
            col_entries.append((key, idx, start))
        entries.append(col_entries)
    return groups, entries


def _entry_width(key: str) -> int:
    return 4 if key == "u4" else int(key[1 : key.index("_")])


def _col_u32_parts(col: Column, var_slot_vals: dict, i: int):
    """One column's value as a list of (width_bytes, [N] u32) parts in
    row-byte order, each part holding the value's bytes in its LOW
    bits. Pure arithmetic — no narrow-minor-dim arrays anywhere."""
    d = col.dtype
    if d.id == TypeId.STRING:
        off_u32, len_u32 = var_slot_vals[i]
        return [(4, off_u32.astype(jnp.uint32)), (4, len_u32.astype(jnp.uint32))]
    if d.id == TypeId.DECIMAL128:
        limbs = col.data.T  # [4, N]: one small transpose, contiguous rows
        return [(4, limbs[k]) for k in range(4)]
    w = d.size_bytes
    if w == 8:
        u = col.data
        if jnp.issubdtype(u.dtype, jnp.floating):
            u = lax.bitcast_convert_type(u, jnp.uint64)
        u = u.astype(jnp.uint64) if u.dtype != jnp.uint64 else u
        lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
        return [(4, lo), (4, hi)]
    if w == 4:
        u = col.data
        if u.dtype != jnp.uint32:
            u = lax.bitcast_convert_type(u, jnp.uint32)
        return [(4, u)]
    if w == 2:
        u = lax.bitcast_convert_type(col.data, jnp.uint16).astype(jnp.uint32)
        return [(2, u)]
    # w == 1 (int8/uint8/bool)
    u = col.data
    if u.dtype == jnp.bool_:
        u = u.astype(jnp.uint32)
    else:
        u = lax.bitcast_convert_type(u, jnp.uint8).astype(jnp.uint32)
    return [(1, u)]


def _fixed_planes32(
    layout: RowLayout,
    cols: Sequence[Column],
    var_slot_vals: dict,
    pad_to: int,
) -> jnp.ndarray:
    """[ceil(pad_to/4), N] uint32 PLANE STACK: lane p holds bytes
    [4p, 4p+4) of every row (column slots + padding + validity), as
    little-endian u32 words.

    TPU-layout-aware build: every interleave formulation that writes
    narrow lane slices ([N, w] pieces into a wide row) runs at ~0.3 GB/s
    on TPU — sub-128-lane writes waste 64x+ of each vector store (three
    designs measured: static-permutation take, ordered 160-piece concat,
    per-group stack). Instead each u32 LANE of the row is composed
    arithmetically as a contiguous [N] plane and the planes stack along
    axis 0 (dense memcpy). Callers either transpose ONCE to row-major
    ([P, N] -> [N, P], measured ~590 GB/s r+w chained; _fixed_section32)
    or feed the stack straight to the sublane-expand kernel
    (ragged_bytes.expand_u32_planes) whose u8 transpose is cheaper."""
    n = len(cols[0]) if cols else 0
    num_lanes = (pad_to + 3) // 4
    plane_parts: List[List[jnp.ndarray]] = [[] for _ in range(num_lanes)]

    def _emit(byte_off: int, val_u32: jnp.ndarray):
        lane, sub = divmod(byte_off, 4)
        if lane >= num_lanes:
            return
        if sub:
            val_u32 = val_u32 << jnp.uint32(8 * sub)
        plane_parts[lane].append(val_u32)

    for i, col in enumerate(cols):
        pos = layout.col_starts[i]
        for width, val in _col_u32_parts(col, var_slot_vals, i):
            _emit(pos, val)
            pos += width

    # validity bytes, composed from transposed per-column masks — byte
    # b's bit c%8 is column 8b+c's valid bit
    if cols:
        valid_t = jnp.stack([c.valid_mask() for c in cols], axis=0)  # [C, N]
        for b in range((len(cols) + 7) // 8):
            byte = jnp.zeros((n,), jnp.uint32)
            for bit in range(8):
                c = 8 * b + bit
                if c < len(cols):
                    byte = byte | (valid_t[c].astype(jnp.uint32) << jnp.uint32(bit))
            _emit(layout.validity_offset + b, byte)

    zero = jnp.zeros((n,), jnp.uint32)
    planes = [_or_compose(parts, zero) for parts in plane_parts]
    return jnp.stack(planes, axis=0) if planes else jnp.zeros((0, n), jnp.uint32)


def _fixed_section32(
    layout: RowLayout,
    cols: Sequence[Column],
    var_slot_vals: dict,
    pad_to: int,
) -> jnp.ndarray:
    """[N, ceil(pad_to/4)] u32 row-major lanes (see _fixed_planes32)."""
    return _fixed_planes32(layout, cols, var_slot_vals, pad_to).T


def _or_compose(parts: List[jnp.ndarray], zero: jnp.ndarray) -> jnp.ndarray:
    """OR-compose a lane's (disjoint) shifted byte parts."""
    if not parts:
        return zero
    out = parts[0]
    for v in parts[1:]:
        out = out | v
    return out


def _fixed_section(
    layout: RowLayout,
    cols: Sequence[Column],
    var_slot_vals: dict,
    pad_to: int,
) -> jnp.ndarray:
    """[N, pad_to] uint8 view of _fixed_section32 (byte-level callers —
    the scatter fallback). The u32->u8 bitcast goes through the chunked
    converter: whole-array 2-D bitcasts materialize a 32x tile-padded
    temp, worst exactly on the huge inputs this fallback serves."""
    from .ragged_bytes import u32_rows_to_u8_flat

    n = len(cols[0]) if cols else 0
    f32 = _fixed_section32(layout, cols, var_slot_vals, pad_to)
    by = u32_rows_to_u8_flat(f32).reshape(n, -1)
    return by[:, :pad_to]


# ---------------------------------------------------------------------------
# convert_to_rows
# ---------------------------------------------------------------------------


def _batch_boundaries(row_sizes: np.ndarray) -> List[Tuple[int, int, int]]:
    """Split rows into <=2GiB batches: list of (row_start, row_end, nbytes).

    Mirror of build_batches (row_conversion.cu:1465-1543): greedy scan of
    cumulative row sizes against the size_type ceiling.
    """
    n = len(row_sizes)
    if n == 0:
        return [(0, 0, 0)]
    cum = np.concatenate([[0], np.cumsum(row_sizes, dtype=np.int64)])
    batches = []
    start = 0
    while start < n:
        end = int(np.searchsorted(cum, cum[start] + MAX_BATCH_BYTES, side="right")) - 1
        if end == start:
            raise ValueError(f"row {start} larger than 2GiB batch limit")
        end = min(end, n)
        batches.append((start, end, int(cum[end] - cum[start])))
        start = end
    return batches


def _to_rows_fixed(layout: RowLayout, cols: Sequence[Column], n: int) -> jnp.ndarray:
    """All-fixed-width table -> [N * row_size] uint8 blob.

    TPU: plane stack [P, N] -> sublane-expand kernel -> u8 transpose ->
    flatten (the u32 transpose is skipped entirely; round-3 profile
    took this axis from 50.8 ms to ~9 ms at 1M x 212). Elsewhere: the
    row-major u32 section + chunked bitcast."""
    from .ragged_bytes import _use_pallas, expand_u32_planes, u32_rows_to_u8_flat

    if _use_pallas() and n >= 8:
        planes = _fixed_planes32(layout, cols, {}, layout.row_size_fixed)
        return expand_u32_planes(planes).T.reshape(-1)
    f32 = _fixed_section32(layout, cols, {}, layout.row_size_fixed)
    return u32_rows_to_u8_flat(f32)


# Padded-row memory amplification cap for the fast mixed path: the
# padded RP matrix costs N * (fixed_end + maxvar) bytes, so one huge
# outlier string must not blow device memory (fall back to the scatter
# path instead, which is slow but O(actual bytes)).
_PADDED_ROWS_BYTE_BUDGET = 4 << 30


@partial(jax.jit, static_argnums=(0,))
def _jit_fixed_and_slots(layout: RowLayout, cols: Tuple[Column, ...]):
    """Fixed sections (u32 lanes) + per-row string slot values, one
    program."""
    n = len(cols[0])
    var_cols = [cols[i] for i in layout.variable_cols]
    lens = [c.offsets[1:] - c.offsets[:-1] for c in var_cols]
    var_starts = []
    acc = jnp.full((n,), layout.fixed_end, dtype=jnp.int32)
    for ln in lens:
        var_starts.append(acc)
        acc = acc + ln
    slot_vals = {
        ci: (var_starts[k].astype(jnp.uint32), lens[k].astype(jnp.uint32))
        for k, ci in enumerate(layout.variable_cols)
    }
    fixed32 = _fixed_section32(layout, cols, slot_vals, layout.fixed_end)
    return fixed32, tuple(var_starts), tuple(lens)


@partial(jax.jit, static_argnums=(5, 6, 7))
def _jit_var_section(
    chars: Tuple[jnp.ndarray, ...],
    starts: Tuple[jnp.ndarray, ...],
    lens: Tuple[jnp.ndarray, ...],
    shifts: Tuple[jnp.ndarray, ...],
    tail_lane,  # [N] u32 partial fixed lane when fixed_end % 4 != 0
    tail_bytes: int,
    maxlens: Tuple[int, ...],
    maxvar: int,
):
    """All string columns -> the [N, maxvar/4] u32 variable REGION in
    ONE program: per-column padded extraction (windowed tile gather +
    Pallas rotate), then one Pallas accumulation pass whose shift
    ladders live in VMEM — as plain XLA the ladders materialize
    O(log(maxvar) * cols) full-width HLO temps at once ("35 GB / OOM
    at the 155-col x 1M axis": read before the benchmark, on
    benchmarks/microbench.py's table, not re-measured), and per-column
    dispatches cost a host↔device round trip each. At the reference's
    own 155-col x 1M axis (PR 34, on the chip) the worker that runs
    this inside the one fused program peaks at 3.65 GB.

    The region starts at byte 4*(fixed_end//4): when fixed_end is not
    lane-aligned, the trailing validity bytes (``tail_lane``) ride in
    as a pseudo-column at shift 0 so the u32 pipeline never needs a
    sub-lane boundary between the fixed and variable parts."""
    from .ragged_bytes import _pow2_ceil, padded_extract, var_accumulate

    p_mats, all_shifts = [], []
    if tail_bytes:
        tail = lax.bitcast_convert_type(tail_lane[:, None], jnp.uint8).reshape(-1, 4)
        mask = (jnp.arange(4, dtype=jnp.int32) < tail_bytes)[None, :]
        p_mats.append(jnp.where(mask, tail, 0))
        all_shifts.append(jnp.zeros((tail_lane.shape[0],), jnp.int32))
    # Serialize the per-column extractions ONLY under memory pressure:
    # each padded matrix is N * pow2(maxlen) bytes and the tile windows
    # another ~2x the char payload; when all K coexist a wide axis can
    # tip over HBM ("~4 GB at 155-col x 1M with large maxlens": read
    # before the benchmark on another table) — but forcing N
    # sequential kernels costs real wall time, so small extractions
    # stay concurrent (the cell's fifteen 32-byte columns reckon to
    # 0.98 GB here and are not serialized).
    n_rows = tail_lane.shape[0]
    est = sum(
        n_rows * max(_pow2_ceil(min(_round_up(maxlens[k], 4), maxvar)), 4)
        + 2 * int(chars[k].shape[0])
        for k in range(len(chars))
    )
    serialize = est > (1 << 30)
    seq = None
    for k in range(len(chars)):
        lc = min(_round_up(maxlens[k], 4), maxvar)
        st = starts[k].astype(jnp.int64)
        if serialize and seq is not None:
            st = st + (seq[0, 0].astype(jnp.int64) & 0)
        p = padded_extract(chars[k], st, maxlens[k])[:, :lc]
        p = jnp.where(jnp.arange(lc, dtype=jnp.int32)[None, :] < lens[k][:, None], p, 0)
        if serialize:
            p = lax.optimization_barrier(p)
            seq = p
        p_mats.append(p)
        all_shifts.append(shifts[k])
    return var_accumulate(tuple(p_mats), tuple(all_shifts), maxvar)


@partial(jax.jit, static_argnums=(3, 4))
def _jit_assemble(fixed32, var32, row_offsets, total_bytes: int, min_row: int):
    from .ragged_bytes import assemble_rows

    sizes = row_offsets[1:] - row_offsets[:-1]
    return assemble_rows((fixed32, var32), sizes, row_offsets, total_bytes, min_row)


@tracing.launches
@partial(jax.jit, static_argnums=(0, 3, 4, 5))
def _jit_encode_strings_fused(
    layout: RowLayout,
    cols: Tuple[Column, ...],
    row_offsets: jnp.ndarray,
    total_bytes: int,
    maxlens: Tuple[int, ...],
    maxvar: int,
) -> jnp.ndarray:
    """Mixed fixed+string table -> [total_bytes] u8 blob, the whole
    encode as ONE program of regular ops (ops/ragged_bytes design memo);
    the three stage jits inline:

    1. fixed sections and slot values assemble ([N, fixed_end]),
    2. each string column extracts to a padded [N, L_k] matrix with ONE
       overlapping-tile gather + per-row rotate, and the variable
       section accumulates by per-row byte shifts (strings are disjoint
       per row, so sum == placement),
    3. padded rows compact to the exact 8-aligned ragged blob with the
       dst-centric two-source tile assembly (monotonic gathers).

    The reference does this with a warp-per-row memcpy
    (row_conversion.cu:827-874); on TPU the same movement is gathers of
    fixed-width tiles + lane arithmetic. ``row_offsets`` are the [N+1]
    int64 dst offsets, ``maxlens`` each STRING column's longest length,
    ``maxvar`` the padded width of the variable section.

    The one form there is, with no staged fallback behind it: run
    stage by stage the same three stages materialize outputs this
    program never allocates, and at the reference's axis (155 columns,
    15 STRING of 0-32 bytes, 1 Mi rows, 1.17 GB of rows) that stops
    with RESOURCE_EXHAUSTED on a 16 GB chip, where this program runs in
    639 ms and its worker peaks at 3.65 GB (PR 34; 250 s to compile
    cold). A compile or run-time failure here raises as any operator's
    does."""
    var_cols = [cols[i] for i in layout.variable_cols]
    fixed32, var_starts, lens = _jit_fixed_and_slots(layout, tuple(cols))
    n = len(cols[0])

    # the u32 variable REGION starts at the last lane boundary <=
    # fixed_end; string shifts are relative to it, and any partial
    # fixed lane's validity bytes ride in as a pseudo column
    fe4 = layout.fixed_end // 4
    rem = layout.fixed_end % 4
    region = _round_up(rem + maxvar, 64)
    tail_lane = fixed32[:, fe4] if rem else jnp.zeros((n,), jnp.uint32)

    chars, starts, lens_in, shifts, mls = [], [], [], [], []
    for k, col in enumerate(var_cols):
        if maxlens[k] == 0:
            continue
        chars.append(col.chars)
        starts.append(col.offsets[:-1])
        lens_in.append(lens[k])
        shifts.append(var_starts[k] - 4 * fe4)
        # maxlens are table-global; a batch slice's local maximum is
        # bounded by its own maxvar, so clamping is lossless — and
        # required: the padded-extract gather width is sized by this
        # value, so an outlier string in ANOTHER batch must not inflate
        # this batch's temporaries
        mls.append(min(maxlens[k], maxvar))

    if not chars and not rem:
        var32 = jnp.zeros((n, region // 4), jnp.uint32)
    else:
        var32 = _jit_var_section(
            tuple(chars), tuple(starts), tuple(lens_in), tuple(shifts),
            tail_lane, rem, tuple(mls), region,
        )
    fixed_part = fixed32[:, :fe4] if rem else fixed32  # avoid a 1 GB slice copy
    return _jit_assemble(
        fixed_part, var32, row_offsets, total_bytes,
        _round_up(layout.fixed_end, JCUDF_ROW_ALIGNMENT),
    )


def _to_rows_strings(
    layout: RowLayout,
    cols: Sequence[Column],
    row_offsets: jnp.ndarray,  # [N] int64 dest offset of each row in blob
    total_bytes: int,
) -> jnp.ndarray:
    """Mixed fixed+string table -> [total_bytes] uint8 blob.

    Scatter FALLBACK for tables whose padded-row form would exceed the
    device-memory budget (huge outlier strings): element-granular, slow,
    but O(actual bytes). The hot path is _jit_encode_strings_fused.
    """
    n = len(cols[0])
    var_cols = [cols[i] for i in layout.variable_cols]
    lens = [c.offsets[1:] - c.offsets[:-1] for c in var_cols]  # [N] int32 each

    # dest offset (relative to row start) where each string col's chars land:
    # fixed_end + sum of lengths of preceding string cols in the same row.
    var_starts = []
    acc = jnp.full((n,), layout.fixed_end, dtype=jnp.int32)
    for ln in lens:
        var_starts.append(acc)
        acc = acc + ln

    slot_vals = {
        ci: (var_starts[k].astype(jnp.uint32), lens[k].astype(jnp.uint32))
        for k, ci in enumerate(layout.variable_cols)
    }
    fixed = _fixed_section(layout, cols, slot_vals, layout.fixed_end)

    blob = jnp.zeros((total_bytes,), dtype=jnp.uint8)
    # scatter the fixed section in row chunks: the [rows, fixed_end]
    # index matrix is O(total fixed bytes) — materialized whole it is a
    # multi-GB HLO temp at the 155-col x 1M mixed axis (compile-time
    # OOM); ~64MB of indices per scatter keeps the temp bounded
    chunk = max(1, (64 << 20) // 8 // max(layout.fixed_end, 1))  # bytes of i64 indices
    span = jnp.arange(layout.fixed_end, dtype=jnp.int64)[None, :]
    for r0 in range(0, n, chunk):
        r1 = min(r0 + chunk, n)
        fixed_idx = row_offsets[r0:r1, None] + span
        blob = blob.at[fixed_idx.reshape(-1)].set(
            fixed[r0:r1].reshape(-1), mode="drop"
        )

    for k, col in enumerate(var_cols):
        nchars = int(col.chars.shape[0])
        if nchars == 0:
            continue
        offs = col.offsets  # [N+1] int32
        j = jnp.arange(nchars, dtype=jnp.int32)
        row_of = jnp.searchsorted(offs, j, side="right").astype(jnp.int32) - 1
        dest = (
            row_offsets[row_of]
            + var_starts[k][row_of].astype(jnp.int64)
            + (j - offs[row_of]).astype(jnp.int64)
        )
        blob = blob.at[dest].set(col.chars, mode="drop")
    return blob


# the blob's eager bitcast is a program of its own name
_launch_bitcast = tracing.launches(lax.bitcast_convert_type)


def _wrap_batch_as_list_column(
    blob: jnp.ndarray, rel_offsets: jnp.ndarray, uniform_stride: int = 0
) -> Column:
    child = Column(dt.INT8, data=_launch_bitcast(blob, jnp.int8))
    col = Column(dt.LIST, offsets=rel_offsets.astype(jnp.int32), child=child)
    if uniform_stride:
        # producer-known constant row stride: lets the decoder skip the
        # uniformity probe entirely (a blocking host sync). Host metadata,
        # deliberately NOT part of the pytree: it is a cache, not data.
        col._uniform_stride = uniform_stride
    return col


def _count_to_rows(rows: int, batches: List[Column], string_cols: int = 0,
                   size_waits: int = 0, padded: int = 0, scatter: int = 0) -> None:
    """Registry-direct (on with tracing off): what a call of
    convert_to_rows moved and which form of the encode served it."""
    reg = metrics.registry()
    reg.counter("rowconv.to_rows.calls").inc()
    reg.counter("rowconv.to_rows.rows").inc(rows)
    reg.counter("rowconv.to_rows.bytes_out").inc(
        sum(int(b.child.data.shape[0]) for b in batches)
    )
    reg.counter("rowconv.to_rows.batches").inc(len(batches))
    reg.counter("rowconv.to_rows.string_cols").inc(string_cols)
    reg.counter("rowconv.to_rows.size_waits").inc(size_waits)
    reg.counter("rowconv.to_rows.padded").inc(padded)
    reg.counter("rowconv.to_rows.scatter").inc(scatter)


def _encode_strings_batch(
    layout: RowLayout,
    cols: Sequence[Column],
    row_offsets: jnp.ndarray,  # [rows + 1] int64, the batch's own
    nbytes: int,
    maxlens: Tuple[int, ...],
    max_size: int,  # the batch's largest row
    batch: int,
) -> Tuple[jnp.ndarray, str]:
    """One batch of a mixed table -> ([nbytes] u8 blob, the form that
    made it). The padded form wherever its [rows, fixed_end + maxvar]
    matrix fits the budget; the scatter form for an outlier string that
    it cannot hold. Dispatch only: nothing here waits for the device."""
    rows = len(cols[0])
    # static padded width of the var section, bucketed to 64B so
    # batches of similar shape share one compiled program
    maxvar = max(_round_up(max_size - layout.fixed_end, 64), 8)
    form = (
        "padded"
        if rows * (layout.fixed_end + maxvar) <= _PADDED_ROWS_BYTE_BUDGET
        else "scatter"
    )
    with tracing.span(
        "rowconv.encode", form=form, batch=batch, rows=rows, bytes=nbytes, maxvar=maxvar
    ):
        if form == "padded":
            blob = _jit_encode_strings_fused(
                layout, tuple(cols), row_offsets, nbytes, maxlens, maxvar
            )
        else:  # huge outlier strings: padded form would OOM
            blob = _to_rows_strings(layout, cols, row_offsets[:-1], nbytes)
    return blob, form


@op_boundary("convert_to_rows")
def convert_to_rows(table: Table) -> List[Column]:
    """Table -> one or more LIST<INT8> columns of JCUDF rows.

    Parity: RowConversion.convertToRows (RowConversion.java:35) ->
    spark_rapids_jni::convert_to_rows (row_conversion.cu:1903-1959).
    """
    layout = compute_row_layout(table.dtypes())
    n = table.num_rows
    cols = table.columns

    if n == 0:
        out = [_wrap_batch_as_list_column(jnp.zeros((0,), jnp.uint8), jnp.zeros((1,), jnp.int32))]
        _count_to_rows(0, out, string_cols=len(layout.variable_cols))
        return out

    if not layout.variable_cols:
        row_size = layout.row_size_fixed
        row_sizes = np.full((n,), row_size, dtype=np.int64)
        batches = _batch_boundaries(row_sizes)
        out = []
        for k, (rs, re, nbytes) in enumerate(batches):
            with tracing.span(
                "rowconv.encode", form="fixed", batch=k, rows=re - rs, bytes=nbytes, maxvar=0
            ):
                if len(batches) <= 4:
                    # STATIC batch offsets: XLA folds the slice into the
                    # relayout kernel's first read instead of materializing
                    # a sliced copy of all 212 columns — the traced-offset
                    # form cost the >2GiB axis an extra full pass (r4:
                    # 23.3 GB/s at 4M vs 72.9 at 1M; VERDICT r4 item 5).
                    # One compile per (length, offset) pair; bounded by the
                    # <=4 batch cap (~8 GiB of rows), past which the
                    # traced-offset program keeps compile count at O(1).
                    blob = _jit_to_rows_fixed_static(layout, tuple(cols), rs, re - rs)
                else:
                    blob = _jit_to_rows_fixed_sliced(layout, tuple(cols), rs, re - rs)
                rel = jnp.arange(re - rs + 1, dtype=jnp.int32) * row_size
            out.append(_wrap_batch_as_list_column(blob, rel, uniform_stride=row_size))
        _count_to_rows(n, out)
        return out

    # string path: per-row sizes -> batch split -> encode per batch.
    # ONE jitted program for the sizes and ONE small transfer for all
    # the host has to know before it can launch the encode: the byte
    # total, the largest row and each STRING column's longest string
    # (2 + K scalars; a column built by the sidecar's decode carries no
    # memo of its longest string, and asking each for it was one more
    # wait a column). Offsets stay on device.
    var_offs = tuple(cols[i].offsets for i in layout.variable_cols)
    size_waits = 1
    with tracing.span("rowconv.sizes", rows=n, string_cols=len(var_offs)) as sp:
        sizes_dev, offsets_dev, stats = _jit_row_size_stats(layout, var_offs)
        # host sync: the one wait
        stats = [int(v) for v in np.asarray(tracing.device_wait(stats, "row_sizes"))]
        total, max_size, maxlens = stats[0], stats[1], tuple(stats[2:])
        single = total <= MAX_BATCH_BYTES
        if not single:
            # host sync: full batch metadata
            row_sizes = np.asarray(tracing.device_wait(sizes_dev, "row_sizes_full"))
            size_waits = 2
        sp.annotate(total_bytes=total, max_row=max_size)

    forms = []
    if single:  # no further host pulls
        blob, form = _encode_strings_batch(
            layout, cols, offsets_dev, total, maxlens, max_size, 0
        )
        forms.append(form)
        out = [_wrap_batch_as_list_column(blob, offsets_dev)]
    else:
        out = []
        for k, (rs, re, nbytes) in enumerate(_batch_boundaries(row_sizes)):
            batch_cols = [_slice_column(c, rs, re) for c in cols]
            sizes = jnp.asarray(row_sizes[rs:re], dtype=jnp.int64)
            row_offsets = jnp.concatenate([jnp.zeros((1,), jnp.int64), jnp.cumsum(sizes)])
            blob, form = _encode_strings_batch(
                layout, batch_cols, row_offsets, nbytes, maxlens,
                int(row_sizes[rs:re].max()), k,
            )
            forms.append(form)
            out.append(_wrap_batch_as_list_column(blob, row_offsets))
    _count_to_rows(
        n, out, string_cols=len(var_offs), size_waits=size_waits,
        padded=int("padded" in forms), scatter=int("scatter" in forms),
    )
    return out


@tracing.launches
@partial(jax.jit, static_argnums=(0,))
def _jit_row_size_stats(layout: RowLayout, var_offsets: Tuple[jnp.ndarray, ...]):
    """([N] int64 8-aligned row sizes ON DEVICE, [N+1] offsets, [2 + K]
    {sum, max, each STRING column's longest length}) for the string
    path, one program: everything the host needs to size the encode
    rides in ONE small transfer (the caller pulls the [N] sizes too only
    when the table spans multiple 2 GiB batches)."""
    n = var_offsets[0].shape[0] - 1
    lens_total = jnp.zeros((n,), dtype=jnp.int64)
    maxlens = []
    for offs in var_offsets:
        lens = (offs[1:] - offs[:-1]).astype(jnp.int64)
        lens_total = lens_total + lens
        maxlens.append(jnp.max(lens))
    sizes = (
        (lens_total + layout.fixed_end + JCUDF_ROW_ALIGNMENT - 1)
        // JCUDF_ROW_ALIGNMENT
        * JCUDF_ROW_ALIGNMENT
    )
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int64), jnp.cumsum(sizes)])
    return sizes, offsets, jnp.stack([jnp.sum(sizes), jnp.max(sizes)] + maxlens)


def _slice_column(col: Column, rs: int, re: int) -> Column:
    if rs == 0 and re == len(col):
        return col
    v = None if col.validity is None else col.validity[rs:re]
    if col.dtype.id == TypeId.STRING:
        offs = col.offsets[rs : re + 1]
        base, end = offs[0], offs[-1]
        chars = lax.dynamic_slice_in_dim(col.chars, base, int(tracing.device_wait(end - base, "string_chars")))
        return Column(col.dtype, validity=v, offsets=offs - base, chars=chars)
    return Column(col.dtype, data=col.data[rs:re], validity=v)


# ---------------------------------------------------------------------------
# convert_from_rows
# ---------------------------------------------------------------------------


@op_boundary("convert_from_rows")
def convert_from_rows(rows: Column, dtypes: Sequence[DType]) -> Table:
    """LIST<INT8> (or LIST<UINT8>) column of JCUDF rows + schema -> Table.

    Parity: RowConversion.convertFromRows (RowConversion.java:137) ->
    convert_from_rows (row_conversion.cu:2031-2252).
    """
    if rows.dtype.id != TypeId.LIST:
        raise ValueError("convert_from_rows expects a LIST<INT8> column")
    dtypes = list(dtypes)
    layout = compute_row_layout(dtypes)
    n = len(rows)
    blob = _row_bytes(rows)

    if n == 0:
        _count_from_rows(0, 0, None)
        return Table([_empty_column(d) for d in dtypes])

    # dispatch only: the decode's device time is waited for by whoever
    # reads the columns (the sidecar's reply: sidecar.worker.d2h)
    with tracing.span(
        "rowconv.decode", rows=n, cols=len(dtypes), bytes=int(blob.shape[0])
    ) as sp:
        uniform = _offsets_uniform(rows, blob.shape[0], layout.row_size_fixed, n)
        form = "uniform" if uniform else "gathered"
        sp.annotate(form=form)
        # the rows' starts only where the row gather or a STRING column reads them
        starts = (
            rows.offsets[:-1].astype(jnp.int64)
            if not uniform or layout.variable_cols
            else None
        )
        if uniform:
            # constant row stride (always true for all-fixed-width tables we
            # produced): the row gather is a free reshape + static slice,
            # fused with the group decode in one program
            col_datas, valid = _decode_fixed_uniform(layout, tuple(dtypes), blob)
        else:
            fixed = _gather_fixed(layout, blob, starts, n)
            col_datas, valid = _decode_fixed_cols(layout, tuple(dtypes), fixed)
        table = _assemble_from_rows(dtypes, col_datas, valid, blob, starts, n)
    _count_from_rows(n, int(blob.shape[0]), form)
    return table


def _row_bytes(rows: Column) -> jnp.ndarray:
    """The rows' blob as uint8: a UINT8 child as it is (the sidecar puts
    the wire's bytes so), an INT8 child through one bitcast program."""
    data = rows.child.data
    return data if data.dtype == jnp.uint8 else _launch_bitcast(data, jnp.uint8)


def rows_from_host(offsets: np.ndarray, blob: np.ndarray) -> Column:
    """A LIST<UINT8> rows column from host ``int32`` offsets and row bytes,
    each put on the device once, as it is. The row stride is read from the
    host offsets here: ``_uniform_stride`` is that stride where every row
    has it and 0 where the offsets are ragged, so ``convert_from_rows``
    takes no device round trip to choose its form."""
    col = Column(
        dt.LIST,
        offsets=jnp.asarray(offsets),
        child=Column(dt.UINT8, data=jnp.asarray(blob)),
    )
    stride = int(offsets[1]) if len(offsets) > 1 and offsets[0] == 0 else 0
    uniform = stride > 0 and bool(np.all(np.diff(offsets) == stride))
    col._uniform_stride = stride if uniform else 0
    return col


def _count_from_rows(rows: int, bytes_in: int, form) -> None:
    """Registry-direct (on with tracing off): what a call of
    convert_from_rows moved and which form of the decode served it."""
    reg = metrics.registry()
    reg.counter("rowconv.from_rows.calls").inc()
    reg.counter("rowconv.from_rows.rows").inc(rows)
    reg.counter("rowconv.from_rows.bytes_in").inc(bytes_in)
    reg.counter("rowconv.from_rows.uniform").inc(int(form == "uniform"))
    reg.counter("rowconv.from_rows.gathered").inc(int(form == "gathered"))
    reg.counter("rowconv.from_rows.stride_probes")  # shows 0 until _offsets_uniform probes


def _gather_fixed(layout: RowLayout, blob, starts, n: int):
    """Gather each row's fixed section out of a ragged blob: [N, fixed_end] u8.

    The naive [N, fixed_end] index-matrix gather materializes an i64
    index array as big as 8x the fixed bytes (OOM at 1M x 1012 on a
    16 GB chip, observed round 3): on TPU the rows come out of ONE
    overlapping-tile gather + Pallas rotate (padded_extract), elsewhere
    the index matrix is chunked to ~64 MB."""
    fe = layout.fixed_end
    if not layout.variable_cols:
        return _jit_gather_fixed(blob, starts, fe, n)
    from .ragged_bytes import _use_pallas

    if _use_pallas() and n >= 8:
        return _jit_padded_gather(blob, starts, fe)
    chunk = max(1, (64 << 20) // 8 // max(fe, 1))
    span = jnp.arange(fe, dtype=jnp.int64)[None, :]
    parts = []
    for r0 in range(0, n, chunk):
        idx = starts[r0 : min(r0 + chunk, n), None] + span
        parts.append(blob[idx])
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


@partial(jax.jit, static_argnums=(2,))
def _jit_padded_gather(blob, starts, fixed_end: int):
    from .ragged_bytes import padded_extract

    return padded_extract(blob, starts, fixed_end)[:, :fixed_end]


@tracing.launches
@jax.jit
def _offsets_uniform_probe(offsets, stride):
    return (offsets[0] == 0) & jnp.all(offsets[1:] - offsets[:-1] == stride)


def _offsets_uniform(rows: Column, blob_len: int, stride: int, n: int) -> bool:
    """Constant-row-stride check. Prefer the stride the producer attached
    (zero syncs): ``convert_to_rows``' fixed-width batches carry it, and
    the sidecar's worker reads it from the host offsets it already holds
    (``rows_from_host``). A rows column that arrives on the device from
    another producer is reduced ON DEVICE and one scalar pulled — a full
    host↔device round trip, counted in ``rowconv.from_rows.stride_probes``."""
    if blob_len != n * stride:
        return False
    known = getattr(rows, "_uniform_stride", None)
    if known is not None:
        return known == stride
    metrics.registry().counter("rowconv.from_rows.stride_probes").inc()
    probe = _offsets_uniform_probe(rows.offsets, jnp.asarray(stride, rows.offsets.dtype))
    return bool(tracing.device_wait(probe, "row_stride"))


def _finish_column(d: DType, data, vmask, blob, starts) -> Column:
    """Wrap one decoded column's device data as a Column (strings gather
    their character bytes out of the row blob here)."""
    if d.id == TypeId.STRING:
        from .ragged_bytes import ragged_compact

        in_off, ln32 = data
        in_off = in_off.astype(jnp.int64)
        ln = ln32.astype(jnp.int32)
        offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(ln, dtype=jnp.int32)])
        total = int(offs[-1])  # host sync: chars allocation size
        chars = ragged_compact(blob, starts + in_off, offs.astype(jnp.int64), total)
        return Column(d, validity=vmask, offsets=offs, chars=chars)
    return Column(d, data=data, validity=vmask)


@jax.jit
def _jit_string_offsets(lns: Tuple[jnp.ndarray, ...]):
    """Per-string-column output offsets + a [K] totals vector, ONE
    program (the per-column `int(offs[-1])` host syncs cost a full
    round trip each — 16 of them dominated the mixed decode)."""
    offs = tuple(
        jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(ln, dtype=jnp.int32)])
        for ln in lns
    )
    return offs, jnp.stack([o[-1] for o in offs])


@partial(jax.jit, static_argnums=(0,))
def _jit_string_chars(
    totals: Tuple[int, ...],
    blob: jnp.ndarray,
    starts: jnp.ndarray,
    in_offs: Tuple[jnp.ndarray, ...],
    offs: Tuple[jnp.ndarray, ...],
):
    """All string columns' character gathers in ONE compiled program
    (compile count and dispatch count stop scaling with the string
    column count).

    Round 4: each column's chars come out via ragged_compact — the
    word-granular compaction (2 monotone u64 gathers + funnel per 8
    output bytes, ~2 ns/byte) that replaces the per-BYTE u8 element
    gather (~8 ns/byte at 0.034 GB/s measured; the axis's 7.5 s floor
    in round 3). Dst offsets are dense cumsums and row bases
    (starts[r] + in_off[r]) are monotone over rows, exactly
    ragged_compact's contract. Reference analog: the warp-per-row
    copy_strings_from_rows (row_conversion.cu:1141)."""
    from .ragged_bytes import build_pool32, ragged_compact

    pool32 = build_pool32(blob) if any(totals) else None  # ONCE per blob
    outs = []
    for k, total in enumerate(totals):
        if total == 0:
            outs.append(jnp.zeros((0,), jnp.uint8))
            continue
        base = starts + in_offs[k]
        outs.append(
            ragged_compact(blob, base, offs[k].astype(jnp.int64), total, pool32=pool32)
        )
    return tuple(outs)


def _pallas_string_chars(totals, blob, starts, in_offs, offs, mode):
    """Kernel-tier string decode (ISSUE 13): every string column's
    chars through the FUSED pallas_ragged_compact kernel — the offset
    walk, windowed byte gather, boundary masking, and head merge run
    in-VMEM instead of materializing the XLA formulation's per-column
    scatter/gather intermediates in HBM. The per-column window probes
    batch into ONE host sync (the _jit_string_offsets discipline: 16
    per-column syncs dominated the mixed decode). Returns None when any
    column's probed windows exceed the kernel caps — the caller keeps
    the fused XLA program."""
    from .pallas_kernels import pallas_decode_probe, pallas_ragged_compact
    from .ragged_bytes import build_pool32

    live = [k for k, t in enumerate(totals) if t > 0]
    bases = {}
    offs64 = {}
    probes = []
    for k in live:
        bases[k] = starts + in_offs[k]
        offs64[k] = offs[k].astype(jnp.int64)
        probes.append(pallas_decode_probe(bases[k], offs64[k], totals[k]))
    if not live:
        return tuple(jnp.zeros((0,), jnp.uint8) for _ in totals)
    hints = np.asarray(jnp.stack(probes))  # ONE host sync for all columns
    pool32 = build_pool32(blob)  # ONCE per blob
    outs = [jnp.zeros((0,), jnp.uint8)] * len(totals)
    for j, k in enumerate(live):
        out = pallas_ragged_compact(
            blob, bases[k], offs64[k], totals[k], pool32=pool32,
            interpret=mode == "interpret", hint=hints[j],
        )
        if out is None:
            return None
        outs[k] = out
    return tuple(outs)


def _assemble_from_rows(dtypes, col_datas, valid_cols, blob, starts, n) -> Table:
    from ..utils.dispatch import note_tier
    from .pallas_kernels import kernel_tier_mode

    str_idx = [i for i, d in enumerate(dtypes) if d.id == TypeId.STRING]
    prebuilt = {}
    if str_idx and n > 0:
        lns = tuple(col_datas[i][1].astype(jnp.int32) for i in str_idx)
        offs, totals_dev = _jit_string_offsets(lns)
        totals = tuple(int(t) for t in np.asarray(totals_dev))  # ONE host sync
        in_offs = tuple(col_datas[i][0].astype(jnp.int64) for i in str_idx)
        chars = None
        mode = kernel_tier_mode("SRJT_PALLAS_DECODE")
        if mode:
            # a kernel exception propagates: None is selection by shape
            chars = _pallas_string_chars(
                totals, blob, starts, in_offs, offs, mode
            )
        if chars is not None:
            note_tier("pallas", "string_decode")
        else:
            note_tier("xla", "string_decode")
            chars = _jit_string_chars(totals, blob, starts, in_offs, offs)
        for k, i in enumerate(str_idx):
            prebuilt[i] = Column(
                dtypes[i], validity=valid_cols[i], offsets=offs[k], chars=chars[k]
            )
    return Table(
        [
            prebuilt[i]
            if i in prebuilt
            else _finish_column(d, col_datas[i], valid_cols[i], blob, starts)
            for i, d in enumerate(dtypes)
        ]
    )


@dataclasses.dataclass
class GroupedRows:
    """Decoded JCUDF rows in the width-grouped device layout.

    The TPU-first counterpart of ``convert_from_rows``
    (row_conversion.cu:2031-2252 materializes one cudf column per schema
    entry): here the decode runs as ONE program producing O(distinct
    widths) device arrays, and per-column materialization is deferred.
    Fused query pipelines should consume ``groups``/``valid_t``
    directly; ``column(i)`` / ``to_table()`` materialize the
    ColumnVector-shaped contract on demand. The grouped form keeps the
    decode a single dispatch with O(width-groups) outputs — the form a
    downstream fused program can consume without 2*num_columns buffer
    round-trips through the runtime.
    """

    dtypes: Tuple[DType, ...]
    layout: RowLayout
    groups: dict  # width-group key -> [k, N] typed lanes (transposed)
    valid_t: jnp.ndarray  # [C, N] bool
    blob: jnp.ndarray  # [total_bytes] u8 row blob (string chars live here)
    starts: jnp.ndarray  # [N] i64 row start offsets

    def __len__(self) -> int:
        return int(self.valid_t.shape[1])

    def column(self, i: int) -> Column:
        """Materialize a single column (eager; for selective access)."""
        if len(self) == 0:
            return _empty_column(self.dtypes[i])
        _, entries = _entry_plan(self.layout, self.dtypes)
        d = self.dtypes[i]
        data, vmask = _extract_column(self.groups, self.valid_t, entries, i, d)
        return _finish_column(d, data, vmask, self.blob, self.starts)

    def to_table(self) -> Table:
        """Materialize every column through ONE jitted extraction (a
        per-column eager loop would re-pay the O(columns) dispatch
        overhead this representation exists to avoid)."""
        if len(self) == 0:
            return Table([_empty_column(d) for d in self.dtypes])
        col_datas, valids = _extract_all(
            self.layout, self.dtypes, tuple(self.groups), tuple(self.groups.values()),
            self.valid_t,
        )
        return _assemble_from_rows(
            self.dtypes, col_datas, valids, self.blob, self.starts, len(self)
        )


@partial(jax.jit, static_argnums=(0, 1, 2))
def _extract_all(layout, dtypes, group_keys, garrs, valid_t):
    groups = dict(zip(group_keys, garrs))
    _, entries = _entry_plan(layout, dtypes)
    col_datas, valids = [], []
    for i, d in enumerate(dtypes):
        data, v = _extract_column(groups, valid_t, entries, i, d)
        col_datas.append(data)
        valids.append(v)
    return tuple(col_datas), tuple(valids)


@op_boundary("convert_from_rows_grouped")
def convert_from_rows_grouped(rows: Column, dtypes: Sequence[DType]) -> GroupedRows:
    """LIST<INT8> rows + schema -> GroupedRows (one compiled program,
    no per-column buffers). See GroupedRows for when to prefer this
    over ``convert_from_rows``."""
    if rows.dtype.id != TypeId.LIST:
        raise ValueError("convert_from_rows_grouped expects a LIST<INT8> column")
    dtypes = tuple(dtypes)
    layout = compute_row_layout(dtypes)
    n = len(rows)
    blob = _row_bytes(rows)
    starts = rows.offsets[:-1].astype(jnp.int64)
    if n == 0:
        return GroupedRows(
            dtypes, layout, {}, jnp.zeros((len(dtypes), 0), bool), blob, starts
        )

    uniform = _offsets_uniform(rows, blob.shape[0], layout.row_size_fixed, n)
    if uniform:
        garrs, valid_t = _decode_grouped_uniform(layout, dtypes, blob)
    else:
        fixed = _gather_fixed(layout, blob, starts, n)
        garrs, valid_t = _decode_grouped_fixed(layout, dtypes, fixed)
    group_keys, _ = _entry_plan(layout, dtypes)
    groups = dict(zip(group_keys, garrs))
    return GroupedRows(dtypes, layout, groups, valid_t, blob, starts)


@partial(jax.jit, static_argnums=(0, 1))
def _decode_grouped_uniform(layout: RowLayout, dtypes: Tuple[DType, ...], blob: jnp.ndarray):
    n = blob.shape[0] // layout.row_size_fixed
    ga, vt = _decode_groups_core(layout, dtypes, _uniform_fixed(layout, blob, n))
    return tuple(ga.values()), vt


def _uniform_fixed(layout: RowLayout, blob: jnp.ndarray, n: int) -> jnp.ndarray:
    """Row view of a uniform-stride blob. The planes path keeps the full
    (8-aligned) row width — its transpose wants lane-aligned input and
    the pad bytes are never read; the byte-slice path trims to
    fixed_end so its strided slices touch fewer bytes."""
    from .ragged_bytes import _use_pallas

    rows = blob.reshape(n, layout.row_size_fixed)
    if _use_pallas() and n >= 8:
        return rows
    return rows[:, : layout.fixed_end]


@partial(jax.jit, static_argnums=(0, 1))
def _decode_grouped_fixed(layout: RowLayout, dtypes: Tuple[DType, ...], fixed: jnp.ndarray):
    ga, vt = _decode_groups_core(layout, dtypes, fixed)
    return tuple(ga.values()), vt


@tracing.launches
@partial(jax.jit, static_argnums=(0, 1))
def _decode_fixed_uniform(layout: RowLayout, dtypes: Tuple[DType, ...], blob: jnp.ndarray):
    """Uniform-stride decode: [n*row_size] u8 blob -> grouped columns in
    ONE program (reshape is free; XLA fuses the slice into the group
    gathers, so bytes move HBM->HBM exactly once)."""
    n = blob.shape[0] // layout.row_size_fixed
    return _decode_fixed_groups(layout, dtypes, _uniform_fixed(layout, blob, n))


@tracing.launches
@partial(jax.jit, static_argnums=(0, 1))
def _decode_fixed_cols(layout: RowLayout, dtypes: Tuple[DType, ...], fixed: jnp.ndarray):
    """[N, fixed_end] u8 -> (per-column data arrays, [N, C] validity).

    Inverse of _fixed_section's grouped assembly: one static permutation
    gather per width group, then a bitcast back to typed lanes — the
    whole decode is a single compiled program whose op count scales with
    distinct widths, not columns. STRING columns yield their (offset,
    length) u32 slot pair; DECIMAL128 yields [N, 4] limbs.
    """
    return _decode_fixed_groups(layout, dtypes, fixed)


def _decode_groups_from_planes(
    layout: RowLayout, dtypes: Tuple[DType, ...], fixed: jnp.ndarray
):
    """TPU decode core: [N, W] u8 rows -> the same (group_arrays,
    valid_t) contract as _decode_groups_core, via the sublane-pack
    kernel instead of strided byte slices.

    fixed.T IS the byte-plane stack (row j = byte j of every row), so
    pack_u8_planes turns it into [W/4, N] u32 words — one streaming
    kernel — and every group extraction is a contiguous ROW take of the
    plane array plus lane-constant shifts (slot alignment guarantees
    4-byte entries sit at lane boundaries). Replaces the 4-strided-
    u8-slice lane build that dominated decode (14.4 of 13.6..14 ms at
    1M x 212, round-3 profile)."""
    from .ragged_bytes import pack_u8_planes

    n, w = fixed.shape
    pad = (-w) % 4
    if pad:
        fixed = jnp.pad(fixed, ((0, 0), (0, pad)))
    planes = pack_u8_planes(fixed.T)  # [W/4, N] u32

    groups, entries = _entry_plan(layout, dtypes)
    group_arrays: dict = {}
    for key, count in groups.items():
        ew = _entry_width(key)
        byte_off = np.zeros((count,), np.int64)
        for col_entries in entries:
            for k2, idx, row_byte in col_entries:
                if k2 == key:
                    byte_off[idx] = row_byte
        b4 = jnp.asarray(byte_off // 4, jnp.int32)
        if ew == 4:
            lanes = jnp.take(planes, b4, axis=0)  # [k, N] u32
        elif ew == 8:
            lo = jnp.take(planes, b4, axis=0).astype(jnp.uint64)
            hi = jnp.take(planes, b4 + 1, axis=0).astype(jnp.uint64)
            lanes = lo | (hi << jnp.uint64(32))
        else:  # ew in (1, 2): sub-word shift is constant per entry
            base = jnp.take(planes, b4, axis=0)
            sh = jnp.asarray((byte_off % 4) * 8, np.uint32)[:, None]
            if ew == 2:
                lanes = lax.convert_element_type(
                    (base >> sh) & jnp.uint32(0xFFFF), jnp.uint16)
            else:
                lanes = lax.convert_element_type(
                    (base >> sh) & jnp.uint32(0xFF), jnp.uint8)
        if key == "u4":
            typed = lanes
        else:
            target = jnp.dtype(key[key.index("_") + 1:])
            typed = lanes if lanes.dtype == target else lax.bitcast_convert_type(lanes, target)
        group_arrays[key] = lax.optimization_barrier(typed)  # [k, N]

    c = len(dtypes)
    vbyte = layout.validity_offset + np.arange(c) // 8
    vbase = jnp.take(planes, jnp.asarray(vbyte // 4, jnp.int32), axis=0)  # [C, N]
    vsh = jnp.asarray((vbyte % 4) * 8 + np.arange(c) % 8, np.uint32)[:, None]
    valid_t = lax.optimization_barrier(((vbase >> vsh) & jnp.uint32(1)).astype(bool))
    return group_arrays, valid_t


def _decode_groups_core(layout: RowLayout, dtypes: Tuple[DType, ...], fixed: jnp.ndarray):
    """[N, fixed_end] u8 -> ({group key: [k, N] typed lanes}, [C, N] validity).

    The width-grouped, TRANSPOSED device representation: O(distinct
    widths) arrays regardless of column count. This is the form fused
    query pipelines consume, and the form `convert_from_rows_grouped`
    returns — a per-column decode of a 212-column table costs 424
    buffer registrations the grouped form avoids.
    """
    from .ragged_bytes import _use_pallas

    if _use_pallas() and fixed.shape[0] >= 8:
        return _decode_groups_from_planes(layout, dtypes, fixed)
    return _decode_groups_bytes(layout, dtypes, fixed)


def _decode_groups_bytes(layout: RowLayout, dtypes: Tuple[DType, ...], fixed: jnp.ndarray):
    """Byte-slice decode core (the non-Pallas implementation; see
    _decode_groups_core for the representation contract). Kept callable
    directly so the planes core can be cross-checked against it on any
    backend — on a TPU host the dispatcher above would otherwise route
    both sides of the comparison to the planes path."""
    groups, entries = _entry_plan(layout, dtypes)

    # NOTE on shapes: everything stays 2-D. A tempting "lane view"
    # (reshape [N, P/w, w] + bitcast) OOMs on TPU — XLA tile-pads the
    # tiny minor dim (w -> 128), a 32x memory blow-up for w=4. Instead,
    # wide lanes are built ARITHMETICALLY from strided byte slices
    # (fixed[:, b::4]), which are large-minor 2-D ops, and every group
    # read is a take of lane indices — w× fewer gather elements than
    # byte addressing.
    pad_w = _round_up(fixed.shape[1], 8)
    fixed_p = (
        jnp.pad(fixed, ((0, 0), (0, pad_w - fixed.shape[1])))
        if pad_w != fixed.shape[1]
        else fixed
    )
    widths = {_entry_width(k) for k in groups}
    lane16 = lane32 = None
    if 2 in widths:
        b = [fixed_p[:, i::2].astype(jnp.uint16) for i in range(2)]
        lane16 = b[0] | (b[1] << jnp.uint16(8))  # [N, P/2]
    if 4 in widths or 8 in widths:
        b = [fixed_p[:, i::4].astype(jnp.uint32) for i in range(4)]
        lane32 = b[0] | (b[1] << jnp.uint32(8)) | (b[2] << jnp.uint32(16)) | (
            b[3] << jnp.uint32(24)
        )  # [N, P/4]

    group_arrays: dict = {}
    for key, count in groups.items():
        w = _entry_width(key)
        lane_idx = np.zeros((count,), np.int32)
        for col_entries in entries:
            for k2, idx, row_byte in col_entries:
                if k2 == key:
                    lane_idx[idx] = row_byte // (4 if w == 8 else w)
        idxs = jnp.asarray(lane_idx)
        if w == 1:
            lanes = jnp.take(fixed_p, idxs, axis=1)  # [N, k] u8
        elif w == 2:
            lanes = jnp.take(lane16, idxs, axis=1)
        elif w == 4:
            lanes = jnp.take(lane32, idxs, axis=1)
        else:  # w == 8: two u32 lanes -> one u64
            lo = jnp.take(lane32, idxs, axis=1).astype(jnp.uint64)
            hi = jnp.take(lane32, idxs + 1, axis=1).astype(jnp.uint64)
            lanes = lo | (hi << jnp.uint64(32))
        if key == "u4":
            typed = lanes
        else:
            target = jnp.dtype(key[key.index("_") + 1 :])
            typed = lanes if lanes.dtype == target else lax.bitcast_convert_type(lanes, target)
        # materialize the group ONCE and TRANSPOSED: without the barrier
        # XLA rematerializes the gather inside every per-column consumer
        # fusion (O(bytes * columns)); without the transpose each
        # per-column extraction is a minor-axis lane slice, which on TPU
        # tiles reads a full (8, 128) tile per element — ~128x HBM read
        # amplification across 212 columns was the 6 GB/s decode of
        # round 1. Row slices of the [k, N] layout are contiguous.
        group_arrays[key] = lax.optimization_barrier(typed.T)  # [k, N]

    valid = _unpack_validity(
        fixed[:, layout.validity_offset : layout.fixed_end], len(dtypes)
    )
    # transposed for the same reason as the data groups: per-column
    # validity reads must be contiguous rows, not lane slices
    valid_t = lax.optimization_barrier(valid.T)  # [C, N]
    return group_arrays, valid_t


def _extract_column(group_arrays, valid_t, entries, i: int, d: DType):
    """One column's (data, validity) out of the grouped representation."""
    ents = entries[i]
    if d.id == TypeId.STRING:
        data = (group_arrays["u4"][ents[0][1]], group_arrays["u4"][ents[1][1]])
    elif d.id == TypeId.DECIMAL128:
        data = jnp.stack([group_arrays["u4"][e[1]] for e in ents], axis=1)
    else:
        key, idx, _ = ents[0]
        lane = group_arrays[key][idx]
        if key.startswith("w1_"):
            lane = lax.bitcast_convert_type(lane, jnp.dtype(key[3:]))
        data = lane
    return data, valid_t[i]


def _decode_fixed_groups(layout: RowLayout, dtypes: Tuple[DType, ...], fixed: jnp.ndarray):
    group_arrays, valid_t = _decode_groups_core(layout, dtypes, fixed)
    _, entries = _entry_plan(layout, dtypes)

    # split per column INSIDE the program: the caller assembling Columns
    # must not pay one eager dispatch per column (212-col tables)
    col_datas = []
    valid_cols = []
    for i, d in enumerate(dtypes):
        data, vmask = _extract_column(group_arrays, valid_t, entries, i, d)
        col_datas.append(data)
        valid_cols.append(vmask)
    return tuple(col_datas), tuple(valid_cols)


def _empty_column(d: DType) -> Column:
    if d.id == TypeId.STRING:
        return Column(d, offsets=jnp.zeros((1,), jnp.int32), chars=jnp.zeros((0,), jnp.uint8))
    if d.id == TypeId.DECIMAL128:
        return Column(d, data=jnp.zeros((0, 4), jnp.uint32))
    return Column(d, data=jnp.zeros((0,), d.jnp_dtype))


# ---------------------------------------------------------------------------
# fixed-width-optimized variants (legacy API surface, RowConversion.java:118-173)
# ---------------------------------------------------------------------------


def _check_optimized(dtypes: Sequence[DType]) -> RowLayout:
    dtypes = list(dtypes)
    if len(dtypes) >= MAX_COLS_OPTIMIZED:
        raise ValueError(
            f"fixed-width-optimized path supports < {MAX_COLS_OPTIMIZED} columns, got {len(dtypes)}"
        )
    for d in dtypes:
        if not d.is_fixed_width:
            raise ValueError(f"fixed-width-optimized path requires fixed-width types, got {d!r}")
    layout = compute_row_layout(dtypes)
    if layout.row_size_fixed > MAX_ROW_SIZE_OPTIMIZED:
        raise ValueError(f"row size {layout.row_size_fixed} exceeds 1KB limit")
    return layout


@op_boundary("convert_to_rows_fixed_width_optimized")
def convert_to_rows_fixed_width_optimized(table: Table) -> List[Column]:
    """Legacy <100-column fixed-width entry (RowConversion.java:118).

    Produces the identical JCUDF layout as convert_to_rows — the reference
    keeps two implementations only as a CUDA launch-shape optimization
    (row_conversion.cu:299-416); under XLA one lowering serves both, so this
    validates limits then delegates (the dual-implementation cross-check of
    row_conversion.cpp:43-60 holds by construction).
    """
    _check_optimized(table.dtypes())
    return convert_to_rows(table)


@op_boundary("convert_from_rows_fixed_width_optimized")
def convert_from_rows_fixed_width_optimized(rows: Column, dtypes: Sequence[DType]) -> Table:
    """Legacy fixed-width decode entry (RowConversion.java:158)."""
    _check_optimized(dtypes)
    return convert_from_rows(rows, dtypes)


# ---------------------------------------------------------------------------
# jit wrappers (one executable per (layout, n) size class)
# ---------------------------------------------------------------------------


@tracing.launches
@jax.jit
def _jit_gather_fixed_impl(blob, starts, iota):
    return blob[starts[:, None] + iota[None, :]]


def _jit_gather_fixed(blob, starts, fixed_end: int, n: int):
    return _jit_gather_fixed_impl(blob, starts, jnp.arange(fixed_end, dtype=jnp.int64))


@tracing.launches
@partial(jax.jit, static_argnums=(0, 2, 3))
def _jit_to_rows_fixed_static(layout: RowLayout, cols: Tuple[Column, ...],
                              rs: int, n: int):
    """Batch encode with a STATIC slice start: static slices fuse into
    the consuming relayout (no materialized per-column copies). Chosen
    for tables with <=4 batches; see convert_to_rows."""
    sliced = tuple(
        Column(c.dtype, data=lax.slice_in_dim(c.data, rs, rs + n),
               validity=None if c.validity is None
               else lax.slice_in_dim(c.validity, rs, rs + n))
        for c in cols
    )
    return _to_rows_fixed(layout, sliced, n)


@tracing.launches
@partial(jax.jit, static_argnums=(0, 3))
def _jit_to_rows_fixed_sliced(layout: RowLayout, cols: Tuple[Column, ...],
                              rs, n: int):
    """Batch encode with the row slicing INSIDE the program: per-column
    eager slices cost one dispatch each (212 columns x batches of
    round-trips through a remote backend dominated the >2GiB axis).
    Only the batch LENGTH is static (shapes need it); the start rides
    as a traced scalar so a many-batch table compiles once per distinct
    size, not once per offset."""
    sliced = tuple(
        Column(c.dtype, data=lax.dynamic_slice_in_dim(c.data, rs, n),
               validity=None if c.validity is None
               else lax.dynamic_slice_in_dim(c.validity, rs, n))
        for c in cols
    )
    return _to_rows_fixed(layout, sliced, n)
