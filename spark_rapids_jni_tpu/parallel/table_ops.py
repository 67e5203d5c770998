"""Table-level distributed relational ops: the tier Spark's plugin
actually calls (SURVEY §2.9 shuffle + §2.8 relational surface, lifted
from the raw int-array APIs in distributed.py / join_distributed.py to
``columnar.Table`` in / ``columnar.Table`` out).

Design:
- **Strings ride the exchange as dictionary codes.** The ICI all_to_all
  framing is static-shape fixed-width (parallel/shuffle.py); a STRING
  column dictionary-encodes to int32 codes against a batch-global
  dictionary (vectorized np.unique over the padded byte matrix), the
  codes exchange like any int lane, and receivers decode with one device
  ragged gather. This is the "the rejection becomes an encode step"
  path; the dictionary itself is replicated (it is the low-cardinality
  side by construction).
- **Composite keys hash-join exactly.** Destination routing chains
  murmur3 across key lanes (Spark Murmur3Hash parity,
  distributed.py:_hash_dest_multi). The per-shard sorted-run join runs
  on a 64-bit chained hash of the key tuple and VERIFIES every
  candidate pair on the raw lanes, so hash collisions cost only output
  slots, never correctness.
- **Counted capacity.** The sharded layer's exchange counts the rows
  bound for each destination before it moves any (``exchange_sharded``:
  one small program, one host read) and sizes its buckets from the
  fullest, on a ladder of powers of two: a skewed key is sized on the
  first try, a filtered input's empty slots buy nothing, and the stages
  behind work on slots in proportion to the rows. ``distributed_join_table``
  keeps its own all-to-all with a guessed capacity
  (``default_capacity``: ``max(4 * per_shard / n_parts, 64)``, capped at
  ``per_shard``) and the overflow flag as its resize signal.
- Null semantics follow Spark: null keys form one group (they exchange
  with a validity lane joined into the key tuple); aggregates skip null
  values; joins never match null keys.

FLOAT64 columns aggregate EXACTLY on every backend: the u64 IEEE-bit
lanes ride the exchange untouched and the shard aggregator runs the
windowed integer accumulator (ops/f64acc) — distributed SUM/MEAN/
MIN/MAX on doubles are bit-identical to the single-chip exact path.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..columnar import Column, Table
from ..columnar import dtype as dt
from ..columnar.dtype import TypeId
from ..ops import bitutils
from ..ops.hashing import murmur3_raw
from ..utils import metrics, tracing
from ..utils.dispatch import op_boundary
from ..utils.errors import FatalDeviceError
from .distributed import _hash_dest_multi
from .join_distributed import shard_join_pairs
from .shuffle import _bucketize
from ._smcache import cached_sm, shard_map

__all__ = [
    "dict_encode",
    "dict_decode",
    "default_capacity",
    "exchange_table",
    "distributed_groupby_table",
    "distributed_join_table",
    "ShardedTable",
    "ExchangeOverflow",
    "shard_table",
    "replicate_table",
    "exchange_sharded",
    "groupby_sharded",
    "join_sharded",
    "gather_table",
]


def _exchange_counter(name: str):
    """``exchange.<name>``, registry-direct as the plan tier's counters
    are: an overflow has to be countable in a run that records nothing
    else."""
    return metrics.registry().counter(f"exchange.{name}")


def _count_exchange(rows_in: int, lane_bytes: int, programs: int = 1) -> None:
    """One all-to-all program (or one more side of it): the rows that
    entered and the bytes of their lanes."""
    _exchange_counter("programs").inc(programs)
    _exchange_counter("rows_in").inc(rows_in)
    _exchange_counter("bytes_offered").inc(rows_in * lane_bytes)


def default_capacity(per_shard: int, n_parts: int) -> int:
    """Skew-aware per-destination bucket capacity."""
    return min(per_shard, max(4 * ((per_shard + n_parts - 1) // n_parts), 64))


def _pad_lanes(lanes: List[jnp.ndarray], n: int, n_parts: int):
    """Pad every lane to a mesh-divisible row count; returns (padded
    lanes, present lane). Padding rows carry present=False and are
    excluded from every downstream semantic (group segmentation, join
    matching, compaction) — the eager Table tier's occupancy framing."""
    pad = (-n) % n_parts
    present = jnp.concatenate([jnp.ones((n,), bool), jnp.zeros((pad,), bool)]) if pad else jnp.ones((n,), bool)
    if pad == 0:
        return list(lanes), present
    out = []
    for a in lanes:
        z = jnp.zeros((pad,) + a.shape[1:], a.dtype)
        out.append(jnp.concatenate([a, z]))
    return out, present


# ---------------------------------------------------------------------------
# string dictionary codec
# ---------------------------------------------------------------------------


class StringDictionary:
    """Batch-global sorted dictionary: host-built (np.unique), device-
    resident parts for the decode gather."""

    def __init__(self, lens: np.ndarray, chars: np.ndarray):
        self.lens_h = lens
        offs = np.zeros(len(lens) + 1, np.int64)
        np.cumsum(lens, out=offs[1:])
        self.offs = jnp.asarray(offs)
        self.lens = jnp.asarray(lens.astype(np.int32))
        self.chars = jnp.asarray(chars)

    def __len__(self) -> int:
        return len(self.lens_h)


def dict_encode(col: Column) -> Tuple[Column, StringDictionary]:
    """STRING column -> (INT32 code column, dictionary). Codes of null
    rows are 0 with validity preserved. Vectorized: one padded-matrix
    np.unique, no per-row Python."""
    if col.dtype.id != TypeId.STRING:
        raise ValueError("dict_encode takes a STRING column")
    offs = np.asarray(col.offsets)
    chars = np.asarray(col.chars)
    n = len(offs) - 1
    lens = (offs[1:] - offs[:-1]).astype(np.int32)
    L = max(int(lens.max()) if n else 1, 1)
    padded = np.zeros((n, L + 4), np.uint8)  # +4: length tiebreaker lane
    idx = offs[:-1, None] + np.arange(L)[None, :]
    inb = np.arange(L)[None, :] < lens[:, None]
    if chars.shape[0]:
        padded[:, :L] = np.where(inb, chars[np.clip(idx, 0, chars.shape[0] - 1)], 0)
    padded[:, L:] = lens[:, None].view(np.uint8).reshape(n, 4) if n else 0
    keyed = padded.view([("bytes", np.uint8, L + 4)]).reshape(n)
    uniq, inverse = np.unique(keyed, return_inverse=True)
    codes = inverse.astype(np.int32)

    u = uniq["bytes"].reshape(len(uniq), L + 4)
    u_lens = u[:, L:].copy().view(np.int32).reshape(-1)
    take = np.arange(L)[None, :] < u_lens[:, None]
    u_chars = u[:, :L][take]
    d = StringDictionary(u_lens, u_chars)
    return Column(dt.INT32, data=jnp.asarray(codes), validity=col.validity), d


def dict_decode(codes: jnp.ndarray, dictionary: StringDictionary, validity=None) -> Column:
    """INT32 codes -> STRING column via one device ragged gather."""
    from ..ops.bitutils import ragged_positions

    codes = jnp.clip(codes, 0, max(len(dictionary) - 1, 0))
    lens = dictionary.lens[codes] if len(dictionary) else jnp.zeros(codes.shape, jnp.int32)
    offs, row_of, pos, total = ragged_positions(lens)
    if total == 0:
        chars = jnp.zeros((0,), jnp.uint8)
    else:
        chars = dictionary.chars[dictionary.offs[codes[row_of]] + pos]
    return Column(dt.STRING, validity=validity, offsets=offs, chars=chars)


# ---------------------------------------------------------------------------
# Table <-> lane decomposition (what actually rides the exchange)
# ---------------------------------------------------------------------------


def _col_lanes(col: Column):
    """Column -> (data_lane, validity_lane_or_None, meta) where meta
    rebuilds the column after the exchange."""
    tid = col.dtype.id
    if tid == TypeId.STRING:
        codes, d = dict_encode(col)
        return codes.data, col.validity, ("string", d)
    if tid in (TypeId.LIST, TypeId.STRUCT):
        raise ValueError("nested columns: exchange leaf lanes individually")
    return col.data, col.validity, ("fixed", col.dtype)


def _rebuild(meta, data, validity) -> Column:
    kind, aux = meta
    if kind == "string":
        return dict_decode(data, aux, validity=validity)
    return Column(aux, data=data, validity=validity)


def _encoded(table: Table):
    """STRING columns as their dictionary codes (INT32 lanes ride an
    exchange; the dictionaries stay where they are), and the dictionaries
    that bring them back."""
    cols, dicts = [], {}
    for name, c in zip(table.names, table.columns):
        if c.dtype.id == TypeId.STRING:
            c, dicts[name] = dict_encode(c)
        elif c.dtype.id in (TypeId.LIST, TypeId.STRUCT):
            raise ValueError("nested columns: exchange leaf lanes individually")
        cols.append(c)
    return Table(cols, list(table.names)), dicts


def _decoded(table: Table, dicts) -> Table:
    cols = [dict_decode(c.data, dicts[name], validity=c.validity) if name in dicts else c
            for name, c in zip(table.names, table.columns)]
    return Table(cols, list(table.names))


@op_boundary("exchange_table")
def exchange_table(table: Table, key_cols: Sequence[str], mesh: Mesh, axis: str = "data") -> Tuple[Table, bool]:
    """Hash-repartition a Table (strings included) over the mesh and hand
    the received rows back as one compacted Table: the sharded layer below
    from end to end (``shard_table`` -> ``exchange_sharded`` ->
    ``gather_table``). Rows of equal key tuples met on one shard. The flag
    is always False and stays for the callers that unpack it: the buckets
    are sized from the counted rows, so none overflows."""
    enc, dicts = _encoded(table)
    out = gather_table(exchange_sharded(shard_table(enc, mesh, axis), key_cols))
    return _decoded(out, dicts), False


# ---------------------------------------------------------------------------
# distributed groupby on Tables
# ---------------------------------------------------------------------------


def _segment_aggs(val_arrays, hows, val_present, f64_flags, order, ps, seg, capacity: int):
    """The aggregates of rows taken in ``order``: ``seg`` numbers each
    row's group (``capacity`` for a row that is in none), ``ps`` marks the
    rows that count. Returns (agg_arrays, agg_valid_arrays)."""
    if f64_flags is None:
        f64_flags = [False] * len(val_arrays)
    aggs = []
    agg_valid = []
    for v, how, vp, is_f64bits in zip(val_arrays, hows, val_present, f64_flags):
        # is_f64bits comes from the COLUMN dtype (FLOAT64 IEEE-bit lane)
        # — never inferred from the jnp dtype, which a genuine UINT64
        # integer column shares
        vs = v[order]
        vps = (ps & vp[order]) if vp is not None else ps
        cnt = jax.ops.segment_sum(vps.astype(jnp.int64), seg, num_segments=capacity + 1)[:capacity]
        if how in ("sum", "mean"):
            if is_f64bits:
                from ..ops.f64acc import segment_mean_f64bits, segment_sum_f64bits

                if how == "sum":
                    s = segment_sum_f64bits(vs, seg, capacity + 1, valid=vps)[:capacity]
                else:
                    s, _c = segment_mean_f64bits(vs, seg, capacity + 1, valid=vps)
                    s = s[:capacity]
                aggs.append(s)
            else:
                x = jnp.where(vps, vs, 0)
                is_u64 = x.dtype == jnp.uint64
                if is_u64:
                    # same two's-complement sum bits (mod 2^64); the
                    # mean re-reads them unsigned
                    x = lax.bitcast_convert_type(x, jnp.int64)
                elif jnp.issubdtype(x.dtype, jnp.integer):
                    x = x.astype(jnp.int64)
                s = jax.ops.segment_sum(x, seg, num_segments=capacity + 1)[:capacity]
                if how == "sum":
                    aggs.append(
                        lax.bitcast_convert_type(s, jnp.uint64) if is_u64 else s
                    )
                elif jnp.issubdtype(vs.dtype, jnp.integer):
                    # exact int mean: limb-divide the exact int64 sum
                    from ..ops.f64acc import mean_i64_div

                    if is_u64:
                        aggs.append(
                            mean_i64_div(
                                lax.bitcast_convert_type(s, jnp.uint64), cnt, unsigned=True
                            )
                        )
                    else:
                        aggs.append(mean_i64_div(s, cnt))
                else:
                    aggs.append(s / jnp.maximum(cnt, 1).astype(s.dtype))
            agg_valid.append(cnt > 0)
        elif how == "count":
            aggs.append(cnt)
            agg_valid.append(jnp.ones((capacity,), bool))
        elif how in ("min", "max"):
            if is_f64bits:
                # exact total-order comparison on the stored bits
                from jax import lax as _lax

                from ..ops import bitutils as _bt
                from ..ops.aggregate import _from_total_order
                from ..columnar import dtype as _dt

                key = _bt.total_order_key(vs, _dt.FLOAT64)
                k = _lax.bitcast_convert_type(key ^ jnp.uint64(1 << 63), jnp.int64)
                fill = jnp.iinfo(jnp.int64).max if how == "min" else jnp.iinfo(jnp.int64).min
                f = jax.ops.segment_min if how == "min" else jax.ops.segment_max
                r = f(jnp.where(vps, k, fill), seg, num_segments=capacity + 1)[:capacity]
                key_back = _lax.bitcast_convert_type(r, jnp.uint64) ^ jnp.uint64(1 << 63)
                aggs.append(_from_total_order(key_back, _dt.FLOAT64))
            else:
                if jnp.issubdtype(vs.dtype, jnp.integer):
                    fill = jnp.iinfo(vs.dtype).max if how == "min" else jnp.iinfo(vs.dtype).min
                else:
                    fill = jnp.inf if how == "min" else -jnp.inf
                x = jnp.where(vps, vs, fill)
                f = jax.ops.segment_min if how == "min" else jax.ops.segment_max
                aggs.append(f(x, seg, num_segments=capacity + 1)[:capacity])
            agg_valid.append(cnt > 0)
        else:
            raise ValueError(f"unknown agg {how!r} (supported: {_SHARDED_HOWS})")
    return aggs, agg_valid


@op_boundary("distributed_groupby_table")
def distributed_groupby_table(
    table: Table,
    key_cols: Sequence[str],
    aggs: Sequence[Tuple[str, str, str]],  # (value_col, how, out_name)
    mesh: Mesh,
    axis: str = "data",
) -> Tuple[Table, bool]:
    """GROUP BY key_cols with multiple aggregates across the mesh —
    Table in, compacted Table out (keys + one column per aggregate): the
    sharded layer below from end to end (``shard_table`` ->
    ``exchange_sharded`` -> ``groupby_sharded`` -> ``gather_table``).
    String keys group via dictionary codes and decode on the way out. An
    exchange that would not fit the device budget at the capacity it
    counted (a skewed key's is a whole shard's rows) splits the batch
    instead (``_groupby_split_retry``: the reference's 2 GiB batching
    discipline). The flag is always False and stays for the callers that
    unpack it."""
    from ..utils.memory import MemoryBudgetExceeded

    for v, how, _o in aggs:
        if how not in _SHARDED_HOWS:
            raise ValueError(f"unknown agg {how!r}")
        if table.column(v).dtype.id == TypeId.STRING:
            raise ValueError("aggregating STRING columns is not supported")
    used = list(dict.fromkeys(list(key_cols) + [v for v, _h, _o in aggs]))  # only these ride the exchange
    enc, dicts = _encoded(table.select(used))
    try:
        st = exchange_sharded(shard_table(enc, mesh, axis), key_cols)
    except MemoryBudgetExceeded:
        return _groupby_split_retry(table, key_cols, aggs, mesh, axis)
    return _decoded(gather_table(groupby_sharded(st, key_cols, aggs)), dicts), False


_MERGE_HOW = {"sum": "sum", "count": "sum", "count_all": "sum", "min": "min", "max": "max"}


def _groupby_split_retry(
    table: Table,
    key_cols: Sequence[str],
    aggs: Sequence[Tuple[str, str, str]],
    mesh: Mesh,
    axis: str,
) -> Tuple[Table, bool]:
    """Split the batch in half row-wise, run each half (recursively
    subject to the same budget), and re-aggregate the partial results
    on a single chip. ``mean`` decomposes into sum+count for the
    partials and recombines at the end; every other supported aggregate
    is merge-associative."""
    from ..ops.aggregate import groupby_aggregate
    from ..ops.copying import slice_table
    from ..utils.memory import _note_split

    _note_split()
    n = table.num_rows
    if n < 2:
        # halving cannot go below one row: retrying is unproductive,
        # so this must NOT be retryable (taxonomy: fatal ends the
        # split recursion instead of burning the attempt budget)
        raise FatalDeviceError("cannot split a single-row batch further")
    # mean is not merge-associative: compute sum + count in the partials
    inner_aggs: List[Tuple[str, str, str]] = []
    for vname, how, oname in aggs:
        if how == "mean":
            inner_aggs.append((vname, "sum", f"{oname}__s"))
            inner_aggs.append((vname, "count", f"{oname}__c"))
        else:
            inner_aggs.append((vname, how, oname))

    mid = (n // 2 + mesh.shape[axis] - 1) // mesh.shape[axis] * mesh.shape[axis]
    mid = min(max(mid, 1), n - 1)
    parts = []
    for lo, hi in ((0, mid), (mid, n)):
        half = slice_table(table, lo, hi)
        parts.append(distributed_groupby_table(half, key_cols, inner_aggs, mesh, axis=axis)[0])

    from ..ops.copying import concatenate

    merged_in = concatenate(parts)
    keys_t = Table([merged_in.column(k) for k in key_cols], list(key_cols))
    val_names = [o for _v, _h, o in inner_aggs]
    vals_t = Table([merged_in.column(o) for o in val_names], val_names)
    merge_aggs = [(o, _MERGE_HOW[h]) for (_v, h, o) in inner_aggs]
    merged = groupby_aggregate(keys_t, vals_t, merge_aggs)

    out_cols = [merged.column(k) for k in key_cols]
    out_names = list(key_cols)
    for vname, how, oname in aggs:
        if how == "mean":
            s = merged.column(f"{oname}__s_sum")
            c = merged.column(f"{oname}__c_sum")
            valid = c.data > 0
            if s.validity is not None:
                valid = valid & s.validity
            if s.dtype.id == TypeId.FLOAT64:
                # exact recombination: merged partial-sum bits / count
                from ..ops.f64acc import div_f64bits_by_int

                mbits = div_f64bits_by_int(s.data, jnp.maximum(c.data, 1))
                out_cols.append(Column(dt.FLOAT64, data=mbits, validity=valid))
            elif jnp.issubdtype(s.data.dtype, jnp.integer):
                from ..ops.f64acc import mean_i64_div

                mbits = mean_i64_div(s.data.astype(jnp.int64), jnp.maximum(c.data, 1))
                out_cols.append(Column(dt.FLOAT64, data=mbits, validity=valid))
            else:
                # FLOAT32 partials divide in their own float lane
                m = s.data / jnp.maximum(c.data, 1).astype(s.data.dtype)
                out_cols.append(
                    Column(
                        dt.FLOAT64,
                        data=bitutils.float_store(m, dt.FLOAT64),
                        validity=valid,
                    )
                )
        else:
            mcol = merged.column(f"{oname}_{_MERGE_HOW[how]}")
            out_cols.append(mcol)
        out_names.append(oname)
    return Table(out_cols, out_names), False


# ---------------------------------------------------------------------------
# distributed join on Tables
# ---------------------------------------------------------------------------


def _hash64(key_arrays) -> jnp.ndarray:
    """64-bit chained murmur over the key tuple (two independent seeds);
    collisions are verified away pair-by-pair, so this only routes."""
    h1 = None
    h2 = None
    for k in key_arrays:
        h1 = murmur3_raw(k) if h1 is None else murmur3_raw(k, seed=h1)
        h2 = murmur3_raw(k, seed=jnp.uint32(0x9E3779B9)) if h2 is None else murmur3_raw(k, seed=h2)
    lo = h1.astype(jnp.uint64)
    hi = h2.astype(jnp.uint64)
    return lax.bitcast_convert_type((hi << 32) | lo, jnp.int64)


@op_boundary("distributed_join_table")
def distributed_join_table(
    left: Table,
    right: Table,
    on: Sequence[str],
    mesh: Mesh,
    how: str = "inner",
    axis: str = "data",
    capacity: Optional[int] = None,
    out_capacity: Optional[int] = None,
    max_retries: int = 2,
) -> Tuple[Table, bool]:
    """Shuffled hash join on Tables across the mesh: `how` in
    {inner, left_semi, left_anti}. Composite keys route by chained
    murmur3 and match on a verified 64-bit hash run; string key/payload
    columns travel as dictionary codes. Null keys never match (Spark).

    Output: inner -> left columns + right non-key columns; semi/anti ->
    left columns. Compacted global Table + overflow flag.

    Capacities default skew-aware (O(N/P) buffers); on overflow with
    defaulted capacities the join recomputes with 4x larger buffers
    (up to `max_retries` times) before surfacing the flag.
    """
    if how not in ("inner", "left_semi", "left_anti"):
        raise ValueError(f"how={how!r} not supported (inner/left_semi/left_anti)")
    n_parts = mesh.shape[axis]
    per_l = (left.num_rows + n_parts - 1) // n_parts
    per_r = (right.num_rows + n_parts - 1) // n_parts
    auto = capacity is None and out_capacity is None
    if capacity is None:
        capacity = max(
            default_capacity(max(per_l, 1), n_parts),
            default_capacity(max(per_r, 1), n_parts),
        )
    if out_capacity is None:
        out_capacity = (
            max(per_l, 64) if how != "inner" else max(2 * max(per_l, per_r), 64)
        )
    for attempt in range(max_retries + 1):
        table, ovf = _join_once(
            left, right, on, mesh, how, axis, int(capacity), int(out_capacity), attempt
        )
        if ovf:
            _exchange_counter("overflows").inc()
        if not ovf or not auto:
            return table, ovf
        if attempt < max_retries:
            _exchange_counter("capacity_retries").inc()
        capacity = min(capacity * 4, max(per_l, per_r, 1))
        out_capacity *= 4
    return table, ovf


def _join_once(
    left: Table,
    right: Table,
    on: Sequence[str],
    mesh: Mesh,
    how: str,
    axis: str,
    capacity: int,
    out_capacity: int,
    attempt: int = 0,
) -> Tuple[Table, bool]:
    n_parts = mesh.shape[axis]
    cap_out = int(out_capacity)

    # STRING join keys need ONE dictionary spanning both tables (codes
    # from independent encodes would never compare equal): encode the
    # concatenated column, split the codes back per side.
    shared: dict = {}
    for name in on:
        lc, rc = left.column(name), right.column(name)
        if lc.dtype.id == TypeId.STRING or rc.dtype.id == TypeId.STRING:
            if lc.dtype.id != rc.dtype.id:
                raise ValueError(f"join key {name!r} has mismatched types")
            both = Column(
                dt.STRING,
                validity=None,
                offsets=jnp.concatenate(
                    [lc.offsets, rc.offsets[1:] + lc.offsets[-1]]
                ),
                chars=jnp.concatenate([lc.chars, rc.chars]),
            )
            codes, d = dict_encode(both)
            nl = len(lc)
            shared[name] = (codes.data[:nl], codes.data[nl:], d)

    def lanes_of(tbl: Table, side: int):
        lanes, metas, has_v = [], [], []
        for nm, c in zip(tbl.names, tbl.columns):
            if nm in shared:
                data = shared[nm][side]
                validity, meta = c.validity, ("string", shared[nm][2])
            else:
                data, validity, meta = _col_lanes(c)
            lanes.append(data)
            metas.append(meta)
            has_v.append(validity is not None)
            if validity is not None:
                lanes.append(validity)
        return lanes, metas, has_v

    l_lanes, l_metas, l_hasv = lanes_of(left, 0)
    r_lanes, r_metas, r_hasv = lanes_of(right, 1)

    def key_positions(tbl, has_v):
        # (data lane idx, validity lane idx or None) per key column —
        # key lanes ride the exchange ONCE, inside the payload; both the
        # routing hash (pre-exchange) and the collision verification
        # (post-exchange) index the payload lanes at these positions
        out = []
        for name in on:
            i = tbl.names.index(name)
            lane_pos = sum(1 + int(h) for h in has_v[:i])
            out.append((lane_pos, lane_pos + 1 if has_v[i] else None))
        return out

    l_kpos = key_positions(left, l_hasv)
    r_kpos = key_positions(right, r_hasv)
    n_on = len(on)

    # pad each side to a mesh-divisible row count (present=False rows
    # never match and never survive compaction)
    l_lanes, l_present = _pad_lanes(l_lanes, left.num_rows, n_parts)
    r_lanes, r_present = _pad_lanes(r_lanes, right.num_rows, n_parts)
    nl_lanes, nr_lanes = len(l_lanes), len(r_lanes)

    def keys_from(lanes, kpos):
        ks, null_mask = [], None
        for dpos, vpos in kpos:
            ks.append(lanes[dpos])
            if vpos is not None:
                v = lanes[vpos].astype(bool)
                null_mask = v if null_mask is None else (null_mask & v)
        return ks, null_mask

    def body(*arrs):
        lpres, rpres = arrs[0], arrs[1]
        lps = list(arrs[2 : 2 + nl_lanes])
        rps = list(arrs[2 + nl_lanes :])
        lks, lkv = keys_from(lps, l_kpos)
        rks, rkv = keys_from(rps, r_kpos)

        ld = _hash_dest_multi(lks, n_parts)
        rd = _hash_dest_multi(rks, n_parts)
        a2a = lambda x: lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)

        def exchange(arr_list, dest):
            outs, mask, ovf = [], None, jnp.zeros((), bool)
            for a in arr_list:
                b, m, o = _bucketize(a, dest, n_parts, capacity)
                outs.append(a2a(b).reshape((-1,) + a.shape[1:]))
                mask, ovf = m, ovf | o
            rm = a2a(mask).reshape(-1)
            return outs, rm, ovf

        lh = _hash64(lks)
        rh = _hash64(rks)
        l_all, lm, o1 = exchange([lh, lpres] + lps, ld)
        r_all, rm, o2 = exchange([rh, rpres] + rps, rd)
        lh_r, lpres_r, lps_r = l_all[0], l_all[1], l_all[2:]
        rh_r, rpres_r, rps_r = r_all[0], r_all[1], r_all[2:]
        lks_r, lkv_r = keys_from(lps_r, l_kpos)
        rks_r, rkv_r = keys_from(rps_r, r_kpos)

        lm = lm & lpres_r
        rm = rm & rpres_r
        lpresent = lm if lkv_r is None else (lm & lkv_r)
        rpresent = rm if rkv_r is None else (rm & rkv_r)
        li, ri, pv, o3 = shard_join_pairs(lh_r, lpresent, rh_r, rpresent, cap_out)
        # verify raw key equality (hash collisions only shed here)
        for a, b in zip(lks_r, rks_r):
            pv = pv & (a[li] == b[ri])

        def wsel(mask, arr):  # mask rows, broadcast over trailing dims
            m = mask.reshape(mask.shape + (1,) * (arr.ndim - 1))
            return jnp.where(m, arr, jnp.zeros((), arr.dtype))

        if how == "inner":
            outs = tuple(wsel(pv, x[li]) for x in lps_r)
            outs += tuple(wsel(pv, x[ri]) for x in rps_r)
            return outs + (pv, lm, (o1 | o2 | o3)[None])

        # semi/anti: reduce pair hits onto left rows
        hit = (
            jnp.zeros(lh_r.shape, jnp.int32).at[li].add(pv.astype(jnp.int32), mode="drop") > 0
        )
        keep = (lm & hit) if how == "left_semi" else (lm & ~hit)
        return tuple(lps_r) + (keep, lm, (o1 | o2 | o3)[None])

    in_lanes = [l_present, r_present] + l_lanes + r_lanes
    n_out = (nl_lanes + nr_lanes if how == "inner" else nl_lanes) + 3
    spec = P(axis)
    f = cached_sm(
        ("join_table", mesh, axis, int(capacity), cap_out, how,
         tuple(l_kpos), tuple(r_kpos), nl_lanes, nr_lanes,
         tuple(str(a.dtype) for a in in_lanes)),
        lambda: jax.jit(shard_map(
            body, mesh=mesh, in_specs=(spec,) * len(in_lanes), out_specs=(spec,) * n_out
        )),
    )
    with tracing.span("exchange.join", rows_in=left.num_rows + right.num_rows, keys=list(on),
                      capacity=int(capacity), parts=n_parts, attempt=attempt, how=how):
        outs = f(*in_lanes)
    _count_exchange(left.num_rows, sum(int(a.dtype.itemsize) for a in l_lanes))
    _count_exchange(right.num_rows, sum(int(a.dtype.itemsize) for a in r_lanes), programs=0)
    ovf = bool(np.asarray(tracing.device_wait(outs[-1], "overflow_flags")).any())
    keep = np.asarray(outs[-3])
    sel = jnp.asarray(np.flatnonzero(keep))

    def rebuild(tbl: Table, metas, has_v, received, skip_keys: bool):
        cols, names = [], []
        it = iter(received)
        for name, meta, nullable in zip(tbl.names, metas, has_v):
            data = next(it)[sel]
            validity = next(it)[sel].astype(bool) if nullable else None
            if skip_keys and name in on:
                continue
            cols.append(_rebuild(meta, data, validity))
            names.append(name)
        return cols, names

    received = [jnp.asarray(o) for o in outs[: n_out - 3]]
    l_recv = received[:nl_lanes]
    cols, names = rebuild(left, l_metas, l_hasv, l_recv, skip_keys=False)
    if how == "inner":
        r_recv = received[nl_lanes:]
        rc, rn = rebuild(right, r_metas, r_hasv, r_recv, skip_keys=True)
        for c, nm in zip(rc, rn):
            names.append(nm if nm not in names else f"{nm}_right")
            cols.append(c)
    return Table(cols, names=names), ovf


# ---------------------------------------------------------------------------
# the sharded layer: Tables that STAY laid out over the mesh between stages
# ---------------------------------------------------------------------------
#
# ``exchange_table`` and ``distributed_groupby_table`` above are this
# layer from end to end: they place a Table, run one stage and hand back a
# compacted Table, so whatever partitioning the exchange established is
# gone with the compaction. A plan compiled for a mesh (plan/compiler.py
# under a ``plan.distribute.MeshBinding``) keeps its fact tables in the
# layout below from the scan to the last keyed stage: one exchange
# establishes a partitioning, and the group-bys and joins on the same key
# after it run shard-local, with no collective. ``distributed_join_table``
# is another operator, not this layer's twin: it shuffles BOTH sides on a
# composite key and pairs many rows to many; ``join_sharded`` probes one
# integer key where the rows already lie.


class ExchangeOverflow(RuntimeError):
    """A destination bucket overflowed the capacity its exchange had
    counted for it: raised, never answered with rows missing."""


class ShardedTable:
    """Fixed-width columns as global arrays of ``world x L`` slots,
    row-sharded over ``axis``; ``present`` marks the slots that hold a
    row (padding, filtered-out rows and empty bucket slots do not).
    ``part`` names the columns on which equal keys are known to share a
    shard (``()``: rows lie where the file order or a filter left them);
    ``ordered`` a column whose values ascend over the slots of every shard,
    absent slots included, and are distinct where present (a group-by's
    key: a probe of it needs no sort). ``overflow`` holds, for every
    exchange these rows came through (a join's right side's too), its
    keys, its capacity and its overflow flag, still on the device:
    ``gather_table`` reads them. STRING columns do not ride here: they
    stay on replicated tables."""

    __slots__ = ("table", "present", "mesh", "axis", "part", "ordered", "overflow")

    def __init__(self, table: Table, present, mesh: Mesh, axis: str = "data",
                 part: Tuple[str, ...] = (), ordered: Optional[str] = None, overflow: tuple = ()):
        self.table, self.present, self.mesh, self.axis = table, present, mesh, axis
        self.part, self.ordered, self.overflow = tuple(part), ordered, tuple(overflow)

    @property
    def names(self) -> List[str]:
        return self.table.names

    @property
    def num_rows(self) -> int:
        """Slots, not rows: counting the rows would wait for the device."""
        return int(self.present.shape[0])

    @property
    def n_parts(self) -> int:
        return self.mesh.shape[self.axis]

    def column(self, name) -> Column:
        return self.table.column(name)

    def select(self, names) -> "ShardedTable":
        names = list(names)
        return ShardedTable(self.table.select(names), self.present, self.mesh, self.axis,
                            self.part if set(self.part) <= set(names) else (),
                            self.ordered if self.ordered in names else None, self.overflow)

    def replace(self, table: Table = None, present=None, part=None, overflow=None) -> "ShardedTable":
        """Other columns over the same slots, or fewer rows present: the
        slots keep their order, so what is known of it holds for every
        column that is carried over as it was."""
        ordered = self.ordered
        if table is not None and ordered is not None:
            kept = ordered in table.names and table.column(ordered).data is self.column(ordered).data
            ordered = ordered if kept else None
        return ShardedTable(self.table if table is None else table,
                            self.present if present is None else present,
                            self.mesh, self.axis, self.part if part is None else part, ordered,
                            self.overflow if overflow is None else overflow)


@op_boundary("shard_table")
def shard_table(table: Table, mesh: Mesh, axis: str = "data") -> ShardedTable:
    """Place a Table row-sharded in file order: shard i holds rows
    [i*L, (i+1)*L), the tail padded with absent slots."""
    from .mesh import row_sharding

    n_parts, n = mesh.shape[axis], table.num_rows
    with tracing.span("exchange.place", rows=n, parts=n_parts, how="sharded",
                      cols=table.num_columns):
        sh = row_sharding(mesh, axis)
        for c in table.columns:
            if not c.dtype.is_fixed_width:
                raise ValueError(f"a {c.dtype!r} column cannot be row-sharded: keep its table replicated")
        lanes, spots = _lanes_of(table)
        lanes, present = _pad_lanes(lanes, n, n_parts)
        if n == 0:  # one absent slot a shard: no program is traced over no slots at all
            lanes = [jnp.zeros((n_parts,) + a.shape[1:], a.dtype) for a in lanes]
            present = jnp.zeros((n_parts,), bool)
        lanes = [jax.device_put(a, sh) for a in lanes]
        return ShardedTable(_table_from(table, spots, lanes), jax.device_put(present, sh), mesh, axis)


@op_boundary("replicate_table")
def replicate_table(table: Table, mesh: Mesh) -> Table:
    """A whole copy of a (small) Table on every chip of the mesh."""
    from .mesh import replicated

    with tracing.span("exchange.place", rows=table.num_rows, parts=int(mesh.size), how="replicated",
                      cols=table.num_columns):
        return jax.device_put(table, replicated(mesh))


_CAPACITY_FLOOR = 1024


def _counted_capacity(max_bucket: int, per_shard: int) -> int:
    """Bucket capacity of the sharded exchange from the rows it counted:
    the power of two at or above the fullest bucket, no smaller than a
    floor under which every sparse input shares one program, no larger
    than ``per_shard`` (a shard has no more rows than that to send). A
    capacity is a SHAPE: coarse steps keep inputs that hold the same keys
    in another order, or a few rows more, on one compiled program."""
    return min(per_shard, max(_CAPACITY_FLOOR, 1 << max(max_bucket - 1, 0).bit_length()))


def _lanes_of(table: Table):
    """The arrays of a Table of fixed-width columns in one list (a column's
    validity right behind its data), and where each column's are."""
    lanes, spots = [], []
    for c in table.columns:
        spots.append((len(lanes), c.validity is not None))
        lanes.append(c.data)
        if c.validity is not None:
            lanes.append(c.validity)
    return lanes, spots


def _table_from(like: Table, spots, lanes) -> Table:
    """``_lanes_of`` undone, over other arrays of the same kinds."""
    return Table([Column(c.dtype, data=lanes[i], validity=lanes[i + 1] if v else None)
                  for c, (i, v) in zip(like.columns, spots)], list(like.names))


def _route(dest, n_parts: int, capacity: int):
    """Where each bucket slot reads from: rows sorted by destination
    (``n_parts``, an absent row's, last), bucket p slot s <- the s-th row
    of run p. Gathers only. Returns (src[n_parts, capacity],
    filled[n_parts, capacity], overflow)."""
    n = dest.shape[0]
    order = jnp.argsort(dest, stable=False)  # which row of a run lands in which slot of its bucket is free
    start = jnp.searchsorted(dest[order], jnp.arange(n_parts + 1, dtype=jnp.int32), side="left").astype(jnp.int32)
    count = start[1:] - start[:-1]
    slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    src = order[jnp.clip(start[:-1, None] + slot, 0, n - 1)]
    return src, slot < count[:, None], jnp.any(count > capacity)


@op_boundary("exchange_sharded")
def exchange_sharded(st: ShardedTable, key_cols: Sequence[str]) -> ShardedTable:
    """Hash-repartition on ``key_cols`` with one all-to-all a lane: rows
    of equal keys end on one shard, and the result says so (``part``).
    Count, then size: a first small program routes every present row and
    counts the rows each shard sends to each destination; the host reads
    the counts (the exchange's one wait) and the all-to-all program runs
    once, at ``_counted_capacity`` of the fullest bucket. Slots that hold
    no row (a filter's, a join's) buy no capacity, and no bucket can
    overflow. The all-to-all program computes its overflow flag all the
    same; the flag rides on the result unread (``overflow``) until
    ``gather_table``'s transfer, which raises ``ExchangeOverflow`` if it
    is set: a row is never dropped silently."""
    from ..utils.memory import MemoryBudgetExceeded, device_memory_budget, exchange_bytes_estimate

    mesh, axis, n_parts = st.mesh, st.axis, st.n_parts
    per_shard = st.num_rows // n_parts
    lanes, spots = _lanes_of(st.table)
    lane_bytes = sum(int(np.dtype(a.dtype).itemsize) * int(np.prod(a.shape[1:], dtype=np.int64)) for a in lanes)
    keys = [st.column(k) for k in key_cols]
    nullable = tuple(c.validity is not None for c in keys)
    key_in = [a for c in keys for a in (c.data, c.validity) if a is not None]

    def count_program(pres, *key_arrs):
        it, ks = iter(key_arrs), []
        for has_v in nullable:
            data = next(it)
            if jnp.issubdtype(data.dtype, jnp.integer):
                # an integer routes by its VALUE, whatever its width (an INT32 would hash as one
                # block, an INT64 as two): the two sides of a join then agree on the shard
                data = data.astype(jnp.int64)
            # NULLs masked to zero: every NULL routes alike, whatever the column's nullability on
            # the other side of a join
            ks.append(jnp.where(next(it), data, jnp.zeros((), data.dtype)) if has_v else data)
        dest = jnp.where(pres, _hash_dest_multi(ks, n_parts), jnp.int32(n_parts))  # an absent row: bound for nowhere
        sent = jnp.sum(dest[None, :] == jnp.arange(n_parts, dtype=jnp.int32)[:, None], axis=1, dtype=jnp.int32)
        return dest, sent

    spec = P(axis)
    count = cached_sm(
        ("exchange_count", mesh, axis, nullable, tuple(str(a.dtype) for a in key_in)),
        lambda: jax.jit(shard_map(count_program, mesh=mesh, in_specs=(spec,) * (1 + len(key_in)),
                                  out_specs=(spec, spec))),
    )
    with tracing.span("exchange.table", keys=list(key_cols), parts=n_parts) as sp:
        dest, sent = count(st.present, *key_in)
        # the one wait: n_parts x n_parts counts; the rows that entered are their sum
        sent = np.asarray(tracing.device_wait(sent, "exchange_counts"))
        rows_in, max_bucket = int(sent.sum()), int(sent.max())
        capacity = _counted_capacity(max_bucket, per_shard)
        slots_out = n_parts * n_parts * capacity
        sp.annotate(rows_in=rows_in, capacity=capacity, max_bucket=max_bucket, fill=rows_in / slots_out)
        est = exchange_bytes_estimate(lane_bytes + 5, n_parts, capacity)  # a slot: its lanes, a 4-byte route index, a flag
        if est > device_memory_budget():
            raise MemoryBudgetExceeded(
                f"exchange at capacity {capacity} needs ~{est} device bytes a chip "
                f"(budget {device_memory_budget()}); split the batch")

        def exchange_program(dest, *payload):
            src, filled, ovf = _route(dest, n_parts, capacity)
            a2a = lambda x: lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)
            outs = [a2a(a[src]).reshape((-1,) + a.shape[1:]) for a in payload]
            return tuple(outs) + (a2a(filled).reshape(-1), ovf[None])

        f = cached_sm(
            ("exchange_sharded", mesh, axis, capacity, tuple(str(a.dtype) + str(a.shape[1:]) for a in lanes)),
            lambda: jax.jit(shard_map(exchange_program, mesh=mesh, in_specs=(spec,) * (1 + len(lanes)),
                                      out_specs=(spec,) * (len(lanes) + 2))),
        )
        *received, present, ovf = f(dest, *lanes)
    _count_exchange(rows_in, lane_bytes)
    _exchange_counter("slots_out").inc(slots_out)
    return ShardedTable(_table_from(st.table, spots, received), present, mesh, axis, part=tuple(key_cols),
                        overflow=st.overflow + ((tuple(key_cols), capacity, ovf),))


_SHARDED_HOWS = ("sum", "count", "count_all", "min", "max", "mean")


@op_boundary("groupby_sharded")
def groupby_sharded(st: ShardedTable, key_cols: Sequence[str],
                    aggs: Sequence[Tuple[Optional[str], str, str]]) -> ShardedTable:
    """GROUP BY on a table already partitioned on (a subset of) the keys:
    every group is whole on its shard, so each shard groups what it holds
    and nothing moves. One output slot a group, at most as many as the
    shard has slots, so no capacity can overflow. Aggregates come out as
    the eager tier's do (``count`` INT64, FLOAT64 sums exact bits)."""
    if not st.part or not set(st.part) <= set(key_cols):
        raise ValueError(f"groupby_sharded on {list(key_cols)} needs a table partitioned on some of them, "
                         f"not on {list(st.part)}")
    for _s, how, _o in aggs:
        if how not in _SHARDED_HOWS:
            raise ValueError(f"unknown agg {how!r} (supported: {_SHARDED_HOWS})")
    for k in key_cols:  # a FLOAT64's IEEE bits, a date's days and a dictionary code are integer lanes too
        lane = st.column(k).data
        if lane.ndim != 1 or not jnp.issubdtype(lane.dtype, jnp.integer):
            raise ValueError(f"groupby_sharded sorts integer key lanes, {k!r} is {st.column(k).dtype!r}")
    mesh, axis, n_parts = st.mesh, st.axis, st.n_parts
    cap = st.num_rows // n_parts
    key_lanes, key_nullable = [], []
    for k in key_cols:
        c = st.column(k)
        key_nullable.append(c.validity is not None)
        if c.validity is None:
            key_lanes.append(c.data)
        else:  # NULL keys form one group: the validity joins the key tuple
            key_lanes += [jnp.where(c.validity, c.data, jnp.zeros((), c.data.dtype)), c.validity.astype(jnp.int32)]
    val_lanes, val_valid, hows, f64_flags, srcs = [], [], [], [], []
    for source, how, _o in aggs:
        src = st.column(key_cols[0] if source is None else source)
        srcs.append(src)
        val_lanes.append(src.data)
        val_valid.append(None if how == "count_all" else src.validity)
        hows.append("count" if how == "count_all" else how)
        f64_flags.append(src.dtype.id == TypeId.FLOAT64)
    valid_lanes = [v for v in val_valid if v is not None]
    n_keys, n_vals = len(key_lanes), len(val_lanes)

    def groupby_program(pres, *arrs):
        ks, vs, vps = list(arrs[:n_keys]), list(arrs[n_keys:n_keys + n_vals]), iter(arrs[n_keys + n_vals:])
        vp_full = [None if v is None else next(vps) for v in val_valid]
        # one UNSTABLE sort on the key lanes alone (the chip's compiler takes minutes over a stable
        # sort with an occupancy lane in front): absent rows ride under the largest key and count
        # in no group; a group that only they make is marked invalid
        top = jnp.iinfo(ks[0].dtype).max
        k0 = jnp.where(pres, ks[0], top)
        order = lax.sort((k0, *ks[1:], jnp.arange(cap, dtype=jnp.int32)), num_keys=n_keys, is_stable=False)[-1]
        sk, ps = [k0[order]] + [k[order] for k in ks[1:]], pres[order]
        changed = jnp.zeros((cap - 1,), bool)
        for k in sk:
            changed = changed | (k[1:] != k[:-1])
        group = jnp.cumsum(jnp.concatenate([jnp.ones((1,), bool), changed])).astype(jnp.int32) - 1
        gv = jax.ops.segment_sum(ps.astype(jnp.int32), group, num_segments=cap) > 0
        seg = jnp.where(ps, group, cap)
        gas, gavs = _segment_aggs(vs, hows, vp_full, f64_flags, order, ps, seg, cap)
        gks = [jnp.zeros((cap,), k.dtype).at[seg].set(kk, mode="drop") for k, kk in zip(ks, sk)]
        gks[0] = jnp.where(gv, gks[0], top)  # the first key ascends over every slot, the empty ones too
        return tuple(gks) + tuple(gas) + tuple(gavs) + (gv,)

    spec = P(axis)
    f = cached_sm(
        ("groupby_sharded", mesh, axis, cap, tuple(hows), tuple(f64_flags),
         tuple(v is not None for v in val_valid), tuple(str(a.dtype) for a in key_lanes + val_lanes)),
        lambda: jax.jit(shard_map(groupby_program, mesh=mesh,
                                  in_specs=(spec,) * (1 + n_keys + n_vals + len(valid_lanes)),
                                  out_specs=(spec,) * (n_keys + 2 * n_vals + 1))),
    )
    with tracing.span("exchange.groupby", rows_in=st.num_rows, keys=list(key_cols), capacity=cap, parts=n_parts):
        outs = f(st.present, *key_lanes, *val_lanes, *valid_lanes)
    gks, gas, gavs, gv = outs[:n_keys], outs[n_keys:n_keys + n_vals], outs[n_keys + n_vals:-1], outs[-1]
    cols, names, li = [], [], 0
    for k, nullable in zip(key_cols, key_nullable):
        validity = gks[li + 1].astype(bool) if nullable else None
        cols.append(Column(st.column(k).dtype, data=gks[li], validity=validity))
        names.append(k)
        li += 2 if nullable else 1
    for (_s, how, oname), src, g, gav in zip(aggs, srcs, gas, gavs):
        if how in ("count", "count_all"):
            cols.append(Column(dt.INT64, data=g))
        elif src.dtype.id == TypeId.FLOAT64 or (how == "mean" and src.dtype.is_integral):
            cols.append(Column(dt.FLOAT64, data=g, validity=gav))  # exact paths: ready-made IEEE bits
        elif how == "mean":
            cols.append(Column(dt.FLOAT64, data=bitutils.float_store(g, dt.FLOAT64), validity=gav))
        elif how == "sum" and src.dtype.is_integral:
            cols.append(Column(dt.UINT64 if g.dtype == jnp.uint64 else dt.INT64,
                               data=g if g.dtype == jnp.uint64 else g.astype(jnp.int64), validity=gav))
        else:
            cols.append(Column(src.dtype, data=g, validity=gav))
        names.append(oname)
    ordered = key_cols[0] if len(key_cols) == 1 and not key_nullable[0] else None
    return ShardedTable(Table(cols, names), gv, mesh, axis, part=st.part, ordered=ordered, overflow=st.overflow)


@op_boundary("join_sharded")
def join_sharded(left: ShardedTable, right, on: Tuple[str, str], how: str,
                 payload: Sequence[str] = ()) -> ShardedTable:
    """Join a sharded left side, slot for slot, against a right side that
    is either a replicated Table (a broadcast join: every shard probes
    the whole of it) or a ShardedTable partitioned compatibly (every
    shard probes its own part). Nothing moves. ``how``: ``semi`` and
    ``anti`` keep or drop left rows; ``inner`` also brings ``payload``
    columns of the right side and takes the right key as UNIQUE (the
    caller has checked: a dimension's primary key), so a left row
    matches at most once and the output is the left's slots. One integer
    key a side; a NULL key matches nothing."""
    if how not in ("inner", "semi", "anti"):
        raise ValueError(f"how={how!r} not supported (inner/semi/anti)")
    mesh, axis, n_parts = left.mesh, left.axis, left.n_parts
    lkey, rkey = left.column(on[0]), right.column(on[1])
    if not (lkey.dtype.is_integral and rkey.dtype.is_integral):
        raise ValueError("join_sharded takes one integer key a side")
    if lkey.dtype.id != rkey.dtype.id and TypeId.UINT64 in (lkey.dtype.id, rkey.dtype.id):
        raise ValueError(f"join_sharded: {lkey.dtype!r} and {rkey.dtype!r} keys do not meet in int64")
    sharded_right = isinstance(right, ShardedTable)
    if sharded_right:
        if not left.part or left.part != (on[0],) or right.part != (on[1],):
            raise ValueError(f"join_sharded: sides partitioned on {left.part} and {right.part}, joined on {on}")
        r_present, r_rows = right.present, right.num_rows
    else:
        from .mesh import replicated

        right = jax.device_put(right, replicated(mesh))
        rkey = right.column(on[1])
        r_present, r_rows = None, right.num_rows
    pay = [right.column(p) for p in (payload if how == "inner" else ())]
    flags = (lkey.validity is not None, rkey.validity is not None, r_present is not None,
             tuple(c.validity is not None for c in pay))
    in_order = sharded_right and right.ordered == on[1] and rkey.validity is None

    def join_program(*arrs):
        it = iter(arrs)
        lk, lp = next(it).astype(jnp.int64), next(it)
        if flags[0]:
            lp = lp & next(it)
        rk = next(it).astype(jnp.int64)
        rp = jnp.ones(rk.shape, bool)
        if flags[1]:
            rp = rp & next(it)
        if flags[2]:
            rp = rp & next(it)
        if rk.shape[0] == 0:
            hit = jnp.zeros(lk.shape, bool)
            row = jnp.zeros(lk.shape, jnp.int32)
        elif in_order:
            # a group-by's keys: they ascend over the slots and are distinct where present
            at = jnp.minimum(jnp.searchsorted(rk, lk, side="left").astype(jnp.int32), rk.shape[0] - 1)
            hit = lp & rp[at] & (rk[at] == lk)
            row = at
        elif how == "inner":
            order = jnp.lexsort((rk, ~rp))  # absent last, so that the row found is a real one
            n_valid = jnp.sum(rp.astype(jnp.int32))
            probe = jnp.where(rp[order], rk[order], jnp.iinfo(jnp.int64).max)
            pos = jnp.searchsorted(probe, lk, side="left").astype(jnp.int32)
            at = jnp.minimum(pos, rk.shape[0] - 1)
            hit = lp & (pos < n_valid) & (probe[at] == lk)
            row = order[at]
        else:
            # membership alone: an unstable sort of the keys, the absent ones under the largest key;
            # a probe for that very key finds them too, so it hits only if a real row has it
            probe = jnp.sort(jnp.where(rp, rk, jnp.iinfo(jnp.int64).max), stable=False)
            top = jnp.sum((rp & (rk == jnp.iinfo(jnp.int64).max)).astype(jnp.int32)) > 0
            at = jnp.minimum(jnp.searchsorted(probe, lk, side="left").astype(jnp.int32), rk.shape[0] - 1)
            hit = lp & (probe[at] == lk) & ((lk != jnp.iinfo(jnp.int64).max) | top)
            row = at
        if how == "anti":
            return (lp & ~hit,)
        outs = [hit]
        for has_v in flags[3]:
            data = next(it)
            outs.append(data[row])
            if has_v:
                outs.append(next(it)[row] & hit)
        return tuple(outs)

    l_in = [lkey.data, left.present] + ([lkey.validity] if flags[0] else [])
    r_in = [rkey.data] + ([rkey.validity] if flags[1] else []) + ([r_present] if flags[2] else [])
    for c in pay:
        r_in += [c.data] + ([c.validity] if c.validity is not None else [])
    spec, rspec = P(axis), (P(axis) if sharded_right else P())
    n_out = 1 + sum(1 + int(v) for v in flags[3]) if how != "anti" else 1
    f = cached_sm(
        ("join_sharded", mesh, axis, how, sharded_right, in_order, flags,
         tuple(str(a.dtype) for a in l_in + r_in)),
        lambda: jax.jit(shard_map(join_program, mesh=mesh, in_specs=(spec,) * len(l_in) + (rspec,) * len(r_in),
                                  out_specs=(spec,) * n_out)),
    )
    with tracing.span("exchange.join", rows_in=left.num_rows, keys=list(on), capacity=r_rows, parts=n_parts,
                      how=how, broadcast=not sharded_right):
        outs = f(*l_in, *r_in)
    cols, names = list(left.table.columns), list(left.names)
    it = iter(outs[1:])
    for p, c in zip(payload if how == "inner" else (), pay):
        data = next(it)
        cols.append(Column(c.dtype, data=data, validity=next(it) if c.validity is not None else None))
        names.append(p)
    return left.replace(table=Table(cols, names), present=outs[0],
                        overflow=left.overflow + (right.overflow if sharded_right else ()))


@op_boundary("gather_table")
def gather_table(st: ShardedTable) -> Table:
    """The rows of a ShardedTable as one compacted Table, a whole copy on
    every chip: the way out of the sharded layer (a result, or the input
    of a stage that is not keyed). Waits for the device to learn which
    slots hold rows."""
    from .mesh import replicated

    with tracing.span("exchange.gather", slots=st.num_rows, parts=st.n_parts, cols=st.table.num_columns) as sp:
        present, flags = jax.device_get(tracing.device_wait(
            (st.present, [flag for _keys, _cap, flag in st.overflow]), "overflow_flags"))
        for (keys, cap, _flag), flag in zip(st.overflow, flags):
            if flag.any():
                _exchange_counter("overflows").inc()
                raise ExchangeOverflow(f"exchange on {list(keys)}: a bucket of {cap} slots overflowed")
        sel = np.flatnonzero(present)
        sp.annotate(rows_out=int(sel.size))
        idx, rep = jnp.asarray(sel), replicated(st.mesh)
        cols = [Column(c.dtype, data=jax.device_put(c.data[idx], rep),
                       validity=None if c.validity is None else jax.device_put(c.validity[idx], rep))
                for c in st.table.columns]
        return Table(cols, list(st.names))
