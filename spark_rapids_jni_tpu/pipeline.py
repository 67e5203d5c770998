"""Compiled query pipelines: (plan, schema) -> ONE XLA program.

The execution model the Spark plugin needs per offloaded stage
(SURVEY §2.8's cudf hash-agg path, reference plugin behavior): rewrite a
physical plan's scan->filter->project->aggregate stage into a single
compiled program per (plan, schema) pair, so a remote/TPU backend pays
one dispatch per ColumnarBatch instead of one per operator. Round 1
hand-fused exactly two queries (models/compiled.py); this is the
general mechanism — the hand-fused forms are now thin plans.

Design notes (TPU-first):
- ``Table`` is a jax pytree, so the whole plan body traces under one
  ``jax.jit``; the plan spec (expressions, group keys, agg list) is
  Python-static and closed over per CompiledPipeline instance.
- Grouped aggregation uses BOUNDED key domains (dictionary-coded group
  columns, the plugin's common case): group ids are computed as a mixed
  radix over the per-key domains and reduced with dense segment
  reductions — no sort, no data-dependent shapes, empty groups carried
  densely and compacted host-side at the end.
- Filters never materialize a filtered table: rows outside the
  predicate fall into a trash segment (grouped) or a masked identity
  (global), exactly like the hand-fused kernels did.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .columnar import Column, Table
from .columnar import dtype as dt
from .ops import bitutils
from .ops.expressions import Expression
from .utils import deadline, metrics, tracing
from .utils.dispatch import op_boundary

__all__ = ["Agg", "GroupKey", "JoinSpec", "PlanSpec", "CompiledPipeline", "compile_plan"]

_AGG_HOWS = ("sum", "count", "count_all", "min", "max", "mean")


@dataclasses.dataclass(frozen=True)
class Agg:
    """One aggregate over an input or projected column."""

    source: str
    how: str
    name: Optional[str] = None  # output column name; default source_how

    @property
    def out_name(self) -> str:
        return self.name or f"{self.source}_{self.how}"


@dataclasses.dataclass(frozen=True)
class GroupKey:
    """Bounded-domain group key: values must lie in [0, num_keys)."""

    column: str
    num_keys: int


@dataclasses.dataclass(frozen=True)
class JoinSpec:
    """Join against a BUILD table (the broadcast dim-join Spark offloads
    per stage; q3's star joins, q95's EXISTS / NOT EXISTS). Two
    TPU-first lowerings, both static-shape inside the one compiled
    program:

    - ``num_keys`` set — bounded-domain: the build side scatters into a
      DENSE [num_keys] presence/payload map (dim keys are bounded) and
      the probe is a row gather.
    - ``num_keys=None`` — SORT-MERGE fallback for arbitrary int64 keys
      (cudf's general hash join has no domain restriction, SURVEY
      §2.8): the build side sorts once (excluded rows park at a +inf
      sentinel), the probe binary-searches (log2 |build| gathers), and
      every candidate verifies raw key equality, so sentinel collisions
      are impossible. Probe misses flow into the same trash-segment
      mask either way.

    ``how``: "inner" gathers ``payload`` columns into the working
    schema and drops probe misses; "semi"/"anti" keep/drop rows by
    presence only (payload must be empty). Build keys must be UNIQUE
    among rows passing ``build_filter`` for inner joins —
    duplicates are surfaced as a loud error, like out-of-domain group
    keys."""

    build: str  # name of the build table passed to __call__
    probe_key: str  # column in the working (fact-side) schema
    build_key: str  # column in the build table
    num_keys: Optional[int] = None  # bounded domain; None = sort-merge
    payload: Tuple[str, ...] = ()
    how: str = "inner"
    build_filter: Optional[Expression] = None

    def __post_init__(self):
        if self.how not in ("inner", "semi", "anti"):
            raise ValueError(f"unknown join {self.how!r}")
        if self.how != "inner" and self.payload:
            raise ValueError("payload columns require an inner join")


@dataclasses.dataclass(frozen=True)
class PlanSpec:
    """Declarative single-stage plan: join* -> filter -> project ->
    aggregate, compiled to ONE program.

    ``joins`` apply in order and splice their payload columns into the
    working schema; ``filter`` and ``project`` see the post-join
    schema; aggregates may reference input, payload, or projected
    names. With no ``group_by`` the stage is a global aggregation
    producing one row.
    """

    filter: Optional[Expression] = None
    project: Tuple[Tuple[str, Expression], ...] = ()
    group_by: Tuple[GroupKey, ...] = ()
    aggregates: Tuple[Agg, ...] = ()
    joins: Tuple[JoinSpec, ...] = ()

    def __post_init__(self):
        if not self.aggregates:
            raise ValueError("plan needs at least one aggregate")
        for a in self.aggregates:
            if a.how not in _AGG_HOWS:
                raise ValueError(f"unknown aggregate {a.how!r}")




class CompiledPipeline:
    """A plan compiled against a schema: call with a Table of that
    schema; every call with the same shapes reuses one XLA executable."""

    def __init__(self, plan: PlanSpec):
        self.plan = plan
        self._fn = tracing.launches(jax.jit(self._trace))
        self._build_handles: Dict[str, object] = {}
        self._build_finalizer = None
        metrics.counter("pipeline.compiles").inc()

    # -- spillable build tables (memgov/, ISSUE 4) --------------------------
    def register_build(self, name: str, table: Table) -> None:
        """Attach a BUILD table to this pipeline through the memory
        governor's spillable catalog: ``__call__`` materializes it
        automatically (no ``builds`` entry needed), and BETWEEN calls
        the table may demote device->host(->disk) under memory pressure
        and re-materialize transparently — bit-identical — on the next
        batch. During a call the handle is pinned so the pressure loop
        cannot demote it mid-dispatch. Registration is bookkeeping
        (always-on); demotion only ever happens under an armed
        governor's pressure loop. A dropped pipeline cleans up after
        itself (weakref finalizer), so catalog entries and their spill
        files never outlive the pipeline that registered them."""
        import weakref

        from . import memgov

        cat = memgov.catalog()
        key = f"pipeline.build.{id(self)}.{name}"
        self._build_handles[name] = cat.register(key, table, kind="build")
        if self._build_finalizer is None:
            # the callback must not capture self: it holds the handle
            # DICT (shared, mutated by register/unregister) instead
            self._build_finalizer = weakref.finalize(
                self, _drop_build_handles, self._build_handles
            )

    def unregister_builds(self) -> None:
        """Drop this pipeline's registered build tables from the
        catalog (and any spill files backing them)."""
        _drop_build_handles(self._build_handles)

    # -- traced body (ONE program) -----------------------------------------
    def _trace(self, table: Table, builds: Dict[str, Table]):
        plan = self.plan
        cols = dict(zip(table.names, table.columns))
        mask = None
        n_dup = jnp.zeros((), jnp.int64)

        n_bad_build = jnp.zeros((), jnp.int64)
        for js in plan.joins:
            if js.num_keys is None:
                hit, joined, dups, bad_build = _sorted_join(js, cols, builds[js.build])
            else:
                hit, joined, dups, bad_build = _dense_join(js, cols, builds[js.build])
            n_dup = n_dup + dups
            n_bad_build = n_bad_build + bad_build
            keep = ~hit if js.how == "anti" else hit
            mask = keep if mask is None else mask & keep
            cols.update(joined)

        if plan.filter is not None:
            work = Table(list(cols.values()), list(cols.keys()))
            pred = plan.filter.evaluate(work)
            fm = pred.data.astype(bool)
            if pred.validity is not None:
                fm = fm & pred.validity
            mask = fm if mask is None else mask & fm

        # projected columns become part of the working schema
        work = Table(list(cols.values()), list(cols.keys()))
        for name, expr in plan.project:
            cols[name] = expr.evaluate(work)

        def masked_valid(col: Column):
            v = None if col.validity is None else col.validity
            if mask is not None:
                v = mask if v is None else (v & mask)
            return v

        if not plan.group_by:
            out = {}
            for agg in plan.aggregates:
                col = cols[agg.source]
                if agg.how == "count_all":
                    # COUNT(*): filter applies, null VALUES still count
                    v = mask
                else:
                    v = masked_valid(col)
                out[agg.out_name] = _global_agg(col, v, agg.how)
            return out, None, None, None, n_dup, n_bad_build

        # mixed-radix group id over the bounded domains; rows filtered
        # out (or null-keyed) land in the trash segment
        num = 1
        for gk in plan.group_by:
            num *= gk.num_keys
        gid = jnp.zeros((table.num_rows,), jnp.int32)
        bad = jnp.zeros((table.num_rows,), bool)  # null key or filtered
        out_of_domain = jnp.zeros((table.num_rows,), bool)
        for gk in plan.group_by:
            kcol = cols[gk.column]
            k = kcol.data.astype(jnp.int32)
            oob = (k < 0) | (k >= gk.num_keys)
            if kcol.validity is not None:
                oob = oob & kcol.validity  # null keys are not "out of domain"
                bad = bad | ~kcol.validity
            out_of_domain = out_of_domain | oob
            bad = bad | oob
            gid = gid * gk.num_keys + jnp.clip(k, 0, gk.num_keys - 1)
        if mask is not None:
            bad = bad | ~mask
            out_of_domain = out_of_domain & mask
        gid = jnp.where(bad, num, gid)
        # rows whose key escaped the declared bounded domain: a plan
        # mis-declaration, surfaced loudly (host wrapper raises)
        n_out_of_domain = jnp.sum(out_of_domain.astype(jnp.int64))

        counts_all = jax.ops.segment_sum(
            jnp.ones_like(gid, jnp.int64), gid, num_segments=num + 1
        )[:num]
        aggs = {}
        for agg in plan.aggregates:
            col = cols[agg.source]
            v = None if col.validity is None else col.validity
            aggs[agg.out_name] = _grouped_agg(col, v, gid, num, agg.how, counts_all)
        return aggs, counts_all, num, n_out_of_domain, n_dup, n_bad_build

    # -- host wrapper -------------------------------------------------------
    @op_boundary("compiled_pipeline")
    def __call__(self, table: Table, builds: Optional[Dict[str, Table]] = None) -> Table:
        """One batch through the compiled program. The op_boundary
        wrapper makes this a deadline-scoped dispatch: pass
        ``deadline_s=`` for a per-call budget (or set SRJT_DEADLINE_SEC
        for the ambient per-query budget), and the whole call —
        including armed retries and their backoffs — is bounded, with
        a cooperative cancel point between the device dispatch and the
        host-side result materialization."""
        plan = self.plan
        # end-to-end pipeline stats: batch/row throughput counters (the
        # op_boundary wrapper already records wall time per dispatch)
        metrics.counter("pipeline.batches").inc()
        metrics.counter("pipeline.rows").inc(table.num_rows)
        # catalog-registered build tables fill in (re-materializing if
        # demoted); an explicit `builds` entry of the same name wins
        pinned = []
        if self._build_handles:
            builds = dict(builds or {})
            for name, h in self._build_handles.items():
                if name not in builds:
                    pinned.append(h.pin())
                    builds[name] = h.get()
        try:
            want = {js.build for js in plan.joins}
            have = set(builds or {})
            if want != have:
                raise ValueError(f"plan needs build tables {sorted(want)}, got {sorted(have)}")
            aggs, counts_all, num, n_oob, n_dup, n_bad_build = self._fn(table, builds or {})
        finally:
            for h in pinned:
                h.unpin()
        # cancel point: a query whose budget died during the compiled
        # dispatch stops HERE, before paying the host syncs/compaction
        deadline.check("compiled_pipeline")
        if plan.joins:
            # one host sync covers both join mis-declaration classes
            dups, bad_build = int(n_dup), int(n_bad_build)
            if dups:
                raise ValueError(
                    f"{dups} duplicate build keys in an inner-join payload map; "
                    "bounded-domain joins require unique build keys"
                )
            if bad_build:
                raise ValueError(
                    f"{bad_build} build rows have join keys outside the declared "
                    "bounded domain; widen the JoinSpec num_keys"
                )
        if n_oob is not None:
            oob = int(n_oob)  # piggybacks on the result-size host sync
            if oob:
                raise ValueError(
                    f"{oob} rows have group keys outside the declared bounded "
                    "domain; widen the GroupKey num_keys or pre-filter"
                )
        if not plan.group_by:
            out_cols, names = [], []
            for agg in plan.aggregates:
                data, valid = aggs[agg.out_name]
                out_cols.append(
                    _wrap_result(data[None], None if valid is None else valid[None], agg.how)
                )
                names.append(agg.out_name)
            return Table(out_cols, names)

        # compact non-empty groups (one host sync for the result size —
        # the same sync every grouped aggregation pays at gather time)
        counts_np = np.asarray(counts_all)
        present = np.nonzero(counts_np > 0)[0]
        idx = jnp.asarray(present, jnp.int32)
        out_cols, names = [], []
        radix = present.copy()
        for gk in reversed(plan.group_by):
            out_cols.insert(0, Column(dt.INT32, data=jnp.asarray(radix % gk.num_keys, jnp.int32)))
            radix //= gk.num_keys
        names = [gk.column for gk in plan.group_by]
        for agg in plan.aggregates:
            data, valid = aggs[agg.out_name]
            out_cols.append(_wrap_result(data[idx], None if valid is None else valid[idx], agg.how))
            names.append(agg.out_name)
        return Table(out_cols, names)


def _global_agg(col: Column, v, how: str):
    """Global (one-group) aggregate: delegates to the grouped kernels
    with a single segment so every exactness path is shared."""
    n = len(col)
    gid = jnp.zeros((n,), jnp.int32)
    m = jnp.ones((n,), bool) if v is None else v
    counts = jnp.sum(m.astype(jnp.int64))[None]
    data, valid = _grouped_agg(col, v, gid, 1, how, counts)
    return data[0], None if valid is None else valid[0]


def _grouped_agg(col: Column, v, gid, num: int, how: str, counts_all):
    """Dense [num] aggregate + optional [num] validity, rows with
    gid==num dropped.

    Exactness contract (VERDICT r3 item 5): FLOAT64 SUM/MEAN ride the
    windowed integer accumulator (ops/f64acc — correctly rounded f64,
    bit-identical CPU vs TPU); integer SUM accumulates in exact int64
    (MEAN divides the exact sum via the limb divider); FLOAT64 and
    integer MIN/MAX compare in the exact total-order / integer domain,
    never through a lossy f32 view. Exact FLOAT64 results return as
    uint64 IEEE bits (detected downstream by _wrap_result). FLOAT32
    keeps the f32 MXU kernel."""
    n = len(col)
    m = jnp.ones((n,), bool) if v is None else v
    gid_v = jnp.where(m, gid, num)  # null values drop from value aggs
    if how == "count_all":
        return counts_all, None
    if how == "count":
        # exact int64 count via key routing
        c = jax.ops.segment_sum(m.astype(jnp.int64), gid_v, num_segments=num + 1)[:num]
        return c, None
    d = col.dtype
    if how in ("sum", "mean"):
        if d.id == dt.TypeId.FLOAT64:
            from .ops.f64acc import segment_mean_f64bits, segment_sum_f64bits

            if how == "sum":
                s = segment_sum_f64bits(col.data, gid_v, num + 1)[:num]
                c = jax.ops.segment_sum(
                    m.astype(jnp.int64), gid_v, num_segments=num + 1
                )[:num]
                return s, c > 0
            mb, c = segment_mean_f64bits(col.data, gid_v, num + 1)
            return mb[:num], c[:num] > 0
        if not d.is_floating:
            # integers: exact int64 accumulation (Spark sum(int)->long);
            # results materialize into FLOAT64 bits without an f32 hop.
            # UINT64 sums share the same two's-complement bits (mod
            # 2^64) — only the final interpretation reads them unsigned
            from jax import lax as _lax

            from .ops.f64acc import (
                i64_to_f64bits,
                mean_i64_div,
                u64_to_f64bits,
            )

            is_u64 = col.data.dtype == jnp.uint64
            vals = _lax.bitcast_convert_type(col.data, jnp.int64) if is_u64 else col.data.astype(jnp.int64)
            s = jax.ops.segment_sum(
                jnp.where(m, vals, 0), gid_v, num_segments=num + 1
            )[:num]
            c = jax.ops.segment_sum(m.astype(jnp.int64), gid_v, num_segments=num + 1)[:num]
            if how == "sum":
                if is_u64:
                    return u64_to_f64bits(_lax.bitcast_convert_type(s, jnp.uint64)), c > 0
                return i64_to_f64bits(s), c > 0
            if is_u64:
                return mean_i64_div(_lax.bitcast_convert_type(s, jnp.uint64), c, unsigned=True), c > 0
            return mean_i64_div(s, c), c > 0
        # FLOAT32: one fused kernel for (sums, per-group valid counts) —
        # segment_sum lowers to the slow XLA scatter class on TPU; the
        # MXU outer-product kernel in groupby_sum_bounded is ~17x faster
        # at the 1M x 4096 axis and falls back to segment_sum off-TPU
        from .ops.aggregate import groupby_sum_bounded

        s, c = groupby_sum_bounded(gid_v, col.data, num)
        if how == "sum":
            return s, c > 0
        cf = c.astype(s.dtype)
        return s / jnp.maximum(cf, 1.0), c > 0
    # min/max validity comes from the per-group valid-row COUNT, never
    # from isfinite(result): a genuine +/-inf value must survive
    has_vals = jax.ops.segment_sum(m.astype(jnp.int32), gid_v, num_segments=num + 1)[:num] > 0
    lo_i, hi_i = jnp.iinfo(jnp.int64).min, jnp.iinfo(jnp.int64).max
    if d.id == dt.TypeId.FLOAT64:
        # exact total-order comparison on the stored bits; the u64 key
        # views as order-preserving int64 so segment_min/max stay on the
        # well-trodden s64 path
        from jax import lax

        from .ops import bitutils as _bt
        from .ops.aggregate import _from_total_order

        key = _bt.total_order_key(col.data, dt.FLOAT64)
        k = lax.bitcast_convert_type(key ^ jnp.uint64(1 << 63), jnp.int64)
        fill = hi_i if how == "min" else lo_i
        red = jax.ops.segment_min if how == "min" else jax.ops.segment_max
        r = red(jnp.where(m, k, fill), gid_v, num_segments=num + 1)[:num]
        key_back = lax.bitcast_convert_type(r, jnp.uint64) ^ jnp.uint64(1 << 63)
        return _from_total_order(key_back, dt.FLOAT64), has_vals
    if not d.is_floating:
        from jax import lax as _lax

        from .ops.f64acc import i64_to_f64bits, u64_to_f64bits

        is_u64 = col.data.dtype == jnp.uint64
        if is_u64:
            # order-preserving signed view (flip the top bit) so the
            # comparison stays correct past 2^63
            vals = _lax.bitcast_convert_type(
                col.data ^ jnp.uint64(1 << 63), jnp.int64
            )
        else:
            vals = col.data.astype(jnp.int64)
        fill = hi_i if how == "min" else lo_i
        red = jax.ops.segment_min if how == "min" else jax.ops.segment_max
        r = red(jnp.where(m, vals, fill), gid_v, num_segments=num + 1)[:num]
        r = jnp.where(has_vals, r, 0)
        if is_u64:
            back = _lax.bitcast_convert_type(r, jnp.uint64) ^ jnp.uint64(1 << 63)
            return u64_to_f64bits(jnp.where(has_vals, back, jnp.uint64(0))), has_vals
        return i64_to_f64bits(r), has_vals
    x = col.data
    if how == "min":
        s = jax.ops.segment_min(jnp.where(m, x, jnp.inf), gid_v, num_segments=num + 1)[:num]
        return s, has_vals
    s = jax.ops.segment_max(jnp.where(m, x, -jnp.inf), gid_v, num_segments=num + 1)[:num]
    return s, has_vals


def _build_enter_mask(js: JoinSpec, bt: Table) -> jnp.ndarray:
    """Build-side liveness: valid key AND build_filter (with its own
    null semantics) — shared by both join lowerings so filter handling
    can never diverge between them."""
    bk = bt.column(js.build_key)
    enter = bk.valid_mask()
    if js.build_filter is not None:
        bf = js.build_filter.evaluate(bt)
        bfm = bf.data.astype(bool)
        if bf.validity is not None:
            bfm = bfm & bf.validity
        enter = enter & bfm
    return enter


def _sorted_join(js: JoinSpec, cols: Dict[str, Column], bt: Table):
    """Sort-merge lowering for unbounded build keys (JoinSpec
    num_keys=None): lexsort the build side by (parked-last, key) so
    entered rows form a sorted prefix at every key — including a
    genuine INT64_MAX key, which therefore cannot collide with the
    parked sentinel — then binary-search every probe and verify raw
    equality AND build-row liveness. Same (hit, joined, dups,
    bad_build) contract as _dense_join (payload columns are always
    emitted, null-filled when the build is empty); bad_build is always
    0 (there is no declared domain to escape)."""
    bk = bt.column(js.build_key)
    n_b = len(bk)
    enter = _build_enter_mask(js, bt)
    keys = bk.data.astype(jnp.int64)
    big = jnp.int64((1 << 63) - 1)

    pcol = cols[js.probe_key]
    pk = pcol.data.astype(jnp.int64)
    n_p = pk.shape[0]

    def null_payloads():
        out: Dict[str, Column] = {}
        for pname in js.payload:
            src_c = bt.column(pname)
            d = src_c.dtype
            if not d.is_fixed_width or d.id == dt.TypeId.DECIMAL128:
                raise ValueError(f"join payload {pname!r}: only plain fixed-width columns")
            shape = (n_p,) + src_c.data.shape[1:]
            out[pname] = Column(
                d,
                data=jnp.zeros(shape, src_c.data.dtype),
                validity=jnp.zeros((n_p,), bool),
            )
        return out

    dups = jnp.zeros((), jnp.int64)
    if n_b == 0:
        hit = jnp.zeros((n_p,), bool)
        return hit, null_payloads(), dups, jnp.zeros((), jnp.int64)

    # parked rows sort AFTER every entered row, entered rows by key:
    # searchsorted(side='left') therefore always lands on an entered
    # row when one exists for the probe key
    order = jnp.lexsort((keys, ~enter)).astype(jnp.int32)
    ks = keys[order]
    es = enter[order]
    sk = jnp.where(es, ks, big)

    if js.how == "inner" and n_b > 1:
        dups = jnp.sum(((ks[1:] == ks[:-1]) & es[1:] & es[:-1]).astype(jnp.int64))

    idx = jnp.clip(
        jnp.searchsorted(sk, pk, side="left"), 0, n_b - 1
    ).astype(jnp.int32)
    src = order[idx]
    hit = (ks[idx] == pk) & es[idx] & pcol.valid_mask()

    joined: Dict[str, Column] = {}
    for pname in js.payload:
        pc = bt.column(pname)
        d = pc.dtype
        if not d.is_fixed_width or d.id == dt.TypeId.DECIMAL128:
            raise ValueError(f"join payload {pname!r}: only plain fixed-width columns")
        data = jnp.where(
            hit.reshape(hit.shape + (1,) * (pc.data.ndim - 1)),
            pc.data[src],
            jnp.zeros((), pc.data.dtype),
        )
        pv = pc.valid_mask()[src] & hit
        joined[pname] = Column(d, data=data, validity=pv)
    return hit, joined, dups, jnp.zeros((), jnp.int64)


def _dense_join(js: JoinSpec, cols: Dict[str, Column], bt: Table):
    """One bounded-domain join: scatter the (filtered) build side into
    dense presence/payload maps, probe by row gather. Returns
    (hit [N] bool, {name: joined Column}, duplicate-key count,
    out-of-domain build-row count — both loud mis-declaration errors)."""
    num = js.num_keys
    bk = bt.column(js.build_key)
    enter = _build_enter_mask(js, bt)
    # domain guard BEFORE the i32 narrowing: an int64 key >= 2^31 must
    # miss, not wrap into the valid domain. A build row INSIDE the
    # filter but OUTSIDE the declared domain is a mis-declaration
    # (silently dropping it would quietly un-match fact rows) — counted
    # and raised host-side like out-of-domain group keys.
    in_dom_b = (bk.data >= 0) & (bk.data < num)
    bad_build = jnp.sum((enter & ~in_dom_b).astype(jnp.int64))
    enter = enter & in_dom_b
    bkeys = bk.data.astype(jnp.int32)
    slot = jnp.where(enter, bkeys, num)  # trash slot for dropped rows

    present = (
        jnp.zeros((num + 1,), bool).at[slot].set(True, mode="drop")[:num]
    )
    dups = jnp.zeros((), jnp.int64)
    if js.how == "inner":
        # duplicate build keys would silently collapse inner-join row
        # multiplicity to semi semantics — always surfaced, with or
        # without payload columns
        cnt = jax.ops.segment_sum(enter.astype(jnp.int32), slot, num_segments=num + 1)[:num]
        dups = jnp.sum((cnt > 1).astype(jnp.int64))

    pcol = cols[js.probe_key]
    indom = (pcol.data >= 0) & (pcol.data < num)
    pkc = jnp.clip(pcol.data, 0, num - 1).astype(jnp.int32)
    hit = present[pkc] & indom & pcol.valid_mask()

    joined: Dict[str, Column] = {}
    for pname in js.payload:
        src = bt.column(pname)
        d = src.dtype
        if not d.is_fixed_width or d.id == dt.TypeId.DECIMAL128:
            raise ValueError(f"join payload {pname!r}: only plain fixed-width columns")
        dense = jnp.zeros((num + 1,), src.data.dtype).at[slot].set(
            jnp.where(enter, src.data, jnp.zeros((), src.data.dtype)), mode="drop"
        )[:num]
        dvalid = (
            jnp.zeros((num + 1,), bool).at[slot].set(src.valid_mask() & enter, mode="drop")[:num]
        )
        joined[pname] = Column(d, data=dense[pkc], validity=dvalid[pkc] & hit)
    return hit, joined, dups, bad_build


def _wrap_result(data, valid, how: str) -> Column:
    if how in ("count", "count_all"):
        return Column(dt.INT64, data=data.astype(jnp.int64), validity=valid)
    if data.dtype == jnp.uint64:
        # exact paths return ready-made FLOAT64 IEEE bits
        return Column(dt.FLOAT64, data=data, validity=valid)
    # f32-lane aggregates store into the FLOAT64 bit format
    return Column(dt.FLOAT64, data=bitutils.float_store(data.astype(jnp.float64), dt.FLOAT64), validity=valid)


def _drop_build_handles(handles: Dict[str, object]) -> None:
    """Close a pipeline's registered build handles (module-level so the
    weakref finalizer keeps no reference to the pipeline itself)."""
    for h in handles.values():
        h.close()
    handles.clear()


def compile_plan(plan: PlanSpec) -> CompiledPipeline:
    """Compile a plan once; reuse across batches of the same schema."""
    return CompiledPipeline(plan)
