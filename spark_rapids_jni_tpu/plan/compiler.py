"""Plan compiler: optimized logical plan -> executable stages (srjt-plan).

The Flare thesis (arxiv 1703.08219) applied to this engine: the hot
scan->join*->filter->project->aggregate region of a query should run as
ONE compiled program, not operator-at-a-time. The compiler walks the
optimized plan and, at every ``Aggregate``, tries to FUSE its input
chain into the same ``pipeline.CompiledPipeline`` the hand-built greens
use — star joins become ``JoinSpec``s (dense bounded-domain when the
``Join.bounded`` hint is set, sort-merge otherwise; a build side that is
itself a subplan is materialized at call time and joined sort-merge),
filters conjoin into the fused mask, projections become fused
projections, and bounded group-key domains are scanned host-side from
the bound tables exactly as the hand-built queries did. Everything the
fused grammar cannot express — fact-fact set ops, post-aggregate joins,
windows, sorts, unions — lowers to the tested ``ops/`` operators over
the (small) intermediate tables. On that operator tier a Filter's and a
Project's expressions are still ONE device program a stage
(``_StageProgram``, ISSUE 33), and so is the float64 normalisation of an
aggregate's outputs (``_normalize_agg_columns``); the operators themselves
launch as ``ops/`` does. A Filter whose rows reach an Aggregate through
nothing but Projects does not compact where its mask keeps half its rows or
more (ISSUE 35): it hands its input on with the mask beside it
(``_MaskedTable``), and the group-by groups the rows that are present.

Estimates (Theseus, arxiv 2508.05029: the plan is where data-movement /
memory decisions belong): every stage carries ``rows``/``bytes``
estimates derived from schema width x bound-table cardinalities at
compile time. The whole-plan peak feeds ``memgov`` admission when the
governor is armed (``CompiledPlan.estimated_memory_bytes`` — the same
``memory_bytes=`` contract the serve scheduler's pre-admission uses),
and after every run the per-stage estimate-vs-actual pairs are recorded
(``last_report``; appended to the ``SRJT_PLAN_REPORT`` JSONL when set)
so CI can gate estimate blowups.

Engine dtype contract (mirrored by ``nodes.infer_schema``): aggregate
outputs materialize as INT64 (counts) / FLOAT64 (everything else) on
BOTH tiers — the operator tier normalizes to the fused pipeline's
``_wrap_result`` convention so a plan's schema never depends on which
tier a stage landed on.
"""

from __future__ import annotations

import json
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..columnar import Column, Table
from ..columnar import dtype as dt
from ..columnar.dtype import DType, TypeId
from ..utils import knobs, metrics, tracing
from .exprs import PExpr, PlanError, conjoin, is_col, is_null_lit
from .nodes import (
    Aggregate,
    Exchange,
    Filter,
    Join,
    Limit,
    Node,
    Project,
    Scan,
    Sort,
    UnionAll,
    Window,
    infer_schema,
)
from .rewrites import rewrite
from .stats.model import calibration_factor


def _durable(name: str):
    """Registry-direct counter (always-on, like serve's shed accounting)
    so the compiler tier can be metrics-asserted without arming the
    event log."""
    return metrics.registry().counter(name)

__all__ = ["CompiledPlan", "compile_ir", "lower_ir"]

Schema = Dict[str, DType]

_FUSED_AGGS = ("sum", "count", "count_all", "min", "max", "mean")
_FILTER_SELECTIVITY = 0.5  # conservative: only UNDERestimates are gated
# The least share of its rows a Filter under an Aggregate has to keep to hand
# its mask on in place of a compacted table. Deferring trades the filter's
# N-sized ``nonzero`` scatter and a gather a column for a group-by over N slots
# in place of k N rows. Reckoned from the v5e's program times at N = 6.0 M
# (PERF.md 5, PR 33; ms): compacting costs 741 + k 336 and the group-by behind
# it k 1,430, deferred the group-by is 1,470 and the filter nothing: they cross
# at k = 0.41. Measured (PERF.md 6, PR 35: q1's plan at N = 6,001,215 with the
# share kept swept, both forms forced, ``benchmarks/calls/pr35_forms.py``): a
# deferred request is 1,327–1,336 ms whatever is kept; a compacted one 628 at
# k = 0.01, 747 at 0.1, 1,002 at 0.25, 1,432 at 0.5, 1,858 at 0.75, 2,506 at
# 0.986: they cross at k = 0.44. One half leaves the stretch between to the
# compacted form (7% dearer there at most) and is right wherever the group-by
# is lighter than q1's eight aggregates, where the crossing lies lower still; a
# filter that keeps 1% is better compacted by 2.1x. Read from the mask's own
# count at run time, never from the planner's estimate (a column without a
# sketch estimates at ``_FILTER_SELECTIVITY`` whatever it keeps).
_DEFER_MIN_KEEP = 0.5
_MAX_DENSE_GROUPS = 1 << 22


def _width(schema: Schema) -> int:
    total = 0
    for d in schema.values():
        # +1: the per-row validity lane. The archived r6 estimate-vs-
        # actual reports (artifacts/plan_compile.jsonl) showed the
        # value-only width UNDERestimating every nullable narrow table
        # by up to 1.25x (a lone INT32 column is 5 bytes/row with its
        # bool mask, not 4) — the one systematic drift in the gated
        # direction, and what let premerge tighten the blowup gate to 3x
        total += (d.size_bytes if d.is_fixed_width else 16) + 1
    return max(total, 1)


def _table_nbytes(t: Table) -> int:
    t = getattr(t, "table", t)  # a ShardedTable: its global arrays (padding included)
    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(t))


def _materialize(low, table: Table, want: DType, rows: int) -> Column:
    """One lowered expression tree over ``table`` as the column the plan's
    schema declares (``want``), ``rows`` long: a scalar result (a bare
    literal projection) is broadcast, an integral or BOOL8 result is pinned
    to the inferred dtype. ``low`` None is the typed SQL NULL. Pure ``jnp``
    over the columns' arrays: the same call serves inside a stage's one
    program and for the trees that stay eager."""
    if low is None:
        # typed SQL NULL: materialize at the DECLARED dtype — the
        # runtime literal tier evaluates NULL as INT32 lanes, which
        # would silently contradict the inferred schema for FLOAT64
        # (or any non-int) rolled keys in a grouping-set union
        if not want.is_fixed_width:
            raise PlanError(f"cannot materialize a NULL literal as {want!r}")
        shape = (rows, 4) if want.id == TypeId.DECIMAL128 else (rows,)
        return Column(want, data=jnp.zeros(shape, want.jnp_dtype),
                      validity=jnp.zeros((rows,), bool))
    c = low.evaluate(table)
    if c.data is not None and c.data.ndim == 0:
        data = jnp.broadcast_to(c.data, (rows,))
        v = None if c.validity is None else jnp.broadcast_to(c.validity, (rows,))
        c = Column(c.dtype, data=data, validity=v)
    elif len(c) != rows:
        raise PlanError(f"projection produced {len(c)} rows for {rows}")
    if c.dtype.id != want.id and c.dtype.is_integral and want.is_integral:
        c = Column(want, data=c.data.astype(want.jnp_dtype), validity=c.validity)
    elif c.dtype.id != want.id and want.id == TypeId.BOOL8:
        c = Column(dt.BOOL8, data=c.data.astype(jnp.uint8), validity=c.validity)
    return c


def _keep(mask: Column, present=None):
    """A predicate's BOOL8 column as the rows that pass: true and not NULL
    (and, over a mesh, in a slot that holds a row)."""
    keep = mask.data.astype(bool)
    if mask.validity is not None:
        keep = keep & mask.validity
    return keep if present is None else present & keep


class _MaskedTable:
    """What a deferred Filter hands on: its INPUT's columns, whole, and
    ``present``, the rows its predicate keeps (``bool[N]`` on the device) —
    the one-chip twin of ``ShardedTable.present``. Only the Project stages
    between such a Filter and its Aggregate, and that Aggregate, ever see
    one (``_defer_filters`` marks a Filter only where they are its sole
    readers)."""

    __slots__ = ("table", "present")

    def __init__(self, table: Table, present):
        self.table, self.present = table, present

    @property
    def num_rows(self) -> int:
        """Slots, not rows (as ``ShardedTable.num_rows``); the Filter's span says ``kept``."""
        return self.table.num_rows


def _rows_of(t):
    """(table, present): a stage's input as its columns and the rows of them
    that are present (None: all of them)."""
    return (t.table, t.present) if isinstance(t, _MaskedTable) else (t, None)


class _StageProgram:
    """The jit boundary of a Filter or Project stage, local or mesh: ALL of
    the stage's expression trees as ONE device program (ISSUE 33).

    Built once, when the stage is lowered: every ``PExpr`` is lowered to its
    ``rt.Expression`` tree here (not on every run), and the trees go three
    ways, by what the tree and the input schema show and by nothing else:

    - a bare column reference is handed on as the column it is, no launch
      (a STRING has offsets and chars and no ``data`` for the evaluator to
      read; a FLOAT64 passed through keeps its u64 lanes, no double-f32
      round trip: the narrowing Projects that pruning inserts are all of
      this kind);
    - a tree that reads fixed-width columns only (or none: a literal, the
      typed NULL) goes into the program. The program takes the columns the
      trees name (``PExpr.refs()``) and nothing else, evaluates every tree
      and ``_materialize``'s post-steps, and returns the output columns, so
      the compiler shares what the trees share (q1's ``price * (1 - disc)``
      inside ``charge``; one ``dd_from_f64bits`` a column) where the eager
      evaluator launched every ``jnp`` call of every tree by itself: ~300
      launches a FLOAT64 operand and ~225 a FLOAT64 result on a chip
      without a float64 datapath. jit's own cache keys on shapes, dtypes,
      validity present or absent and sharding; nothing of a run is kept
      here, so one ``CompiledPlan`` may run on several serve slots at once;
    - a tree that reads a column that is not fixed width (``LIKE`` /
      ``RLIKE``'s matcher and ``part_hash`` over a STRING key read offsets
      and chars and wait for the longest string on the host) is evaluated
      eagerly, whole, as before.

    ``fold`` makes the stage a Filter's: the one tree is its predicate and
    the result is the array of rows that pass (``_keep``, validity and the
    mesh's ``present`` folded in inside the program). ``sharding`` names
    the row layout of a mesh stage's arrays: the outputs are constrained to
    it, so a literal-only column comes back laid out as the inputs are.

    Counted where it engages: ``plan.expr.jitted`` a run that launched the
    program, ``plan.expr.eager`` a run that evaluated a tree eagerly, and
    on the stage's span ``jit`` (every computed tree went into the program)
    and ``exprs`` (how many did)."""

    def __init__(self, exprs, in_schema: Schema, sharding=None, fold: bool = False):
        self.sharding, self.fold = sharding, fold
        self.slots: list = []   # an output each: ("col", name) | ("jit", i) | ("eager", low, want)
        self.trees: list = []   # the program's (lowered tree, want)
        refs = set()
        for e, want in exprs:
            src = is_col(e)
            if src is not None and not fold:
                self.slots.append(("col", src))
                continue
            low = None if is_null_lit(e) else e.lower()
            if all(in_schema[r].is_fixed_width for r in e.refs()):
                self.slots.append(("jit", len(self.trees)))
                self.trees.append((low, want))
                refs |= e.refs()
            else:
                self.slots.append(("eager", low, want))
        self.refs = tuple(sorted(refs))
        self.n_eager = sum(1 for s in self.slots if s[0] == "eager")
        self._program = tracing.launches(jax.jit(self._body, static_argnums=0))

    def _body(self, rows: int, cols, present):
        if cols:
            table = Table(list(cols), list(self.refs))
        else:  # literals only: a table that knows its row count
            table = Table([Column(dt.BOOL8, data=jnp.zeros((rows,), jnp.uint8))], ["__rows"])
        out = [_materialize(low, table, want, rows) for low, want in self.trees]
        if self.fold:
            out = _keep(out[0], present)
        if self.sharding is not None:
            out = jax.lax.with_sharding_constraint(out, self.sharding)
        return out

    def __call__(self, table: Table, rows: int, present=None):
        """The stage's outputs over ``table``: a list of Columns, or the
        array of rows that pass where the stage is a Filter's."""
        ran = None
        if self.trees:
            _durable("plan.expr.jitted").inc()
            ran = self._program(rows, tuple(table.column(r) for r in self.refs), present)
        if self.n_eager:
            _durable("plan.expr.eager").inc()
        tracing.annotate(jit=bool(self.trees) and not self.n_eager, exprs=len(self.trees))
        if self.fold:
            if ran is not None:
                return ran
            _, low, want = self.slots[0]
            return _keep(_materialize(low, table, want, rows), present)
        out = []
        for slot in self.slots:
            if slot[0] == "col":
                out.append(table.column(slot[1]))
            elif slot[0] == "jit":
                out.append(ran[slot[1]])
            else:
                out.append(_materialize(slot[1], table, slot[2], rows))
        return out


def _agg_needs_float64(col: Column, how: str) -> bool:
    """Is an operator-tier aggregate column off the fused tier's
    materialization contract (counts INT64, everything else FLOAT64
    bit-lanes)? A count and a FLOAT64 sum are on it as they are."""
    return how not in ("count", "count_all", "nunique") and col.dtype.id != TypeId.FLOAT64


def _to_float64(col: Column) -> Column:
    """An integer or FLOAT32 aggregate column as FLOAT64 bit-lanes (exact)."""
    from ..ops import bitutils
    from ..ops.f64acc import i64_to_f64bits

    if col.dtype.is_integral:
        return Column(dt.FLOAT64, data=i64_to_f64bits(col.data.astype(jnp.int64)),
                      validity=col.validity)
    if col.dtype.id == TypeId.FLOAT32:
        x = col.data.astype(jnp.float64) if bitutils.backend_has_f64() else col.data
        return Column(dt.FLOAT64, data=bitutils.float_store(x, dt.FLOAT64),
                      validity=col.validity)
    raise PlanError(f"cannot normalize an aggregate over {col.dtype!r}")


@tracing.launches
@jax.jit
def _to_float64_program(cols):
    """``_to_float64`` of every column of one aggregate stage, as one program."""
    return tuple(_to_float64(c) for c in cols)


def _normalize_agg_columns(cols: List[Column], hows: List[str]) -> List[Column]:
    """Bring an aggregate stage's operator-tier outputs onto the fused
    tier's materialization contract so schema inference holds regardless
    of tier: the columns that need the conversion (an integer min or max:
    q95's ``wh_lo`` and ``wh_hi``) go through ONE jitted program together
    (``i64_to_f64bits`` is ~100 ``jnp`` calls a column when launched one by
    one); those that need none (counts, FLOAT64 sums: every aggregate of
    q1 and of the store star) are handed on untouched and cost no launch.
    Elementwise, so over a mesh the outputs lie as the inputs do."""
    need = [i for i, (c, how) in enumerate(zip(cols, hows)) if _agg_needs_float64(c, how)]
    tracing.annotate(jit=bool(need), exprs=len(need))
    if not need:
        return cols
    _durable("plan.expr.jitted").inc()
    done = _to_float64_program(tuple(cols[i] for i in need))
    cols = list(cols)
    for i, c in zip(need, done):
        cols[i] = c
    return cols


class _RunContext:
    """One execution of a compiled plan: node-result memoization (shared
    CTE subtrees run once) + per-stage actual byte accounting. Actuals
    live HERE, not on the shared _Exec objects — one CompiledPlan may
    be running on several serve slots at once, and per-run state on the
    stage objects would tear the estimate-vs-actual report."""

    __slots__ = ("tables", "cache", "actuals", "subcache")

    def __init__(self, tables: Dict[str, Table], subcache=None):
        self.tables = tables
        self.cache: Dict[int, Table] = {}
        self.actuals: Dict[int, Tuple[int, int]] = {}  # exec id -> (rows, bytes)
        # srjt-cache (ISSUE 17): the cross-run subresult cache, or None
        # when caching is off — stages annotated with a ``cache_key``
        # consult it before recomputing
        self.subcache = subcache


class _Exec:
    """One lowered stage: knows its schema, estimates, and inputs."""

    kind = "?"

    # under a MeshBinding: does this stage hand on a ShardedTable (its
    # rows laid out over the mesh), and on which columns is it known to
    # be partitioned. Stages of a plan compiled without a mesh are local.
    sharded = False
    part: Tuple[str, ...] = ()

    # srjt-cache (ISSUE 17): set once at annotation time (before any
    # concurrent run) on stages whose subtree result is cacheable; the
    # key pins (parameterized structure, literal bindings, table
    # generations), so a stale entry is unreachable by construction
    cache_key = None

    def __init__(self, schema: Schema, est_rows: int, inputs: List["_Exec"]):
        self.schema = schema
        self.est_rows = max(int(est_rows), 1)
        self.inputs = inputs
        # srjt-cbo (ISSUE 19): byte estimates carry the per-kind factor
        # learned from archived estimate-vs-actual reports (neutral 1.0
        # on a fresh checkout, clamped to [0.5, 2x]); the floor keeps
        # the verifier's est_bytes >= est_rows invariant under any factor
        self.est_bytes = max(self.est_rows,
                             int(self.est_rows * _width(schema)
                                 * calibration_factor(self.kind)))

    def run(self, ctx: _RunContext) -> Table:
        key = id(self)
        if key in ctx.cache:
            return ctx.cache[key]
        # one span a stage (its inputs' stages nest inside it): the
        # host time of a stage that is in no operator is the span's
        # self time
        with tracing.span(f"plan.{self.kind}") as sp:
            if ctx.subcache is not None and self.cache_key is not None:
                out = ctx.subcache.lookup_or_compute(
                    self.cache_key, lambda: self._run(ctx))
            else:
                out = self._run(ctx)
            sp.annotate(rows_out=out.num_rows)
        ctx.actuals[key] = (out.num_rows, _table_nbytes(out))
        ctx.cache[key] = out
        return out

    def _run(self, ctx: _RunContext) -> Table:
        raise NotImplementedError

    def working_set_est(self) -> int:
        return self.est_bytes + sum(i.est_bytes for i in self.inputs)

    def working_set_actual(self, actuals: Dict[int, Tuple[int, int]]) -> Optional[int]:
        mine = actuals.get(id(self))
        if mine is None:
            return None
        parts = [mine[1]]
        for i in self.inputs:
            got = actuals.get(id(i))
            if got is not None:
                parts.append(got[1])
        return sum(parts)


class _ScanExec(_Exec):
    kind = "scan"

    def __init__(self, node: Scan, schema: Schema, tables):
        super().__init__(schema, tables[node.table].num_rows, [])
        self.table = node.table
        self.columns = list(schema.keys())

    def _run(self, ctx):
        return ctx.tables[self.table].select(self.columns)


class _FilterExec(_Exec):
    kind = "filter"

    def __init__(self, node: Filter, schema: Schema, child: _Exec,
                 est_rows: Optional[int] = None, sharding=None):
        if est_rows is None:
            est_rows = math.ceil(child.est_rows * _FILTER_SELECTIVITY)
        super().__init__(schema, min(est_rows, child.est_rows), [child])
        self.pred = node.predicate
        self.program = _StageProgram([(self.pred, dt.BOOL8)], child.schema,
                                     sharding=sharding, fold=True)
        # set by ``_defer_filters`` once every stage is lowered: this stage's
        # rows reach an op-tier Aggregate through nothing but jitted Projects
        # and nothing else reads them
        self.deferrable = False

    def _run(self, ctx):
        from ..ops import copying

        t = self.inputs[0].run(ctx)
        keep = self.program(t, t.num_rows)  # the mask: one program
        if self.deferrable:
            # the stage's one wait, where ``jnp.nonzero`` would read its size:
            # the mask's own count says whether compacting pays
            kept = int(tracing.device_wait(jnp.sum(keep), "mask_popcount"))
            if kept >= _DEFER_MIN_KEEP * t.num_rows:
                _durable("plan.filter.deferred").inc()
                tracing.annotate(deferred=True, kept=kept)
                return _MaskedTable(t, keep)
        # the compaction is the eager op's: ``jnp.nonzero`` and a gather a column
        out = copying.apply_boolean_mask(t, keep)
        _durable("plan.filter.compacted").inc()
        tracing.annotate(deferred=False, kept=out.num_rows)
        return out


class _ProjectExec(_Exec):
    kind = "project"

    def __init__(self, node: Project, schema: Schema, child: _Exec, sharding=None):
        super().__init__(schema, child.est_rows, [child])
        self.exprs = node.exprs
        self.program = _StageProgram([(e, schema[name]) for name, e in self.exprs],
                                     child.schema, sharding=sharding)

    def _run(self, ctx):
        t, present = _rows_of(self.inputs[0].run(ctx))
        out = Table(self.program(t, t.num_rows), [name for name, _ in self.exprs])
        # under a deferred Filter the trees run over every slot and the mask rides on
        return out if present is None else _MaskedTable(out, present)


class _JoinExec(_Exec):
    kind = "join"

    def __init__(self, node: Join, schema: Schema, left: _Exec, right: _Exec,
                 est_rows: Optional[int] = None):
        if est_rows is None:
            est_rows = (left.est_rows + right.est_rows if node.how == "full"
                        else left.est_rows)
        super().__init__(schema, est_rows, [left, right])
        self.on = node.on
        self.how = node.how

    def _run(self, ctx):
        from ..ops import join as join_ops

        left = self.inputs[0].run(ctx)
        right = self.inputs[1].run(ctx)
        lnames = [l for l, _ in self.on]
        rename = {r: l for l, r in self.on}
        right = Table(list(right.columns),
                      [rename.get(n, n) for n in right.names])
        fn = {
            "inner": join_ops.inner_join,
            "left": join_ops.left_join,
            "full": join_ops.full_join,
            "semi": join_ops.left_semi_join,
            "anti": join_ops.left_anti_join,
        }[self.how]
        out = fn(left, right, on=lnames)
        return out.select(list(self.schema.keys()))


class _ExchangeExec(_Exec):
    """Hash-repartition across the bound exchange fabric (ISSUE 16).
    Unbound — no ``plan.distribute.exchange_context`` in scope — or at
    ``world == 1`` this stage is the identity, so one compiled plan
    serves both the single-host oracle and every rank of the
    distributed run. With a cluster + shard catalog bound, the stage
    installs its child subtree as the dead-rank lineage reproducer
    right before moving rows: recovery replays exactly the lowered
    code that produced the lost input."""

    kind = "exchange"

    def __init__(self, node: Exchange, schema: Schema, child: _Exec):
        super().__init__(schema, child.est_rows, [child])
        self.keys = node.keys
        self.world = node.world

    def _run(self, ctx):
        from .distribute import current_binding

        t = self.inputs[0].run(ctx)
        binding = current_binding()
        if binding is None or self.world <= 1:
            return t
        if binding.world != self.world:
            raise PlanError(
                f"exchange stage compiled for world {self.world} bound to "
                f"a {binding.world}-rank fabric")
        if binding.cluster is not None and binding.shard_tables is not None:
            child = self.inputs[0]
            shards = binding.shard_tables
            binding.cluster.set_lineage(
                lambda r: child.run(_RunContext(shards(r))))
        return binding.exchange.exchange_table(
            t, list(self.keys), binding.peers,
            epoch=binding.stage_epoch(id(self)), cluster=binding.cluster,
        )


# ---------------------------------------------------------------------------
# stages over tables that are laid out over a mesh (plan.distribute.MeshBinding)
# ---------------------------------------------------------------------------
#
# Each hands on a ``parallel.table_ops.ShardedTable``: nothing is compacted
# and nothing leaves its chip but through an Exchange stage, so the
# partitioning one exchange establishes serves every keyed stage after it.
# The Filter and the Project run the local stages' ``_StageProgram`` over the
# row-sharded arrays, with the rows' sharding named for its outputs: one
# launch a stage over the four chips, elementwise, no collective.


class _MeshScanExec(_ScanExec):
    sharded = True  # the bound table is a ShardedTable; selecting its columns is the same call


class _MeshFilterExec(_FilterExec):
    """A row that fails the predicate leaves ``present``; its slot stays."""

    sharded = True

    def __init__(self, node, schema, child, sharding, est_rows=None):
        super().__init__(node, schema, child, est_rows=est_rows, sharding=sharding)
        self.part = child.part

    def _run(self, ctx):
        st = self.inputs[0].run(ctx)
        # one launch: the predicate, its validity and ``present`` in one program
        return st.replace(present=self.program(st.table, st.num_rows, st.present))


class _MeshProjectExec(_ProjectExec):
    sharded = True

    def __init__(self, node, schema, child, sharding):
        super().__init__(node, schema, child, sharding=sharding)
        kept = {name for name, e in node.exprs if is_col(e) == name}
        self.part = child.part if set(child.part) <= kept else ()

    def _run(self, ctx):
        st = self.inputs[0].run(ctx)
        cols = self.program(st.table, st.num_rows)
        return st.replace(table=Table(cols, [name for name, _ in self.exprs]), part=self.part)


class _MeshExchangeExec(_Exec):
    """The Exchange stage on a mesh: one ``shard_map`` program, an ICI
    all-to-all a lane (``table_ops.exchange_sharded``)."""

    kind = "exchange"
    sharded = True

    def __init__(self, node: Exchange, schema: Schema, child: _Exec):
        super().__init__(schema, child.est_rows, [child])
        self.keys = self.part = tuple(node.keys)

    def _run(self, ctx):
        from ..parallel.table_ops import exchange_sharded

        return exchange_sharded(self.inputs[0].run(ctx), self.keys)


class _MeshAggExec(_Exec):
    """A keyed aggregate over rows already partitioned on its keys:
    every shard groups what it holds (``table_ops.groupby_sharded``)."""

    kind = "aggregate"
    sharded = True

    def __init__(self, node: Aggregate, schema: Schema, child: _Exec, est_rows=None):
        super().__init__(schema, child.est_rows if est_rows is None else est_rows, [child])
        self.keys, self.aggs, self.part = node.keys, node.aggs, child.part

    def _run(self, ctx):
        from ..parallel.table_ops import groupby_sharded

        st = groupby_sharded(self.inputs[0].run(ctx), self.keys,
                             [(a.source, a.how, a.name) for a in self.aggs])
        nk = len(self.keys)
        cols = list(st.table.columns[:nk]) + _normalize_agg_columns(
            list(st.table.columns[nk:]), [a.how for a in self.aggs])
        return st.replace(table=Table(cols, list(st.names)))


class _MeshJoinExec(_Exec):
    """A sharded left side against a whole right side (broadcast) or one
    partitioned on the same key: slot for slot, nothing moves
    (``table_ops.join_sharded``)."""

    kind = "join"
    sharded = True

    def __init__(self, node: Join, schema: Schema, left: _Exec, right: _Exec, est_rows=None):
        super().__init__(schema, left.est_rows if est_rows is None else est_rows, [left, right])
        self.on, self.how, self.part = node.on[0], node.how, left.part
        self.payload = [c for c in schema if c not in left.schema]

    def _run(self, ctx):
        from ..parallel.table_ops import join_sharded

        out = join_sharded(self.inputs[0].run(ctx), self.inputs[1].run(ctx), self.on, self.how,
                           payload=self.payload)
        return out.select(list(self.schema.keys()))


class _GatherExec(_Exec):
    """The way out of the mesh stages: the rows that are present, as one
    compacted Table whole on every chip (``table_ops.gather_table``)."""

    kind = "gather"

    def __init__(self, child: _Exec):
        super().__init__(child.schema, child.est_rows, [child])

    def _run(self, ctx):
        from ..parallel.table_ops import gather_table

        return gather_table(self.inputs[0].run(ctx))


class _AggExec(_Exec):
    """Operator-tier grouped/global aggregation (the general fallback:
    arbitrary key dtypes, var/std/nunique, DISTINCT)."""

    kind = "aggregate"

    def __init__(self, node: Aggregate, schema: Schema, child: _Exec,
                 est_rows: Optional[int] = None):
        super().__init__(schema, child.est_rows if est_rows is None else est_rows,
                         [child])
        self.keys = node.keys
        self.aggs = node.aggs

    def _run(self, ctx):
        from ..ops.aggregate import groupby_aggregate

        t, present = _rows_of(self.inputs[0].run(ctx))
        n = t.num_rows
        if not self.keys and n == 0:
            return self._global_of_nothing()
        if self.keys:
            keys_tbl = t.select(list(self.keys))
        else:
            keys_tbl = Table(
                [Column(dt.INT32, data=jnp.zeros((n,), jnp.int32))], ["__g"]
            )
        spec = []
        for a in self.aggs:
            src = a.source if a.source is not None else (
                self.keys[0] if self.keys else t.names[0]
            )
            spec.append((src, a.how, a.name))
        agg = groupby_aggregate(keys_tbl, t, [(s, h) for s, h, _ in spec], present=present)
        if not self.keys and agg.num_rows == 0:
            return self._global_of_nothing()  # a deferred Filter's mask kept no row
        # groupby_aggregate names outputs {src}_{how} in order after the
        # keys; rebind positionally to the AggSpec names and normalize
        # onto the fused materialization contract
        nk = keys_tbl.num_columns
        out_cols: List[Column] = [agg.column(i) for i in range(len(self.keys))]
        out_cols += _normalize_agg_columns([agg.column(nk + j) for j in range(len(spec))],
                                           [how for _, how, _ in spec])
        return Table(out_cols, list(self.keys) + [name for _, _, name in spec])

    def _global_of_nothing(self) -> Table:
        """SQL global aggregates yield ONE row on empty input (the fused
        tier does; the sort-based kernel yields zero groups)."""
        cols = []
        for a in self.aggs:
            if a.how in ("count", "count_all", "nunique"):
                cols.append(Column(dt.INT64, data=jnp.zeros((1,), jnp.int64)))
            else:
                cols.append(Column(
                    dt.FLOAT64, data=jnp.zeros((1,), jnp.uint64),
                    validity=jnp.zeros((1,), bool),
                ))
        return Table(cols, [a.name for a in self.aggs])


class _FusedAggExec(_Exec):
    """The fused tier: one ``CompiledPipeline`` dispatch for the whole
    join*->filter->project->aggregate stage. ``builds`` maps build name
    -> either a compile-time Table (direct dim build) or an _Exec run at
    call time (materialized subplan build)."""

    kind = "fused_aggregate"

    def __init__(self, schema: Schema, pipeline, fact: _Exec,
                 builds: Dict[str, object], est_rows: int,
                 out_names: List[str]):
        build_execs = [b for b in builds.values() if isinstance(b, _Exec)]
        super().__init__(schema, est_rows, [fact] + build_execs)
        self.pipeline = pipeline
        self.builds = builds
        self.out_names = out_names
        self._static_build_bytes = sum(
            _table_nbytes(b) for b in builds.values() if isinstance(b, Table)
        )
        self.est_bytes += self._static_build_bytes

    def _run(self, ctx):
        fact = self.inputs[0].run(ctx)
        builds = {}
        for name, b in self.builds.items():
            builds[name] = b.run(ctx) if isinstance(b, _Exec) else b
        out = self.pipeline(fact, builds)
        _durable("plan.fused_dispatches").inc()
        return Table(list(out.columns), self.out_names)


class _WindowExec(_Exec):
    kind = "window"

    def __init__(self, node: Window, schema: Schema, child: _Exec):
        super().__init__(schema, child.est_rows, [child])
        self.node = node

    def _run(self, ctx):
        from ..ops.window import window_aggregate

        t = self.inputs[0].run(ctx)
        return window_aggregate(
            t, list(self.node.partition_by), list(self.node.order_by),
            list(self.node.aggs),
        )


class _SortExec(_Exec):
    kind = "sort"

    def __init__(self, node: Sort, schema: Schema, child: _Exec):
        super().__init__(schema, child.est_rows, [child])
        self.keys = node.keys

    def _run(self, ctx):
        from ..ops.sort import sort_by_key

        t = self.inputs[0].run(ctx)
        keys = Table([t.column(c) for c, _ in self.keys],
                     [f"k{i}" for i in range(len(self.keys))])
        return sort_by_key(t, keys, ascending=[asc for _, asc in self.keys])


class _LimitExec(_Exec):
    kind = "limit"

    def __init__(self, node: Limit, schema: Schema, child: _Exec):
        super().__init__(schema, min(child.est_rows, node.n), [child])
        self.n = node.n

    def _run(self, ctx):
        from ..ops import copying

        t = self.inputs[0].run(ctx)
        return copying.slice_table(t, 0, min(self.n, t.num_rows))


class _UnionExec(_Exec):
    kind = "union_all"

    def __init__(self, schema: Schema, children: List[_Exec]):
        super().__init__(schema, sum(c.est_rows for c in children), children)

    def _run(self, ctx):
        from ..ops import copying

        names = list(self.schema.keys())
        parts = [c.run(ctx).select(names) for c in self.inputs]
        return copying.concatenate(parts)


# ---------------------------------------------------------------------------
# fused-stage detection
# ---------------------------------------------------------------------------


class _Bail(Exception):
    """Internal: this aggregate does not fit the fused grammar — fall
    back to the operator tier (never an error)."""


def _int_domain(col: Column) -> Optional[int]:
    """[0, num) bounded domain of an integer column (host scan at bind
    time, the same sync the hand-built queries pay), or None when the
    column is empty/negative/non-integral."""
    if not col.dtype.is_integral:
        return None
    if len(col) == 0:
        return 1
    lo = int(jnp.min(col.data))
    if lo < 0:
        return None
    return int(jnp.max(col.data)) + 1


class _Fuser:
    """Pattern-match one Aggregate's input chain onto a PlanSpec."""

    def __init__(self, lowerer: "_Lowerer", agg: Aggregate):
        self.low = lowerer
        self.agg = agg
        self.joins: List[Join] = []
        self.filters: List[PExpr] = []
        self.project: Optional[Project] = None
        self.fact: Optional[Scan] = None

    def _walk(self, n: Node, under_join: bool) -> None:
        if isinstance(n, Project) and all(
            is_col(e) == name for name, e in n.exprs
        ):
            # passthrough-only narrowing (pruning inserts these): a
            # no-op for the fused working schema at any depth
            self._walk(n.input, under_join)
        elif isinstance(n, Project) and not under_join:
            if self.project is not None:
                raise _Bail("stacked projects")
            self.project = n
            self._walk(n.input, under_join)
        elif isinstance(n, Filter):
            self.filters.append(n.predicate)
            self._walk(n.input, True)
        elif isinstance(n, Join):
            if n.how not in ("inner", "semi", "anti") or len(n.on) != 1:
                raise _Bail("join shape")
            self._walk(n.left, True)
            self.joins.append(n)
        elif isinstance(n, Scan):
            if self.fact is not None:
                raise _Bail("two facts")
            self.fact = n
        else:
            raise _Bail(type(n).__name__)

    def try_build(self) -> Optional[_FusedAggExec]:
        from ..pipeline import Agg as PAgg
        from ..pipeline import GroupKey, JoinSpec, PlanSpec, compile_plan

        agg = self.agg
        if agg.grouping_sets is not None or not agg.aggs:
            return None
        if any(a.how not in _FUSED_AGGS for a in agg.aggs):
            return None
        try:
            self._walk(agg.input, False)
        except _Bail:
            return None
        if self.fact is None:
            return None
        fact_schema = self.low.schema_of(self.fact)

        # the working schema the pipeline sees: fact columns + payloads
        work: Dict[str, str] = {c: self.fact.table for c in fact_schema}
        specs: List[JoinSpec] = []
        builds: Dict[str, object] = {}
        try:
            for idx, j in enumerate(self.joins):
                spec, bname, build = self._build_side(j, work, idx)
                if bname in builds:
                    return None  # duplicate build name (self-join w/o alias)
                specs.append(spec)
                builds[bname] = build
                if j.how == "inner":
                    for pname in spec.payload:
                        work[pname] = bname
        except _Bail:
            return None

        # projections: passthrough names stay; computed exprs fuse
        proj_entries: List[Tuple[str, object]] = []
        visible = set(work)
        key_source: Dict[str, str] = {}
        if self.project is not None:
            visible = set()
            for name, e in self.project.exprs:
                src = is_col(e)
                if src is not None and src == name:
                    visible.add(name)
                    key_source[name] = name
                else:
                    proj_entries.append((name, e))
                    visible.add(name)
        else:
            key_source = {c: c for c in work}

        # group keys: un-projected INT32 columns with scannable domains
        gks: List[GroupKey] = []
        domain_product = 1
        for k in agg.keys:
            src = key_source.get(k)
            if src is None or src not in work:
                return None
            owner = work[src]
            src_col = self._owner_column(owner, src, builds)
            if src_col is None or src_col.dtype.id != TypeId.INT32:
                return None
            num = _int_domain(src_col)
            if num is None:
                return None
            domain_product *= num
            if domain_product > _MAX_DENSE_GROUPS:
                return None
            gks.append(GroupKey(k, num))

        # aggregate sources must be visible post-project
        if not fact_schema:
            return None
        paggs = []
        for a in agg.aggs:
            src = a.source
            if a.how == "count_all":
                src = next(iter(fact_schema))
            if src not in visible:
                return None
            paggs.append(PAgg(src, a.how, a.name))

        filt = None
        if self.filters:
            filt = conjoin(self.filters).lower()
        spec = PlanSpec(
            joins=tuple(specs),
            filter=filt,
            project=tuple((n, e.lower()) for n, e in proj_entries),
            group_by=tuple(gks),
            aggregates=tuple(paggs),
        )
        out_schema = self.low.schema_of(agg)
        out_names = list(out_schema.keys())
        est_rows = min(self.low.exec_of(self.fact).est_rows,
                       domain_product if gks else 1)
        if self.low.est is not None and gks:
            # sketch ndv product is usually tighter than the dense
            # key-domain product (domains count holes, ndv does not)
            est_rows = min(est_rows, self.low.est.agg_rows(
                self.low.exec_of(self.fact).est_rows, agg.keys))
        pipeline = compile_plan(spec)
        fact_exec = self.low.exec_of(self.fact)
        _durable("plan.fused_stages").inc()
        return _FusedAggExec(out_schema, pipeline, fact_exec, builds,
                             est_rows, out_names)

    def _owner_column(self, owner: str, name: str, builds) -> Optional[Column]:
        """The bind-time column backing a group key: a fact column or a
        DIRECT build's payload column (materialized builds have no
        bind-time data to scan)."""
        if owner == self.fact.table:
            return self.low.tables[self.fact.table].column(name)
        b = builds.get(owner)
        if isinstance(b, Table) and name in b.names:
            return b.column(name)
        return None

    def _build_side(self, j: Join, work, idx: int) -> Tuple[object, str, object]:
        """Lower one join's right side: a Scan (+Filter) reduces to a
        compile-time build table + fused build_filter; anything else
        materializes its subplan at call time (sort-merge)."""
        from ..pipeline import JoinSpec

        probe, bkey = j.on[0]
        if probe not in work:
            raise _Bail("probe key not in working schema")
        right = j.right
        rschema = self.low.schema_of(right)
        payload = tuple(n for n in rschema if n != bkey) if j.how == "inner" else ()
        for pname in payload:
            d = rschema[pname]
            if not d.is_fixed_width or d.id == TypeId.DECIMAL128:
                raise _Bail("payload dtype")

        pred = None
        cur = right
        if isinstance(cur, Project) and all(
            is_col(e) == name for name, e in cur.exprs
        ):
            cur = cur.input  # pruning's narrowing wrapper
        if isinstance(cur, Filter):
            pred = cur.predicate
            cur = cur.input
        if isinstance(cur, Scan):
            bname = cur.key
            bt = self.low.tables[cur.table]
            needed = [bkey] + [p for p in payload if p != bkey]
            if pred is not None:
                needed += [r for r in pred.refs() if r not in needed]
            for c in needed:
                if c not in bt.names:
                    raise _Bail("build column missing")
            build_tbl = bt.select(needed)
            num_keys = None
            if j.bounded:
                num_keys = _int_domain(build_tbl.column(bkey))
                if num_keys is None:
                    raise _Bail("unbounded build key domain")
            spec = JoinSpec(
                build=bname, probe_key=probe, build_key=bkey,
                num_keys=num_keys, payload=payload, how=j.how,
                build_filter=None if pred is None else pred.lower(),
            )
            return spec, bname, build_tbl
        # materialized build: run the subplan, join sort-merge
        bexec = self.low.lower(right)
        bname = f"__build_{idx}_{bkey}"
        spec = JoinSpec(build=bname, probe_key=probe, build_key=bkey,
                        num_keys=None, payload=payload, how=j.how)
        return spec, bname, bexec


# ---------------------------------------------------------------------------
# the lowerer
# ---------------------------------------------------------------------------


class _Lowerer:
    def __init__(self, tables: Dict[str, Table], catalog: Dict[str, Schema],
                 est=None, mesh=None):
        self.tables = tables
        self.catalog = catalog
        # srjt-cbo (ISSUE 19): sketch-backed stats.Estimator, or None —
        # stages then keep the original hand-tuned row heuristics
        self.est = est
        # plan.distribute.MeshBinding, or None: with it the stages over
        # its sharded tables lower to the _Mesh*Exec family
        self.mesh = mesh
        self._schemas: dict = {}
        self._execs: Dict[int, _Exec] = {}
        self._gathers: Dict[int, _Exec] = {}
        self._unique: Dict[Tuple[str, str], bool] = {}
        self.all_execs: List[_Exec] = []

    def schema_of(self, node: Node) -> Schema:
        return infer_schema(node, self.catalog, self._schemas)

    def exec_of(self, node: Node) -> _Exec:
        return self.lower(node)

    def lower(self, node: Node) -> _Exec:
        key = id(node)
        if key in self._execs:
            return self._execs[key]
        ex = self._lower(node)
        self._execs[key] = ex
        if ex not in self.all_execs:
            self.all_execs.append(ex)
        return ex

    def local(self, node: Node) -> _Exec:
        """The stage of ``node`` as a local one: a stage over a mesh
        gets a gather on top (once, however many stages read it)."""
        ex = self.lower(node)
        if not ex.sharded:
            return ex
        if id(ex) not in self._gathers:
            self._gathers[id(ex)] = _GatherExec(ex)
            self.all_execs.append(self._gathers[id(ex)])
        return self._gathers[id(ex)]

    def _on_mesh(self, node: Node) -> bool:
        """Does a table that the mesh binding shards lie under ``node``?"""
        if isinstance(node, Scan):
            return node.table in self.mesh.sharded
        return any(self._on_mesh(i) for i in node.inputs())

    def _unique_key(self, node: Node, key: str) -> bool:
        """Is ``key`` provably unique in ``node``'s output: a column of a
        bound whole table (checked once, on the host) under nothing but
        filters and pass-through projections?"""
        while isinstance(node, (Filter, Project)):
            if isinstance(node, Project) and not any(n == key and is_col(e) == key for n, e in node.exprs):
                return False
            node = node.input
        if not isinstance(node, Scan) or node.table in self.mesh.sharded:
            return False
        if (node.table, key) not in self._unique:
            import numpy as np

            c = self.tables[node.table].column(key)
            vals = np.asarray(c.data)
            if c.validity is not None:
                vals = vals[np.asarray(c.validity)]
            self._unique[(node.table, key)] = bool(c.dtype.is_integral and len(np.unique(vals)) == len(vals))
        return self._unique[(node.table, key)]

    def _row_sharding(self):
        """How the arrays of a stage over the mesh lie: rows over the binding's axis."""
        from ..parallel.mesh import row_sharding

        return row_sharding(self.mesh.mesh, self.mesh.axis)

    def _lower_mesh(self, node: Node, schema: Schema) -> Optional[_Exec]:
        """The stage over the mesh, where ``node`` reads sharded rows and
        the sharded layer can run it; None sends it down the local path
        (which gathers what it reads)."""
        if isinstance(node, Scan):
            return _MeshScanExec(node, schema, self.tables) if node.table in self.mesh.sharded else None
        if isinstance(node, Join):
            child = self.lower(node.left)
        elif isinstance(node, (Filter, Project, Exchange, Aggregate)):
            child = self.lower(node.input)
        else:
            return None
        if not child.sharded:
            return None
        if isinstance(node, Filter):
            rows = (self.est.filter_rows(child.est_rows, node.predicate)
                    if self.est is not None else None)
            return _MeshFilterExec(node, schema, child, self._row_sharding(), est_rows=rows)
        if isinstance(node, Project):
            return _MeshProjectExec(node, schema, child, self._row_sharding())
        if isinstance(node, Exchange):
            if node.world != self.mesh.world:
                raise PlanError(f"exchange stage placed for world {node.world} compiled for a "
                                f"mesh of {self.mesh.world}")
            return _MeshExchangeExec(node, schema, child)
        if isinstance(node, Aggregate):
            from ..parallel.table_ops import _SHARDED_HOWS

            # the shard-local group-by sorts integer key lanes (absent rows under the largest
            # value): any other key type groups on the local tier, behind a gather
            if (node.keys and node.grouping_sets is None and child.part
                    and set(child.part) <= set(node.keys)
                    and all(child.schema[k].is_integral for k in node.keys)
                    and all(a.how in _SHARDED_HOWS for a in node.aggs)):
                rows = (self.est.agg_rows(child.est_rows, node.keys)
                        if self.est is not None else None)
                return _MeshAggExec(node, schema, child, est_rows=rows)
            return None
        # a Join with its left side on the mesh
        if len(node.on) != 1 or node.how not in ("inner", "semi", "anti"):
            return None
        (lkey, rkey), right = node.on[0], self.lower(node.right)
        ld, rd = child.schema[lkey], right.schema[rkey]
        # the keys meet in int64 (an exchange routes an integer by its value, whatever its
        # width): every integer type but a UINT64 against another
        if not (ld.is_integral and rd.is_integral) or (
                ld.id != rd.id and TypeId.UINT64 in (ld.id, rd.id)):
            return None
        if right.sharded:
            ok = node.how != "inner" and child.part == (lkey,) and right.part == (rkey,)
        else:
            # an inner join brings columns over: fixed-width ones, and from
            # a side whose key is unique, so that a row matches once
            ok = node.how != "inner" or (self._unique_key(node.right, rkey) and all(
                d.is_fixed_width for c, d in schema.items() if c not in child.schema))
        if not ok:
            return None
        rows = (self.est.join_rows(node.how, child.est_rows, right.est_rows, node.on)
                if self.est is not None else None)
        return _MeshJoinExec(node, schema, child, right, est_rows=rows)

    def _lower(self, node: Node) -> _Exec:
        schema = self.schema_of(node)
        if self.mesh is not None:
            ex = self._lower_mesh(node, schema)
            if ex is not None:
                return ex
        if isinstance(node, Scan):
            return _ScanExec(node, schema, self.tables)
        if isinstance(node, Filter):
            child = self.local(node.input)
            rows = (self.est.filter_rows(child.est_rows, node.predicate)
                    if self.est is not None else None)
            return _FilterExec(node, schema, child, est_rows=rows)
        if isinstance(node, Project):
            return _ProjectExec(node, schema, self.local(node.input))
        if isinstance(node, Join):
            left = self.local(node.left)
            right = self.local(node.right)
            rows = (self.est.join_rows(node.how, left.est_rows,
                                       right.est_rows, node.on)
                    if self.est is not None else None)
            return _JoinExec(node, schema, left, right, est_rows=rows)
        if isinstance(node, Aggregate):
            # the fused tier reads whole tables: not one that is sharded
            fused = (None if self.mesh is not None and self._on_mesh(node)
                     else _Fuser(self, node).try_build())
            if fused is not None:
                self.all_execs.append(fused)
                return fused
            _durable("plan.ops_stages").inc()
            child = self.local(node.input)
            rows = (self.est.agg_rows(child.est_rows, node.keys)
                    if self.est is not None else None)
            return _AggExec(node, schema, child, est_rows=rows)
        if isinstance(node, Exchange):
            return _ExchangeExec(node, schema, self.local(node.input))
        if isinstance(node, Window):
            return _WindowExec(node, schema, self.local(node.input))
        if isinstance(node, Sort):
            return _SortExec(node, schema, self.local(node.input))
        if isinstance(node, Limit):
            return _LimitExec(node, schema, self.local(node.input))
        if isinstance(node, UnionAll):
            return _UnionExec(schema, [self.local(b) for b in node.branches])
        raise PlanError(
            f"cannot lower {type(node).__name__}: sugar nodes must be "
            "rewritten away before compilation")


def _defer_filters(stages: List[_Exec]) -> None:
    """Mark the one-chip Filter stages that may hand their mask on in place
    of a compacted table (ISSUE 35): under an op-tier Aggregate (the fuser
    bailed; never a mesh stage) through nothing but Projects whose every
    tree is a reference or in the stage's one program (a jitted elementwise
    program cannot raise on a row the filter would have removed; a STRING
    handed on costs nothing), with no other reader anywhere along the way
    (``_Lowerer.lower`` memoises by node: a shared CTE subtree keeps its
    compaction). Two conditions of shape, decided here once every stage is
    lowered and its readers can be counted; the third, that the mask keeps
    at least ``_DEFER_MIN_KEEP`` of its rows, the stage reads at run time."""
    readers: Dict[int, int] = {}
    for ex in stages:
        for i in ex.inputs:
            readers[id(i)] = readers.get(id(i), 0) + 1
    for ex in stages:
        if type(ex) is not _AggExec:
            continue
        below = ex.inputs[0]
        while (type(below) is _ProjectExec and readers[id(below)] == 1
               and not below.program.n_eager):
            below = below.inputs[0]
        if type(below) is _FilterExec and readers[id(below)] == 1:
            below.deferrable = True


# ---------------------------------------------------------------------------
# the public compile surface
# ---------------------------------------------------------------------------


def _count_nodes(node: Node) -> int:
    seen = set()

    def visit(n):
        if id(n) in seen:
            return
        seen.add(id(n))
        for i in n.inputs():
            visit(i)

    visit(node)
    return len(seen)


class CompiledPlan:
    """A bound, optimized, lowered plan. Calling it runs the query over
    the bound tables and returns the result Table. Carries the
    plan-derived ``estimated_memory_bytes`` the memory governor and the
    serve scheduler consume, and a ``last_report`` with per-stage
    estimate-vs-actual bytes after each run."""

    def __init__(self, name: str, root: _Exec, tables: Dict[str, Table],
                 stages: List[_Exec], raw_nodes: int, opt_nodes: int,
                 rewrites_fired: Dict[str, int], opt_plan: Node,
                 obligations: Optional[list] = None,
                 node_execs: Optional[Dict[int, _Exec]] = None,
                 modeled: Optional[dict] = None, mesh=None):
        self.name = name
        self.schema = dict(root.schema)
        self.optimized = opt_plan
        # translation-validation records from the rewrite pass, carried
        # for srjt-plancheck (plan.verifier.verify_obligations)
        self.obligations = list(obligations or ())
        self._root = root
        self._tables = tables
        self._stages = stages
        self._raw_nodes = raw_nodes
        self._opt_nodes = opt_nodes
        self._rewrites = dict(rewrites_fired)
        # srjt-cache (ISSUE 17): id(optimized node) -> lowered stage,
        # so the cache layer can annotate cacheable subtrees with their
        # keys; and the cross-run subresult cache the run context
        # consults (None = caching off). Both are set once before the
        # plan is ever run concurrently.
        self._node_execs = dict(node_execs or {})
        self.subcache = None
        # srjt-cbo (ISSUE 19): {"author": cost, "chosen": cost,
        # "joins": n} when the search ran — the premerge modeled-cost
        # gate's source; None on the cache-hit / CBO-off paths
        self.modeled = dict(modeled) if modeled else None
        # the plan.distribute.MeshBinding this plan was compiled for, or None
        self.mesh = mesh
        self.estimated_memory_bytes = max(
            s.working_set_est() for s in stages
        )
        self.last_report: Optional[dict] = None
        _durable("plan.compiles").inc()

    def exec_for(self, node: Node) -> Optional[_Exec]:
        """The lowered stage an optimized-plan node became, when it
        lowered to a stage of its own (fused pipelines consume their
        inner nodes)."""
        return self._node_execs.get(id(node))

    @property
    def stages(self) -> list:
        """The lowered stage DAG (read-only view) — what
        ``plan.verifier.verify_estimates`` walks for the per-stage
        ``memory_bytes`` presence/monotonicity checks."""
        return list(self._stages)

    @property
    def rewrites_fired(self) -> Dict[str, int]:
        return dict(self._rewrites)

    def __call__(self) -> Table:
        from .. import memgov

        _durable("plan.executions").inc()
        admitted = 0
        adm = memgov.admit(f"plan.{self.name}", nbytes=self.estimated_memory_bytes)
        if adm is not None:
            admitted = self.estimated_memory_bytes
            _durable("plan.admit_bytes").inc(admitted)
            metrics.event("plan.admit", query=self.name, nbytes=admitted)
        try:
            ctx = _RunContext(self._tables, subcache=self.subcache)
            out = self._root.run(ctx)
        finally:
            if adm is not None:
                adm.release()
        # the report is built from THIS run's context and published as
        # one fresh dict — concurrent runs each see a coherent report
        # (last writer wins on the attribute)
        self.last_report = self._report(admitted, ctx.actuals)
        path = knobs.get_str("SRJT_PLAN_REPORT")
        if path:
            with open(path, "a") as f:
                f.write(json.dumps(self.last_report) + "\n")
        return out

    def _report(self, admitted: int, actuals: Dict[int, Tuple[int, int]]) -> dict:
        stages = []
        est_peak = self.estimated_memory_bytes
        actual_peak = 0
        for s in self._stages:
            ws = s.working_set_actual(actuals)
            if ws is not None:
                actual_peak = max(actual_peak, ws)
            mine = actuals.get(id(s))
            stages.append({
                "kind": s.kind,
                "est_rows": s.est_rows,
                "est_bytes": s.est_bytes,
                "actual_rows": None if mine is None else mine[0],
                "actual_bytes": None if mine is None else mine[1],
            })
        return {
            "query": self.name,
            "nodes_raw": self._raw_nodes,
            "nodes_optimized": self._opt_nodes,
            "rewrites": self._rewrites,
            "stages": stages,
            "fused_stages": sum(1 for s in self._stages
                                if s.kind == "fused_aggregate"),
            "est_peak_bytes": est_peak,
            "actual_peak_bytes": actual_peak,
            "peak_blowup": (actual_peak / est_peak) if est_peak else None,
            "memgov_admitted_bytes": admitted,
            "modeled_cost_author": (
                None if self.modeled is None else self.modeled["author"]),
            "modeled_cost_chosen": (
                None if self.modeled is None else self.modeled["chosen"]),
            "join_count": (
                None if self.modeled is None else self.modeled["joins"]),
        }


def compile_ir(plan: Node, tables: Dict[str, Table],
               name: str = "plan", mesh=None) -> CompiledPlan:
    """Validate, rewrite, and lower a logical plan against bound tables.
    The returned ``CompiledPlan`` is a zero-argument callable producing
    the result Table; submit it to ``serve`` directly (the scheduler
    derives ``memory_bytes=`` from its stage estimates).

    ``mesh`` is a ``plan.distribute.MeshBinding``: the tables are placed
    through it (its sharded tables row-sharded over the chips, the
    others whole on each), the stages over sharded rows run as
    ``shard_map`` programs, an Exchange stage as an all-to-all, and the
    compiled plan carries the binding (``cp.mesh``). Without it every
    Exchange stage is the identity, or the cross-process fabric's where
    ``exchange_context`` binds one."""
    placed = tables
    if mesh is not None:
        placed = mesh.place(tables)
        tables = {t: getattr(p, "table", p) for t, p in placed.items()}
    catalog = {t: {n: c.dtype for n, c in zip(tbl.names, tbl.columns)}
               for t, tbl in tables.items()}
    raw_nodes = _count_nodes(plan)
    infer_schema(plan, catalog)
    res = rewrite(plan, catalog)
    # srjt-cbo (ISSUE 19): the cost-based search runs AFTER the default
    # rewrite (so rule-idempotence of the default set is undisturbed);
    # every reorder / build-side / strategy fire lands in the same
    # obligation ledger the verifier discharges
    from . import optimizer as _cbo
    from . import stats as _stats

    opt_plan, fired, obligations = res.plan, dict(res.fired), list(res.obligations)
    modeled = None
    est = _stats.make_estimator(tables)
    if _cbo.enabled() and est is not None:
        cres = _cbo.optimize(opt_plan, catalog, tables, est=est)
        opt_plan = cres.plan
        for rule, n in cres.fired.items():
            fired[rule] = fired.get(rule, 0) + n
        obligations.extend(cres.obligations)
        modeled = {"author": cres.author_cost, "chosen": cres.chosen_cost,
                   "joins": cres.join_count}
    for rule, n in fired.items():
        _durable(f"plan.rewrites.{rule}").inc(n)
    low = _Lowerer(tables, catalog, est=est, mesh=mesh)
    root = low.local(opt_plan)
    _defer_filters(low.all_execs)
    cp = CompiledPlan(name, root, placed, low.all_execs, raw_nodes,
                      _count_nodes(opt_plan), fired, opt_plan,
                      obligations=obligations, node_execs=low._execs,
                      modeled=modeled, mesh=mesh)
    # srjt-ooc (ISSUE 18): a plan whose peak exceeds the armed device
    # budget degrades to streamed partitioned execution instead of
    # split-retrying to failure; a no-op unless SRJT_OOC_ENABLED
    from .ooc import maybe_out_of_core

    return maybe_out_of_core(cp, tables)


def lower_ir(opt_plan: Node, tables: Dict[str, Table], name: str = "plan", *,
             raw_nodes: Optional[int] = None,
             rewrites_fired: Optional[Dict[str, int]] = None,
             obligations: Optional[list] = None) -> CompiledPlan:
    """Lower an ALREADY-OPTIMIZED plan, skipping the rewrite pass — the
    plan-cache hit path (srjt-cache, ISSUE 17): the cached entry's
    optimized structure was verifier-green at insert, so binding fresh
    literals only needs schema inference + lowering. The caller passes
    through the cached entry's rewrite tallies and obligation ledger so
    the compiled artifact stays auditable (``verify_obligations`` still
    discharges the ORIGINAL firings — a literal rebind is
    structure-preserving by construction)."""
    catalog = {t: {n: c.dtype for n, c in zip(tbl.names, tbl.columns)}
               for t, tbl in tables.items()}
    infer_schema(opt_plan, catalog)
    opt_nodes = _count_nodes(opt_plan)
    # srjt-cbo (ISSUE 19): the cache-hit path skips the SEARCH (the
    # cached structure already won it) but keeps sketch-driven row
    # estimates — admission numbers must not depend on cache hit/miss
    from . import stats as _stats

    low = _Lowerer(tables, catalog, est=_stats.make_estimator(tables))
    root = low.lower(opt_plan)
    _defer_filters(low.all_execs)
    _durable("plan.lower_only").inc()
    cp = CompiledPlan(name, root, tables, low.all_execs,
                      raw_nodes if raw_nodes is not None else opt_nodes,
                      opt_nodes, dict(rewrites_fired or {}), opt_plan,
                      obligations=obligations, node_execs=low._execs)
    # srjt-ooc (ISSUE 18): the cache-hit path re-selects out-of-core
    # per binding — the cached entry stores the UN-partitioned plan
    # (partition count is a budget decision, not plan structure)
    from .ooc import maybe_out_of_core

    return maybe_out_of_core(cp, tables)
