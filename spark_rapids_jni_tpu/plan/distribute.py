"""Distributed plan assembly: Exchange insertion + run-time binding.

The two halves of ISSUE 16's "compiled plans gain exchange stages":

**insert_exchanges(plan, world)** is the *structural* half — a
deterministic tree rebuild that wraps every keyed Aggregate's input in
an ``Exchange`` on the grouping keys, so each rank aggregates only the
key space hashed to it. It is deliberately NOT a registered rewrite
rule: rewrite rules are semantics-preserving *per-process* transforms
with translation-validation obligations, while Exchange changes
where rows live, which is only meaning-preserving under the N-rank
execution contract this module owns. Joins stay local: the shard
binding replicates every non-sharded table on every rank (broadcast
join), so only the aggregate's key space needs movement — the same
shape Spark picks for a fact-table scan joined to small dims.

**exchange_context(...)** is the *runtime* half — a contextvar-scoped
binding from the logical Exchange stages to a concrete
``TcpExchange`` + peer map (+ optional ``ClusterView`` for fenced
recovery). Outside any binding — or at ``world == 1`` — an Exchange
stage is the identity, so the SAME compiled plan runs single-host
(plancheck, tests, the oracle side of the chaos gate) and distributed
without recompilation. Stage epochs are allocated in first-run order,
which the compiled plan makes deterministic and identical on every
rank; each stage gets its own epoch namespace
(``base_epoch + i * _STAGE_EPOCH_STRIDE``) so two exchange stages in
one plan can never collide in the publish store.

Recovery lineage: with a cluster AND ``shard_tables`` bound, each
Exchange stage installs ``lineage(r) = replay my child subtree over
rank r's catalog shard`` just before it moves rows — the Spark
lineage story, but the replay is the already-lowered exec subtree, so
a dead rank's exchange input is recomputed by exactly the code that
produced the original.
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Callable, Collection, Dict, Optional, Tuple

from ..columnar import Table
from .exprs import PlanError, is_col
from .nodes import Aggregate, Exchange, Exists, Join, Node, Project, Scan, UnionAll
from .rewrites import _with_inputs

__all__ = ["insert_exchanges", "exchange_context", "current_binding",
           "merge_partials",
           "ExchangeBinding", "MeshBinding"]

# one epoch namespace per exchange stage; a worker's own result
# publishes ride base_epoch + 1, which stage 0 (base_epoch) and stage
# 1 (base_epoch + 16) both clear
_STAGE_EPOCH_STRIDE = 16


_WHOLE = None  # a side that every rank holds whole (a replicated table, a gathered result)


def insert_exchanges(plan: Node, world: int,
                     sharded: Optional[Collection[str]] = None) -> Node:
    """Rebuild ``plan`` with an ``Exchange`` wherever a keyed stage needs
    its rows co-located and they are not yet. ``sharded`` names the
    tables whose rows are split over the ranks; every other table is
    whole on every rank. The walk carries, for each node, the columns
    its output is partitioned on:

    - a keyed Aggregate needs its input partitioned on some of its keys,
      else it gets ``Exchange(keys)``; a global one is left alone (its
      distribution is the coordinator's merge, or the mesh's gather);
    - a Join whose right side is whole is a broadcast join and gets no
      exchange; otherwise both sides have to be partitioned on the join
      keys, pair for pair, and each side that is not gets an exchange.
      A side already partitioned that way keeps its partitioning: q95's
      per-order group-by, its two semi-joins and its last per-order
      aggregate shuffle each lineage once (Spark's ``EnsureRequirements``);
    - Filter, Sort, Limit, Window and a Project that passes the columns
      through under their names keep the partitioning of their input.

    Without ``sharded`` (the cross-process binding, whose shard catalog
    replicates every table but the fact) the right side of every Join
    counts as whole. Shared subtrees stay shared (memo by identity)."""
    if world < 1:
        raise PlanError(f"insert_exchanges: world must be >= 1, got {world}")
    split = None if sharded is None else frozenset(sharded)
    memo: Dict[int, Tuple[Node, Optional[Tuple[str, ...]]]] = {}

    def exchanged(n: Node, keys) -> Node:
        return Exchange(n, tuple(keys), world)

    def walk(n: Node) -> Tuple[Node, Optional[Tuple[str, ...]]]:
        if id(n) in memo:
            return memo[id(n)]
        kids = [walk(i) for i in n.inputs()]
        nodes = tuple(k for k, _ in kids)
        part = kids[0][1] if kids else ()
        if isinstance(n, Scan):
            out, part = n, (() if split is None or n.table in split else _WHOLE)
        elif isinstance(n, Exchange):
            out, part = _with_inputs(n, nodes), (_WHOLE if part is _WHOLE else tuple(n.keys))
        elif isinstance(n, Aggregate) and n.keys:
            child = nodes[0]
            if part is not _WHOLE and not (part and set(part) <= set(n.keys)):
                child, part = exchanged(child, n.keys), tuple(n.keys)
            if n.grouping_sets is not None and part is not _WHOLE:
                part = ()  # rolled-up keys come out NULL: the groups of one set only are whole
            out = Aggregate(child, keys=n.keys, aggs=n.aggs, grouping_sets=n.grouping_sets)
        elif isinstance(n, Aggregate):
            out, part = _with_inputs(n, nodes), (() if split is None else _WHOLE)
        elif isinstance(n, (Join, Exists)):  # an Exists desugars to a semi or anti Join on the same pairs
            (left, lpart), (right, rpart) = kids
            if split is None:
                rpart = _WHOLE
            if rpart is not _WHOLE and lpart is not _WHOLE:
                l2r = dict(n.on)
                if lpart and all(k in l2r for k in lpart):
                    want = lpart  # the left stays where it is; the right comes to it
                elif rpart and all(k in l2r.values() for k in rpart):
                    r2l = {r: l for l, r in n.on}
                    want = tuple(r2l[k] for k in rpart)
                else:
                    want = tuple(l for l, _ in n.on)
                if lpart != want:
                    left = exchanged(left, want)
                if rpart != tuple(l2r[k] for k in want):
                    right = exchanged(right, tuple(l2r[k] for k in want))
                part = () if getattr(n, "how", "semi") == "full" else want
            else:
                part = _WHOLE if lpart is _WHOLE else lpart  # a whole left side: the mesh gathers the right
            out = _with_inputs(n, (left, right))
        elif isinstance(n, Project):
            out = _with_inputs(n, nodes)
            if part:
                kept = {name for name, e in n.exprs if is_col(e) == name}
                part = part if set(part) <= kept else ()
        elif isinstance(n, UnionAll):
            out = _with_inputs(n, nodes)
            part = _WHOLE if all(p is _WHOLE for _, p in kids) else ()
        else:
            out = _with_inputs(n, nodes)
        memo[id(n)] = (out, part)
        return memo[id(n)]

    return walk(plan)[0]


def merge_partials(partials, sort_keys) -> Table:
    """Coordinator-side merge of per-rank results: concatenate and
    re-apply the plan's Sort keys (``((column, ascending), ...)``).
    Bit-identical to the single-host run whenever (a) the exchange
    made every rank's groups complete — true by construction — and (b) the
    sort keys form a total order (the distributed TPC-DS plans end in
    one: the group key breaks ties)."""
    from ..ops.copying import concatenate
    from ..ops.sort import sort_by_key

    merged = concatenate(list(partials))
    if not sort_keys:
        return merged
    keys = Table([merged.column(c) for c, _ in sort_keys],
                 [f"k{i}" for i in range(len(sort_keys))])
    return sort_by_key(merged, keys,
                       ascending=[asc for _, asc in sort_keys])


class ExchangeBinding:
    """The concrete fabric a plan's Exchange stages run against:
    ``exchange`` (a TcpExchange), ``peers`` (rank -> host:port, this
    rank excluded), optional ``cluster`` (ClusterView: fencing +
    failover) and ``shard_tables`` (rank -> catalog shard, the lineage
    reproducer)."""

    def __init__(self, exchange, peers: Dict[int, str], *,
                 cluster=None,
                 shard_tables: Optional[Callable[[int], Dict[str, Table]]] = None,
                 base_epoch: int = 0) -> None:
        self.exchange = exchange
        self.peers = dict(peers)
        self.cluster = cluster
        self.shard_tables = shard_tables
        self.base_epoch = int(base_epoch)
        self._stage_epochs: Dict[int, int] = {}

    @property
    def world(self) -> int:
        return len(self.peers) + 1

    def stage_epoch(self, stage_id: int) -> int:
        """Deterministic per-stage epoch: allocated in first-run
        order, which the compiled plan's data dependencies make
        identical on every rank."""
        if stage_id not in self._stage_epochs:
            self._stage_epochs[stage_id] = (
                self.base_epoch + len(self._stage_epochs) * _STAGE_EPOCH_STRIDE
            )
        return self._stage_epochs[stage_id]


class MeshBinding:
    """The other fabric: the chips of one ``jax.sharding.Mesh``, the
    exchange an ICI all-to-all inside one program
    (``parallel/table_ops.py``'s sharded layer). Given to
    ``compile_ir(plan, tables, mesh=...)``, which places the tables
    through it and lowers the stages over ``sharded`` tables to
    ``shard_map`` programs; the compiled plan carries it, so a request is
    ``serve.Scheduler.submit(cp).result()`` as on one chip. ``sharded``
    names the tables whose rows are split over ``axis`` in file order
    (what ``insert_exchanges(plan, world, sharded=...)`` was told); the
    others are copied whole to every chip."""

    def __init__(self, mesh, sharded: Collection[str], axis: str = "data") -> None:
        self.mesh, self.axis, self.sharded = mesh, axis, frozenset(sharded)

    @property
    def world(self) -> int:
        return int(self.mesh.shape[self.axis])

    def place(self, tables: Dict[str, Table]) -> dict:
        """Each table where this binding wants it; one already placed
        passes through."""
        from ..parallel.table_ops import ShardedTable, replicate_table, shard_table

        out = {}
        for name, t in tables.items():
            if isinstance(t, ShardedTable):
                out[name] = t
            elif name in self.sharded:
                out[name] = shard_table(t, self.mesh, self.axis)
            else:
                out[name] = replicate_table(t, self.mesh)
        return out


_BINDING: contextvars.ContextVar[Optional[ExchangeBinding]] = \
    contextvars.ContextVar("srjt_exchange_binding", default=None)


def current_binding() -> Optional[ExchangeBinding]:
    return _BINDING.get()


@contextlib.contextmanager
def exchange_context(exchange, peers: Dict[int, str], *,
                     cluster=None,
                     shard_tables: Optional[Callable[[int], Dict[str, Table]]] = None,
                     base_epoch: int = 0):
    """Bind the plan compiler's Exchange stages to a live fabric for
    the dynamic extent of the block (contextvar-scoped: thread- and
    task-local, exactly like the deadline scopes)."""
    token = _BINDING.set(ExchangeBinding(
        exchange, peers, cluster=cluster, shard_tables=shard_tables,
        base_epoch=base_epoch,
    ))
    try:
        yield
    finally:
        _BINDING.reset(token)
