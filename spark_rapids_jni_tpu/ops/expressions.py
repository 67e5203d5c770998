"""Expression evaluation over Tables (cudf AST / Spark expression tier).

A small composable AST — column refs, literals, arithmetic, comparisons,
boolean logic, null predicates — evaluated column-at-a-time with Spark
SQL null semantics (null propagates through operators; AND/OR are
three-valued-logic). The TPU shape: every node is a pure jnp map over
[N] arrays, so an entire predicate/projection tree fuses into one XLA
kernel at jit time.

Where the jit boundary is: ``evaluate`` itself is plain (every ``jnp``
call of a tree is a launch of its own when it is called eagerly: a
FLOAT64 operand on a backend without float64 is ~300 of them, a FLOAT64
result ~225), so that callers trace whole trees into THEIR program. Two
do: the fused pipeline (``pipeline.py``: filter and projections inside the
one ``CompiledPipeline`` program) and, since ISSUE 33, every Filter and
Project stage of a compiled plan, local or over a mesh
(``plan/compiler.py::_StageProgram``: all of a stage's trees in one
program, built when the stage is lowered). What stays eager there, by what
the tree reads: a tree that names a column which is not fixed width. The
nodes that read STRING lanes are not in this module (``plan/exprs.py``'s
``_RegexEval`` and ``_PartHashEval`` over a STRING key): they read offsets
and chars and wait on the host for the longest string, which no trace
can. A direct ``Expression.evaluate(table)`` outside those two is eager,
as before.

Example::

    e = (col("qty") * col("price")).alias("revenue")
    pred = (col("qty") > lit(5)) & ~col("returned").is_null()
    revenue = e.evaluate(table)
"""

from __future__ import annotations

import operator
from typing import Optional

import jax.numpy as jnp

from ..columnar import Column, Table
from ..columnar import dtype as dt
from ..columnar.dtype import DType, TypeId
from . import bitutils

__all__ = ["col", "lit", "when", "Expression"]


def _is_dd(x) -> bool:
    from .f64acc import DD

    return isinstance(x, DD)


class _Value:
    """Evaluated expression: floating data is carried as arithmetic values
    (float_view) and re-bit-packed only at column materialization."""

    __slots__ = ("data", "valid", "dtype")

    def __init__(self, data, valid, dtype: Optional[DType]):
        self.data = data
        self.valid = valid  # None == all valid
        self.dtype = dtype


def _to_value(col_: Column) -> _Value:
    d = col_.dtype
    if d.id == TypeId.FLOAT64:
        if bitutils.backend_has_f64():
            return _Value(bitutils.float_view(col_.data, d), col_.validity, d)
        # no f64 datapath (TPU): carry a double-f32 pair — ~2^-48
        # relative per op vs the 2^-24 of the plain-f32 view it replaces
        # (exactness contract in ops/f64acc; VERDICT r3 item 5)
        from .f64acc import dd_from_f64bits

        return _Value(dd_from_f64bits(col_.data), col_.validity, d)
    if d.id == TypeId.BOOL8:
        return _Value(col_.data.astype(bool), col_.validity, d)
    return _Value(col_.data, col_.validity, d)


def _is_host_scalar(x) -> bool:
    return isinstance(x, (int, float)) and not isinstance(x, bool)


def _tie(lit, peer):
    """A literal operand of an arithmetic node as lanes of its peer:
    ``select(peer == peer, lit, NaN)`` (a NaN lane of the peer makes the
    result NaN either way). Traced into a program, a bare literal is a
    compile-time constant, and XLA's algebraic simplifier rewrites
    arithmetic on constants in ways that round otherwise than the same
    tree launched one call at a time: ``(c + b) - c`` to ``b + (c - c)``
    inside 2Sum (the dd pair's error term is then 0: q1's
    ``1 - l_discount`` read 0.99000001 for 0.99 under jit), ``(x + c1) - c2``
    to ``x + (c1 - c2)``, ``x / c`` to ``x * (1 / c)``. Tied to its peer
    the literal is no constant, and the program computes what the eager
    evaluator does, lane for lane. Integer arithmetic is exact under
    those rewrites and is left alone (a weak Python int keeps the
    column's dtype)."""
    if _is_dd(peer):
        from .f64acc import DD, dd_from_any

        c, ok, nan = dd_from_any(lit), peer.hi == peer.hi, jnp.float32(jnp.nan)
        return DD(jnp.where(ok, c.hi, nan), jnp.where(ok, c.lo, nan))
    peer = jnp.asarray(peer)
    if jnp.issubdtype(peer.dtype, jnp.floating):
        return jnp.where(peer == peer, jnp.asarray(lit, peer.dtype), jnp.asarray(jnp.nan, peer.dtype))
    return lit


def _both_valid(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


class Expression:
    def evaluate(self, table: Table) -> Column:
        v = self._eval(table)
        data = v.data
        if isinstance(data, (int, float)):  # bare literal
            data = jnp.asarray(data)
        if _is_dd(data):
            from .f64acc import dd_to_f64bits

            return Column(dt.FLOAT64, data=dd_to_f64bits(data), validity=v.valid)
        if isinstance(data, jnp.ndarray) and data.dtype == bool:
            return Column(dt.BOOL8, data=data.astype(jnp.uint8), validity=v.valid)
        if data.dtype in (jnp.float64, jnp.float32) and (
            v.dtype is None or v.dtype.id == TypeId.FLOAT64
        ):
            return Column(dt.FLOAT64, data=bitutils.float_store(data.astype(jnp.float64) if bitutils.backend_has_f64() else data, dt.FLOAT64), validity=v.valid)
        out_d = v.dtype if v.dtype is not None else _infer(data.dtype)
        return Column(out_d, data=data, validity=v.valid)

    def _eval(self, table: Table) -> _Value:
        raise NotImplementedError

    # -- operator sugar -----------------------------------------------------
    def _bin(self, other, fn, bool_out=False):
        return _BinOp(self, _wrap(other), fn, bool_out)

    def __add__(self, o):
        return self._bin(o, operator.add)

    def __sub__(self, o):
        return self._bin(o, operator.sub)

    def __mul__(self, o):
        return self._bin(o, operator.mul)

    def __truediv__(self, o):
        return _Div(self, _wrap(o))

    def __mod__(self, o):
        return self._bin(o, operator.mod)

    def __eq__(self, o):  # noqa: A003
        return self._bin(o, operator.eq, bool_out=True)

    def __ne__(self, o):
        return self._bin(o, operator.ne, bool_out=True)

    def __lt__(self, o):
        return self._bin(o, operator.lt, bool_out=True)

    def __le__(self, o):
        return self._bin(o, operator.le, bool_out=True)

    def __gt__(self, o):
        return self._bin(o, operator.gt, bool_out=True)

    def __ge__(self, o):
        return self._bin(o, operator.ge, bool_out=True)

    def __and__(self, o):
        return _And(self, _wrap(o))

    def __or__(self, o):
        return _Or(self, _wrap(o))

    def __invert__(self):
        return _Not(self)

    def is_null(self):
        return _IsNull(self, True)

    def is_not_null(self):
        return _IsNull(self, False)

    def cast(self, d: DType):
        return _Cast(self, d)

    __hash__ = None


class _ColumnRef(Expression):
    def __init__(self, name: str):
        self.name = name

    def _eval(self, table: Table) -> _Value:
        return _to_value(table.column(self.name))


class _Literal(Expression):
    def __init__(self, value):
        self.value = value

    def _eval(self, table: Table) -> _Value:
        if self.value is None:
            n = table.num_rows
            return _Value(jnp.zeros((n,), jnp.int32), jnp.zeros((n,), bool), None)
        if isinstance(self.value, (int, float)) and not isinstance(self.value, bool):
            # keep the HOST scalar: if the peer operand is a dd pair the
            # promotion splits the full f64 literal exactly (an early
            # jnp.asarray would round it to one f32 on the TPU tier)
            return _Value(self.value, None, None)
        return _Value(jnp.asarray(self.value), None, None)


class _BinOp(Expression):
    def __init__(self, a, b, fn, bool_out):
        self.a, self.b, self.fn, self.bool_out = a, b, fn, bool_out

    def _eval(self, table):
        va, vb = self.a._eval(table), self.b._eval(table)
        da, db = va.data, vb.data
        if _is_dd(da) or _is_dd(db):
            # promote BOTH sides before the operator: a jnp array's own
            # dunder would coerce the DD NamedTuple to a [2, N] array
            from .f64acc import dd_from_any

            da, db = dd_from_any(da), dd_from_any(db)
        if not self.bool_out:  # arithmetic: a literal operand is no compile-time constant
            if _is_host_scalar(va.data) and not _is_host_scalar(vb.data):
                da = _tie(da, db)
            elif _is_host_scalar(vb.data) and not _is_host_scalar(va.data):
                db = _tie(db, da)
        data = self.fn(da, db)
        d = None if self.bool_out else (va.dtype if va.dtype is not None else vb.dtype)
        if d is not None and not d.is_fixed_width:
            d = None
        # arithmetic output dtype follows jnp promotion unless it matches input
        if d is not None and not self.bool_out and not _is_dd(data):
            if data.dtype != d.jnp_dtype and not d.is_floating:
                d = None
        return _Value(data, _both_valid(va.valid, vb.valid), d)


class _Div(Expression):
    """SQL divide: always floating point, null on divide-by-zero."""

    def __init__(self, a, b):
        self.a, self.b = a, b

    def _eval(self, table):
        va, vb = self.a._eval(table), self.b._eval(table)
        lit_a, lit_b = _is_host_scalar(va.data), _is_host_scalar(vb.data)
        if bitutils.backend_has_f64():
            num = va.data if lit_a else jnp.asarray(va.data).astype(jnp.float64)
            denom = jnp.asarray(vb.data).astype(jnp.float64)
            if lit_b and not lit_a:
                denom = _tie(vb.data, num)
            zero = denom == 0
            data = num / jnp.where(zero, 1, denom)
        else:
            # dd division on the f64-emulating tier (~2^-48 relative)
            from .f64acc import DD, dd_from_any

            num = dd_from_any(va.data)
            den = dd_from_any(vb.data)
            if lit_b and not lit_a:
                den = _tie(den, num)
            elif lit_a and not lit_b:
                num = _tie(num, den)
            zero = (den.hi == 0) & (den.lo == 0)
            safe = DD(jnp.where(zero, jnp.float32(1), den.hi), jnp.where(zero, jnp.float32(0), den.lo))
            data = num / safe
        valid = _both_valid(va.valid, vb.valid)
        valid = _both_valid(valid, ~zero)
        return _Value(data, valid, dt.FLOAT64)


class _And(Expression):
    def __init__(self, a, b):
        self.a, self.b = a, b

    def _eval(self, table):
        va, vb = self.a._eval(table), self.b._eval(table)
        a = jnp.asarray(va.data).astype(bool)
        b = jnp.asarray(vb.data).astype(bool)
        av = jnp.ones_like(a) if va.valid is None else va.valid
        bv = jnp.ones_like(b) if vb.valid is None else vb.valid
        data = a & b
        # 3VL: false dominates null
        valid = (av & bv) | (av & ~a) | (bv & ~b)
        return _Value(data, valid, None)


class _Or(Expression):
    def __init__(self, a, b):
        self.a, self.b = a, b

    def _eval(self, table):
        va, vb = self.a._eval(table), self.b._eval(table)
        a = jnp.asarray(va.data).astype(bool)
        b = jnp.asarray(vb.data).astype(bool)
        av = jnp.ones_like(a) if va.valid is None else va.valid
        bv = jnp.ones_like(b) if vb.valid is None else vb.valid
        data = a | b
        valid = (av & bv) | (av & a) | (bv & b)  # true dominates null
        return _Value(data, valid, None)


class _Not(Expression):
    def __init__(self, a):
        self.a = a

    def _eval(self, table):
        v = self.a._eval(table)
        return _Value(~jnp.asarray(v.data).astype(bool), v.valid, None)


class _IsNull(Expression):
    def __init__(self, a, want_null):
        self.a, self.want_null = a, want_null

    def _eval(self, table):
        v = self.a._eval(table)
        if v.valid is None:
            shape = jnp.shape(jnp.asarray(v.data))[:1] if not _is_dd(v.data) else v.data.shape[:1]
            res = jnp.zeros(shape, bool) if self.want_null else jnp.ones(shape, bool)
        else:
            res = ~v.valid if self.want_null else v.valid
        return _Value(res, None, None)


class _When(Expression):
    """SQL CASE WHEN cond THEN a ELSE b END. 3VL: a NULL condition
    selects the ELSE branch (SQL's CASE treats unknown as not-matched);
    result validity follows the CHOSEN branch per row.

    EAGER EVALUATION (ADVICE r5 low #4): both THEN and ELSE evaluate
    for every row before the select — the columnar/XLA formulation has
    no per-row lazy branch. Consequence: an error-capable op in the
    UNTAKEN branch still raises (an ANSI cast raising CastError on a
    row the condition would have guarded fails the whole expression),
    deviating from SQL CASE's guarded-evaluation guarantee. Callers
    relying on CASE-as-guard must mask/neutralize the branch input
    BEFORE the error-capable op (e.g. substitute a safe value where
    the condition selects the other branch), as Spark's own
    conditional-expression rewrite does."""

    def __init__(self, cond, then, other):
        self.cond, self.then, self.other = cond, then, other

    def _eval(self, table):
        vc = self.cond._eval(table)
        c = jnp.asarray(vc.data).astype(bool)
        if vc.valid is not None:
            c = c & vc.valid
        vt, vo = self.then._eval(table), self.other._eval(table)
        dtd, dod = vt.data, vo.data
        if _is_dd(dtd) or _is_dd(dod):
            from .f64acc import DD, dd_from_any

            t_, o_ = dd_from_any(dtd), dd_from_any(dod)
            data = DD(jnp.where(c, t_.hi, o_.hi), jnp.where(c, t_.lo, o_.lo))
        else:
            data = jnp.where(c, dtd, dod)
        if vt.valid is None and vo.valid is None:
            valid = None
        else:
            tvb = jnp.ones_like(c) if vt.valid is None else vt.valid
            ovb = jnp.ones_like(c) if vo.valid is None else vo.valid
            valid = jnp.where(c, tvb, ovb)
        d = vt.dtype if vt.dtype is not None else vo.dtype
        return _Value(data, valid, d)


class _Cast(Expression):
    def __init__(self, a, d: DType):
        self.a, self.d = a, d

    def _eval(self, table):
        v = self.a._eval(table)
        data = v.data
        if isinstance(data, (int, float)):
            data = jnp.asarray(data)
        if self.d.id == TypeId.FLOAT64 and not bitutils.backend_has_f64():
            from .f64acc import dd_from_any

            return _Value(dd_from_any(data), v.valid, self.d)
        if self.d.is_floating:
            target = jnp.float64 if bitutils.backend_has_f64() else jnp.float32
            return _Value(data.astype(target), v.valid, self.d)
        return _Value(data.astype(self.d.jnp_dtype), v.valid, self.d)


def _infer(np_dtype) -> DType:
    m = {
        "int8": dt.INT8, "int16": dt.INT16, "int32": dt.INT32, "int64": dt.INT64,
        "uint8": dt.UINT8, "uint16": dt.UINT16, "uint32": dt.UINT32, "uint64": dt.UINT64,
        "float32": dt.FLOAT32, "float64": dt.FLOAT64, "bool": dt.BOOL8,
    }
    return m[str(np_dtype)]


def _wrap(v) -> Expression:
    return v if isinstance(v, Expression) else _Literal(v)


def col(name: str) -> Expression:
    return _ColumnRef(name)


def lit(value) -> Expression:
    return _Literal(value)


def when(cond, then, otherwise) -> Expression:
    """SQL ``CASE WHEN cond THEN then ELSE otherwise END``.

    The workhorse conditional ~40 of the TPC-DS q1-q99 use (pivots,
    guarded ratios, bucketed counts — see QUERIES.md); Spark lowers it
    to cudf copy_if_else in the reference engine tier (SURVEY §2.8).
    ``then``/``otherwise`` may be expressions or literals; chained CASE
    arms nest: ``when(c1, a, when(c2, b, c))``."""
    return _When(_wrap(cond), _wrap(then), _wrap(otherwise))
