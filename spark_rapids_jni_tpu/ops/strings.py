"""String operator tier (cudf strings replacement, SURVEY §2.8).

The RAPIDS plugin offloads Spark string expressions to cudf's strings
kernels; this module rebuilds the surface TPU-first. Ragged Arrow
(offsets + chars) data is densified to a padded [N, L] byte matrix
(L = max length in the batch — one static shape per size class, the
XLA-friendly formulation of cudf's warp-per-string loops), operated on
vectorized, and re-compacted to ragged storage.

Ops: length, upper/lower (ASCII), substring (start/len, negative start
from the end like Spark SUBSTR), concat (columns + scalar separator),
contains / startswith / endswith (literal pattern), strip.
Null propagation follows Spark: null in -> null out.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..columnar import Column, Table
from ..columnar import dtype as dt
from ..columnar.dtype import TypeId
from ..utils.dispatch import op_boundary

__all__ = [
    "length",
    "upper",
    "lower",
    "substring",
    "concat",
    "concat_ws",
    "contains",
    "instr",
    "startswith",
    "endswith",
    "strip",
]


def _check_string(col: Column) -> None:
    if col.dtype.id != TypeId.STRING:
        raise ValueError("string op on non-string column")


def to_padded(col: Column) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Ragged -> ([N, L] uint8 right-padded with 0, [N] int32 lengths).
    Width comes from the memoized ``Column.max_char_len`` (a per-call
    host sync here used to dominate whole kernels)."""
    _check_string(col)
    offs = col.offsets
    lens = offs[1:] - offs[:-1]
    n = len(col)
    if n == 0:
        return jnp.zeros((0, 1), jnp.uint8), jnp.zeros((0,), jnp.int32)
    max_len = max(col.max_char_len, 1)
    nchars = int(col.chars.shape[0])
    if nchars == 0:  # every row empty (or null): nothing to gather
        return jnp.zeros((n, max_len), jnp.uint8), lens.astype(jnp.int32)
    idx = offs[:-1, None] + jnp.arange(max_len, dtype=jnp.int32)[None, :]
    inb = jnp.arange(max_len, dtype=jnp.int32)[None, :] < lens[:, None]
    padded = jnp.where(inb, col.chars[jnp.clip(idx, 0, nchars - 1)], 0)
    return padded, lens.astype(jnp.int32)


def from_padded(padded: jnp.ndarray, lens: jnp.ndarray, validity=None) -> Column:
    """[N, L] bytes + [N] lengths -> ragged STRING column (compaction).

    Rides ragged_compact (word-granular funnel gathers, ~2 ns/B): the
    padded matrix flattens to a pool whose per-row base r*L is monotone
    — exactly the compaction contract. The old padded[row_of, pos] form
    was one element gather per CHARACTER (~8 ns/B, the slow class)."""
    from .ragged_bytes import ragged_compact

    lens = lens.astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(lens, dtype=jnp.int32)])
    total = int(offs[-1])  # host sync: chars allocation size
    if total == 0:
        chars = jnp.zeros((0,), jnp.uint8)
    else:
        n, width = padded.shape
        base = jnp.arange(n, dtype=jnp.int64) * width
        chars = ragged_compact(padded.reshape(-1), base, offs.astype(jnp.int64), total)
    return Column(dt.STRING, validity=validity, offsets=offs, chars=chars)


@op_boundary("strings.length")
def length(col: Column) -> Column:
    """Byte length per row (Spark length() on binary semantics)."""
    _check_string(col)
    lens = (col.offsets[1:] - col.offsets[:-1]).astype(jnp.int32)
    return Column(dt.INT32, data=lens, validity=col.validity)


def _case_map_ascii(col: Column, offset: int, lo: int, hi: int) -> Column:
    padded, lens = to_padded(col)
    in_range = (padded >= lo) & (padded <= hi)
    out = jnp.where(in_range, padded + jnp.uint8(offset), padded)
    return from_padded(out, lens, col.validity)


def _case_map_unicode(col: Column, to_upper: bool) -> Column:
    """UTF-8-aware 1:1 case map over codepoints (BMP table; multi-char
    special casings identity-mapped — same core restriction as cudf's
    to_upper/to_lower). Re-encodes because cased pairs can change UTF-8
    length (e.g. U+023A <-> U+2C65 is 2 vs 3 bytes)."""
    from .utf8 import case_table, decode_padded, encode_padded

    padded, lens = to_padded(col)
    cp, cp_lens, _ = decode_padded(padded, lens)
    tab = case_table(to_upper)
    mapped = jnp.where(cp < 0x10000, tab[jnp.clip(cp, 0, 0xFFFF)], cp)
    out, out_lens = encode_padded(mapped, cp_lens)
    return from_padded(out, out_lens, col.validity)


def _is_ascii(col: Column) -> bool:
    if col.chars.shape[0] == 0:
        return True
    return bool(jnp.all(col.chars < 0x80))


@op_boundary("strings.upper")
def upper(col: Column) -> Column:
    """Spark upper(): Unicode 1:1 case map; pure-ASCII batches take the
    branchless byte path (one data-dependent host check, same class of
    sync as the padded-width allocation)."""
    _check_string(col)
    if _is_ascii(col):
        return _case_map_ascii(col, -32 & 0xFF, ord("a"), ord("z"))
    return _case_map_unicode(col, to_upper=True)


@op_boundary("strings.lower")
def lower(col: Column) -> Column:
    _check_string(col)
    if _is_ascii(col):
        return _case_map_ascii(col, 32, ord("A"), ord("Z"))
    return _case_map_unicode(col, to_upper=False)


@op_boundary("strings.substring")
def substring(col: Column, start: int, slen: Optional[int] = None) -> Column:
    """Spark SUBSTRING semantics: 1-based start; 0 treated as 1; negative
    start counts from the end; slen None -> to end of string."""
    _check_string(col)
    padded, lens = to_padded(col)
    n, L = padded.shape
    # Spark UTF8String.substringSQL: the window [begin, begin+len) is
    # computed BEFORE clamping, so a negative start consumes its length
    # budget off-string (substring('hello', -6, 3) == 'he', -10 -> '')
    if start > 0:
        begin_raw = jnp.full((n,), start - 1, jnp.int32)
    elif start == 0:
        begin_raw = jnp.zeros((n,), jnp.int32)
    else:
        begin_raw = lens + start
    end_raw = lens if slen is None else begin_raw + max(slen, 0)
    begin = jnp.clip(begin_raw, 0, lens)
    end = jnp.clip(end_raw, 0, lens)
    out_lens = jnp.maximum(end - begin, 0)
    j = jnp.arange(L, dtype=jnp.int32)[None, :]
    src = begin[:, None] + j
    out = jnp.where(j < out_lens[:, None], jnp.take_along_axis(padded, jnp.clip(src, 0, L - 1), axis=1), 0)
    return from_padded(out, out_lens, col.validity)


@op_boundary("strings.concat")
def concat(
    cols: Sequence[Column], separator: bytes = b"", null_policy: str = "propagate"
) -> Column:
    """Row-wise concatenation with a scalar separator.

    ``null_policy`` selects between Spark's two distinct operators
    (they differ ONLY in null handling, so both ride one kernel):

    - ``"propagate"`` — Spark ``concat`` semantics: a null row in any
      input nulls the whole output row.
    - ``"skip"`` — Spark ``concat_ws`` semantics: null inputs are
      skipped entirely (contributing neither text nor a separator
      slot); the result is never null for a non-null separator.
    """
    if null_policy not in ("propagate", "skip"):
        raise ValueError(f"unknown null_policy {null_policy!r}")
    cols = list(cols)
    if not cols:
        raise ValueError("concat needs at least one column")
    for c in cols:
        _check_string(c)
    sep = np.frombuffer(separator, np.uint8)
    n = len(cols[0])

    parts = [to_padded(c) for c in cols]
    if null_policy == "skip":
        kept = [
            jnp.ones((n,), bool) if c.validity is None else c.validity for c in cols
        ]
    else:
        # every input contributes text; nullness is applied to the
        # output validity mask instead
        kept = [jnp.ones((n,), bool)] * len(cols)

    # per-row output length: kept parts + a separator before each kept
    # part that follows at least one earlier kept part
    out_lens = jnp.zeros((n,), jnp.int32)
    emitted = jnp.zeros((n,), bool)
    sep_present: list = []
    for k, (_, lens) in enumerate(parts):
        present = (emitted & kept[k]) if (k > 0 and len(sep)) else jnp.zeros((n,), bool)
        sep_present.append(present)
        out_lens = out_lens + present * len(sep) + jnp.where(kept[k], lens, 0)
        emitted = emitted | kept[k]
    L = max(int(jnp.max(out_lens)) if n else 1, 1)

    out = jnp.zeros((n, L), jnp.uint8)
    cursor = jnp.zeros((n,), jnp.int32)
    for k, (padded, lens) in enumerate(parts):
        if k > 0 and len(sep):
            sep_lens = jnp.where(sep_present[k], len(sep), 0).astype(jnp.int32)
            sep_j = jnp.arange(len(sep), dtype=jnp.int32)[None, :]
            dest = cursor[:, None] + sep_j
            out = _scatter_rows(out, dest, jnp.broadcast_to(jnp.asarray(sep)[None, :], (n, len(sep))), sep_lens, sep_j)
            cursor = cursor + sep_lens
        eff_lens = jnp.where(kept[k], lens, 0).astype(jnp.int32)
        src_j = jnp.arange(padded.shape[1], dtype=jnp.int32)[None, :]
        dest = cursor[:, None] + src_j
        out = _scatter_rows(out, dest, padded, eff_lens, src_j)
        cursor = cursor + eff_lens

    validity = None
    if null_policy == "propagate":
        masks = [c.validity for c in cols if c.validity is not None]
        if masks:
            v = masks[0]
            for m in masks[1:]:
                v = v & m
            validity = v
    return from_padded(out, out_lens, validity)


@op_boundary("strings.concat_ws")
def concat_ws(cols: Sequence[Column], separator: bytes) -> Column:
    """Spark ``concat_ws``: null inputs skipped, never-null output."""
    return concat(cols, separator, null_policy="skip")


def _scatter_rows(out, dest, vals, lens, src_j):
    """Scatter vals[:, :lens] into out rows at dest positions (bounded)."""
    L = out.shape[1]
    keep = src_j < lens[:, None]
    dest_c = jnp.clip(dest, 0, L - 1)
    contrib = jnp.zeros_like(out).at[
        jnp.arange(out.shape[0], dtype=jnp.int32)[:, None], dest_c
    ].add(jnp.where(keep, vals, 0))
    return out | contrib  # disjoint regions: OR == placement


def _match_at(padded, lens, pattern: bytes, pos):
    """[N, P?] bool: pattern matches at byte position(s) pos."""
    pat = np.frombuffer(pattern, np.uint8)
    m = len(pat)
    n, L = padded.shape
    if m == 0:
        return jnp.ones_like(pos, bool)
    ok = jnp.ones(pos.shape, bool)
    for t in range(m):
        src = jnp.clip(pos + t, 0, L - 1)
        ok = ok & (jnp.take_along_axis(padded, src, axis=1) == pat[t])
    ok = ok & (pos + m <= lens[:, None])
    return ok


def _bool_col(data, validity) -> Column:
    return Column(dt.BOOL8, data=data.astype(jnp.uint8), validity=validity)


@op_boundary("strings.contains")
def contains(col: Column, pattern: bytes) -> Column:
    """Literal substring search (Spark Contains)."""
    _check_string(col)
    padded, lens = to_padded(col)
    n, L = padded.shape
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    pos = jnp.broadcast_to(pos, (n, L))
    hit = jnp.any(_match_at(padded, lens, pattern, pos), axis=1)
    return _bool_col(hit, col.validity)


@op_boundary("strings.startswith")
def startswith(col: Column, pattern: bytes) -> Column:
    _check_string(col)
    padded, lens = to_padded(col)
    pos = jnp.zeros((padded.shape[0], 1), jnp.int32)
    return _bool_col(_match_at(padded, lens, pattern, pos)[:, 0], col.validity)


@op_boundary("strings.endswith")
def endswith(col: Column, pattern: bytes) -> Column:
    _check_string(col)
    padded, lens = to_padded(col)
    pos = jnp.maximum(lens - len(pattern), 0)[:, None]
    ok = _match_at(padded, lens, pattern, pos)[:, 0] & (lens >= len(pattern))
    return _bool_col(ok, col.validity)


@op_boundary("strings.strip")
def strip(col: Column) -> Column:
    """Trim ASCII spaces both sides (Spark trim)."""
    _check_string(col)
    padded, lens = to_padded(col)
    n, L = padded.shape
    j = jnp.arange(L, dtype=jnp.int32)[None, :]
    is_space = (padded == ord(" ")) & (j < lens[:, None])
    non_space = (padded != ord(" ")) & (j < lens[:, None])
    any_ns = jnp.any(non_space, axis=1)
    first_ns = jnp.argmax(non_space, axis=1).astype(jnp.int32)
    last_ns = (L - 1 - jnp.argmax(non_space[:, ::-1], axis=1)).astype(jnp.int32)
    begin = jnp.where(any_ns, first_ns, 0)
    out_lens = jnp.where(any_ns, last_ns - first_ns + 1, 0)
    src = jnp.clip(begin[:, None] + j, 0, L - 1)
    out = jnp.where(j < out_lens[:, None], jnp.take_along_axis(padded, src, axis=1), 0)
    return from_padded(out, out_lens, col.validity)


@op_boundary("strings.instr")
def instr(col: Column, pattern: bytes) -> Column:
    """Spark instr/locate: 1-based CHARACTER position of the first
    literal occurrence, 0 when absent (empty pattern -> 1). A valid
    UTF-8 needle can only match at character boundaries, so the byte
    hit converts to a character index by counting lead bytes before it."""
    _check_string(col)
    padded, lens = to_padded(col)
    n, L = padded.shape
    pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None, :], (n, L))
    hits = _match_at(padded, lens, pattern, pos)
    any_hit = jnp.any(hits, axis=1)
    first = jnp.argmax(hits, axis=1).astype(jnp.int32)
    # byte position -> character position: lead (non-continuation)
    # bytes strictly before the hit
    lead = ((padded & 0xC0) != 0x80) & (pos < lens[:, None])
    cum = jnp.cumsum(lead.astype(jnp.int32), axis=1)
    chars_before = jnp.where(
        first > 0,
        jnp.take_along_axis(cum, jnp.clip(first - 1, 0, L - 1)[:, None], axis=1)[:, 0],
        0,
    )
    out = jnp.where(any_hit, chars_before + 1, 0)
    if len(pattern) == 0:
        out = jnp.ones((n,), jnp.int32)
    return Column(dt.INT32, data=out, validity=col.validity)
