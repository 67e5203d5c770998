"""Device-resident columns: the TPU-native analog of ``cudf::column``.

The reference's entire JNI surface trades in ``ai.rapids.cudf.ColumnVector``
handles (reference RowConversion.java:19, SURVEY §2.8). Here a column is a
small pytree of jax arrays, so every op composes under ``jax.jit`` /
``shard_map`` and XLA owns layout & fusion:

- fixed width:  ``data``    [N]        (DECIMAL128: [N, 4] uint32 limbs, LE)
- validity:     ``validity``[N] bool   (True == valid; None == all valid;
                                        matches cudf's set-bit-means-valid)
- STRING:       ``offsets`` [N+1] int32, ``chars`` [nbytes] uint8
- LIST:         ``offsets`` [N+1] int32, ``child``  Column
- STRUCT:       ``children`` tuple of Columns (+ ``child_names``), all
                length N (cudf struct_column layout)

Host<->device conversion goes through numpy only at the API edges (the
role the reference's HostMemoryBuffer + JNI marshalling play).
"""

from __future__ import annotations

import decimal
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .dtype import DType, TypeId

__all__ = ["Column"]


def _pack_decimal128_host(values: Sequence[int]) -> np.ndarray:
    """Unscaled python ints -> [N, 4] uint32 little-endian limbs (two's complement)."""
    out = np.empty((len(values), 4), dtype=np.uint32)
    mask = (1 << 128) - 1
    for i, v in enumerate(values):
        u = v & mask
        for j in range(4):
            out[i, j] = (u >> (32 * j)) & 0xFFFFFFFF
    return out


def _unpack_decimal128_host(limbs: np.ndarray) -> list:
    """[N, 4] uint32 limbs -> unscaled python ints (signed)."""
    vals = []
    for row in limbs:
        u = 0
        for j in range(4):
            u |= int(row[j]) << (32 * j)
        if u >= 1 << 127:
            u -= 1 << 128
        vals.append(u)
    return vals


@jax.tree_util.register_pytree_node_class
class Column:
    """An immutable device column. Registered as a pytree so Tables of
    Columns flow through jit/shard_map boundaries directly."""

    def __init__(
        self,
        dtype: DType,
        data: Optional[jnp.ndarray] = None,
        validity: Optional[jnp.ndarray] = None,
        offsets: Optional[jnp.ndarray] = None,
        chars: Optional[jnp.ndarray] = None,
        child: Optional["Column"] = None,
        children: Optional[tuple] = None,
        child_names: Optional[tuple] = None,
    ):
        self.dtype = dtype
        self.data = data
        self.validity = validity
        self.offsets = offsets
        self.chars = chars
        self.child = child
        self.children = tuple(children) if children is not None else None
        self.child_names = tuple(child_names) if child_names is not None else None

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.data, self.validity, self.offsets, self.chars, self.child, self.children)
        return children, (self.dtype, self.child_names)

    @classmethod
    def tree_unflatten(cls, aux, children):
        dtype, child_names = aux if isinstance(aux, tuple) else (aux, None)
        data, validity, offsets, chars, child, struct_children = children
        return cls(dtype, data=data, validity=validity, offsets=offsets, chars=chars,
                   child=child, children=struct_children, child_names=child_names)

    # -- shape --------------------------------------------------------------
    def __len__(self) -> int:
        if self.dtype.id in (TypeId.STRING, TypeId.LIST):
            return int(self.offsets.shape[0]) - 1
        if self.dtype.id == TypeId.STRUCT:
            if self.validity is not None:
                return int(self.validity.shape[0])
            return len(self.children[0]) if self.children else 0
        return int(self.data.shape[0])

    @property
    def num_rows(self) -> int:
        return len(self)

    @property
    def max_char_len(self) -> int:
        """Max byte length across rows (STRING columns): the padded-
        matrix width every string kernel needs. Memoized — at most one
        device sync per column, and host-side constructors prepopulate
        it for free (through a remote backend the sync is a full RTT)."""
        ml = self.__dict__.get("_max_char_len")
        if ml is None:
            if len(self) == 0:
                ml = 0
            else:
                from ..utils import tracing  # not at import: utils reaches memgov

                offs = self.offsets
                ml = int(tracing.device_wait(jnp.max(offs[1:] - offs[:-1]), "max_char_len"))
            self._max_char_len = ml
        return ml

    @property
    def null_count(self) -> int:
        if self.validity is None:
            return 0
        return int(jnp.sum(~self.validity))

    def has_nulls(self) -> bool:
        return self.validity is not None and self.null_count > 0

    def valid_mask(self) -> jnp.ndarray:
        """Materialized [N] bool validity (all-True when validity is None)."""
        if self.validity is not None:
            return self.validity
        return jnp.ones((len(self),), dtype=bool)

    # -- construction -------------------------------------------------------
    @classmethod
    def from_pylist(cls, values: Sequence[Any], dtype: DType) -> "Column":
        """Build a device column from host python values; None == null.

        Decimal columns accept unscaled ints or ``decimal.Decimal`` (scaled by
        ``dtype.scale``); BOOL8 accepts bools; STRING accepts str/bytes.
        """
        n = len(values)
        has_null = any(v is None for v in values)
        validity = None
        if has_null:
            validity = jnp.asarray(np.array([v is not None for v in values], dtype=bool))

        tid = dtype.id
        if tid == TypeId.STRING:
            encoded = [b"" if v is None else (v.encode() if isinstance(v, str) else bytes(v)) for v in values]
            lens = np.array([len(e) for e in encoded], dtype=np.int32)
            offsets = np.zeros(n + 1, dtype=np.int32)
            np.cumsum(lens, out=offsets[1:])
            chars = np.frombuffer(b"".join(encoded), dtype=np.uint8).copy()
            col = cls(
                dtype,
                validity=validity,
                offsets=jnp.asarray(offsets),
                chars=jnp.asarray(chars),
            )
            # free while host-side: saves ops/strings.to_padded a device
            # sync (a full RTT on remote backends) per op
            col._max_char_len = int(lens.max()) if n else 0
            return col
        if tid == TypeId.DECIMAL128:
            unscaled = [0 if v is None else _to_unscaled(v, dtype.scale) for v in values]
            return cls(dtype, data=jnp.asarray(_pack_decimal128_host(unscaled)), validity=validity)
        if tid in (TypeId.DECIMAL32, TypeId.DECIMAL64):
            unscaled = [0 if v is None else _to_unscaled(v, dtype.scale) for v in values]
            return cls(dtype, data=jnp.asarray(np.array(unscaled, dtype=dtype.np_dtype)), validity=validity)
        if tid == TypeId.BOOL8:
            host = np.array([0 if v is None else int(bool(v)) for v in values], dtype=np.uint8)
            return cls(dtype, data=jnp.asarray(host), validity=validity)
        if tid == TypeId.FLOAT64:
            host = np.array([0.0 if v is None else v for v in values], dtype=np.float64)
            return cls(dtype, data=jnp.asarray(host.view(np.uint64)), validity=validity)
        host = np.array([0 if v is None else v for v in values], dtype=dtype.np_dtype)
        return cls(dtype, data=jnp.asarray(host), validity=validity)

    @classmethod
    def from_numpy(cls, arr: np.ndarray, dtype: Optional[DType] = None,
                   validity: Optional[np.ndarray] = None) -> "Column":
        if dtype is None:
            dtype = _infer_dtype(arr.dtype)
        v = None if validity is None else jnp.asarray(validity.astype(bool))
        if dtype.id == TypeId.FLOAT64:
            host = arr.astype(np.float64, copy=False).view(np.uint64)
        else:
            host = arr.astype(dtype.np_dtype, copy=False)
        return cls(dtype, data=jnp.asarray(host), validity=v)

    @classmethod
    def strings_from_parts(cls, offsets, chars, validity=None) -> "Column":
        from . import dtype as dt

        return cls(dt.STRING, validity=validity, offsets=jnp.asarray(offsets), chars=jnp.asarray(chars))

    @classmethod
    def list_from_parts(cls, offsets, child: "Column", validity=None) -> "Column":
        from . import dtype as dt

        return cls(dt.LIST, validity=validity, offsets=jnp.asarray(offsets), child=child)

    @classmethod
    def struct_from_parts(cls, children: Sequence["Column"], names: Sequence[str],
                          validity=None) -> "Column":
        from . import dtype as dt

        return cls(dt.STRUCT, validity=validity, children=tuple(children),
                   child_names=tuple(names))

    # -- host round trip (test/debug surface, like cudf::test wrappers) -----
    def to_pylist(self) -> list:
        tid = self.dtype.id
        valid = np.asarray(self.valid_mask())
        if tid == TypeId.STRING:
            offs = np.asarray(self.offsets)
            chars = np.asarray(self.chars).tobytes()
            out = []
            for i in range(len(self)):
                if not valid[i]:
                    out.append(None)
                else:
                    out.append(chars[offs[i]:offs[i + 1]].decode("utf-8", errors="replace"))
            return out
        if tid == TypeId.LIST:
            offs = np.asarray(self.offsets)
            child_vals = self.child.to_pylist()
            return [
                None if not valid[i] else child_vals[offs[i]:offs[i + 1]]
                for i in range(len(self))
            ]
        if tid == TypeId.STRUCT:
            names = self.child_names or tuple(f"f{j}" for j in range(len(self.children)))
            per_child = [c.to_pylist() for c in self.children]
            return [
                None if not valid[i] else {nm: per_child[j][i] for j, nm in enumerate(names)}
                for i in range(len(self))
            ]
        if tid == TypeId.DECIMAL128:
            unscaled = _unpack_decimal128_host(np.asarray(self.data))
            return [None if not valid[i] else unscaled[i] for i in range(len(self))]
        host = np.asarray(self.data)
        if tid == TypeId.BOOL8:
            return [None if not valid[i] else bool(host[i]) for i in range(len(self))]
        if tid == TypeId.FLOAT64:
            host = host.view(np.float64)
        return [None if not valid[i] else host[i].item() for i in range(len(self))]

    def to_decimal_pylist(self) -> list:
        """Decimal columns as ``decimal.Decimal`` values (scaled)."""
        assert self.dtype.is_decimal
        scale = self.dtype.scale
        return [
            None if v is None else decimal.Decimal(v).scaleb(scale)
            for v in self.to_pylist()
        ]

    def __repr__(self):
        return f"Column({self.dtype!r}, rows={len(self)}, nulls={self.null_count})"


def _to_unscaled(v, scale: int) -> int:
    if isinstance(v, decimal.Decimal):
        q = v.scaleb(-scale)
        return int(q.to_integral_value(rounding=decimal.ROUND_HALF_UP))
    return int(v)


def _infer_dtype(np_dt: np.dtype) -> DType:
    from . import dtype as dt

    table = {
        np.dtype(np.int8): dt.INT8,
        np.dtype(np.int16): dt.INT16,
        np.dtype(np.int32): dt.INT32,
        np.dtype(np.int64): dt.INT64,
        np.dtype(np.uint8): dt.UINT8,
        np.dtype(np.uint16): dt.UINT16,
        np.dtype(np.uint32): dt.UINT32,
        np.dtype(np.uint64): dt.UINT64,
        np.dtype(np.float32): dt.FLOAT32,
        np.dtype(np.float64): dt.FLOAT64,
        np.dtype(np.bool_): dt.BOOL8,
    }
    if np_dt not in table:
        raise ValueError(f"cannot infer DType from {np_dt}")
    return table[np_dt]
