"""Device sidecar worker: the JNI->TPU execution path.

The reference's JNI entry points land directly on device kernels
(RowConversionJni.cpp:42 -> row_conversion.cu:1903) because CUDA lives
in-process. The TPU runtime here is JAX/XLA, whose Python front end
cannot be embedded in a JVM executor process; the deployment model
(PACKAGING.md) is therefore a SIDECAR: ``libsrjt.so`` spawns this
module as a child process that owns the chip, and dispatches ops over a
Unix-domain socket with a length-prefixed binary protocol. The JVM
process never hosts a Python interpreter; the native library falls back
to its host-CPU engine when no sidecar/chip is available.

Wire protocol (little-endian):
  request:  [u32 op] [u64 payload_len] [u32 crc?] [payload]
  response: [u32 status(0=ok)] [u64 payload_len] [u32 crc?] [payload | utf-8 error]

Integrity (ISSUE 5): a client that sets the CRC_FLAG bit (0x40000000)
of ``op`` appends a 4-byte CRC trailer (utils/integrity.py) right
after the 12-byte header, covering the payload wherever it lives
(socket stream or arena); the worker verifies it — a mismatch answers
``status 1`` with a ``DataCorruption:`` message (retryable: the client
re-sends) — and echoes the flag back on the response with a trailer of
its own, which the client verifies before trusting a byte. The flag is
negotiated PER FRAME, so the native C++ client (which never sets it)
keeps the legacy framing byte for byte, and ``SRJT_INTEGRITY_CHECKS=0``
restores the seed posture with zero extra syscalls.

Round 5 shared-memory data plane (VERDICT r4 missing #2): a client may
send OP_SET_ARENA (9, payload = u64 size) with a memfd attached via
SCM_RIGHTS; the worker mmaps it. Afterwards either side may flag the
HIGH BIT of op/status to mean "payload lives at arena offset 0, only
the header crossed the socket". Clients that never set an arena get the
plain streaming protocol unchanged. The worker also accepts MULTIPLE
concurrent connections (one thread each, own arena each) — the
connection-pool client overlaps in-flight ops instead of serializing
under one mutex (reference PTDS posture, CMakeLists.txt:189-193).

Ops (round 4 extends the surface so every reference JNI entry can land
on the device — RowConversionJni.cpp:42, CastStringJni.cpp:48,
DecimalUtilsJni.cpp:22, ZOrderJni.cpp:24 all reach device kernels;
VERDICT r3 item 2):
  0 PING              -> payload = jax backend name (b"tpu"/b"cpu"/...)
  1 GROUPBY_SUM_F32   in:  u32 num_keys, u64 n, i64[n] keys, f32[n] vals
                      out: f32[num_keys] sums, i64[num_keys] counts
                      (groupby_sum_bounded: the MXU outer-product kernel
                      on TPU)
  2 CONVERT_TO_ROWS   in:  serialized table (see _read_table)
                      out: u32 nbatches, per batch: u64 nrows,
                           i32[nrows+1] offsets, u64 blob_len, u8 blob
  3 CONVERT_FROM_ROWS in:  u32 ncols, i32[ncols] type_ids, i32[ncols]
                           scales, u64 nrows, i32[nrows+1] offsets,
                           u64 blob_len, u8 blob
                      out: serialized table (_write_table's walker
                           layout; inside the worker the reply is
                           ``ReplyPieces``, the headers and host arrays
                           in wire order, which ``reply()`` gathers onto
                           the wire unjoined: the bytes on the wire are
                           the same)
  4 CAST_TO_INTEGER   in:  u8 ansi, i32 out_type_id, serialized table
                           (one STRING column)
                      out: serialized table (one column); ANSI failures
                           return status 2: i64 row, u8 is_null,
                           utf-8 value
  5 CAST_TO_DECIMAL   in:  u8 ansi, i32 precision, i32 scale,
                           serialized table (one STRING column)
                      out: as op 4
  6 ZORDER            in:  serialized table
                      out: serialized table (one LIST<UINT8> column:
                           offsets + bytes ride the STRING framing)
  7 DECIMAL128_MUL    in:  i32 product_scale, serialized table (a, b)
                      out: serialized table (overflow BOOL8, product)
  8 DECIMAL128_DIV    in:  i32 quotient_scale, serialized table (a, b)
                      out: as op 7
  10 STATS            -> utf-8 JSON: {"backend", "snapshot", "memgov",
                         "device", "memory"} — the
                         worker's metrics-registry snapshot
                         (utils/metrics.py): per-op request counts,
                         error counts, op timings. The observability
                         verb both clients (SupervisedClient.worker_stats,
                         native sidecar.cc stats_json) poll to fold
                         worker-side counters into their own registry.
  255 SHUTDOWN        -> empty ok, then the server exits

Response status codes: 0 ok, 1 generic error (utf-8 message; the C++
client falls back to the host engine), 2 CAST ERROR (semantic ANSI
failure — the client re-raises through the g_cast_error protocol, it
must NOT fall back and silently re-run on the CPU).

Supervision (this round): ``SupervisedClient`` is the Python-side
client with the robustness contract a wedged worker demands —
per-request DEADLINE (``SRJT_SIDECAR_DEADLINE_S``, falling back to
the C++ client's ``SRJT_SIDECAR_TIMEOUT_SEC`` so one knob tunes both
twins; socket timeout, so a hung worker surfaces as RetryableError
instead of blocking the executor forever), heartbeat PING (``SRJT_SIDECAR_HEARTBEAT_S``: a
connection idle past the interval is probed with a cheap PING before
carrying a heavy op), reconnect-on-desync (any transport fault or
malformed frame closes the socket; the next request dials fresh), and
host degrade: ``call()`` runs the op through the retry orchestrator
(utils/retry.py) and, when the worker is truly gone (fatal
classification or retry exhaustion), executes the SAME op in-process
via ``_dispatch`` — the host-CPU engine — so results keep flowing.
``worker_errors_are_classified``: a worker-side error message
prefixed ``RetryableError:`` / ``FatalDeviceError:`` (the worker's
op_boundary taxonomy stringified over the wire) is re-raised as that
class on the client, which is what makes remote faults retryable.

Crash tolerance (ISSUE 5): a SINGLE worker is a single point of
failure for all device state — ``sidecar_pool.SidecarPool`` supervises
N of these workers with health-checked routing, failover, automatic
respawn, and SET_ARENA re-hydration (the pool owns the arena memfd, so
a replacement worker re-maps the same pages). The circuit breaker
below then guards the POOL: it records failures only when every worker
is unhealthy.

Deadlines + circuit breaker (ISSUE 3): under an active deadline scope
(utils/deadline.py) every request's socket deadline is
``min(SRJT_SIDECAR_TIMEOUT_SEC, remaining budget)`` and reconnect
loops abort the moment the budget is gone — an expired budget raises
``DeadlineExceeded`` (non-retryable), never a raw socket timeout. The
process-global circuit breaker (``breaker()``; states/knobs in
utils/deadline.py, ``SRJT_BREAKER_THRESHOLD`` /
``SRJT_BREAKER_COOLDOWN_SEC``) opens after consecutive supervision
failures: while open, ``call()`` degrades to the host engine
immediately — no dial, no timeout wait — and after the cooldown one
half-open probe rides the device path; success restores device mode.
Transitions are registry-direct metrics, visible in
``runtime.stats_report()``.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import socket
import struct
import sys
import threading
import time

OP_PING = 0
OP_GROUPBY_SUM_F32 = 1
OP_CONVERT_TO_ROWS = 2
OP_CONVERT_FROM_ROWS = 3
OP_CAST_TO_INTEGER = 4
OP_CAST_TO_DECIMAL = 5
OP_ZORDER = 6
OP_DECIMAL128_MUL = 7
OP_DECIMAL128_DIV = 8
OP_SET_ARENA = 9
OP_STATS = 10
OP_SHUTDOWN = 255

# readable per-op metric names (worker-side request counters)
_OP_NAMES = {
    OP_PING: "PING",
    OP_GROUPBY_SUM_F32: "GROUPBY_SUM_F32",
    OP_CONVERT_TO_ROWS: "CONVERT_TO_ROWS",
    OP_CONVERT_FROM_ROWS: "CONVERT_FROM_ROWS",
    OP_CAST_TO_INTEGER: "CAST_TO_INTEGER",
    OP_CAST_TO_DECIMAL: "CAST_TO_DECIMAL",
    OP_ZORDER: "ZORDER",
    OP_DECIMAL128_MUL: "DECIMAL128_MUL",
    OP_DECIMAL128_DIV: "DECIMAL128_DIV",
    OP_SET_ARENA: "SET_ARENA",
    OP_STATS: "STATS",
    OP_SHUTDOWN: "SHUTDOWN",
}


def op_name(op: int) -> str:
    return _OP_NAMES.get(op, f"OP_{op}")

ARENA_FLAG = 0x80000000  # high bit of op/status: payload at arena[0:len]
CRC_FLAG = 0x40000000  # op/status bit: a u32 CRC trailer follows the header
# srjt-trace (ISSUE 12): op bit negotiated per request exactly like
# CRC_FLAG — when set, a fixed 17-byte trace-context blob (trace id,
# parent span id, flags; utils/tracing.wire_context) rides the socket
# right after the CRC trailer (or the header when CRC is off), BEFORE
# the payload/region descriptor. The worker installs the context for
# the request's dynamic extent so its spans parent to the caller's
# span in its own per-process span log. The native C++ client never
# sets it, so the legacy walker stays byte-for-byte; responses never
# carry it.
TRACE_FLAG = 0x20000000
_FLAG_MASK = ARENA_FLAG | CRC_FLAG | TRACE_FLAG

# slab-arena data plane (ISSUE 6): a SET_ARENA payload of >= 16 bytes
# carries a u64 mode word after the size; mode bit 0 marks the arena a
# SLAB of per-request regions (sidecar_pool.ArenaSlab). On a slab-mode
# connection an ARENA_FLAG request's stream payload is a REGION
# DESCRIPTOR naming where the real payload lives — the worker validates
# it against the 32-byte region header the client wrote into the slab
# (magic + generation + request id + capacity + payload length), so a
# stale or clobbered region surfaces as a retryable desync, never as
# somebody else's bytes. Responses land back inside the same region
# (header-only frame) when they fit, else stream. Legacy 8-byte
# SET_ARENA payloads (the native C++ client) keep the single-buffer
# offset-0 protocol byte for byte.
ARENA_MODE_LEGACY = 0
ARENA_MODE_SLAB = 1
REGION_MAGIC = 0x524A5253  # b"SRJR" little-endian
REGION_HDR = struct.Struct("<IIQQQ")  # magic, generation, request_id, capacity, payload_len
REGION_HDR_LEN = REGION_HDR.size  # 32
REGION_DESC = struct.Struct("<QQI")  # offset, request_id, generation

STATUS_OK = 0
STATUS_ERROR = 1
STATUS_CAST_ERROR = 2

# what an untraced request's handling runs under (the worker's
# ``remote_scope`` otherwise): one shared, reusable null context
_NO_SCOPE = contextlib.nullcontext()


class ReplyPieces:
    """An op's reply as an ordered list of bytes-like pieces: small
    ``struct.pack`` headers and the host arrays the device-to-host copy
    produced, as byte views. The wire carries the pieces back to back,
    so it is byte for byte what ``tobytes()`` gives; ``reply()`` gathers
    them into the region, the arena or the stream under a running CRC
    and never builds that one object. A plain ``bytes`` reply is the
    one-piece case (``pieces_of``)."""

    __slots__ = ("pieces", "_len")

    def __init__(self, pieces):
        self.pieces = [_byte_view(p) for p in pieces]
        self._len = sum(len(p) for p in self.pieces)

    def __len__(self) -> int:
        return self._len

    def tobytes(self) -> bytes:
        """One owned object, for the callers that need one: the host
        fallback's return value, the ``corrupt`` chaos hook."""
        return b"".join(self.pieces)


def _byte_view(piece) -> memoryview:
    """``piece`` (bytes-like or a host ndarray) as a flat byte view of
    the same memory; the view keeps its owner alive."""
    if hasattr(piece, "dtype"):  # ndarray: flat (a copy only if strided), as bytes
        piece = piece.reshape(-1).view("u1")
    return memoryview(piece)


def pieces_of(body) -> list:
    """The bytes-like pieces of a reply, in wire order."""
    return body.pieces if isinstance(body, ReplyPieces) else [body]


def as_bytes(body) -> bytes:
    """A reply as one ``bytes`` (a ``bytes`` reply is returned as is)."""
    return body.tobytes() if isinstance(body, ReplyPieces) else body


def _take_fds(ancdata, fds: list) -> None:
    """Capture SCM_RIGHTS file descriptors from a ``recvmsg`` into ``fds``."""
    import array

    for level, ctype, cdata in ancdata:
        if level == socket.SOL_SOCKET and ctype == socket.SCM_RIGHTS:
            a = array.array("i")
            a.frombytes(cdata[: len(cdata) - (len(cdata) % a.itemsize)])
            fds.extend(a)


_FD_SPACE = socket.CMSG_SPACE(4 * 4)  # room for four SCM_RIGHTS ints


def _recv_exact(conn: socket.socket, n: int, fds: list = None) -> bytes:
    """Read exactly n bytes. With ``fds`` given, capture any SCM_RIGHTS
    file descriptors that arrive attached to the stream (the
    OP_SET_ARENA memfd travels with its header bytes) into it; without,
    plain recv (client-side use, where no fds ever arrive)."""
    buf = bytearray()
    while len(buf) < n:
        if fds is None:
            chunk = conn.recv(n - len(buf))  # srjt-lint: allow-blocking(worker/probe-side request wait: the CLIENT owns every deadline; the server parks here between requests by design)
        else:
            chunk, ancdata, _flags, _addr = conn.recvmsg(  # srjt-lint: allow-blocking(worker-side request wait, SCM_RIGHTS variant; the client owns the deadline)
                n - len(buf), _FD_SPACE
            )
            _take_fds(ancdata, fds)
        if not chunk:
            raise ConnectionError("sidecar: peer closed")
        buf.extend(chunk)
    return bytes(buf)


def _recv_into(conn: socket.socket, view: memoryview, fds: list) -> None:
    """Fill ``view`` from the stream (the worker's payload read into its
    kept buffer): ``_recv_exact`` with the caller's memory as the
    destination, SCM_RIGHTS captured alike."""
    got = 0
    while got < len(view):
        n, ancdata, _flags, _addr = conn.recvmsg_into([view[got:]], _FD_SPACE)
        _take_fds(ancdata, fds)
        if not n:
            raise ConnectionError("sidecar: peer closed")
        got += n


_IOV_MAX = 512  # buffers a sendmsg; POSIX guarantees 1024 on Linux


def _send_gathered(conn: socket.socket, pieces) -> None:
    """``sendall`` of the pieces back to back without joining them: one
    gathering ``sendmsg`` after another until every byte is out."""
    pending = [p for p in map(memoryview, pieces) if len(p)]
    at = 0
    while at < len(pending):
        sent = conn.sendmsg(pending[at : at + _IOV_MAX])
        while at < len(pending) and sent >= len(pending[at]):
            sent -= len(pending[at])
            at += 1
        if sent:
            pending[at] = pending[at][sent:]


# wire table format negotiation (ISSUE 6): the worker answers each
# request in the table layout the REQUEST used. ``_read_table`` records
# the sniffed format here (one slot per connection thread — each
# connection is handled on its own thread and ops are synchronous), and
# ``_write_table`` consults it, so the native C++ client's legacy
# walker layout round-trips byte for byte while framed clients get the
# versioned columnar frame codec (columnar/frames.py) back.
_REQ_FMT = threading.local()


def _read_table(payload: bytes, pos: int = 0):
    """``_decode_table`` under its span: the whole of the table's way
    from wire bytes to device columns (per-column host views and
    host-to-device puts; the span times what the host did)."""
    from .utils import tracing

    with tracing.span(
        "sidecar.worker.decode_table", bytes=len(payload) - pos
    ) as sp:
        table = _decode_table(payload, pos)
        sp.annotate(cols=len(table.columns))
    return table


def _decode_table(payload: bytes, pos: int = 0):
    """Deserialize a table from ``payload[pos:]``. Sniffs the versioned
    columnar frame magic (columnar/frames.py) first — framed payloads
    decode through the shared codec (per-column CRC verified); anything
    else is the legacy walker layout: u32 ncols; per col: i32
    type_id, i32 scale, u64 n, u8 has_validity, [n] u8 validity, then
    either (u64 data_len, bytes) for fixed width or (i32[n+1] offsets,
    u64 chars_len, bytes) for STRING and LIST (byte child). The offset
    parameter avoids copying multi-hundred-MB payloads just to skip an
    op header."""
    import jax.numpy as jnp
    import numpy as np

    from .columnar import Column, Table, frames
    from .columnar.dtype import DType, TypeId

    if frames.is_frame(payload, pos):
        _REQ_FMT.framed = True
        return frames.decode_table(payload, where="sidecar.table_frame", offset=pos)
    _REQ_FMT.framed = False
    (ncols,) = struct.unpack_from("<I", payload, pos)
    pos += 4
    cols = []
    for _ in range(ncols):
        type_id, scale = struct.unpack_from("<ii", payload, pos)
        pos += 8
        (n,) = struct.unpack_from("<Q", payload, pos)
        pos += 8
        has_validity = payload[pos]
        pos += 1
        validity = None
        if has_validity:
            validity = jnp.asarray(np.frombuffer(payload, np.uint8, n, pos).astype(bool))
            pos += n
        tid = TypeId(type_id)
        d = DType(tid, scale if tid.name.startswith("DECIMAL") else 0)
        if tid in (TypeId.STRING, TypeId.LIST):
            offs = np.frombuffer(payload, np.int32, n + 1, pos)
            pos += 4 * (n + 1)
            (clen,) = struct.unpack_from("<Q", payload, pos)
            pos += 8
            chars = np.frombuffer(payload, np.uint8, clen, pos)
            pos += clen
            if tid == TypeId.LIST:
                cols.append(
                    Column(
                        d,
                        validity=validity,
                        offsets=jnp.asarray(offs),
                        child=Column(
                            DType(TypeId.INT8), data=jnp.asarray(chars).view(jnp.int8)
                        ),
                    )
                )
            else:
                cols.append(
                    Column(d, validity=validity, offsets=jnp.asarray(offs), chars=jnp.asarray(chars))
                )
        else:
            (dlen,) = struct.unpack_from("<Q", payload, pos)
            pos += 8
            # a view of the payload, not a copy (``payload`` is the
            # worker's kept buffer or a host-fallback caller's bytes)
            raw = memoryview(payload)[pos : pos + dlen]
            pos += dlen
            if tid == TypeId.DECIMAL128:
                data = np.frombuffer(raw, np.uint32).reshape(n, 4)
            else:
                data = np.frombuffer(raw, np.dtype(d.np_dtype))
            cols.append(Column(d, data=jnp.asarray(data), validity=validity))
    return Table(cols)


def _op_groupby_sum(payload: bytes) -> bytes:
    import numpy as np

    from .ops.aggregate import groupby_sum_bounded

    (num_keys,) = struct.unpack_from("<I", payload, 0)
    (n,) = struct.unpack_from("<Q", payload, 4)
    keys = np.frombuffer(payload, np.int64, n, 12)
    vals = np.frombuffer(payload, np.float32, n, 12 + 8 * n)
    import jax.numpy as jnp

    sums, counts = groupby_sum_bounded(
        jnp.asarray(keys), jnp.asarray(vals), int(num_keys)
    )
    return np.asarray(sums, np.float32).tobytes() + np.asarray(counts, np.int64).tobytes()


def _write_table(table, framed: bool = None):
    """Serialize a Table for the wire. ``framed=None`` (the worker's
    posture) echoes the format the current request's ``_read_table``
    sniffed, so the C++ client parses responses with the same legacy
    walker it serializes requests with, and framed clients decode the
    shared codec. LIST<INT8|UINT8> columns reuse the STRING framing
    (offsets + byte child) in the legacy form.

    The legacy form comes back as ``ReplyPieces``: the small headers
    and the host arrays themselves, in wire order, never joined here
    (``reply()`` gathers them; ``.tobytes()`` gives the one object).
    The framed form is the codec's ``bytes``."""
    import numpy as np

    from .columnar.dtype import TypeId
    from .utils import tracing

    if framed is None:
        framed = getattr(_REQ_FMT, "framed", False)
    if framed:
        from .columnar import frames

        # the shared codec brings each column to the host as it frames
        # it: its device-to-host copies are inside this span
        with tracing.span("sidecar.worker.encode_reply", framed=True) as sp:
            resp = frames.encode_table(table)
            sp.annotate(bytes=len(resp))
        return resp
    # two passes, so that neither span sits in the per-column loop:
    # every device array to the host first (the wait for the kernel
    # that produced it included), then the list of wire pieces
    with tracing.span("sidecar.worker.d2h") as sp:
        # ONE wait for all the table's arrays: what is left of the span is the copy
        tracing.device_wait(table, "result")
        host = []
        for col in table.columns:
            d = col.dtype
            validity = (
                None if col.validity is None else np.asarray(col.validity, np.uint8)
            )
            if d.id in (TypeId.STRING, TypeId.LIST):
                offs = np.asarray(col.offsets, np.int32)
                chars = (
                    np.asarray(col.chars, np.uint8)
                    if d.id == TypeId.STRING
                    else np.asarray(col.child.data).view(np.uint8)
                )
                host.append((d, len(col), validity, offs, chars))
            else:
                host.append((d, len(col), validity, None, np.asarray(col.data)))
        sp.annotate(bytes=sum(
            a.nbytes for h in host for a in h[2:] if a is not None
        ))
    with tracing.span("sidecar.worker.encode_reply") as sp:
        out = [struct.pack("<I", len(host))]
        for d, n, validity, offs, raw in host:
            out.append(struct.pack("<iiQ", int(d.id.value), int(d.scale), n))
            if validity is not None:
                out.append(b"\x01")
                out.append(validity)
            else:
                out.append(b"\x00")
            if offs is not None:  # STRING / LIST: offsets, then the byte child
                out.append(offs)
            out.append(struct.pack("<Q", raw.nbytes))
            out.append(raw)
        resp = ReplyPieces(out)
        sp.annotate(bytes=len(resp), pieces=len(resp.pieces))
    return resp


def _op_convert_to_rows(payload: bytes) -> ReplyPieces:
    import numpy as np

    from .ops.row_conversion import convert_to_rows
    from .utils import tracing

    table = _read_table(payload)
    batches = convert_to_rows(table)
    # device to host (the wait for the transcode kernel included), then
    # the list of wire pieces over those host arrays: one span each
    with tracing.span("sidecar.worker.d2h") as sp:
        # ONE wait for the encode: what is left of the span is the copy
        tracing.device_wait(batches, "result")
        host = [
            (
                len(col),
                np.asarray(col.offsets, np.int32),
                np.asarray(col.child.data).view(np.uint8),
            )
            for col in batches
        ]
        sp.annotate(bytes=sum(o.nbytes + b.nbytes for _, o, b in host))
    with tracing.span("sidecar.worker.encode_reply") as sp:
        out = [struct.pack("<I", len(host))]
        for n, offs, blob in host:
            out.append(struct.pack("<Q", n))
            out.append(offs)
            out.append(struct.pack("<Q", blob.size))
            out.append(blob)
        resp = ReplyPieces(out)
        sp.annotate(bytes=len(resp), pieces=len(resp.pieces))
    return resp


def _op_convert_from_rows(payload: bytes) -> ReplyPieces:
    """The rows' way to the device under ``sidecar.worker.decode_table``
    (the phase the to-row ops' span of that name times: wire bytes to
    device arrays; here the parse, and one put each of the offsets and
    of the rows as they came, their stride read on the host), the decode,
    then ``_write_table``'s legacy reply: ``ReplyPieces``."""
    import numpy as np

    from .columnar.dtype import DType, TypeId
    from .ops.row_conversion import convert_from_rows, rows_from_host
    from .utils import tracing

    with tracing.span("sidecar.worker.decode_table", bytes=len(payload)) as sp:
        pos = 0
        (ncols,) = struct.unpack_from("<I", payload, pos)
        pos += 4
        type_ids = np.frombuffer(payload, np.int32, ncols, pos)
        pos += 4 * ncols
        scales = np.frombuffer(payload, np.int32, ncols, pos)
        pos += 4 * ncols
        (nrows,) = struct.unpack_from("<Q", payload, pos)
        pos += 8
        offs = np.frombuffer(payload, np.int32, nrows + 1, pos)
        pos += 4 * (nrows + 1)
        (blen,) = struct.unpack_from("<Q", payload, pos)
        pos += 8
        blob = np.frombuffer(payload, np.uint8, blen, pos)
        dtypes = [
            DType(TypeId(int(t)), int(s) if TypeId(int(t)).name.startswith("DECIMAL") else 0)
            for t, s in zip(type_ids, scales)
        ]
        rows = rows_from_host(offs, blob)
        # the put returns before its bytes are on the device: with tracing
        # on, the phase waits for them (off, nothing waits here)
        tracing.device_wait(rows, "rows_put")
        sp.annotate(rows=int(nrows), cols=int(ncols))
    return _write_table(convert_from_rows(rows, dtypes))


def _op_cast_to_integer(payload: bytes) -> bytes:
    from .columnar import Table
    from .columnar.dtype import DType, TypeId
    from .ops.cast_string import string_to_integer

    ansi = payload[0]
    (out_type,) = struct.unpack_from("<i", payload, 1)
    table = _read_table(payload, 5)
    out = string_to_integer(
        table.columns[0], ansi_mode=ansi != 0, out_dtype=DType(TypeId(out_type))
    )
    return _write_table(Table([out]))


def _op_cast_to_decimal(payload: bytes) -> bytes:
    from .columnar import Table
    from .ops.cast_decimal import string_to_decimal

    ansi = payload[0]
    precision, scale = struct.unpack_from("<ii", payload, 1)
    table = _read_table(payload, 9)
    out = string_to_decimal(table.columns[0], ansi != 0, precision, scale)
    return _write_table(Table([out]))


def _op_zorder(payload: bytes) -> bytes:
    from .columnar import Table
    from .ops.zorder import interleave_bits_table

    table = _read_table(payload)
    return _write_table(Table([interleave_bits_table(table)]))


def _op_decimal128(payload: bytes, div: bool) -> bytes:
    from .ops.decimal_utils import divide128, multiply128

    (out_scale,) = struct.unpack_from("<i", payload, 0)
    table = _read_table(payload, 4)
    a, b = table.columns[0], table.columns[1]
    res = divide128(a, b, out_scale) if div else multiply128(a, b, out_scale)
    return _write_table(res)


def _device_section() -> dict:
    """What JAX says of this process's device(s): the worker owns the
    chip, so only it can answer. ``memory`` is keyed by device id and
    holds ``{}`` where the backend reports no memory statistics (the
    CPU)."""
    import jax

    devs = jax.devices()
    memory = {}
    for d in devs:
        ms = d.memory_stats() or {}
        memory[str(d.id)] = {
            k: int(ms[k])
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in ms
        }
    return {
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
        "memory": memory,
    }


def _op_stats(backend: str) -> bytes:
    """STATS verb: the worker's metrics-registry snapshot as JSON plus
    the memory governor's section (admission + catalog state — arena
    registrations surface here AND as ``memgov.arena*`` gauges in the
    snapshot), its ``device`` (platform, kind, count) and per-device
    ``memory``. The worker counts per-op requests/errors
    registry-direct (always on, independent of SRJT_METRICS_ENABLED —
    the verb must answer even when hot-path instrumentation is
    disarmed); the compile counters (``xla.*``,
    utils/compile_cache.py) ride the same snapshot. The verb is also a
    flush point of the span log (utils/trace_sink.py): whoever polls
    the worker can read its spans afterwards."""
    import json

    from . import memgov
    from .utils import metrics, trace_sink

    trace_sink.flush()
    return json.dumps(
        {
            "backend": backend,
            "snapshot": metrics.snapshot(),
            "memgov": memgov.stats_section(),
            **_device_section(),
        }
    ).encode()


def _dispatch(op: int, payload, backend: str):
    """Run one op over ``payload`` (any bytes-like object). The reply is
    ``bytes`` or, from the ops that answer with host arrays,
    ``ReplyPieces``; ``as_bytes`` makes one object of either."""
    # fresh wire-format slot per dispatch: host-fallback callers reuse
    # threads, and a stale `framed` sniff from an earlier request would
    # make an op that never reads a table echo the wrong table layout
    _REQ_FMT.framed = False
    if op == OP_PING:
        return backend.encode()
    if op == OP_STATS:
        return _op_stats(backend)
    if op == OP_GROUPBY_SUM_F32:
        return _op_groupby_sum(payload)
    if op == OP_CONVERT_TO_ROWS:
        return _op_convert_to_rows(payload)
    if op == OP_CONVERT_FROM_ROWS:
        return _op_convert_from_rows(payload)
    if op == OP_CAST_TO_INTEGER:
        return _op_cast_to_integer(payload)
    if op == OP_CAST_TO_DECIMAL:
        return _op_cast_to_decimal(payload)
    if op == OP_ZORDER:
        return _op_zorder(payload)
    if op == OP_DECIMAL128_MUL:
        return _op_decimal128(payload, div=False)
    if op == OP_DECIMAL128_DIV:
        return _op_decimal128(payload, div=True)
    raise ValueError(f"unknown op {op}")


def _handle_conn(conn: socket.socket, backend: str, shutdown) -> None:
    """One client connection: its own optional arena, its own thread."""
    import mmap

    from . import memgov
    from .utils import faultinj, integrity, metrics, tracing
    from .utils.errors import DataCorruption

    reg = metrics.registry()  # worker-side counters: always-on
    arena = None  # mmap over the client's memfd
    arena_mode = ARENA_MODE_LEGACY  # SET_ARENA mode word (slab vs legacy)
    # memory-governor bookkeeping (always-on, like the request counters):
    # the mmap'd arena is host memory no budget would otherwise see —
    # it registers as a host-tier PINNED catalog entry, keyed per
    # connection, and surfaces in the STATS verb / stats_report()
    arena_key = f"sidecar.arena.conn{id(conn)}"
    # the kept request buffer: every payload is copied (arena, region)
    # or received (stream) into it, so no request faults in a fresh
    # object of its own size. It grows to the largest payload seen and
    # is host memory this connection holds between requests: registered
    # like the arena mapping, dropped with the connection.
    scratch = bytearray()
    scratch_key = f"sidecar.scratch.conn{id(conn)}"
    fds: list = []

    def reply(status: int, body, with_crc: bool, crc_body=None, region=None):
        """One response frame. ``body`` is ``bytes`` or ``ReplyPieces``:
        the trailer is a running CRC over the pieces in order (equal to
        the CRC of the joined bytes) and each piece is written at its
        offset into the region or the arena, or gathered down the
        stream — the body is never joined. ``crc_body`` is what the
        trailer covers when it differs from the bytes on the wire — the
        injected ``corrupt`` chaos flips bytes AFTER checksumming,
        exactly like a transport fault, so the client's CRC check MUST
        fail.
        ``region`` is the (offset, capacity, request_id, generation) of
        a slab-mode region request: a fitting OK response lands back
        inside that region (header-only frame) after the in-slab header
        is re-validated against the request's id+generation; slab-mode
        connections never answer through the arena otherwise — the
        legacy single-buffer opportunism is exactly what serialized the
        whole pool on one lock."""
        reg.counter(
            "sidecar.worker.reply.gathered_bytes"
            if isinstance(body, ReplyPieces)
            else "sidecar.worker.reply.joined_bytes"
        ).inc(len(body))
        trailer = b""
        if with_crc and integrity.is_enabled():
            status |= CRC_FLAG
            covered = body if crc_body is None else crc_body
            # over the worker's own in-hand arrays, never the shared pages
            with tracing.span("integrity.crc", bytes=len(covered), where="reply"):
                crc = 0
                for piece in pieces_of(covered):
                    crc = integrity.checksum(piece, crc)
                trailer = integrity.pack_crc(crc)
        ok = (status & ~_FLAG_MASK) == STATUS_OK
        via, start = "stream", 0  # where the body goes: region / arena / stream
        if ok and region is not None and 0 < len(body) <= region[1]:
            # re-validate the in-slab header IMMEDIATELY before writing:
            # a slow-but-alive worker whose client already timed out and
            # failed over would otherwise clobber the region under the
            # retry attempt (the client bumps the generation on every
            # rewrite, so a stale attempt sees a mismatch here). The
            # check and the write are not atomic — a write straddling
            # the retry's rewrite can still tear the pages — but both
            # sides checksum IN-HAND bytes (never an mmap re-read), so
            # a tear fails CRC verification and heals retryably. On
            # mismatch fall through to the stream answer — this socket
            # is the only place this attempt's client could still be
            # listening, and the slab stays untouched.
            off = region[0]
            magic, hgen, hrid, _cap, _plen = REGION_HDR.unpack_from(arena, off)
            if magic == REGION_MAGIC and hrid == region[2] and hgen == region[3]:
                via, start = "region", off + REGION_HDR_LEN
        elif (
            ok and arena is not None and arena_mode == ARENA_MODE_LEGACY
            and 0 < len(body) <= len(arena)
        ):
            via = "arena"
        with tracing.span("sidecar.worker.reply_write", bytes=len(body), via=via):
            if via == "stream":
                _send_gathered(
                    conn,
                    [struct.pack("<IQ", status, len(body)) + trailer,
                     *pieces_of(body)],
                )
            else:
                for piece in pieces_of(body):
                    arena[start : start + len(piece)] = piece
                    start += len(piece)
                conn.sendall(
                    struct.pack("<IQ", status | ARENA_FLAG, len(body)) + trailer
                )

    try:
        while True:
            try:
                hdr = _recv_exact(conn, 12, fds)
            except ConnectionError:
                return  # client went away: this connection only
            wire_op, plen = struct.unpack("<IQ", hdr)
            op = wire_op & ~_FLAG_MASK
            in_arena = bool(wire_op & ARENA_FLAG)
            with_crc = bool(wire_op & CRC_FLAG)
            reg.counter(f"sidecar.worker.requests.{op_name(op)}").inc()
            # the CRC trailer rides the SOCKET right after the header,
            # even for arena-resident payloads — read it before any
            # early-out so the stream stays framed
            req_crc = (
                integrity.unpack_crc(_recv_exact(conn, 4, fds)) if with_crc else None
            )
            # srjt-trace (ISSUE 12): the trace-context blob follows the
            # trailer, before the payload/descriptor — read it
            # unconditionally when flagged so the stream stays framed
            # even if tracing is disarmed on this side
            tctx = (
                tracing.decode_wire_context(
                    _recv_exact(conn, tracing.TRACE_CTX_LEN, fds)
                )
                if wire_op & TRACE_FLAG
                else None
            )
            # srjt-trace: the caller's context covers the WHOLE handling
            # of the request — payload read, CRC passes, the op, the
            # reply — so every phase span below parents to the client's
            # ``sidecar.request``; the span log is written when the
            # scope exits (a disarmed worker's ``remote_scope`` is a
            # pass). Untraced requests pay one shared null context.
            with _NO_SCOPE if tctx is None else tracing.remote_scope(*tctx):
                region = None  # (offset, capacity) of a slab-mode region request
                if in_arena and arena_mode == ARENA_MODE_SLAB:
                    # slab mode: the stream payload is a region DESCRIPTOR;
                    # the real payload sits behind the region header the
                    # client wrote into the shared slab. Every mismatch —
                    # stale generation, foreign request id, bad geometry —
                    # answers retryably so the client rewrites the region
                    # (or replays SET_ARENA) and re-sends.
                    desc = _recv_exact(conn, plen, fds) if plen else b""
                    err = None
                    if len(desc) != REGION_DESC.size:
                        err = f"bad region descriptor length {len(desc)}"
                    elif arena is None:
                        err = "no uploaded arena (re-send SET_ARENA)"
                    else:
                        off, rid, gen = REGION_DESC.unpack(desc)
                        if off + REGION_HDR_LEN > len(arena):
                            err = f"region offset {off} out of bounds"
                        else:
                            magic, hgen, hrid, cap, pl = REGION_HDR.unpack_from(arena, off)
                            if magic != REGION_MAGIC or hrid != rid or hgen != gen:
                                err = (
                                    f"region header desync at {off} "
                                    f"(rid {hrid} != {rid} or gen {hgen} != {gen})"
                                )
                            elif pl > cap or off + REGION_HDR_LEN + cap > len(arena):
                                err = f"region geometry invalid (len {pl} cap {cap})"
                            else:
                                region = (off, cap, rid, gen)
                                via, start, plen = "region", off + REGION_HDR_LEN, pl
                    if err is not None:
                        reply(
                            STATUS_ERROR,
                            f"RetryableError: arena region: {err}".encode(),
                            with_crc,
                        )
                        continue
                elif in_arena:
                    if arena is None or plen > len(arena):
                        # retryable by prefix: a redialed connection lost its
                        # per-connection arena — the client replays SET_ARENA
                        # and re-sends (sidecar_pool._ensure_arena)
                        reply(
                            STATUS_ERROR,
                            b"RetryableError: arena request without an uploaded"
                            b" arena (re-send SET_ARENA)",
                            with_crc,
                        )
                        continue
                    via, start = "arena", 0
                else:
                    via = "stream"
                # header parsed -> the payload's bytes in hand, in the
                # kept buffer. The copy stays: the CRC below is verified
                # over bytes in hand, never a re-read of shared pages,
                # and the reply lands where the request lay. Overwriting
                # the previous request's bytes is safe: a connection
                # handles one request at a time, and whatever viewed
                # them (host arrays of ``_decode_table``, which the CPU
                # backend's ``jnp.asarray`` may alias; the reply's
                # pieces) was dropped when that request was answered.
                with tracing.span("sidecar.worker.payload_read", bytes=plen, via=via):
                    if plen > len(scratch):
                        scratch = bytearray(plen)
                        memgov.catalog().register_host_bytes(
                            scratch_key, plen, pinned=True, kind="scratch"
                        )
                        reg.counter("sidecar.worker.scratch.grows").inc()
                    elif plen:
                        reg.counter("sidecar.worker.scratch.reuses").inc()
                    dst = memoryview(scratch)[:plen]
                    if via == "stream":
                        _recv_into(conn, dst, fds)
                    else:
                        with memoryview(arena) as shared:
                            dst[:] = shared[start : start + plen]
                    payload = dst.toreadonly()
                _REQ_FMT.framed = False  # set by _read_table when it sniffs a frame
                if req_crc is not None and integrity.is_enabled():
                    reg.counter("sidecar.integrity.frames_checked").inc()
                    try:
                        with tracing.span(
                            "integrity.crc", bytes=len(payload),
                            where="verify_request",
                        ):
                            integrity.verify(payload, req_crc, "sidecar.request")
                    except DataCorruption as e:
                        # taxonomy prefix on the wire: the client re-raises
                        # DataCorruption (retryable) and re-sends the frame
                        reply(STATUS_ERROR, f"{type(e).__name__}: {e}".encode(), with_crc)
                        continue
                # chaos mode (VERDICT r4 item 7): SRJT_CHAOS_EXIT_ON_OP=<n>
                # makes the worker DIE mid-op — after consuming the request,
                # before any response — modeling the round-4 "kernel fault"
                # worker crash. Clients must classify the dead transport,
                # fall back to the host engine, and reconnect cleanly.
                from .utils import knobs

                chaos = knobs.get_int("SRJT_CHAOS_EXIT_ON_OP")
                if chaos is not None and op == chaos:
                    os._exit(42)
                try:
                    # per-request fault hook (ISSUE 5): `crash` rules keyed
                    # `sidecar.worker.<OP>` SIGKILL the worker here — after
                    # consuming the request, before any response — and
                    # error kinds surface as status-1 replies
                    if faultinj.is_enabled():
                        faultinj.maybe_inject(f"sidecar.worker.{op_name(op)}")
                    if op == OP_SET_ARENA:
                        (size,) = struct.unpack_from("<Q", payload, 0)
                        # >= 16-byte payloads carry the arena MODE word
                        # (bit 0 = slab of per-request regions); the native
                        # client's 8-byte payload keeps the legacy protocol
                        mode = (
                            struct.unpack_from("<Q", payload, 8)[0]
                            if len(payload) >= 16
                            else ARENA_MODE_LEGACY
                        )
                        if not fds:
                            raise ValueError("SET_ARENA without an fd")
                        fd = fds.pop(0)
                        for extra in fds:
                            os.close(extra)
                        fds.clear()
                        if arena is not None:
                            # replace = unregister-then-register: close the
                            # old mapping AND retire its accounting entry
                            # before the new map exists, so a failed re-map
                            # can't leave stale host-tier bytes and a
                            # successful one never double-counts
                            # (regression: memgov.arena* gauges stay flat
                            # across re-uploads)
                            arena.close()
                            arena = None
                            memgov.catalog().unregister(arena_key)
                        arena = mmap.mmap(fd, size)
                        arena_mode = (
                            ARENA_MODE_SLAB
                            if (mode & ARENA_MODE_SLAB)
                            else ARENA_MODE_LEGACY
                        )
                        os.close(fd)
                        memgov.catalog().register_host_bytes(
                            arena_key, size, pinned=True, kind="arena"
                        )
                        reply(STATUS_OK, b"", with_crc)
                        continue
                    if op == OP_SHUTDOWN:
                        conn.sendall(struct.pack("<IQ", 0, 0))
                        shutdown()
                        return
                    # per-op wall time is hot-path instrumentation: gated
                    # (SRJT_METRICS_ENABLED), unlike the always-on request
                    # COUNTERS above — disarmed, no clock is touched
                    timed = metrics.is_enabled()
                    t0 = time.perf_counter() if timed else 0.0
                    # the worker's half of the cross-process trace: one
                    # span per dispatched op, parented (via the wire
                    # context installed above) to the client's request
                    # span, in THIS process's span log for tracemerge
                    # to join
                    with tracing.span(
                        "sidecar.worker_op", op=op_name(op), backend=backend,
                    ):
                        resp = _dispatch(op, payload, backend)
                    if timed:
                        reg.histogram(f"sidecar.worker.op_us.{op_name(op)}").record(
                            (time.perf_counter() - t0) * 1e6
                        )
                    wire_resp = resp
                    if faultinj.is_enabled():
                        # `corrupt` chaos: flips bytes BELOW the checksum
                        # (the hook copies one object, so pieces are joined)
                        wire_resp = faultinj.maybe_corrupt(
                            f"sidecar.worker.{op_name(op)}", as_bytes(resp)
                        )
                    reply(STATUS_OK, wire_resp, with_crc, crc_body=resp, region=region)
                    # the reply's host arrays (and what they view) die
                    # with the request, not with the next one
                    resp = wire_resp = None
                except Exception as e:  # srjt-lint: allow-broad-except(worker request loop: every failure must become a status-1 reply carrying the taxonomy prefix — the client re-raises the right class across the wire; the worker keeps serving)
                    from .ops.cast_string import CastError

                    reg.counter("sidecar.worker.errors").inc()
                    if isinstance(e, CastError):
                        # semantic ANSI failure: ships row + null-flag +
                        # value so the client re-raises instead of
                        # re-running on the host
                        sv = e.string_with_error
                        val = sv.encode() if isinstance(sv, str) else (bytes(sv) if sv else b"")
                        msg = struct.pack("<qB", int(e.row_with_error), 1 if sv is None else 0) + val
                        reply(STATUS_CAST_ERROR, msg, with_crc)
                    else:
                        reply(STATUS_ERROR, f"{type(e).__name__}: {e}".encode(), with_crc)
    finally:
        if arena is not None:
            arena.close()
            memgov.catalog().unregister(arena_key)
        memgov.catalog().unregister(scratch_key)
        for fd in fds:
            os.close(fd)
        conn.close()


# ---------------------------------------------------------------------------
# supervised Python client (the executor-side path; C++ twin: sidecar.cc)
# ---------------------------------------------------------------------------


def _env_seconds(name: str, default: float = ...) -> float:
    # typed registry accessor (utils/knobs.py): malformed or <= 0
    # values warn and keep the default — a zero deadline would make
    # the socket non-blocking, not timeout-free (the C++ twin applies
    # the same v > 0 rule)
    from .utils import knobs

    return knobs.get_float(name, default=default)


class SupervisedClient:
    """Sidecar client with connection supervision.

    Robustness contract (ISSUE: sidecar connection supervision):

    - every socket operation runs under a per-request DEADLINE; a
      wedged worker yields ``RetryableError("DEADLINE_EXCEEDED...")``
      — never an indefinite block holding the executor,
    - a connection idle longer than ``heartbeat_s`` is probed with a
      PING before carrying a real op, so a silently dead worker is
      detected by a 12-byte round-trip instead of a multi-second op
      timing out,
    - any transport fault or malformed frame DESYNCS the byte stream:
      the socket is closed immediately and the next request reconnects
      fresh (a desynced stream must never carry another frame),
    - ``call()`` wraps ``request()`` in the retry orchestrator and
      degrades to the in-process host-CPU engine (``_dispatch``) when
      the worker is fatally gone — bounded by the deadline, no hang,
      no silent drop.
    """

    def __init__(
        self,
        sock_path: str,
        deadline_s: float = None,
        heartbeat_s: float = None,
    ):
        self.sock_path = sock_path
        if deadline_s is None:
            # one deadline knob across both clients: the C++ twin
            # (native/src/sidecar.cc) reads SRJT_SIDECAR_TIMEOUT_SEC,
            # honored here too; SRJT_SIDECAR_DEADLINE_S (float) wins
            # when both are set
            deadline_s = _env_seconds(
                "SRJT_SIDECAR_DEADLINE_S",
                _env_seconds("SRJT_SIDECAR_TIMEOUT_SEC"),
            )
        self.deadline_s = float(deadline_s)
        self.heartbeat_s = (
            _env_seconds("SRJT_SIDECAR_HEARTBEAT_S")
            if heartbeat_s is None
            else float(heartbeat_s)
        )
        self._sock: socket.socket = None
        self._last_io = 0.0
        self._ever_connected = False
        self.reconnects = 0  # supervision observability: REDIALS only
        self.host_fallbacks = 0
        # shared-memory data plane (set by the pool after SET_ARENA):
        # the worker opportunistically answers through the arena once a
        # connection has one, so the client must be able to READ
        # ARENA_FLAG responses even for stream requests
        self.arena_mm = None

    # -- connection lifecycle ------------------------------------------------

    def connect(self) -> None:
        from .utils import deadline as deadline_mod, metrics
        from .utils.errors import RetryableError

        # reconnect loops abort the moment the query budget is gone:
        # DeadlineExceeded here, never a dial that cannot finish
        d = deadline_mod.current()
        timeout = self.deadline_s
        if d is not None:
            d.check("sidecar.connect")
            timeout = min(timeout, max(d.remaining(), 1e-3))
        self.close()
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.settimeout(timeout)
        try:
            s.connect(self.sock_path)
        except (OSError, socket.timeout) as e:
            s.close()
            raise RetryableError(f"sidecar: UNAVAILABLE: connect failed ({e})") from e
        if self._ever_connected:
            self.reconnects += 1  # a redial, not the initial dial
            metrics.counter("sidecar.reconnects").inc()
            metrics.event("sidecar.reconnect", sock=self.sock_path)
        self._ever_connected = True
        self._sock = s
        self._last_io = time.monotonic()

    def close(self) -> None:
        if self._sock is not None:
            try:
                self._sock.close()
            finally:
                self._sock = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- framed request/response under a deadline ----------------------------

    def _op_budget_s(self, op: int) -> float:
        """ADAPTIVE per-op socket deadline (ISSUE 9): once an op class
        has enough observed samples (``sidecar.op_lat_us.<OP>``,
        recorded registry-direct by ``request()``), the deadline is its
        q99 × ``SRJT_ADAPTIVE_TIMEOUT_MULT``, clamped into
        [``SRJT_ADAPTIVE_TIMEOUT_FLOOR_S``, the static
        ``SRJT_SIDECAR_TIMEOUT_SEC``] — a hung worker is detected in
        seconds instead of the static knob's minutes, while cold-start
        ops (first compile, first dial) keep the conservative static
        deadline. The caller still clamps to the remaining query
        budget, so an adaptive deadline can never outlive the query.
        Clamps are counted (``sidecar.adaptive_timeout_clamps``)."""
        from .utils import metrics

        budget, clamped = metrics.adaptive_timeout_s(
            f"sidecar.op_lat_us.{op_name(op)}", self.deadline_s
        )
        if clamped:
            metrics.registry().counter("sidecar.adaptive_timeout_clamps").inc()
        return budget

    def _recv_deadline(self, n: int, deadline: float) -> bytes:
        """Read exactly n bytes under a WHOLE-REQUEST deadline: the
        socket timeout shrinks to the remaining budget each iteration,
        so a slow-dripping worker (one chunk per almost-deadline) cannot
        stretch one request past ``deadline_s`` total — the bound the
        supervision contract advertises, not a per-recv idle timeout."""
        buf = bytearray()
        while len(buf) < n:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise socket.timeout("request deadline exhausted")
            self._sock.settimeout(remaining)
            chunk = self._sock.recv(min(n - len(buf), 1 << 20))
            if not chunk:
                raise ConnectionError("sidecar: peer closed")
            buf.extend(chunk)
        return bytes(buf)

    def _frame_request(self, op: int, payload: bytes, arena_len, region,
                       use_crc: bool):
        """The request frame's parts: ``(wire_op, plen, trailer, stream
        payload)``. With ``region`` / ``arena_len`` the body is resident
        in shared memory and only a descriptor (or nothing) crosses the
        socket; the CRC trailer always covers the body's IN-HAND bytes."""
        from .utils import integrity, tracing
        from .utils.errors import RetryableError

        wire_op = (op | CRC_FLAG) if use_crc else op
        if region is not None:
            wire_op |= ARENA_FLAG
            # checksum the IN-HAND request bytes, never an mmap re-read:
            # a slow stale worker's slab write straddling the caller's
            # rewrite can tear the shared pages, and a CRC computed over
            # a re-read would bless the torn bytes — computed over the
            # snapshot, any tear fails the worker-side verify and heals
            # as retryable DataCorruption
            body = region.snapshot_bytes()
            payload = REGION_DESC.pack(
                region.offset, region.request_id, region.generation
            )
            plen = len(payload)
        elif arena_len is None:
            body, plen = payload, len(payload)
        else:
            if self.arena_mm is None:
                raise ValueError(
                    "arena_len given but no client-side arena is mapped"
                )
            if arena_len > len(self.arena_mm):
                # enforcement of the PR 5 hardening note (ISSUE 6): an
                # oversized arena request must engage retry-with-split,
                # never truncate — RESOURCE_EXHAUSTED is the class the
                # split machinery keys on, and the message carries the
                # needed size
                raise RetryableError(
                    f"sidecar: RESOURCE_EXHAUSTED: arena request needs "
                    f"{arena_len} bytes but the mapped arena holds "
                    f"{len(self.arena_mm)} — split the batch or lease a "
                    "larger region"
                )
            wire_op |= ARENA_FLAG
            body, plen, payload = bytes(self.arena_mm[:arena_len]), arena_len, b""
        tracing.annotate(bytes=len(body))  # the caller's ``sidecar.client.send``
        trailer = b""
        if use_crc:
            with tracing.span("integrity.crc", bytes=len(body), where="request"):
                trailer = integrity.pack_crc(integrity.checksum(body))
        return wire_op, plen, trailer, payload

    def _raw_request(self, op: int, payload: bytes, arena_len: int = None,
                     region=None):
        """One request/response exchange on the live socket, bounded by
        one per-request deadline end to end — under an active deadline
        scope that is ``min(deadline_s, remaining budget)``, so a hung
        worker can never cost more than the query has left. Any
        transport fault closes the connection (desync discipline) and
        raises RetryableError; an exhausted BUDGET raises
        DeadlineExceeded instead (the caller must see the query
        deadline, never a raw socket timeout).

        With ``arena_len`` the request payload is RESIDENT at
        ``arena_mm[0:arena_len]`` (the legacy single-buffer data
        plane): only the header — and the CRC trailer, computed over
        the ARENA bytes — crosses the socket, under
        ``wire_op | ARENA_FLAG``. With ``region`` (an
        ``sidecar_pool.ArenaRegion``, the slab data plane) the payload
        is resident inside the leased region and only the 20-byte
        region descriptor crosses the socket — N such requests ride N
        workers concurrently, nothing shared but the allocator."""
        from .utils import deadline as deadline_mod, integrity
        from .utils.errors import DataCorruption, RetryableError

        d = deadline_mod.current()
        budget_s = self._op_budget_s(op)
        if d is not None:
            d.check(f"sidecar_op_{op}")
            budget_s = min(budget_s, max(d.remaining(), 1e-3))
        deadline = time.monotonic() + budget_s
        # integrity (ISSUE 5): one boolean read when off — the frame is
        # byte-identical to the legacy protocol, same single sendall.
        # When on, the 4-byte CRC trailer rides the SAME sendall and the
        # worker echoes the flag back with a trailer this side verifies.
        use_crc = integrity.is_enabled()
        # srjt-trace (ISSUE 12): the active sampled context rides the
        # SAME sendall under the TRACE flag bit (negotiated per request
        # exactly like CRC_FLAG — one boolean read when tracing is off,
        # frame byte-identical); the worker's spans then parent to this
        # request's span across the process boundary. Packed BEFORE the
        # phase spans below open, so the remote parent is the enclosing
        # ``sidecar.request`` and the worker's spans are their siblings.
        from .utils import tracing

        tblob = tracing.wire_context() or b""
        try:
            # phase spans (null when no traced query is active): send =
            # request bytes in hand -> sendall returned (its CRC pass
            # nests inside), wait = -> reply header received (the
            # worker's whole handling), reply_read = the reply's bytes
            # into this process's hands
            with tracing.span("sidecar.client.send"):
                wire_op, plen, trailer, payload = self._frame_request(
                    op, payload, arena_len, region, use_crc
                )
                if tblob:
                    wire_op |= TRACE_FLAG
                self._sock.settimeout(budget_s)
                self._sock.sendall(
                    struct.pack("<IQ", wire_op, plen) + trailer + tblob + payload
                )
            with tracing.span("sidecar.client.wait"):
                hdr = self._recv_deadline(12, deadline)
                status, rlen = struct.unpack("<IQ", hdr)
                resp_crc = (
                    integrity.unpack_crc(self._recv_deadline(4, deadline))
                    if status & CRC_FLAG
                    else None
                )
            via = "stream"
            if status & ARENA_FLAG:
                # the worker answered through the shared arena: only the
                # header (and CRC trailer) crossed the socket — a client
                # without the mapping cannot honor the frame (desync)
                if region is not None:
                    if rlen > region.capacity:
                        raise ConnectionError(
                            "region-flagged response exceeds the leased region"
                        )
                    via = "region"
                elif self.arena_mm is None or rlen > len(self.arena_mm):
                    raise ConnectionError(
                        "arena-flagged response without a client-side arena"
                    )
                else:
                    via = "arena"
            with tracing.span("sidecar.client.reply_read", bytes=rlen, via=via):
                if via == "region":
                    resp = region.read(rlen)
                elif via == "arena":
                    resp = bytes(self.arena_mm[:rlen])
                else:
                    resp = self._recv_deadline(rlen, deadline) if rlen else b""
        except socket.timeout as e:
            self.close()
            if d is not None and d.done():
                raise d.exceeded(f"sidecar op {op}") from e
            raise RetryableError(
                f"sidecar: DEADLINE_EXCEEDED: op {op} exceeded "
                f"{budget_s:g}s request deadline"
            ) from e
        except (ConnectionError, OSError) as e:
            self.close()
            raise RetryableError(f"sidecar: Socket closed mid-request ({e})") from e
        if resp_crc is not None and integrity.is_enabled():
            from .utils import metrics

            metrics.registry().counter("sidecar.integrity.frames_checked").inc()
            try:
                with tracing.span(
                    "integrity.crc", bytes=len(resp), where="verify_reply"
                ):
                    integrity.verify(resp, resp_crc, "sidecar.response")
            except DataCorruption:
                # the stream is still framed (full frame consumed) but a
                # link that corrupts one frame gets the desync treatment:
                # close now, dial fresh on the retry that re-fetches
                self.close()
                raise
        self._last_io = time.monotonic()
        return status & ~_FLAG_MASK, resp

    def ping(self) -> str:
        """Heartbeat round-trip; returns the worker's backend name."""
        from .utils import metrics

        metrics.counter("sidecar.heartbeats").inc()
        if self._sock is None:
            self.connect()
        status, resp = self._raw_request(OP_PING, b"")
        if status != STATUS_OK:
            from .utils.errors import RetryableError

            self.close()
            raise RetryableError("sidecar: PING failed (worker unhealthy)")
        return resp.decode()

    def request(self, op: int, payload: bytes, arena_len: int = None,
                region=None) -> bytes:
        """Supervised exchange: reconnect when needed, heartbeat stale
        connections, classify worker-side errors into the
        fatal/retryable taxonomy. With metrics armed, every exchange
        records a latency histogram (``sidecar.request_us``) and
        failures count under ``sidecar.request_failures``.
        ``arena_len`` routes the request through the legacy
        single-buffer data plane and ``region`` through a leased slab
        region (see ``_raw_request``) — both under the SAME deadline
        clamp, CRC protocol, and taxonomy as a stream frame.

        srjt-trace (ISSUE 12): one ``sidecar.request`` span per
        exchange (heartbeat + redial included) when a traced query is
        active — this span is what the worker's cross-process span
        parents to, since ``_raw_request`` packs the CURRENT span id
        into the wire context."""
        from .utils import tracing

        with tracing.span("sidecar.request", op=op_name(op)):
            return self._request(op, payload, arena_len, region)

    def _request(self, op: int, payload: bytes, arena_len: int = None,
                 region=None) -> bytes:
        from .utils import metrics
        from .utils.errors import (
            DataCorruption,
            DeadlineExceeded,
            FatalDeviceError,
            RetryableError,
        )

        if self._sock is None:
            # connect() owns the reconnect accounting (attribute +
            # metric, REDIALS only) — counting here too double-counted
            # every redial and mislabeled the initial dial
            self.connect()
        elif time.monotonic() - self._last_io > self.heartbeat_s:
            try:
                self.ping()
            except RetryableError:
                # stale connection died quietly: one immediate redial,
                # then the request proceeds (or fails retryably)
                self.connect()
        armed = metrics.is_enabled()
        # the clock is read unconditionally (one perf_counter pair per
        # socket round-trip): the per-op latency histogram below is
        # PRODUCT state — adaptive deadlines (ISSUE 9) derive from it —
        # not gated instrumentation
        t0 = time.perf_counter()
        try:
            status, resp = self._raw_request(op, payload, arena_len, region)
        except Exception as e:
            metrics.counter("sidecar.request_failures").inc()
            if isinstance(e, RetryableError) and "DEADLINE_EXCEEDED" in str(e):
                # a timed-out request is the strongest latency sample
                # there is: recording the elapsed budget keeps the
                # adaptive quantile self-correcting (an over-tight
                # clamp pushes q99 back up instead of repeating)
                metrics.registry().histogram(
                    f"sidecar.op_lat_us.{op_name(op)}"
                ).record((time.perf_counter() - t0) * 1e6)
            raise
        if status == STATUS_OK:
            # only SUCCESSFUL exchanges feed the adaptive/quarantine
            # baselines (timeouts feed them above, as the strong slow
            # signal): a storm of fast worker-side ERROR replies —
            # Overloaded sheds, corruption rejects — must not collapse
            # the op-class p50 and turn healthy latencies into strikes
            metrics.registry().histogram(
                f"sidecar.op_lat_us.{op_name(op)}"
            ).record((time.perf_counter() - t0) * 1e6)
        if armed:
            metrics.counter("sidecar.requests").inc()
            metrics.histogram("sidecar.request_us").record(
                (time.perf_counter() - t0) * 1e6
            )
        if status == STATUS_OK:
            return resp
        msg = resp.decode("utf-8", "replace")
        if status == STATUS_CAST_ERROR:
            # semantic ANSI failure: transport healthy, not retryable —
            # surface the protocol payload to the caller unchanged
            raise _cast_error_from_wire(resp)
        # worker-side failure text carries the taxonomy prefix from the
        # worker's own op_boundary classification
        if msg.startswith("DataCorruption:"):
            # the WORKER's CRC check rejected our request frame: the
            # payload rotted in flight — retryable, the retry re-sends
            # (checked before the RetryableError prefix: corruption is
            # its own class so chaos assertions can tell them apart)
            raise DataCorruption(f"sidecar worker: {msg}")
        if msg.startswith("Overloaded:"):
            # the WORKER's serving layer shed at admission (ISSUE 8):
            # the scheduler there is saturated, not broken — same
            # retryable Overloaded class on this side (checked before
            # the generic RetryableError prefix so shed accounting can
            # tell admission pressure from transport faults; the
            # retry_after_s field does not survive the wire — the
            # class and cause text do)
            from .utils.errors import Overloaded

            raise Overloaded(f"sidecar worker: {msg}")
        if msg.startswith("RetryableError:"):
            raise RetryableError(f"sidecar worker: {msg}")
        if msg.startswith("FatalDeviceError:"):
            raise FatalDeviceError(f"sidecar worker: {msg}")
        if msg.startswith("DeadlineExceeded:"):
            # the WORKER's own budget died (it inherits SRJT_DEADLINE_SEC
            # through spawn_worker's env): same non-retryable class on
            # this side, so the breaker records a failure, never a
            # success, and the caller sees the deadline — not a raw
            # RuntimeError
            raise DeadlineExceeded(f"sidecar worker: {msg}")
        # worker-side SEMANTIC error (bad payload, worker API misuse)
        # that round-tripped a healthy transport: deliberately NOT a
        # taxonomy member — the breaker must record success and neither
        # retry nor host-fallback may engage for it
        raise RuntimeError(f"sidecar worker: {msg}")  # srjt-lint: allow-raise(semantic wire error on a healthy transport; taxonomy-wrapping would trip the breaker or retry a non-transient failure)

    # -- degrade-to-host orchestration ---------------------------------------

    def call(self, op: int, payload: bytes) -> bytes:
        """Run ``op`` on the worker under the retry orchestrator;
        degrade to the in-process host-CPU engine when the worker is
        gone. The degrade is BOUNDED three ways (ISSUE 3): the worst
        retry case is max_attempts x (deadline + backoff) — with every
        socket deadline and backoff truncated to the remaining query
        budget; an already-exhausted budget raises DeadlineExceeded up
        front (the host engine cannot run in zero time either); and the
        process-global circuit BREAKER fast-fails straight to the host
        engine while open — no dial, no timeout wait — restoring device
        mode via one half-open probe after the cooldown."""
        from .utils import deadline as deadline_mod, metrics, retry
        from .utils.errors import DeadlineExceeded, DeviceError

        deadline_mod.check(f"sidecar_op_{op}")
        br = breaker()
        if not br.allow():
            # open breaker: the device path is known-bad — degrade
            # immediately, without paying a dial or a timeout wait
            self.host_fallbacks += 1
            metrics.counter("sidecar.host_fallbacks").inc()
            metrics.event("sidecar.breaker_fast_fail", op=op_name(op))
            return as_bytes(_dispatch(op, payload, "host-fallback"))
        try:
            resp = retry.call_with_retry(
                self.request, op, payload, op_name=f"sidecar_op_{op}"
            )
        except DeadlineExceeded:
            # the budget died waiting on the device path: a supervision
            # failure for breaker accounting, but the caller gets the
            # deadline error — there is no time left to degrade into.
            # DELIBERATE conflation: a device path that cannot answer
            # within the budgets the workload actually uses is, for
            # breaker purposes, unavailable — opening means later calls
            # get the host engine's answer inside their budget instead
            # of burning it waiting, and the half-open probe restores
            # device mode the moment it keeps up again. A COOPERATIVE
            # CANCEL is different: a user stopping their query says
            # nothing about device health, so it releases the probe
            # slot with no verdict instead of counting a failure.
            d = deadline_mod.current()
            if d is not None and d.cancelled() and not d.expired():
                br.abort_probe()
            else:
                br.record_failure(cause="deadline")
            self.close()
            raise
        except DeviceError as e:
            # fatal worker (or retry exhaustion): the op still completes
            # — same kernels, host backend, in-process
            br.record_failure(cause=type(e).__name__)
            self.host_fallbacks += 1
            metrics.counter("sidecar.host_fallbacks").inc()
            metrics.event(
                "sidecar.degrade_to_host", op=op_name(op), cls=type(e).__name__
            )
            self.close()
            return as_bytes(_dispatch(op, payload, "host-fallback"))
        except Exception:
            # semantic errors (ANSI cast failures, worker API errors)
            # round-tripped the transport: a healthy device path
            br.record_success()
            raise
        except BaseException:
            # interrupt/exit mid-request: no health verdict either way —
            # just release a half-open probe slot so the breaker cannot
            # wedge in half-open with a probe that never settles
            br.abort_probe()
            raise
        br.record_success()
        return resp

    # -- observability -------------------------------------------------------

    def worker_stats(self, fold: bool = True, timeout_s: float = None) -> dict:
        """Poll the worker's STATS verb: returns the worker's metrics
        snapshot ({"backend", "snapshot"}). With ``fold`` (default) the
        worker's counters land in THIS process's registry via
        utils/metrics.fold_worker_counters (gauges under
        ``sidecar.worker.*``).

        The poll rides a THROWAWAY connection under its own short
        probe deadline (``SRJT_SIDECAR_STATS_TIMEOUT_SEC``, default
        5 s — the native stats_json contract): it never touches the
        supervised socket (no frame interleaving with an in-flight
        data op), never waits out the heavy-op deadline on a wedged
        worker, and never counts itself into ``sidecar.requests`` or
        the ``sidecar.request_us`` latency histogram it exists to
        report."""
        import json

        from .utils import metrics
        from .utils.errors import RetryableError

        if timeout_s is None:
            timeout_s = _env_seconds("SRJT_SIDECAR_STATS_TIMEOUT_SEC")
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.settimeout(float(timeout_s))
        try:
            s.connect(self.sock_path)
            s.sendall(struct.pack("<IQ", OP_STATS, 0))
            hdr = _recv_exact(s, 12)
            status, rlen = struct.unpack("<IQ", hdr)
            if rlen > (4 << 20):
                # same guard as the native twin: a desynced stream's
                # garbage length must not drive a giant allocation (a
                # registry snapshot is KBs, not GBs)
                raise ConnectionError(f"implausible STATS length {rlen}")
            resp = _recv_exact(s, rlen) if rlen else b""
        except (OSError, ConnectionError) as e:
            raise RetryableError(
                f"sidecar: UNAVAILABLE: STATS probe failed ({e})"
            ) from e
        finally:
            s.close()
        if (status & ~_FLAG_MASK) != STATUS_OK:
            raise RetryableError("sidecar: STATS failed (worker unhealthy)")
        try:
            stats = json.loads(resp.decode("utf-8", "replace"))
        except ValueError as e:
            # a desynced stream / non-worker peer answering garbage
            # stays inside the probe's retryable contract — the stats
            # poll must outlive its subject, never crash the caller
            raise RetryableError(
                f"sidecar: malformed STATS payload ({e})"
            ) from e
        if fold:
            metrics.fold_worker_counters(
                (stats.get("snapshot") or {}).get("counters")
            )
        return stats


# ---------------------------------------------------------------------------
# the sidecar path's circuit breaker (process-global: one device path,
# one health verdict — every SupervisedClient shares it)
# ---------------------------------------------------------------------------

_BREAKER = None
_BREAKER_LOCK = threading.Lock()


def breaker():
    """The process-global sidecar CircuitBreaker (utils/deadline.py):
    after ``SRJT_BREAKER_THRESHOLD`` consecutive supervision failures
    it opens and ``SupervisedClient.call`` degrades to the host engine
    without dialing; a half-open probe after
    ``SRJT_BREAKER_COOLDOWN_SEC`` restores device mode on success.
    Lazy so env knobs are read at first use, not import."""
    global _BREAKER
    if _BREAKER is None:
        with _BREAKER_LOCK:
            if _BREAKER is None:
                from .utils.deadline import CircuitBreaker

                _BREAKER = CircuitBreaker("sidecar.breaker")
    return _BREAKER


def _cast_error_from_wire(resp: bytes):
    from .ops.cast_string import CastError

    if len(resp) < 9:
        from .utils.errors import RetryableError

        return RetryableError("sidecar: malformed cast-error frame (desync)")
    (row,) = struct.unpack_from("<q", resp, 0)
    is_null = resp[8] != 0
    val = None if is_null else resp[9:].decode("utf-8", "replace")
    return CastError(int(row), val)


def _reap_worker(proc) -> None:
    """Terminate and REAP a worker on a failed spawn: a leaked child
    holds the chip (and a process-table slot) for the executor's
    lifetime; a dead-but-unwaited one is a zombie. Best-effort — spawn
    cleanup must never mask the original startup error."""
    try:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except Exception:  # srjt-lint: allow-broad-except(best-effort escalation to SIGKILL; reaping must never mask the original startup error)
                proc.kill()
                proc.wait(timeout=10)
        else:
            proc.wait()  # already exited: reap immediately
    except Exception:  # srjt-lint: allow-broad-except(best-effort reap of a dying child; the caller re-raises the original startup error)
        pass


def spawn_worker(
    sock_path: str = None,
    python_exe: str = None,
    startup_timeout_s: float = 60.0,
    env: dict = None,
):
    """Spawn ``python -m spark_rapids_jni_tpu.sidecar``, wait for its
    socket, and verify a PING handshake round-trips (the pure-Python
    twin of SidecarClient's fork/exec path in native/src/sidecar.cc).
    Returns (Popen, sock_path). Caller owns shutdown (OP_SHUTDOWN or
    terminate()). EVERY failure path — connect refused until timeout,
    worker exit during startup, a failed handshake, even an interrupt
    mid-wait — terminates and reaps the child before re-raising."""
    import subprocess
    import tempfile

    from .utils.errors import FatalDeviceError

    if sock_path is None:
        fd, tmp = tempfile.mkstemp(prefix="srjt-sidecar-")
        os.close(fd)
        os.unlink(tmp)
        sock_path = tmp + ".sock"
    full_env = dict(os.environ)
    pkg_parent = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    pp = full_env.get("PYTHONPATH", "")
    if pkg_parent not in pp.split(os.pathsep):
        full_env["PYTHONPATH"] = f"{pkg_parent}{os.pathsep}{pp}" if pp else pkg_parent
    if env:
        full_env.update(env)
    proc = subprocess.Popen(
        [python_exe or sys.executable, "-m", "spark_rapids_jni_tpu.sidecar",
         "--socket", sock_path],
        env=full_env,
    )
    try:
        t_deadline = time.monotonic() + startup_timeout_s
        while True:
            probe = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            # generous per-probe timeout (bounded by the startup budget):
            # the worker only listens once its backend is up, so a
            # connected PING answers immediately — a short timeout here
            # would re-PING on scheduling stalls and skew the worker's
            # exact per-op request accounting
            probe.settimeout(min(10.0, max(1.0, t_deadline - time.monotonic())))
            try:
                probe.connect(sock_path)
                # the socket existing is not the worker being healthy:
                # a PING must round-trip before the caller gets the
                # process (the C++ twin's connect-then-PING discipline)
                probe.sendall(struct.pack("<IQ", OP_PING, 0))
                hdr = _recv_exact(probe, 12)
                status, rlen = struct.unpack("<IQ", hdr)
                if rlen:
                    _recv_exact(probe, rlen)
                if (status & ~_FLAG_MASK) != STATUS_OK:
                    raise FatalDeviceError(
                        "sidecar worker failed the startup PING handshake"
                    )
                return proc, sock_path
            except (OSError, ConnectionError):
                pass  # not listening / not answering yet: keep waiting
            finally:
                probe.close()
            if proc.poll() is not None:
                raise FatalDeviceError(
                    f"sidecar worker exited during startup (rc={proc.returncode})"
                )
            if time.monotonic() > t_deadline:
                raise FatalDeviceError("sidecar worker startup timed out")
            time.sleep(0.05)
    except BaseException:
        _reap_worker(proc)
        raise


def serve(sock_path: str) -> None:
    # The worker owns the device: with no JAX_PLATFORMS in its
    # environment jax picks the chip, and an inherited one (the tests
    # pin "cpu") is read by jax itself at import.
    import threading

    import jax

    import spark_rapids_jni_tpu  # noqa: F401  (x64 flag before arrays)

    backend = jax.default_backend()

    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        os.unlink(sock_path)
    except FileNotFoundError:
        pass
    srv.bind(sock_path)
    srv.listen(16)
    # the parent polls for this line to know the device is up
    print(f"SRJT_SIDECAR_READY backend={backend}", flush=True)

    def shutdown():
        # client-initiated: unlink before the hard exit so no stale
        # socket file outlives the worker
        try:
            os.unlink(sock_path)
        except FileNotFoundError:
            pass
        # os._exit skips atexit: an armed lockdep must persist the
        # worker's lock-order graph NOW or the CI gate never sees the
        # worker side of the package's locks
        from .analysis import lockdep as _lockdep
        from .utils import trace_sink

        _lockdep.flush_report()
        trace_sink.flush()  # same reason: the span log's exit flush
        os._exit(0)

    try:
        while True:
            conn, _ = srv.accept()
            t = threading.Thread(
                target=_handle_conn, args=(conn, backend, shutdown), daemon=True
            )
            t.start()
    finally:
        srv.close()
        try:
            os.unlink(sock_path)
        except FileNotFoundError:
            pass


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--socket", required=True)
    args = ap.parse_args()
    serve(args.socket)


if __name__ == "__main__":
    sys.exit(main())
