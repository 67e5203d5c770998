"""Device time of a request's all-to-all operations: the union of the
intervals of the operations whose name holds ``all-to-all`` or
``all_to_all`` ('XLA Ops' line: the v5e names them
``all_to_all.67 = u32[4,1,674772] all-to-all(...)``), on the device where
that union is longest, mean per request. ``None`` where the trace holds
none: never 0."""
import numpy as np

from benchlib.tracered import _merged


def collective_seconds(trace: dict, w0_ns: float, w1_ns: float) -> "float | None":
    longest = None
    for dev in trace["devices"].values():
        iv = np.array([[s, s + d] for name, s, d in dev["ops"] if "all-to-all" in name or "all_to_all" in name], float).reshape(-1, 2)
        iv = np.clip(iv, w0_ns, w1_ns)
        starts, ends = _merged(iv[iv[:, 1] > iv[:, 0]])
        if len(starts):
            longest = max(longest or 0.0, float((ends - starts).sum()) / 1e9)
    return longest


def read(ctx):
    s = collective_seconds(ctx["trace"], ctx["w0_ns"], ctx["w1_ns"])
    if not s or not ctx["requests"]:
        return None
    return 1e3 * s / len(ctx["requests"])
