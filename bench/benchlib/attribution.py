"""Device time under the span that asked for it.

The program records two things beside its spans (``utils/tracing.py``):
a ``device.wait`` span around every wait of the host for the device
(annotation ``what``: the site), and a ``device.launch`` event
(annotation ``program``) in front of every program the system builds
itself. Both are children of whatever span was open, so both have a
layer: that of their nearest ancestor in the layer's span family, the
families the host metrics read.

*Waits* are summed as they are. *Device time* is matched by order: on
each device the k-th program inside the window (``SLACK_NS`` wider at each
end than the host's clock says) whose name is
``jit_<program>(<fingerprint>)`` is the k-th ``device.launch`` of that
``program`` inside the window (one host thread dispatches, a chip runs its
programs in the order they were enqueued, and the closed loop's window
starts and ends between requests, so both sequences are whole). The
program's duration goes to its launch's layer. Where the two counts of a
name differ nothing is guessed: every reader that would read a launch of
that name returns ``None``. A device that ran none of a name's programs
(a program placed on the other chips) gives that name nothing. Over
several devices a number is the mean over the devices, as ``busy_seconds``
is.

Works on ``ctx`` as ``bench/run.py`` builds it: ``ctx["spans"]`` (name,
``ts``, ``dur_us``, ``span``, ``parent``, ``annotations``) and
``ctx["trace"]["devices"][*]["programs"]`` ([name, start_ns, dur_ns]).
A program without these records (the parent of the PR that added them)
gives every reader ``None``.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

WAIT, LAUNCH = "device.wait", "device.launch"
# The anchor puts the host's clock on the trace's to about a millisecond (q1, PR 36: a program
# whose launch lay 0.65 ms inside the window started 0.19 ms "before" it), and the harness
# traces nothing but the window's requests: a program this close outside the window is the window's.
SLACK_NS = 5e6
JOINS = frozenset(f"op.{how}_join" for how in ("inner", "left", "full", "left_semi", "left_anti"))
GROUPBY_ORDER = frozenset(("groupby.sort", "groupby.segments", "groupby.keys"))

Pred = Callable[[str], bool]


def prefix(*heads: str) -> Pred:
    return lambda name: name.startswith(heads)


def owner(span: dict, by_id: Dict[str, dict], stop: Pred) -> Optional[str]:
    """The name of the nearest ancestor of ``span`` that ``stop`` accepts."""
    p = by_id.get(span.get("parent"))
    while p is not None:
        if stop(p["name"]):
            return p["name"]
        p = by_id.get(p.get("parent"))
    return None


def _by_id(ctx: dict) -> Dict[str, dict]:
    return {s["span"]: s for s in ctx["spans"] if "span" in s}


def _belongs(ctx: dict, records: List[dict], member: Pred, stop: Optional[Pred]) -> List[bool]:
    by_id = _by_id(ctx)
    out = []
    for s in records:
        name = owner(s, by_id, stop or member)
        out.append(name is not None and member(name))
    return out


def waits_ms(ctx: dict, member: Optional[Pred] = None, stop: Optional[Pred] = None) -> Optional[float]:
    """The ``device.wait`` spans whose nearest ancestor accepted by ``stop``
    (default: by ``member``) is accepted by ``member`` (default: every
    wait), summed, mean per request. ``None`` where the run has no such
    span."""
    waits = [s for s in ctx["spans"] if s["name"] == WAIT]
    if member is not None:
        waits = [s for s, ok in zip(waits, _belongs(ctx, waits, member, stop)) if ok]
    if not waits or not ctx["requests"]:
        return None
    return sum(s["dur_us"] for s in waits) / 1e3 / len(ctx["requests"])


def program_of(device_name: str) -> Optional[str]:
    """``jit__f64_sum_mean(5939720371230696005)`` -> ``_f64_sum_mean``."""
    base = device_name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else None


def match(ctx: dict) -> dict:
    """Every device's programs inside the window against the launches
    inside the window, by name and order. Returns::

        {"launches": [span, ...],            # in launch order
         "ns": [[ns on device 0, ...], ...], # a row a launch, a column a device
         "mismatched": {program: [launches, [programs on each device]]},
         "unmatched_ns": [ns on each device], # programs with no launch
         "unmatched_names": {device program name: ns, summed over devices}}

    Memoised on ``ctx``."""
    memo = ctx.get("_attribution")
    if memo is not None:
        return memo
    launches = sorted((s for s in ctx["spans"] if s["name"] == LAUNCH), key=lambda s: s["ts"])
    by_program: Dict[str, List[int]] = {}
    for i, s in enumerate(launches):
        by_program.setdefault(s["annotations"]["program"], []).append(i)
    devices = list(ctx["trace"]["devices"].values())
    w0, w1 = ctx["w0_ns"], ctx["w1_ns"]
    ns = [[0.0] * len(devices) for _ in launches]
    counts: Dict[str, List[int]] = {p: [0] * len(devices) for p in by_program}
    unmatched_ns, unmatched_names = [0.0] * len(devices), {}
    for d, dev in enumerate(devices):
        seen: Dict[str, List[float]] = {}
        for name, start, dur in sorted(dev["programs"], key=lambda p: p[1]):
            if not w0 - SLACK_NS <= start < w1 + SLACK_NS:
                continue
            program = program_of(name)
            if program in by_program:
                seen.setdefault(program, []).append(float(dur))
            else:
                unmatched_ns[d] += dur
                unmatched_names[name] = unmatched_names.get(name, 0.0) + dur
        for program, durs in seen.items():
            counts[program][d] = len(durs)
            if len(durs) == len(by_program[program]):
                for i, dur in zip(by_program[program], durs):
                    ns[i][d] = dur
    mismatched = {}
    for program, idx in by_program.items():
        ran = [c for c in counts[program] if c]
        if not ran or any(c != len(idx) for c in ran):
            mismatched[program] = [len(idx), counts[program]]
    memo = ctx["_attribution"] = {"launches": launches, "ns": ns, "mismatched": mismatched,
                                  "unmatched_ns": unmatched_ns, "unmatched_names": unmatched_names}
    return memo


def device_ms(ctx: dict, member: Pred, stop: Optional[Pred] = None) -> Optional[float]:
    """Device time of the programs launched under the layer's spans, mean
    over the devices, mean per request. ``None`` where the layer launched
    nothing, or where a name it launched has another count on a device."""
    m = match(ctx)
    mine = [i for i, ok in enumerate(_belongs(ctx, m["launches"], member, stop)) if ok]
    if not mine or not ctx["requests"]:
        return None
    if any(m["launches"][i]["annotations"]["program"] in m["mismatched"] for i in mine):
        return None
    n_dev = max(len(m["unmatched_ns"]), 1)
    return sum(sum(m["ns"][i]) for i in mine) / n_dev / 1e6 / len(ctx["requests"])


def unattributed_share(ctx: dict) -> Optional[float]:
    """Percent of the device's busy time in programs matched to no launch
    (eager ``jnp`` calls: ``jit_gather``, ``jit_cumsum``, ...). ``None``
    without a launch in the window (a program that records none), with a
    name whose counts differ, or without busy time."""
    m = match(ctx)
    if not m["launches"] or m["mismatched"] or ctx["busy_s"] <= 0:
        return None
    n_dev = max(len(m["unmatched_ns"]), 1)
    return 100.0 * (sum(m["unmatched_ns"]) / n_dev / 1e9) / ctx["busy_s"]
