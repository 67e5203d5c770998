"""The measured window and the arithmetic of its end-to-end metrics.

The window, word for word (bench/README.md repeats it):

  Closed loop, one client. The window opens at the start of the first
  measured request. Requests are issued back to back until ``--seconds``
  have passed since it opened; the one in flight then is let finish and
  is counted. ``rows_per_s`` is the input rows of every completed
  request over (end of the last - start of the first). Nothing but
  requests sits inside the window: results are kept as handles and
  compared after it closes.
"""

from __future__ import annotations

import gc
import statistics
import time
from typing import Callable, List, NamedTuple


class Request(NamedTuple):
    start: float  # perf_counter seconds
    end: float
    rows: int


def closed_loop(issue: Callable[[int], int], seconds: float, keep: Callable[[int, object], None] = None,
                clock=time.perf_counter) -> List[Request]:
    """Issue requests back to back for ``seconds``. ``issue(i)`` runs the
    i-th request to its last byte and returns (rows, handle). The handle
    goes to ``keep`` (which must not compute) and is compared later."""
    done: List[Request] = []
    gc.collect()
    gc.freeze()  # the harness's own host arrays are not walked mid-window
    try:
        opened = clock()
        i = 0
        while True:
            start = clock() if i else opened
            rows, handle = issue(i)
            end = clock()
            done.append(Request(start, end, rows))
            if keep is not None:
                keep(i, handle)
            i += 1
            if end - opened >= seconds:
                return done
    finally:
        gc.unfreeze()


def latency_p50_ms(requests: List[Request]) -> float:
    """Median over ALL requests of the window."""
    return 1e3 * statistics.median(r.end - r.start for r in requests)


def rows_per_s(requests: List[Request]) -> float:
    """Rows of every completed request over first start to last end."""
    span = requests[-1].end - requests[0].start
    return sum(r.rows for r in requests) / span


def request_lines(requests: List[Request]) -> List[str]:
    """One line a request: start and end since the window opened, rows,
    and the gap since the previous request ended (time that belongs to
    no request)."""
    t0 = requests[0].start
    out, prev_end = [], t0
    for i, r in enumerate(requests):
        out.append(f"request {i} start_s {r.start - t0:.6f} end_s {r.end - t0:.6f} "
                   f"ms {1e3 * (r.end - r.start):.3f} rows {r.rows} gap_ms {1e3 * (r.start - prev_end):.3f}")
        prev_end = r.end
    return out
