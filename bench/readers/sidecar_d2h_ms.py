"""The worker's ``np.asarray`` of the result's device arrays, the wait for the
kernel included: the ``sidecar.worker.d2h`` span, mean per request."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx["spans"], "sidecar.worker.d2h", len(ctx["requests"]))
