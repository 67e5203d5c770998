"""The worker's copy of the request's payload out of the arena region (or the
socket read), header parsed to bytes in hand: the
``sidecar.worker.payload_read`` span, mean per request."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx["spans"], "sidecar.worker.payload_read", len(ctx["requests"]))
