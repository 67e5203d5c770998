"""The worker's ``_read_table``, wire bytes to device columns (per-column host
views and host-to-device puts; host time): the
``sidecar.worker.decode_table`` span, mean per request."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx["spans"], "sidecar.worker.decode_table", len(ctx["requests"]))
