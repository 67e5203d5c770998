"""The worker's write of the reply body into the region, the arena or the
socket: the ``sidecar.worker.reply_write`` span, mean per request."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx["spans"], "sidecar.worker.reply_write", len(ctx["requests"]))
