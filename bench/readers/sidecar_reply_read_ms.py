"""The client's copy of the reply out of the region (or the arena, or the socket
read): the ``sidecar.client.reply_read`` span, mean per request."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx["spans"], "sidecar.client.reply_read", len(ctx["requests"]))
