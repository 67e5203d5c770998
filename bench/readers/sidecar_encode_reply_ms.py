"""The worker's host copies of the result into wire bytes (``tobytes``,
``struct.pack``, ``join``): the ``sidecar.worker.encode_reply`` span, mean
per request."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx["spans"], "sidecar.worker.encode_reply", len(ctx["requests"]))
