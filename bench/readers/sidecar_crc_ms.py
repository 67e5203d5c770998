"""The four frame-level CRC passes of a request, client and worker (request,
verify_request, reply, verify_reply): the ``integrity.crc`` spans, summed,
mean per request."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx["spans"], "integrity.crc", len(ctx["requests"]))
