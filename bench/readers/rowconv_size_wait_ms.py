"""The transcode's wait for its sizes: the ``rowconv.sizes`` spans under
``op.convert_to_rows`` (the one device-to-host transfer that tells the
host the byte total, the largest row and each STRING column's longest
string), summed, mean per request. A program without the span reads
nothing."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx["spans"], "rowconv.sizes", len(ctx["requests"]))
