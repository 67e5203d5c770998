"""The group-by before its aggregates: ``groupby.sort`` (sorted_order),
``groupby.segments`` (segment ids, with the one host sync) and
``groupby.keys`` (the gather of the output keys), mean per request."""
from benchlib.tracered import span_mean_ms

NAMES = ("groupby.sort", "groupby.segments", "groupby.keys")


def read(ctx):
    n = len(ctx["requests"])
    found = [v for v in (span_mean_ms(ctx["spans"], name, n) for name in NAMES) if v is not None]
    return sum(found) if found else None
