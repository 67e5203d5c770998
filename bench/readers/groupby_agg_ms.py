"""The group-by's aggregates: every ``groupby.agg.<how>`` span (one per
aggregate), mean per request."""


def read(ctx):
    hit = [s["dur_us"] for s in ctx["spans"] if s["name"].startswith("groupby.agg.")]
    if not hit or not ctx["requests"]:
        return None
    return sum(hit) / 1e3 / len(ctx["requests"])
