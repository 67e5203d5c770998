"""Host time of a request under the exchange layer's spans: every
``exchange.*`` span of the window (``.table`` an all-to-all program,
``.groupby`` and ``.join`` the shard-local stages it feeds, ``.gather``
the way out of the mesh; they never nest), summed, mean per request."""


def read(ctx):
    hit = [s["dur_us"] for s in ctx["spans"] if s["name"].startswith("exchange.")]
    if not hit or not ctx["requests"]:
        return None
    return sum(hit) / 1e3 / len(ctx["requests"])
