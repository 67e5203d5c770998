"""The one-chip equi-joins of the operator tier: every ``op.inner_join``,
``op.left_join``, ``op.full_join``, ``op.left_semi_join`` and
``op.left_anti_join`` span, mean per request."""

JOINS = frozenset(f"op.{how}_join" for how in ("inner", "left", "full", "left_semi", "left_anti"))


def read(ctx):
    hit = [s["dur_us"] for s in ctx["spans"] if s["name"] in JOINS]
    if not hit or not ctx["requests"]:
        return None
    return sum(hit) / 1e3 / len(ctx["requests"])
