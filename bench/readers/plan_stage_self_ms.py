"""The host time of plan stages that is in no operator: the self time of
every ``plan.<kind>`` span (its duration less what its child ``plan.*``
and ``op.*`` spans cover, found by parent ids), mean per request."""


def read(ctx):
    stages = {s["span"]: s["dur_us"] for s in ctx["spans"] if s["name"].startswith("plan.") and "span" in s}
    if not stages or not ctx["requests"]:
        return None
    for s in ctx["spans"]:
        if s.get("parent") in stages and s["name"].startswith(("plan.", "op.")):
            stages[s["parent"]] -= s["dur_us"]
    return sum(max(v, 0.0) for v in stages.values()) / 1e3 / len(ctx["requests"])
