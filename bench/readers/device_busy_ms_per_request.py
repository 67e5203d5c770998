"""Milliseconds per request in which an operation ran on the device."""


def read(ctx):
    return 1e3 * ctx["busy_s"] / len(ctx["requests"]) if ctx["busy_s"] > 0 else None
