"""Device program launches per request in the traced window."""
from benchlib.tracered import programs_in


def read(ctx):
    n = programs_in(ctx["trace"], ctx["w0_ns"], ctx["w1_ns"])
    return n / len(ctx["requests"]) if n else None
