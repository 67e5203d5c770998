"""Mean time a request's queries spent executing (the ``serve.run`` span)."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx["spans"], "serve.run", len(ctx["requests"]))
