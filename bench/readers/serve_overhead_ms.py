"""What serving adds around execution: ``serve.query`` less ``serve.run``."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    n = len(ctx["requests"])
    query, run = span_mean_ms(ctx["spans"], "serve.query", n), span_mean_ms(ctx["spans"], "serve.run", n)
    return None if query is None or run is None else query - run
