"""Mean time a request's queries waited in the scheduler's queue."""
from benchlib.tracered import span_mean_ms


def read(ctx):
    return span_mean_ms(ctx["spans"], "serve.queue_wait", len(ctx["requests"]))
