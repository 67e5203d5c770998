"""The request's share of its roofline: the least time the chip could
take for the bytes of the schema, over the time it was busy. HBM-bound:
every cell moves bytes and computes almost nothing."""
from benchlib.roofline import least_ms


def read(ctx):
    if ctx["busy_s"] <= 0:
        return None
    busy_ms = 1e3 * ctx["busy_s"] / len(ctx["requests"])
    return 100.0 * least_ms(ctx["request_bytes"], ctx["device"]["kind"]) / busy_ms
