"""Device time of the programs launched under a ``groupby.agg.<how>``
span (``_f64_sum_mean``: one a float64 sum or mean), mean per request."""
from benchlib import attribution


def read(ctx):
    return attribution.device_ms(ctx, attribution.prefix("groupby.agg."))
