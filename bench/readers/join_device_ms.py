"""Device time of the programs launched under an ``op.*_join`` span (the
Pallas probe ``_probe_impl``; on the XLA tier ``lexsort`` and a STRING
key's lanes), mean per request."""
from benchlib import attribution


def read(ctx):
    return attribution.device_ms(ctx, attribution.JOINS.__contains__)
