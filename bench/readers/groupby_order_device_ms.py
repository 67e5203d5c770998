"""Device time of the programs launched under ``groupby.sort``,
``groupby.segments`` or ``groupby.keys`` (``lexsort``, a STRING key's
``_string_lanes``), mean per request."""
from benchlib import attribution


def read(ctx):
    return attribution.device_ms(ctx, attribution.GROUPBY_ORDER.__contains__)
