"""The one-chip joins' waits for the device: the ``device.wait`` spans
under an ``op.*_join`` span (the paged table's build, the output size, a
gathered STRING column's character count), summed, mean per request."""
from benchlib import attribution


def read(ctx):
    return attribution.waits_ms(ctx, attribution.JOINS.__contains__)
