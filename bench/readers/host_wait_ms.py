"""The host's waits for the device: every ``device.wait`` span of the
window (one at each sync site of the program, annotation ``what``),
summed, mean per request."""
from benchlib import attribution


def read(ctx):
    return attribution.waits_ms(ctx)
