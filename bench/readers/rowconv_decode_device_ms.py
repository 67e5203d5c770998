"""Device time of the programs launched under ``op.convert_from_rows`` (the
decode, and the stride probe or the row gather where they run), mean per
request. ``None`` from a program that records no launch there."""
from benchlib import attribution


def read(ctx):
    return attribution.device_ms(ctx, "op.convert_from_rows".__eq__)
