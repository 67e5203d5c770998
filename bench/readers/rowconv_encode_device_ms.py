"""Device time of the programs launched under ``op.convert_to_rows`` (the
sizes program, the encode, the blob's bitcast), mean per request."""
from benchlib import attribution


def read(ctx):
    return attribution.device_ms(ctx, "op.convert_to_rows".__eq__)
