"""Device time of the mesh programs launched under an ``exchange.*`` span
(``count_program``, ``exchange_program``, ``groupby_program``,
``join_program``), mean over the chips, mean per request."""
from benchlib import attribution


def read(ctx):
    return attribution.device_ms(ctx, attribution.prefix("exchange."))
