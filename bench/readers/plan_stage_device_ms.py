"""Device time of the programs a plan stage launches itself: those whose
``device.launch`` lies under a ``plan.<kind>`` span with no ``op.*`` span
between (a Filter's or Project's one ``_body``, an aggregate's
``_to_float64_program``), mean per request."""
from benchlib import attribution


def read(ctx):
    return attribution.device_ms(ctx, attribution.prefix("plan."), stop=attribution.prefix("plan.", "op."))
