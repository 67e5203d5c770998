"""The exchange layer's waits for the device: the ``device.wait`` spans
under an ``exchange.*`` span (``exchange.table``'s counts,
``exchange.gather``'s slots and overflow flags), summed, mean per request.
``exchange_ms`` less this is the layer's host cost."""
from benchlib import attribution


def read(ctx):
    return attribution.waits_ms(ctx, attribution.prefix("exchange."))
