"""The worker's one wait for the result's device arrays before it copies
them to the host: the ``device.wait`` span under ``sidecar.worker.d2h``,
mean per request. ``sidecar_d2h_ms`` less this is the copy."""
from benchlib import attribution


def read(ctx):
    return attribution.waits_ms(ctx, "sidecar.worker.d2h".__eq__)
