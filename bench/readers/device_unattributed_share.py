"""Percent of the device's busy time in programs matched to no
``device.launch``: the eager ``jnp`` calls (``jit_gather``,
``jit_searchsorted``, ...), which no layer's ``*_device_ms`` holds."""
from benchlib import attribution


def read(ctx):
    return attribution.unattributed_share(ctx)
