"""Peak device memory of the process that owns the chip."""


def read(ctx):
    return ctx["memory_peak_bytes"] or None
