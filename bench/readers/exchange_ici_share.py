"""The exchanges' share of the chip's ICI peak: the least time the bytes
that have to leave a chip could take (the driver's
``exchange_bytes_off_chip``: schema and the reference's row counts) over
the time the all-to-all operations took (``exchange_collective_ms``'s
seconds). The three numbers it is made of go to stderr beside it. ``None``
where the trace holds no all-to-all or the driver counted no exchange:
never 0."""
import sys

from benchlib import loader
from benchlib.exchange_bytes import ici_peak


def read(ctx):
    ms = loader.module("readers", "exchange_collective_ms").read(ctx)
    moved = ctx["facts"].get("exchange_bytes_off_chip")
    if not ms or not moved:
        return None
    peak = ici_peak(ctx["device"]["kind"])["ici_bytes_per_s"]
    share = 100.0 * (moved / peak) / (ms / 1e3)
    print(f"exchange_ici_share {share:.6g}% = {moved:.0f} bytes off a chip a request / {peak:.6g} bytes/s "
          f"/ {ms / 1e3:.6g} s of all-to-all a request", file=sys.stderr)
    return share
