"""Rows of every completed request over the traced window, first start to
last end: the same arithmetic as the end-to-end ``rows_per_s``, for a cell
whose rate between runs spreads too widely to carry a bound."""


def read(ctx):
    return ctx["rows_per_s"]
