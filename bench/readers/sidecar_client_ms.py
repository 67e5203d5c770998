"""The program's client side of a request, on the host clock: arena lease
and copy before the send, reply header parse after it."""


def read(ctx):
    t = ctx["client_s"]
    return 1e3 * sum(t) / len(t) if t else None
