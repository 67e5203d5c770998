"""Host seconds in ``plan.compile_ir`` during set-up, all queries of a request."""


def read(ctx):
    return ctx["facts"].get("plan_compile_ir_s")
