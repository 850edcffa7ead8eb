"""Mean `route` span per micro-batch predicate group of the open loop,
in ms, from the program's tracer."""


def read(ctx):
    if ctx.kind != "open" or not ctx.spans or "route" not in ctx.spans:
        return None
    h = ctx.spans["route"]
    return h["sum_us"] / h["count"] / 1e3 if h["count"] else None
