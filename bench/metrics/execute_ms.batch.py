"""Mean `execute` span (method execution of every routed group) per
batch of the closed loop, in ms, from the program's tracer."""


def read(ctx):
    if ctx.kind != "closed" or not ctx.spans or "execute" not in ctx.spans:
        return None
    h = ctx.spans["execute"]
    return h["sum_us"] / h["count"] / 1e3 if h["count"] else None
