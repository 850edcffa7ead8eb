"""Mean host time per `route` span of the closed loop outside its
`launch` spans (the device waits), in ms: routing's host work while the
device sits idle, from the program's tracer (`host_us`)."""


def read(ctx):
    if ctx.kind != "closed" or not ctx.spans or "route" not in ctx.spans:
        return None
    h = ctx.spans["route"]
    if "host_us" not in h or not h["count"]:
        return None
    return h["host_us"] / h["count"] / 1e3
