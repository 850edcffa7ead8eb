"""Mean host time per `execute` span of the closed loop outside its
`launch` spans (the device waits), in ms: method execution's host work
(pattern and group lookups, assembly, exact distances) while the device
sits idle, from the program's tracer (`host_us`)."""


def read(ctx):
    if ctx.kind != "closed" or not ctx.spans or "execute" not in ctx.spans:
        return None
    h = ctx.spans["execute"]
    if "host_us" not in h or not h["count"]:
        return None
    return h["host_us"] / h["count"] / 1e3
