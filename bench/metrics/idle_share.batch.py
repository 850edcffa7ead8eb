"""Share of the traced closed-loop window in which no op ran on the
device, in %: 100 · (1 − busy union / window)."""


def read(ctx):
    t = ctx.trace
    if ctx.kind != "closed" or not t or t["window_s"] <= 0 or not t["devices"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
