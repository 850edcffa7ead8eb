"""Share of the query slots launched in the closed loop's searches that
were padding, in %: 100 · `pad_slots` / `slots`, the counters of the
program's `launch` spans totalled over every `search` tree."""


def read(ctx):
    if ctx.kind != "closed" or not ctx.spans or "search" not in ctx.spans:
        return None
    c = ctx.spans["search"].get("counters", {})
    if not c.get("slots"):
        return None
    return 100.0 * c.get("pad_slots", 0) / c["slots"]
