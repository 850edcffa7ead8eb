"""Mean device launches per `search` (one batch) of the closed loop:
the `launches` counter of the program's `launch` spans (each method
chunk, each routing kernel), totalled over the search's tree."""


def read(ctx):
    if ctx.kind != "closed" or not ctx.spans or "search" not in ctx.spans:
        return None
    h = ctx.spans["search"]
    n = h.get("counters", {}).get("launches")
    if n is None or not h["count"]:
        return None
    return n / h["count"]
