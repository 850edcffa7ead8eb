"""Mean `fanout` time per batch of the closed loop, in ms: from the first
shard's launch of each routed group on a row-sharded corpus until its
merged answer is on the host, summed over the batch's groups, from the
program's tracer. A program without `fanout` spans gives nothing."""


def read(ctx):
    if ctx.kind != "closed" or not ctx.spans:
        return None
    fan, search = ctx.spans.get("fanout"), ctx.spans.get("search")
    if not fan or not search or not search["count"]:
        return None
    return fan["sum_us"] / search["count"] / 1e3
