"""Mean host time per batch inside the closed loop's `fanout` spans and
outside their `launch` spans, in ms: the fan-out's own host work
(padding, issuing each shard's launch, the copies' set-up) while no
launch is being issued or waited on, from the program's tracer
(`host_us`). A program without `fanout` spans gives nothing."""


def read(ctx):
    if ctx.kind != "closed" or not ctx.spans:
        return None
    fan, search = ctx.spans.get("fanout"), ctx.spans.get("search")
    if not fan or "host_us" not in fan or not search or not search["count"]:
        return None
    return fan["host_us"] / search["count"] / 1e3
