"""Device time of the cross-chip merge per batch of the closed loop, in
ms: the summed device seconds of the `merge_topk_accum` ops (the Pallas
merge of the shards' top-k, on the first chip) in the traced window over
the window's batches (`search` spans). The copies of the shards'
candidates to that chip run outside the chip's ops and are left out. A
program that runs no merge kernel (one chip, or off the TPU, where the
merge is an XLA top-k) gives nothing."""

from bench import trace_reduce


def read(ctx):
    if ctx.kind != "closed" or not ctx.trace or not ctx.spans:
        return None
    search = ctx.spans.get("search")
    t = trace_reduce.kernel_seconds(ctx.trace, "merge_topk_accum")
    if t <= 0 or not search or not search["count"]:
        return None
    return t / search["count"] * 1e3
