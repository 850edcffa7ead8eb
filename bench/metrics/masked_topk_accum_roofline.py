"""Share of its roofline that the exact-scan kernel reached in the
traced window, in %: the least time the chip needs for the necessary
work of every query prefilter served there (`bench/work`), over the
summed device time of the `masked_topk_accum` ops. The compute leg runs
at the configuration's `matmul_precision`: the bf16 peak over the
bfloat16 passes that precision takes for one float32 product."""

from bench import trace_reduce


def read(ctx):
    if not ctx.trace or not ctx.peaks or not ctx.prefilter_calls:
        return None
    t = trace_reduce.kernel_seconds(ctx.trace, "masked_topk_accum")
    if t <= 0:
        return None
    passes = ctx.peaks["matmul_passes"][ctx.cfg["matmul_precision"]]
    flops_per_s = ctx.peaks["flops_bf16_per_s"] / passes
    work = ctx.work("masked_topk_accum")
    least = 0.0
    for q in ctx.prefilter_calls:
        if q:
            flops, nbytes = work(q, **ctx.dims)
            least += max(flops / flops_per_s,
                         nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t
