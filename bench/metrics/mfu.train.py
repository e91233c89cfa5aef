"""Model FLOP utilization of training, in %: the model FLOPs of one step
(bench/lib/flops.py::train_step_flops, the embedding's from the
configuration's module) times the steps per second of the
untraced stretch before the trace, over the chips' bfloat16 peak."""


def read(ctx):
    if not ctx.steps_per_s:
        return None
    t = ctx.traffic
    per_step = ctx.flops.train_step_flops(ctx.model, t["graph"]["feat_dim"],
                                          t["batch_size"], ctx.arch)
    peak = ctx.chips * ctx.peaks["flops_bf16"]
    return 100.0 * per_step * ctx.steps_per_s / peak
