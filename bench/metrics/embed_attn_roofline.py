"""Share of its roofline, in %, that the forward `embed_attn` Pallas kernel
reaches: the least time of one call (the larger of its FLOPs over the
bfloat16 peak and its bytes over HBM bandwidth, bench/lib/flops.py) over
its mean device time per call in the trace."""


def read(ctx):
    ns, calls = ctx.tr.kernel_ns(ctx.ops, "embed_attn")
    if calls == 0:
        return None
    rows = 4 * ctx.traffic["batch_size"]
    f, b = ctx.flops.embed_attn_cost(ctx.model, rows)
    least = max(f / ctx.peaks["flops_bf16"], b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9 / calls)
