"""Share of its roofline, in %, that the RNN form of the fused table kernel,
`memory_update_table_rnn`, reaches: the least time of one call (the larger
of its FLOPs over the bfloat16 peak and its bytes over HBM bandwidth,
counting the rows gathered and the distinct rows written; the cost function
is the configuration's, `memory_update_table_rnn_cost` in
`bench/configs/jodie-pres.py`) over its mean device time per call in the
trace. A program without that kernel, or a configuration without that cost
function, gives nothing to read."""


def read(ctx):
    ns, calls = ctx.tr.kernel_ns(ctx.ops, "memory_update_table_rnn")
    cost = getattr(ctx.arch, "memory_update_table_rnn_cost", None)
    if calls == 0 or cost is None or ctx.written_per_step is None:
        return None
    occ = 2 * ctx.traffic["batch_size"]
    f, b = cost(ctx.model, occ, ctx.written_per_step)
    least = max(f / ctx.peaks["flops_bf16"], b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9 / calls)
