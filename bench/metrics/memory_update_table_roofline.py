"""Share of its roofline, in %, that the `memory_update_table` Pallas kernel
reaches: the least time of one call (the larger of its FLOPs over the
bfloat16 peak and its bytes over HBM bandwidth, counting the rows gathered
and the distinct rows written, bench/lib/flops.py) over its mean device
time per call in the trace."""


def read(ctx):
    ns, calls = ctx.tr.kernel_ns(ctx.ops, "memory_update_table")
    if calls == 0 or ctx.written_per_step is None:
        return None
    occ = 2 * ctx.traffic["batch_size"]
    f, b = ctx.flops.memory_update_table_cost(ctx.model, occ,
                                              ctx.written_per_step)
    least = max(f / ctx.peaks["flops_bf16"], b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9 / calls)
