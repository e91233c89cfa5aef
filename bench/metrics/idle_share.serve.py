"""Share of the traced serving window, in %, in which no operation ran on
the device: 1 - (union of device operation intervals / window)."""


def read(ctx):
    if not ctx.ops:
        return None
    return 100.0 * (1.0 - ctx.tr.busy_ns(ctx.ops, ctx.win) / ctx.window_ns)
