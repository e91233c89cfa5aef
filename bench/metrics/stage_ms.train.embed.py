"""Device time per training step, in ms, of the operations under the
program's `embed` stage scope (forward and backward)."""


def read(ctx):
    ns = ctx.tr.scope_ns(ctx.ops, ctx.scopes, "embed")
    if ns == 0:
        return None
    return ns * 1e-6 / ctx.steps / ctx.chips
