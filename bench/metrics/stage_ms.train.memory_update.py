"""Device time per training step, in ms, of the operations under the
program's `memory_update` stage scope (forward and backward)."""


def read(ctx):
    ns = ctx.tr.scope_ns(ctx.ops, ctx.scopes, "memory_update")
    if ns == 0:
        return None
    return ns * 1e-6 / ctx.steps / ctx.chips
