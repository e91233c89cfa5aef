"""Device time per training step, in ms, of the operations under the
program's `maintain_state` stage scope: the PRES tracker update and the
neighbour ring's append (its `searchsorted` loop)."""


def read(ctx):
    ns = ctx.tr.scope_ns(ctx.ops, ctx.scopes, "maintain_state")
    if ns == 0:
        return None
    return ns * 1e-6 / ctx.steps / ctx.chips
