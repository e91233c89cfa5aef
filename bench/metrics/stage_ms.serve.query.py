"""Device time, in ms, of the engine's `serve_query` program per query
call of the open loop: the program's whole body sits under that scope, so
its operations are the operations of the `jit__query_body` programs."""


def read(ctx):
    ns = ctx.tr.module_ns(ctx.ops, "jit__query_body")
    if ns == 0 or not ctx.calls or not ctx.calls["query"]:
        return None
    return ns * 1e-6 / ctx.calls["query"] / ctx.chips
