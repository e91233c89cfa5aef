"""Device time, in ms, of the engine's `serve_ingest` program per ingest
call of the open loop: the program's whole body sits under that scope, so
its operations are the operations of the `jit__ingest_body` programs."""


def read(ctx):
    ns = ctx.tr.module_ns(ctx.ops, "jit__ingest_body")
    if ns == 0 or not ctx.calls or not ctx.calls["ingest"]:
        return None
    return ns * 1e-6 / ctx.calls["ingest"] / ctx.chips
