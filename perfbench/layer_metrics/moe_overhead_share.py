"""routed experts. Of the device time under ``moe_mlp`` in the traced
frames, the share that is not the experts' own products: ``moe_route``,
``moe_dispatch`` and ``moe_combine``."""

from perfbench import scope_reduce, work_moe


def read(ctx):
    red = work_moe.for_ctx(ctx)
    if not red or not work_moe.moe_seconds(red):
        return None
    overhead = sum(red["scope_s"].get(s, 0.0) for s in work_moe.OVERHEAD)
    return scope_reduce.share(overhead, work_moe.moe_seconds(red))
