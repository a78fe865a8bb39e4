"""routed experts. Device self time under the scope ``moe_mlp`` (routing,
dispatch, the grouped products, the combine) over device busy time, in the
traced frames."""

from perfbench import scope_reduce, work_moe


def read(ctx):
    red = work_moe.for_ctx(ctx)
    if not red or not red["busy_s"]:
        return None
    return scope_reduce.share(work_moe.moe_seconds(red), red["busy_s"])
