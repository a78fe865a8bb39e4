"""train engine / ZeRO. Device self time of the ops the backward pass
computes again (``jax.checkpoint`` writes ``rematted_computation`` into
their path) over busy time, in the traced steps: what remat costs."""

from perfbench import scope_reduce


def read(ctx):
    red = scope_reduce.for_ctx(ctx)
    if not red or not red["busy_s"]:
        return None
    return scope_reduce.share(red["remat_s"], red["busy_s"])
