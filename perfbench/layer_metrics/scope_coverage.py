"""frame program. Device self time under any of the program's layer
scopes (``scope_reduce.SCOPES``; XLA's pool-layout copies count under
``kv_commit``) over device busy time, in the traced frames."""

from perfbench import scope_reduce


def read(ctx):
    red = scope_reduce.for_ctx(ctx)
    if not red or not red["busy_s"]:
        return None
    loose = red["scope_s"].get(scope_reduce.UNSCOPED, 0.0)
    return scope_reduce.share(red["busy_s"] - loose, red["busy_s"])
