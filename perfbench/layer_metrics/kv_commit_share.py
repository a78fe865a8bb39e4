"""KV manager. Device time of the page commit (scope ``kv_commit``: the
token-sized scatter, and the relayout copies of both whole pools that XLA
puts around it, found by the pool's shape) over busy time, in the traced
frames (ROADMAP S2)."""

from perfbench import scope_reduce


def read(ctx):
    red = scope_reduce.for_ctx(ctx)
    if not red or not red["busy_s"]:
        return None
    return scope_reduce.share(red["scope_s"].get("kv_commit", 0.0),
                              red["busy_s"])
