"""scheduler / admission. Mean of the stage ``feed``: the router placed
the request in the replica's feed -> the serve loop's poll took it, at the
next frame boundary (the rest of the frame that was in flight; the span
``engine.feed``). Counters ``ttft_feed_ns`` / ``ttft_requests``."""

from perfbench import ttft_stages


def read(ctx):
    return ttft_stages.stage_ms(ctx, "feed")
