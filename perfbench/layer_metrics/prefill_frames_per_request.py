"""frame program. Frames dispatched between a request's admission and
its first token, mean over the requests whose first token was written in
the window. Counters ``ttft_prefill_frames`` / ``ttft_requests``."""

from perfbench import ttft_stages


def read(ctx):
    return ttft_stages.per_request(ctx, "ttft_prefill_frames")
