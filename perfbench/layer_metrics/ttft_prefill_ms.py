"""frame program. Mean of the stage ``prefill``: admission -> the
boundary that absorbed the request's first token (the admission writes, the
request's wide frames, fetch, absorb; the span ``engine.prefill``).
Counters ``ttft_prefill_ns`` / ``ttft_requests``."""

from perfbench import ttft_stages


def read(ctx):
    return ttft_stages.stage_ms(ctx, "prefill")
