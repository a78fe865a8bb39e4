"""Time to first token by stage, from the program's own stamps.

The program stamps a request at every hand-over from the edge's socket to
its first SSE write and folds each first token into counters
(``deepspeed_tpu/inference/v2/telemetry.py`` ``TTFT_COUNTERS``:
``ttft_requests``, ``ttft_total_ns``, ``ttft_<stage>_ns`` for ingress, feed,
queue, prefill and egress, ``ttft_prefill_frames``). A run's context holds
their deltas over the window, so a stage's mean is exact over the requests
whose first token was written in it, traced or not. A program without the
counters (a parent commit) gives every reader here nothing to read.
"""


def per_request(ctx, counter, scale=1.0):
    """``counter`` over ``ttft_requests``, both over the window; None
    where no first token was counted in it."""
    counters = (ctx or {}).get("counters") or {}
    n = counters.get("ttft_requests", 0)
    if not n or counter not in counters:
        return None
    return counters[counter] * scale / n


def stage_ms(ctx, stage):
    return per_request(ctx, f"ttft_{stage}_ns", 1e-6)
