"""scheduler / admission. Mean of the stage ``queue``: the serve loop's
poll -> admission into a slot (the span ``engine.queue``). The MEAN of the
wait ``admit_wait_p90_ms`` takes a p90 of, so it sees a few waiters among
many. Counters ``ttft_queue_ns`` / ``ttft_requests``."""

from perfbench import ttft_stages


def read(ctx):
    return ttft_stages.stage_ms(ctx, "queue")
