"""service edge + router. Mean of the stage ``egress``: the serve loop
absorbed the first token -> the edge wrote and flushed the request's first
``token`` event (the boundary's publish and yield, the driver's event queue,
the router thread, the subscriber's queue, the socket write). Counters
``ttft_egress_ns`` / ``ttft_requests``."""

from perfbench import ttft_stages


def read(ctx):
    return ttft_stages.stage_ms(ctx, "egress")
