"""service edge + router. Mean, over the requests whose first token was
written in the window, of the stage ``ingress``: the edge's handler has the
request's bytes -> the router thread appends it to the replica's feed
(parse, validation, the admission check, the wait in the driver's ingress
queue for the router's tick). Counters ``ttft_ingress_ns`` /
``ttft_requests``."""

from perfbench import ttft_stages


def read(ctx):
    return ttft_stages.stage_ms(ctx, "ingress")
