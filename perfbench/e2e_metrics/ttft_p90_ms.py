"""p90 (Harrell-Davis estimate, ``clientlog.tail_quantile``) over the
requests DUE in the window of (first token event minus the time the
request was due)."""

from perfbench import clientlog


def read(ctx):
    return clientlog.tail_quantile(
        clientlog.ttfts_ms(ctx["records"], ctx["t0"], ctx["t1"]), 90)
