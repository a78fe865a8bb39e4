"""Mean over the requests DUE in the window of (first token event minus
the time the request was due)."""

from perfbench import clientlog


def read(ctx):
    ttfts = clientlog.ttfts_ms(ctx["records"], ctx["t0"], ctx["t1"])
    return sum(ttfts) / len(ttfts) if ttfts else None
