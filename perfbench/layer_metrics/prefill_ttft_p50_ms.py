"""frame program. Median time to first token of the window's requests, for
cells with too few requests for a judged tail: with long prompts it is the
prefill."""

from perfbench import clientlog


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return clientlog.percentile(
        clientlog.ttfts_ms(ctx["records"], ctx["t0"], ctx["t1"]), 50)
