"""Mean gap between output tokens over every token of the window's
requests (``clientlog.mean_gap_ms``)."""

from perfbench import clientlog


def read(ctx):
    return clientlog.mean_gap_ms(ctx["records"], ctx["t0"], ctx["t1"])
