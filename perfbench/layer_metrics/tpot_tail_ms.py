"""service edge + router. The TPOT tail, not judged: p90 (Harrell-Davis
estimate, ``clientlog.tail_quantile``) over the window's requests of (last
token - first token) / (n - 1). It sits on the few requests that decoded
inside wide frames only, so between runs of one commit it is either the
same to 0.1% or off by 2-30%: no bound admits it (PERF.md). The judged
pace is ``tpot_mean_ms``."""

from perfbench import clientlog


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return clientlog.tail_quantile(
        clientlog.tpots_ms(ctx["records"], ctx["t0"], ctx["t1"]), 90)
