"""service edge + router. How late the generator sent: p90 of actual minus
scheduled send. Large means a starved generator, not a fast server."""

from perfbench import clientlog


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    due = clientlog.due_in(ctx["records"], ctx["t0"], ctx["t1"])
    return clientlog.percentile(
        [clientlog.gen_lag_s(r) * 1e3 for r in due], 90)
