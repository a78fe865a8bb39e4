"""service edge + router. The TTFT tail in cells where it is not judged:
p90 (Harrell-Davis) over the requests due in the window of first token
minus the time the request was due. The same number as ``ttft_p90_ms``,
under a name of its own: in the chat cells it spreads by 4-8% between runs
of one commit (PERF.md), and ``ttft_mean_ms`` is judged instead."""

from perfbench import harness


def read(ctx):
    if ctx["kind"] != "serve":
        return None
    return harness.load_module("e2e_metrics", "ttft_p90_ms").read(ctx)
