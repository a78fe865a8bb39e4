"""service edge + router. Share of the window's requests with TTFT and
TPOT both inside the limits the traffic file fixes; a failed request
misses. Attainment at the cell's fixed rate; no PR is judged by it."""

from perfbench import clientlog


def read(ctx):
    limits = ctx["traffic"].get("slo")
    if ctx["kind"] != "serve" or not limits:
        return None
    share = clientlog.slo_share(
        ctx["records"], ctx["t0"], ctx["t1"], limits["ttft_ms"],
        limits["tpot_ms"], ctx["unfinished"])
    return None if share is None else 100.0 * share
