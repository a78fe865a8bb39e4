"""service edge + router. What of TTFT the program does not see: the
client's mean of (first token event - the time the request was due) over
the requests whose FIRST TOKEN reached it in the window, minus the
program's mean of (first SSE write - the handler has the bytes) over the
first tokens it counted in the window (``ttft_total_ns`` /
``ttft_requests``). Both on ``time.monotonic()``. What is left is the
generator's lag, the connect and the socket both ways: a few ms in a sound
run, hundreds when the client starves. The two populations are the same
requests but for a first write near the window's edges (the program counts
one at the frame boundary after the write), so both counts are logged."""

from perfbench import harness, ttft_stages


def read(ctx):
    inside = ttft_stages.per_request(ctx, "ttft_total_ns", 1e-6)
    if inside is None:
        return None
    firsts = [(r["first_t"] - r["sched_t"]) * 1e3
              for r in ctx.get("records") or []
              if r.get("first_t") is not None
              and ctx["t0"] <= r["first_t"] < ctx["t1"]]
    if not firsts:
        return None
    harness.log(f"ttft_outside_program_ms: {len(firsts)} first tokens at "
                f"the client (mean {sum(firsts) / len(firsts):.3f} ms), "
                f"{ctx['counters']['ttft_requests']} counted by the program "
                f"(mean {inside:.3f} ms)")
    return sum(firsts) / len(firsts) - inside
