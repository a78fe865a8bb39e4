"""From the client's log to latencies and rates. Everything a client-side
metric needs is here, so each metric's reader is a line or two.

All times are seconds after the run's epoch. The window is
``[t0, t1)``. A request belongs to the window if it was DUE in it
(``sched_t``); it is followed to its end, which may lie after ``t1``.
"""

import json
import math


def read_log(path):
    with open(path) as fh:
        return [json.loads(line) for line in fh if line.strip()]


def percentile(values, p):
    """The p-th percentile by linear interpolation between order statistics
    (numpy's default), or None of nothing."""
    xs = sorted(values)
    if not xs:
        return None
    rank = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(rank), math.ceil(rank)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def tail_quantile(values, p):
    """The p-th percentile by the Harrell-Davis estimator: a weighted mean
    of all order statistics, the weights a Beta density centred on the
    percentile. A cell's window holds 30-45 requests; there the
    interpolated p90 is one or two order statistics and flips between
    neighbours from run to run (measured, PR 22: 4% spread against 1.4%
    for this estimator on the same runs). None of nothing."""
    from scipy.special import betainc
    xs = sorted(values)
    n = len(xs)
    if not n:
        return None
    a, b = p / 100.0 * (n + 1), (1 - p / 100.0) * (n + 1)
    cdf = [float(betainc(a, b, i / n)) for i in range(n + 1)]
    return sum((cdf[i + 1] - cdf[i]) * x for i, x in enumerate(xs))


def due_in(records, t0, t1):
    return [r for r in records if t0 <= r["sched_t"] < t1]


def ok(records):
    return [r for r in records if r["status"] == "ok"]


def ttft_s(record):
    """First token event minus the time the request was due."""
    return record["first_t"] - record["sched_t"]


def tpot_s(record):
    """Mean gap between output tokens: (last - first) / (n - 1). Tokens
    reach the client a frame at a time, so a raw gap is 0 or a frame; the
    mean over the request is what repeats. None for a single token."""
    n = len(record["tokens"])
    if n < 2:
        return None
    return (record["last_t"] - record["first_t"]) / (n - 1)


def gen_lag_s(record):
    """How late the generator sent: actual minus scheduled send."""
    return record["send_t"] - record["sched_t"]


def ttfts_ms(records, t0, t1):
    return [ttft_s(r) * 1e3 for r in ok(due_in(records, t0, t1))]


def tpots_ms(records, t0, t1):
    return [tpot_s(r) * 1e3 for r in ok(due_in(records, t0, t1))
            if tpot_s(r) is not None]


def mean_gap_ms(records, t0, t1):
    """Mean gap between output tokens over every token of the window's
    requests: summed (last - first) over summed (n - 1). Long answers weigh
    by their tokens, so one short request that rode wide frames does not
    move it; None of nothing."""
    done = [r for r in ok(due_in(records, t0, t1)) if len(r["tokens"]) > 1]
    if not done:
        return None
    return (1e3 * sum(r["last_t"] - r["first_t"] for r in done)
            / sum(len(r["tokens"]) - 1 for r in done))


def steadiness(records, t0, t1):
    """Whether the window saw a steady state, from the client's log:
    requests completed in it over requests due in it, and how many were in
    flight, and how many of those still awaited a first token, at its
    start, middle and end. A request that never got a token waits on."""
    inf = float("inf")

    def at(t, key):
        return sum(r["sched_t"] <= t < (r[key] if r[key] is not None else inf)
                   for r in records)

    times = (t0, (t0 + t1) / 2, t1)
    return {"due": len(due_in(records, t0, t1)),
            "completed": sum(t0 <= r["last_t"] < t1 for r in ok(records)),
            "in_flight": [at(t, "last_t") for t in times],
            "waiting": [at(t, "first_t") for t in times]}


def completed_tokens(records, t0, t1):
    """Tokens the client saw completed inside the window. A generated
    token counts when its event arrives. A prompt is processed somewhere
    between the request's send and its first token, and the client cannot
    see where: its tokens are spread evenly over that interval, and the
    part inside the window counts. (Whole prompts at one instant make the
    count jump by thousands of tokens when a long request crosses the
    window's edge: measured, PR 22, 535 or 394 tokens/s from run to run.)"""
    total = 0.0
    for r in ok(records):
        a, b = r["send_t"], r["first_t"]
        inside = min(b, t1) - max(a, t0)
        if inside > 0:
            total += r["prompt_len"] * inside / (b - a)
        total += sum(n for t, n in r["events"] if t0 <= t < t1)
    return total


def slo_share(records, t0, t1, ttft_limit_ms, tpot_limit_ms, unfinished=0):
    """Share of the window's requests that met both limits. A failed or
    unfinished request misses."""
    due = due_in(records, t0, t1)
    total = len(due) + unfinished
    if not total:
        return None
    met = 0
    for r in ok(due):
        tpot = tpot_s(r)
        if ttft_s(r) * 1e3 <= ttft_limit_ms and (
                tpot is None or tpot * 1e3 <= tpot_limit_ms):
            met += 1
    return met / total
