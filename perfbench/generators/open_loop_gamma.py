"""Open loop with clumped arrivals: independent users who arrive in bursts
(a page load that fans out several calls, a class starting an exercise).
Gaps are drawn from a gamma distribution with the mix's mean ``rate`` and
coefficient of variation ``cv`` (cv 1 is Poisson; shape 1/cv^2, so cv 2 is
shape 0.25: many short gaps, a few long ones), on ``draws.stream``'s PCG64.
Each request is timed from when it was due, as in ``open_loop``."""

from perfbench import draws


def draw_gap(rng, arrivals):
    """Seconds to the next arrival of ``{"process": "gamma", "rate",
    "cv"}``: mean ``1 / rate``, standard deviation ``cv / rate``."""
    if arrivals["process"] != "gamma":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    shape = 1.0 / float(arrivals["cv"]) ** 2
    return float(rng.gamma(shape, 1.0 / (float(arrivals["rate"]) * shape)))


def plan(params, schedule_seed, horizon_s):
    """Every request due in [0, horizon_s), in order of its time."""
    rng = draws.stream(schedule_seed, 0)
    requests, t = [], 0.0
    while True:
        t += draw_gap(rng, params["arrivals"])
        request = draws.draw_request(rng, params["classes"])
        if t >= horizon_s:
            return {"mode": "open", "requests": requests}
        request.update(key=len(requests), t=round(t, 6))
        requests.append(request)
