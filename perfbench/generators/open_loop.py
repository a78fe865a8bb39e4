"""Open loop: independent users. Requests are due on a schedule whatever
the server does, so a queue can grow; each is timed from when it was due."""

from perfbench import draws


def plan(params, schedule_seed, horizon_s):
    """Every request due in [0, horizon_s), in order of its time."""
    rng = draws.stream(schedule_seed, 0)
    requests, t = [], 0.0
    while True:
        t += draws.draw_gap(rng, params["arrivals"])
        request = draws.draw_request(rng, params["classes"])
        if t >= horizon_s:
            return {"mode": "open", "requests": requests}
        request.update(key=len(requests), t=round(t, 6))
        requests.append(request)
