"""Closed loop: callers that wait. Each of ``clients`` sends its next
request when the last one has completed (after ``think_s``), so a slow
server is offered less. The callers do not start in lock-step: caller c
sends its first request ``c * ramp_s / clients`` seconds after the epoch."""

from perfbench import draws

#: more than any client finishes in the longest run (51 s + warm-up)
PER_CLIENT = 512


def plan(params, schedule_seed, horizon_s):
    """Each client's own sequence of requests; the client walks it until
    the window ends."""
    del horizon_s
    requests = []
    for c in range(int(params["clients"])):
        rng = draws.stream(schedule_seed, 0, c)
        for k in range(PER_CLIENT):
            request = draws.draw_request(rng, params["classes"])
            request.update(key=c * PER_CLIENT + k, client=c, k=k)
            requests.append(request)
    clients = int(params["clients"])
    return {"mode": "closed", "clients": clients,
            "think_s": float(params.get("think_s", 0.0)),
            "starts": [c * float(params["ramp_s"]) / clients
                       for c in range(clients)],
            "requests": requests}
