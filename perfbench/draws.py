"""The seeded draws every traffic generator shares: lengths, arrival gaps,
token ids. Arrival and length draws follow the repo's own
``inference/v2/sim/traffic.py`` (Poisson by exponential gaps, log-normal
lengths), on numpy's PCG64 so a stream is the same on every machine.

Streams are prefix-stable: a longer horizon continues the same stream, so
a 20 s sweep and a 45 s window see the same first 20 s.
"""

import math

import numpy as np


def stream(*key):
    """A generator of its own for every purpose: ``stream(seed, tag, i)``."""
    return np.random.default_rng([int(k) for k in key])


def draw_length(rng, spec):
    """One length from ``{"dist": "lognormal", "median", "sigma", "min",
    "max"}`` or ``{"dist": "uniform", "min", "max"}``; clipped to
    [min, max], never under 1."""
    dist = spec["dist"]
    if dist == "lognormal":
        v = rng.lognormal(math.log(spec["median"]), spec["sigma"])
    elif dist == "uniform":
        v = rng.integers(spec["min"], spec["max"] + 1)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return int(max(spec.get("min", 1), min(spec.get("max", v), round(v)), 1))


def draw_gap(rng, arrivals):
    """Seconds to the next arrival of ``{"process": "poisson", "rate"}``:
    an exponential gap."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return float(rng.exponential(1.0 / float(arrivals["rate"])))


def pick_class(rng, classes):
    """One of the mix's request classes, by weight."""
    weights = np.asarray([c.get("weight", 1.0) for c in classes], float)
    return classes[int(rng.choice(len(classes), p=weights / weights.sum()))]


def draw_request(rng, classes):
    """Class name, prompt length and new tokens of one request."""
    cls = pick_class(rng, classes)
    return {"cls": cls["name"], "prompt_len": draw_length(rng, cls["prompt"]),
            "max_new": draw_length(rng, cls["output"])}


def tokens_for(seed, request, vocab):
    """The request's prompt token ids: uniform over the vocabulary from the
    run's ``--seed`` and the request's key. The client and the reference
    check both call this, so no token list crosses a file."""
    ids = stream(seed, 1, request["key"]).integers(
        0, vocab, request["prompt_len"])
    return [int(t) for t in ids]
