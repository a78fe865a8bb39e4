"""Routed experts: the operations and bytes their grouped products need,
from shapes and the program's own counts, and the trace's device time under
the scopes the program writes inside ``moe_mlp``. The yardstick's
arithmetic, like ``work.py``: nothing here reads the program's code.

A routed layer is three grouped products over rows sorted by expert: gate
and up ``(rows, E) x (E, F)``, down ``(rows, F) x (F, E)``, ``F`` the width
of ONE expert (``intermediate_size`` in OLMoE's config.json). A row costs
``3 x E x F`` multiply-adds whatever its expert; an expert that got at least
one row has its three matrices read, ``3 x E x F`` values, whatever its
rows. The program counts both in its frame programs (``expert_rows``,
``experts_touched``, summed over layers and steps) and writes them on each
frame's ``serve/frame_work`` span.

The scopes, innermost on an op's path: ``moe_route`` (router product,
softmax, top-k), ``moe_dispatch`` (sort, gathers, group sizes),
``moe_experts`` (the grouped products and the gate), ``moe_combine``
(weighting and scatter-add). On the chip the grouped product is XLA's own
Mosaic kernel, whose HLO carries the name ``ragged-dot-...`` and NO path:
such an op is found by its name and counted under ``moe_experts``.
"""

import os
import re

from perfbench import scope_reduce, trace_reduce

MOE_SCOPE = "moe_mlp"
SUB_SCOPES = ("moe_route", "moe_dispatch", "moe_experts", "moe_combine")
EXPERTS = "moe_experts"
#: routing, sorting and recombining: what is not the experts' own products
OVERHEAD = ("moe_route", "moe_dispatch", "moe_combine")
GROUPED_PRODUCT = re.compile(r"^ragged-dot")
COUNTERS = ("expert_rows", "experts_touched")


def expert_matrix_values(config):
    """Values in one expert's three matrices: 3 x E x F."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def expert_flops(config, expert_rows):
    """2 FLOPs a multiply-add, each row through its expert's three
    matrices."""
    return 2 * expert_matrix_values(config) * expert_rows


def expert_bytes(config, experts_touched, bytes_per_value=2):
    """Weights read: every touched expert's three matrices once. The rows
    themselves (a few MB) are left out: a floor."""
    return bytes_per_value * expert_matrix_values(config) * experts_touched


def experts_floor_s(config, peaks, *, expert_rows, experts_touched):
    """The least time the chip could take for the grouped products, and
    which peak binds: (seconds, "memory" | "compute")."""
    t_bytes = expert_bytes(config, experts_touched) / peaks["hbm_bytes_per_s"]
    t_flops = expert_flops(config, expert_rows) / peaks["bf16_flops"]
    return max(t_bytes, t_flops), "compute" if t_flops > t_bytes else "memory"


def moe_scope_of(name, path):
    """The innermost of ``SUB_SCOPES`` on the op's path; ``moe_mlp`` for an
    op under that scope and none of them; ``moe_experts`` for the grouped
    product's own kernels, by name; None for any other op."""
    if GROUPED_PRODUCT.match(name):
        return EXPERTS
    parts = [scope_reduce._WRAPPED.sub("", p)
             for p in path.rstrip(":").split("/")]
    for part in reversed(parts):
        if part in SUB_SCOPES:
            return part
    return MOE_SCOPE if MOE_SCOPE in parts else None


def reduce_moe(trace, lo, hi):
    """Over [lo, hi) of the trace's clock, mean over chips: device busy
    seconds and self seconds by MoE scope (``moe_mlp`` holds what lies under
    it and under no sub-scope). None if no operation ran on a device."""
    devices = [line["events"] for plane in trace["planes"]
               if trace_reduce.DEVICE_PLANE.match(plane["name"])
               for line in plane["lines"]
               if line["name"] == trace_reduce.OPS_LINE and line["events"]]
    if not devices:
        return None
    busy_ns, scope_ns = 0, {}
    for events in devices:
        events = [e for e in events if e[1] < hi and e[1] + e[2] > lo]
        busy_ns += trace_reduce.total(trace_reduce.clip(
            trace_reduce.union([e[1], e[1] + e[2]] for e in events), lo, hi))
        keyed = [((e[0], e[3] if len(e) > 3 else ""), e[1], e[2])
                 for e in events]
        for (name, path), start, self_ns in trace_reduce.self_times(keyed):
            scope = moe_scope_of(name, path)
            if scope and lo <= start < hi:
                scope_ns[scope] = scope_ns.get(scope, 0) + self_ns
    n = len(devices)
    return {"busy_s": busy_ns / n / 1e9,
            "scope_s": {k: v / n / 1e9 for k, v in scope_ns.items()}}


def serve_reduction(trace):
    """The traced frames of a serving run, as ``scope_reduce`` takes them
    (whole frames that have their work in the trace): time by MoE scope and
    the frames' expert counters summed. None where the trace has no such
    frames, or their work has no expert counters (a program older than
    they are)."""
    window = trace_reduce.find_span(trace, scope_reduce.WINDOW_SPAN)
    if window is None:
        return None
    frames = scope_reduce.frames_with_work(trace, *window)
    if not frames or any(c not in frames[0][3] for c in COUNTERS):
        return None
    red = reduce_moe(trace, frames[0][0], frames[-1][1])
    if red is None:
        return None
    red["frames"] = len(frames)
    for c in COUNTERS:
        red[c] = sum(work[c] for *_, work in frames)
    return red


_REDUCED = {}


def for_ctx(ctx):
    """The run's MoE reduction, or None: no trace, a program without the
    expert counters, a model without routed experts (no row ever counted),
    nothing on a device."""
    if not ctx or not ctx.get("trace") or ctx.get("kind") != "serve":
        return None
    path = scope_reduce.newest_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        red = serve_reduction(scope_reduce.load_scoped(path))
        if red is not None and not red["expert_rows"]:
            red = None
        _REDUCED[key] = red
    return _REDUCED[key]


def moe_seconds(red):
    """Device seconds under ``moe_mlp``, sub-scopes and all."""
    return sum(red["scope_s"].values())
