"""Attention over a stack whose layers differ: the operations and bytes the
paged kernels need when every layer has a window of its own, from the
program's own counts, and the trace's device time of those kernels. The
yardstick's arithmetic, like ``work.py`` and ``work_moe.py``: nothing here
reads the program's code.

A model of mixed cache kinds (windowed layers on rings of pages, global
layers on whole tables) counts in its frame programs, per step and row, the
KV positions its queries had to read and the query x key pairs they had to
score, SUMMED OVER THE LAYERS with each layer's own window
(``kv_positions_read_layers``, ``attn_pairs_layers``), and the windowed
layers' part of the first (``kv_positions_read_window``); each frame's sums
are on its ``serve/frame_work`` span. A position read costs K and V of every
KV head of ONE layer, ``2 x KVH x D`` values; a pair costs ``4 x H x D``
FLOPs (QK^T and PV): no further factor of the layer count, which the sums
already hold. (``paged_decode_roofline`` multiplies a one-window count by L
and would over-count such a stack.)

The kernels: ``paged_attn_c<C>`` over whole tables and
``paged_attn_ring_c<C>`` over a ring, C = 1 in a narrow frame.
"""

import os
import re

from perfbench import scope_reduce, trace_reduce

KERNEL = re.compile(r"^paged_attn(?:_ring)?_c(\d+)$")
COUNTERS = ("kv_positions_read_layers", "attn_pairs_layers")


def position_bytes(dims, bytes_per_value=2):
    """K and V of one cached position in one layer."""
    return 2 * dims["KVH"] * dims["D"] * bytes_per_value


def pair_flops(dims):
    """One query x key pair in one layer: 2 FLOPs a multiply-add, two
    products over every query head."""
    return 4 * dims["H"] * dims["D"]


def attention_floor_s(dims, peaks, *, positions, pairs):
    """The least time the chip could take to read ``positions`` (summed
    over layers) or to score ``pairs``, whichever is larger."""
    return max(positions * position_bytes(dims) / peaks["hbm_bytes_per_s"],
               pairs * pair_flops(dims) / peaks["bf16_flops"])


def kernel_seconds(kernel_s, wide):
    """Device seconds of the paged kernels of one width class, both
    kinds."""
    return sum(s for name, s in kernel_s.items() if KERNEL.match(name)
               and (int(KERNEL.match(name).group(1)) > 1) == wide)


def serve_reduction(trace):
    """The layered counts of the traced frames (the whole frames that have
    their work in the trace, as ``scope_reduce`` takes them), summed by
    frame width. None where the trace has no such frames or their work has
    no layered counts (a model of one kind, a program older than they
    are)."""
    window = trace_reduce.find_span(trace, scope_reduce.WINDOW_SPAN)
    if window is None:
        return None
    frames = scope_reduce.frames_with_work(trace, *window)
    if not frames or any(c not in frames[0][3] for c in COUNTERS):
        return None
    red = {}
    for split in ("narrow", "wide"):
        rows = [w for *_, w in frames
                if (w["width"] > 1) == (split == "wide")]
        for c in COUNTERS:
            red[f"{c}_{split}"] = sum(w[c] for w in rows)
    return red


_REDUCED = {}


def for_ctx(ctx):
    """The run's layered counts, or None: no trace, or none in it."""
    if not ctx or not ctx.get("trace") or ctx.get("kind") != "serve":
        return None
    path = scope_reduce.newest_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        _REDUCED[key] = serve_reduction(scope_reduce.load_scoped(path))
    return _REDUCED[key]


def roofline(ctx, split, wide):
    """The paged kernels' share of their roofline in the traced frames of
    one width class: least time for the layer-summed work over the device
    time of every paged kernel of that width."""
    red, work = scope_reduce.for_ctx(ctx), for_ctx(ctx)
    if not red or not work:
        return None
    kernel_s = kernel_seconds(red["kernel_s"], wide)
    if not kernel_s:
        return None
    import jax
    from perfbench import peaks
    pk = peaks.peaks_for(jax.devices()[0].device_kind)
    floor_s = attention_floor_s(
        ctx["dims"], pk,
        positions=work[f"kv_positions_read_layers_{split}"],
        pairs=work[f"attn_pairs_layers_{split}"])
    return 100.0 * floor_s / kernel_s
