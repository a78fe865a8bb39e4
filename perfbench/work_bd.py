"""A model that generates by diffusion over blocks (SDAR's ``sdar_moe``):
the operations and bytes its frames need, from the configuration's published
keys, its ``diffusion`` group and the program's own counts, and the trace's
device time under the unmasking's scope and in the block step's attention
kernel. The yardstick's arithmetic, like ``work.py``, ``work_moe.py`` and
``work_gdn.py``: nothing here reads the program's code.

What the program writes and this reads. A row past its prompt holds a block
of L = ``diffusion.block_length`` positions; each of its steps is one
forward of those L positions (counted in ``target_forwards``), either a
DENOISING step (``bd_denoise_forwards``: logits at the L positions, some
unmasked, ``bd_positions_unmasked``; nothing emitted, no K, V kept) or the
COMMIT of the mask-free block (``bd_commit_forwards``: K, V kept, up to L
tokens out, logits unused). A narrow frame is L positions wide
(``serve_frame/w<L>``) and its attention is the by-head paged kernel at L
query positions a row, ``paged_attn_c<L>``; confidence, selection and the
block's update run under the scope ``bd_unmask`` (inside ``sample``). Each
frame's ``serve/frame_work`` span carries those counts beside
``prefill_tokens``, ``tokens_emitted``, ``expert_rows`` and, for ONE layer
at full context, ``kv_positions_read`` (positions whose K and V a row's
step reads: its committed context and its own L) and ``attn_pairs``
(query x key pairs: L times that; a prefill chunk's w times its context
and itself).

Needed FLOPs of a frame: every position a row-forward or a prefill chunk
computed is one the algorithm asks for (a block's L positions see each
other, so none is a speculation that may be thrown away): the layers'
matrices that every position meets, ``num_experts_per_tok`` experts' rows
a position and layer (the program's own count: dropless, live positions
only), the counted pairs, and the head on the L rows of a DENOISING forward.
No credit for the head of a commit, whose logits the algorithm does not
use, nor for the head on a prefilling row.
"""

import os
import re

from perfbench import harness, scope_reduce, trace_reduce

UNMASK_SCOPE = "bd_unmask"
FRAME_COUNTERS = ("prefill_tokens", "tokens_emitted", "target_forwards",
                  "expert_rows", "kv_positions_read", "attn_pairs",
                  "bd_denoise_forwards", "bd_commit_forwards")


def sizes(config):
    return {"e": config["hidden_size"], "layers": config["num_hidden_layers"],
            "h": config["num_attention_heads"],
            "kvh": config["num_key_value_heads"], "d": config["head_dim"],
            "v": config["vocab_size"], "experts": config["num_experts"],
            "k": config["num_experts_per_tok"],
            "f": config["moe_intermediate_size"],
            "blk": int(config["diffusion"]["block_length"])}


def block_kernel(config):
    """The name of the narrow block step's attention kernel."""
    return f"paged_attn_c{sizes(config)['blk']}"


def mixer_params(config):
    """A layer's matrices every position is multiplied by: q, k, v, o and
    the router."""
    s = sizes(config)
    return (2 * s["e"] * s["h"] * s["d"] + 2 * s["e"] * s["kvh"] * s["d"]
            + s["e"] * s["experts"])


def position_flops(config):
    """A computed position through every layer's matrices that every
    position meets; its experts' rows and its pairs are counted apart."""
    return 2 * sizes(config)["layers"] * mixer_params(config)


def expert_row_flops(config):
    """One row through one routed expert's three matrices."""
    s = sizes(config)
    return 2 * 3 * s["e"] * s["f"]


def pair_flops(config):
    """One query x key pair of every layer: QK^T and PV over every query
    head."""
    s = sizes(config)
    return 4 * s["layers"] * s["h"] * s["d"]


def head_flops(config):
    s = sizes(config)
    return 2 * s["e"] * s["v"]


def position_bytes(config, bytes_per_value=2):
    """K and V of one cached position, every layer and KV head."""
    s = sizes(config)
    return s["layers"] * 2 * s["kvh"] * s["d"] * bytes_per_value


def frame_flops(config, work):
    """FLOPs the algorithm needed for one frame (``work``: the frame's
    ``serve/frame_work`` counts; module docstring)."""
    blk = sizes(config)["blk"]
    positions = work["prefill_tokens"] + blk * work["target_forwards"]
    return (positions * position_flops(config)
            + work["expert_rows"] * expert_row_flops(config)
            + work["attn_pairs"] * pair_flops(config)
            + blk * work["bd_denoise_forwards"] * head_flops(config))


def attention_floor_s(config, peaks, *, positions, pairs):
    """The least time for an attention's work: ``positions`` cached
    positions' K and V read (one layer's count; x the layers here), or
    ``pairs`` scored, whichever takes longer. (seconds, "compute" |
    "memory")."""
    t_bytes = positions * position_bytes(config) / peaks["hbm_bytes_per_s"]
    t_flops = pairs * pair_flops(config) / peaks["bf16_flops"]
    return max(t_bytes, t_flops), \
        "compute" if t_flops > t_bytes else "memory"


def unmask_seconds(trace, lo, hi):
    """Over [lo, hi) of the trace's clock, mean over chips: self seconds of
    the device's operations with ``bd_unmask`` on their path. None if no
    operation ran on a device."""
    devices = [line["events"] for plane in trace["planes"]
               if trace_reduce.DEVICE_PLANE.match(plane["name"])
               for line in plane["lines"]
               if line["name"] == trace_reduce.OPS_LINE and line["events"]]
    if not devices:
        return None
    total = 0
    for events in devices:
        keyed = [((e[0], e[3] if len(e) > 3 else ""), e[1], e[2])
                 for e in events if e[1] < hi and e[1] + e[2] > lo]
        for (_, path), start, self_ns in trace_reduce.self_times(keyed):
            parts = [scope_reduce._WRAPPED.sub("", p)
                     for p in path.rstrip(":").split("/")]
            if UNMASK_SCOPE in parts and lo <= start < hi:
                total += self_ns
    return total / len(devices) / 1e9


def serve_reduction(trace, config):
    """The traced frames of a serving run (whole frames that have their
    work in the trace, as ``scope_reduce`` takes them): needed FLOPs, the
    NARROW frames' (a block wide) attention counts, and device seconds
    under ``bd_unmask``. None where the trace has no such frames or their
    work lacks the block counts (a program that does not generate so)."""
    window = trace_reduce.find_span(trace, scope_reduce.WINDOW_SPAN)
    if window is None:
        return None
    frames = scope_reduce.frames_with_work(trace, *window)
    if not frames or any(c not in frames[0][3] for c in FRAME_COUNTERS):
        return None
    blk = sizes(config)["blk"]
    narrow = [w for *_, w in frames if w["width"] == blk]
    return {"frames": len(frames), "narrow_frames": len(narrow),
            "flops": sum(frame_flops(config, w) for *_, w in frames),
            "positions_narrow": sum(w["kv_positions_read"] for w in narrow),
            "pairs_narrow": sum(w["attn_pairs"] for w in narrow),
            "unmask_s": unmask_seconds(trace, frames[0][0], frames[-1][1])}


_REDUCED = {}


def for_ctx(ctx):
    """The run's reduction, or None: no trace, a configuration without a
    ``diffusion`` group, or no block counts in the trace."""
    if not ctx or not ctx.get("trace") or ctx.get("kind") != "serve" \
            or "diffusion" not in ctx.get("config", {}):
        return None
    path = scope_reduce.newest_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        red = _REDUCED[key] = serve_reduction(scope_reduce.load_scoped(path),
                                              ctx["config"])
        if red:
            harness.log(
                f"block diffusion: {red['frames']} traced frames "
                f"({red['narrow_frames']} a block wide) needed "
                f"{red['flops'] / 1e12:.2f} TFLOP; bd_unmask "
                f"{red['unmask_s'] or 0.0:.3f} s; the narrow attention read "
                f"{red['positions_narrow']} positions a layer and scored "
                f"{red['pairs_narrow']} pairs")
    return _REDUCED[key]


def device_peaks():
    import jax
    from perfbench import peaks
    return peaks.peaks_for(jax.devices()[0].device_kind)


def step_mfu(ctx):
    """The traced frames' needed FLOPs over the device's busy seconds in
    them times the bf16 peak."""
    work = for_ctx(ctx)
    red = scope_reduce.for_ctx(ctx) if work else None
    if not red or not red["busy_s"]:
        return None
    return 100.0 * work["flops"] / (
        red["busy_s"] * device_peaks()["bf16_flops"])


def unmask_share(ctx):
    """Device self time under ``bd_unmask`` over busy, in the traced
    frames."""
    work = for_ctx(ctx)
    red = scope_reduce.for_ctx(ctx) if work else None
    if not red or not red["busy_s"] or work["unmask_s"] is None:
        return None
    return 100.0 * work["unmask_s"] / red["busy_s"]


def block_attn_roofline(ctx):
    """The narrow block step's paged attention against its roofline in the
    traced frames: the least time for its counted work over the device time
    of ``paged_attn_c<L>``."""
    work = for_ctx(ctx)
    red = scope_reduce.for_ctx(ctx) if work else None
    if not red:
        return None
    kernel_s = sum(s for name, s in red["kernel_s"].items()
                   if re.sub(r"\.\d+$", "", name)
                   == block_kernel(ctx["config"]))
    if not kernel_s:
        return None
    floor_s, _ = attention_floor_s(
        ctx["config"], device_peaks(), positions=work["positions_narrow"],
        pairs=work["pairs_narrow"])
    return 100.0 * floor_s / kernel_s


def tokens_per_forward(ctx):
    """Tokens emitted over row-forwards of rows past their prompt, from the
    program's counters over the window."""
    c = ctx.get("counters") or {}
    if not c.get("bd_denoise_forwards") or not c.get("target_forwards"):
        return None
    return c["tokens_emitted"] / c["target_forwards"]


def commit_forward_share(ctx):
    """Of those row-forwards, the ones that only commit."""
    c = ctx.get("counters") or {}
    if not c.get("bd_denoise_forwards") or not c.get("target_forwards"):
        return None
    return 100.0 * c["bd_commit_forwards"] / c["target_forwards"]
