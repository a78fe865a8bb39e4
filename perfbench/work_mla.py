"""Latent attention (MLA) and a shortcut-connected stack: the operations and
bytes the MATHEMATICS needs, whatever implements it, from the
configuration's published keys and the program's own counts, and the
trace's device time of the latent paged kernels. The yardstick's
arithmetic, like ``work.py``, ``work_moe.py`` and ``work_layers.py``:
nothing here reads the program's code.

A model with latent attention counts in its frame programs, per step, the
latent rows its attention layers had to read and the query x row pairs they
had to score, SUMMED OVER THE ATTENTION LAYERS (``latent_positions_read``,
``latent_pairs``); each frame's sums are on its ``serve/frame_work`` span.
A row read costs its ``kv_lora_rank + qk_rope_head_dim`` values, whatever
is stored; a pair costs the expanded form's two products over every head,
``2 x H x (d_nope + d_rope + d_v)`` FLOPs, with no term for absorption or
expansion: an absorbed kernel does 3.4 x the products and can read at most
~29% where compute binds, and no implementation can read over 100.

The kernels: ``paged_attn_mla_c<C>``, C = 1 in a narrow frame.
"""

import os
import re

from perfbench import scope_reduce, trace_reduce

KERNEL = re.compile(r"^paged_attn_mla_c(\d+)$")
COUNTERS = ("latent_positions_read", "latent_pairs")
#: the frame's counts a whole step's FLOPs are reckoned from
STEP_COUNTERS = ("prefill_tokens", "target_forwards", "tokens_emitted",
                 "expert_rows", "latent_pairs")


def row_bytes(config, bytes_per_value=2):
    """One cached row of one attention layer: the latent and the shared
    RoPE key."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) \
        * bytes_per_value


def pair_flops(config):
    """One query x row pair in one attention layer, expanded form: 2 FLOPs
    a multiply-add, scores over nope + rope and values over v, every
    head."""
    return 2 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])


def mla_params(config):
    """One latent attention's matrices: q_a, q_b, kv_a, kv_b, o."""
    e, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    return (e * rq + rq * h * (dn + dr) + e * (rkv + dr)
            + rkv * h * (dn + dv) + h * dv * e)


def token_flops(config):
    """A live token through one shortcut-connected layer beside its
    experts: two latent attentions' projections, two dense gated FFNs and
    the router over every output."""
    e = config["hidden_size"]
    dense = 3 * e * config["ffn_hidden_size"]
    router = e * (config["n_routed_experts_published"]
                  + config["zero_expert_num"])
    return 2 * (2 * mla_params(config) + 2 * dense + router)


def expert_row_flops(config):
    """One row through one expert's three matrices."""
    return 2 * 3 * config["hidden_size"] * config["expert_ffn_hidden_size"]


def step_flops(config, *, live_tokens, expert_rows, pairs, emitting_rows):
    """FLOPs the frames' live tokens need: every layer's matrices, the
    experts' rows, the attention's pairs (both summed over layers by the
    program) and the head on the rows that emit."""
    return (live_tokens * token_flops(config) * config["num_layers"]
            + expert_rows * expert_row_flops(config)
            + pairs * pair_flops(config)
            + emitting_rows * 2 * config["hidden_size"]
            * config["vocab_size"])


def attention_floor_s(config, peaks, *, positions, pairs):
    """The least time the chip could take to read ``positions`` rows or to
    score ``pairs`` (both summed over the attention layers), whichever is
    larger."""
    return max(positions * row_bytes(config) / peaks["hbm_bytes_per_s"],
               pairs * pair_flops(config) / peaks["bf16_flops"])


def kernel_seconds(kernel_s, wide):
    """Device seconds of the latent paged kernels of one width class."""
    return sum(s for name, s in kernel_s.items() if KERNEL.match(name)
               and (int(KERNEL.match(name).group(1)) > 1) == wide)


def serve_reduction(trace):
    """The traced frames' counts (the whole frames that have their work in
    the trace, as ``scope_reduce`` takes them): the latent counts summed by
    frame width, and ``STEP_COUNTERS`` summed over all. None where the
    trace has no such frames or their work has no latent counts (a model
    without latent attention, a program older than they are)."""
    window = trace_reduce.find_span(trace, scope_reduce.WINDOW_SPAN)
    if window is None:
        return None
    frames = scope_reduce.frames_with_work(trace, *window)
    if not frames or any(c not in frames[0][3]
                         for c in COUNTERS + STEP_COUNTERS):
        return None
    red = {c: sum(w[c] for *_, w in frames) for c in STEP_COUNTERS}
    for split in ("narrow", "wide"):
        rows = [w for *_, w in frames
                if (w["width"] > 1) == (split == "wide")]
        for c in COUNTERS:
            red[f"{c}_{split}"] = sum(w[c] for w in rows)
    return red


_REDUCED = {}


def for_ctx(ctx):
    """The run's latent counts, or None: no trace, or none in it."""
    if not ctx or not ctx.get("trace") or ctx.get("kind") != "serve":
        return None
    path = scope_reduce.newest_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        _REDUCED[key] = serve_reduction(scope_reduce.load_scoped(path))
    return _REDUCED[key]


def device_peaks():
    import jax
    from perfbench import peaks
    return peaks.peaks_for(jax.devices()[0].device_kind)


def roofline(ctx, split, wide):
    """The latent paged kernels' share of their roofline in the traced
    frames of one width class: least time for the counted work over the
    device time of every latent kernel of that width."""
    red, work = scope_reduce.for_ctx(ctx), for_ctx(ctx)
    if not red or not work:
        return None
    kernel_s = kernel_seconds(red["kernel_s"], wide)
    if not kernel_s:
        return None
    floor_s = attention_floor_s(
        ctx["config"], device_peaks(),
        positions=work[f"latent_positions_read_{split}"],
        pairs=work[f"latent_pairs_{split}"])
    return 100.0 * floor_s / kernel_s


def step_mfu(ctx):
    """The traced frames' needed FLOPs over the device's busy seconds in
    them times the bf16 peak."""
    red, work = scope_reduce.for_ctx(ctx), for_ctx(ctx)
    if not red or not work or not red["busy_s"]:
        return None
    flops = step_flops(
        ctx["config"],
        live_tokens=work["prefill_tokens"] + work["target_forwards"],
        expert_rows=work["expert_rows"], pairs=work["latent_pairs"],
        emitting_rows=work["tokens_emitted"])
    return 100.0 * flops / (red["busy_s"] * device_peaks()["bf16_flops"])
