"""A model that drafts for itself with its multi-token-prediction module
(DeepSeek-V3's family; GLM-4.7-Flash's ``glm4_moe_lite``): the operations
the MAIN model needs for the tokens a run ingested or emitted, from the
configuration's published keys and the program's own counts, and the
trace's device time under the module's scope and in the narrow frames'
latent kernels. The yardstick's arithmetic, like ``work.py``,
``work_moe.py`` and ``work_mla.py``: nothing here reads the program's code.

What the program writes and this reads. A speculative narrow step runs the
module once (scope ``mtp_draft``, holding ``mtp_proj``; its attention is the
kernel ``paged_attn_mla_c1`` over its own cache layer) and the stack two
positions wide (``paged_attn_mla_c2``). Each frame's ``serve/frame_work``
span carries, beside the counts ``work_mla`` reads, ``drafted_tokens``,
``accepted_draft_tokens``, ``target_forwards`` (the verify forwards) and the
module's own lanes ``mtp_latent_positions_read`` / ``mtp_expert_rows`` /
``mtp_experts_touched``; ``expert_rows``, ``experts_touched``,
``latent_positions_read`` and ``latent_pairs`` count the STACK alone, every
position it computed: both of a verify's. The module's routed layer runs
its grouped products under ``mtp_draft/.../moe_experts``, so the device
time ``work_moe`` finds under ``moe_experts`` is the stack's and the
module's together, and ``experts_roofline`` holds it against the rows and
touched experts of both.

Needed FLOPs give no credit to the draft, nor to a verified position that
emitted nothing:

- a WIDE frame speculates nothing, so every live token it computed was
  needed: ``expert_rows / (k x routed layers)`` of them (dropless routing,
  live positions only), each through every layer's matrices, its experts'
  rows, the counted pairs, and the head on the rows that emitted;
- a NARROW frame needed one position's forward for each token it EMITTED
  (``tokens_emitted``: one a verify at acceptance 0, two where the draft
  was accepted): the layers' matrices, k experts' rows a routed layer, the
  head, and the pairs of the emitting positions. The program counts the
  rows both positions of a verify at context c read, c + 2 a layer; an
  emitting first position scores c + 1 pairs and a second c + 2, so over a
  frame of V verifies that emitted M tokens the needed pairs a layer are
  taken as ``reads x M / V - M`` (reads = the counted rows a layer): exact
  where every verify emits one, and where every verify emits two one pair
  a verify short.
"""

import os
import re

from perfbench import scope_reduce, trace_reduce, work_mla, work_moe

DRAFT_SCOPE = "mtp_draft"
#: the latent kernels of a narrow frame: the module's one position and the
#: verify's two (``work_mla.KERNEL`` would class ``_c2`` wide)
NARROW_KERNEL = re.compile(r"^paged_attn_mla_c[12]$")
FRAME_COUNTERS = ("prefill_tokens", "tokens_emitted", "target_forwards",
                  "expert_rows", "experts_touched", "latent_positions_read",
                  "latent_pairs", "mtp_latent_positions_read",
                  "mtp_expert_rows", "mtp_experts_touched")


def routed_layers(config):
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def token_flops(config):
    """A live token through the stack's matrices that every token meets:
    each layer's latent attention, the leading layers' dense gated FFN,
    each routed layer's router and shared expert. Its routed experts and
    its attention's pairs are counted apart."""
    e = config["hidden_size"]
    dense = config["first_k_dense_replace"] * 3 * e \
        * config["intermediate_size"]
    routed = routed_layers(config) * (
        e * config["n_routed_experts"]
        + config["n_shared_experts"] * 3 * e
        * config["moe_intermediate_size"])
    return 2 * (config["num_hidden_layers"] * work_mla.mla_params(config)
                + dense + routed)


def expert_row_flops(config):
    """One row through one routed expert's three matrices."""
    return 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def head_flops(config):
    return 2 * config["hidden_size"] * config["vocab_size"]


def frame_flops(config, work):
    """FLOPs the main model needed for one frame's tokens (``work``: the
    frame's ``serve/frame_work`` counts)."""
    k = config["num_experts_per_tok"]
    rows_a_token = k * routed_layers(config)
    emitted = work["tokens_emitted"]
    if work["width"] > 1:
        live = work["expert_rows"] / rows_a_token
        rows, pairs = work["expert_rows"], work["latent_pairs"]
    else:
        live, rows = emitted, emitted * rows_a_token
        verifies = work["target_forwards"]
        pairs = 0 if not verifies else max(
            0.0, work["latent_positions_read"] * emitted / verifies
            - config["num_hidden_layers"] * emitted)
    return (live * token_flops(config) + rows * expert_row_flops(config)
            + pairs * work_mla.pair_flops(config)
            + emitted * head_flops(config))


def draft_seconds(trace, lo, hi):
    """Over [lo, hi) of the trace's clock, mean over chips: self seconds of
    the device's operations whose path lies under ``mtp_draft``. None if no
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
            if lo <= start < hi and DRAFT_SCOPE in parts:
                total += self_ns
    return total / len(devices) / 1e9


def serve_reduction(trace, config):
    """The traced frames of a serving run (whole frames that have their
    work in the trace, as ``scope_reduce`` takes them): the main model's
    needed FLOPs, the narrow frames' latent rows and pairs (the stack's and
    the module's), and the device seconds under ``mtp_draft``. None where
    the trace has no such frames or their work lacks the module's counts
    (a model without one, a program older than they are)."""
    window = trace_reduce.find_span(trace, scope_reduce.WINDOW_SPAN)
    if window is None:
        return None
    frames = scope_reduce.frames_with_work(trace, *window)
    if not frames or any(c not in frames[0][3] for c in FRAME_COUNTERS):
        return None
    narrow = [w for *_, w in frames if w["width"] == 1]
    return {
        "frames": len(frames), "frames_narrow": len(narrow),
        "flops": sum(frame_flops(config, w) for *_, w in frames),
        "positions_narrow": sum(w["latent_positions_read"]
                                + w["mtp_latent_positions_read"]
                                for w in narrow),
        # the module's one query a row scores each row it reads once
        "pairs_narrow": sum(w["latent_pairs"]
                            + w["mtp_latent_positions_read"]
                            for w in narrow),
        # the grouped products' work, the stack's and the module's
        "expert_rows": sum(w["expert_rows"] + w["mtp_expert_rows"]
                           for *_, w in frames),
        "experts_touched": sum(w["experts_touched"]
                               + w["mtp_experts_touched"]
                               for *_, w in frames),
        "draft_s": draft_seconds(trace, frames[0][0], frames[-1][1])}


_REDUCED = {}


def for_ctx(ctx):
    """The run's reduction, or None: no trace, or no module's counts."""
    if not ctx or not ctx.get("trace") or ctx.get("kind") != "serve":
        return None
    path = scope_reduce.newest_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        _REDUCED[key] = serve_reduction(scope_reduce.load_scoped(path),
                                        ctx["config"])
    return _REDUCED[key]


def step_mfu(ctx):
    """The traced frames' needed FLOPs of the main model over the device's
    busy seconds in them times the bf16 peak."""
    red, work = scope_reduce.for_ctx(ctx), for_ctx(ctx)
    if not red or not work or not red["busy_s"]:
        return None
    return 100.0 * work["flops"] / (
        red["busy_s"] * work_mla.device_peaks()["bf16_flops"])


def decode_roofline(ctx):
    """The narrow frames' latent kernels against their roofline: least
    time for the rows they had to read and the pairs they had to score over
    their device seconds."""
    red, work = scope_reduce.for_ctx(ctx), for_ctx(ctx)
    if not red or not work:
        return None
    kernel_s = sum(s for name, s in red["kernel_s"].items()
                   if NARROW_KERNEL.match(name))
    if not kernel_s:
        return None
    floor_s = work_mla.attention_floor_s(
        ctx["config"], work_mla.device_peaks(),
        positions=work["positions_narrow"], pairs=work["pairs_narrow"])
    return 100.0 * floor_s / kernel_s


def experts_roofline(ctx):
    """The grouped products of the stack's routed layers and of the
    module's, against their roofline in the traced frames:
    ``work_moe.experts_floor_s`` (every touched expert's three matrices
    read once a product, or the rows multiplied, whichever takes longer) at
    the width of ONE expert, over the device seconds under ``moe_experts``."""
    moe, work = work_moe.for_ctx(ctx), for_ctx(ctx)
    if not moe or not work or not moe["scope_s"].get(work_moe.EXPERTS):
        return None
    config = dict(ctx["config"],
                  intermediate_size=ctx["config"]["moe_intermediate_size"])
    floor_s, _ = work_moe.experts_floor_s(
        config, work_mla.device_peaks(), expert_rows=work["expert_rows"],
        experts_touched=work["experts_touched"])
    return 100.0 * floor_s / moe["scope_s"][work_moe.EXPERTS]


def draft_share(ctx):
    """Device self time under ``mtp_draft`` over busy, in the traced
    frames."""
    red, work = scope_reduce.for_ctx(ctx), for_ctx(ctx)
    if not red or not work or work["draft_s"] is None or not red["busy_s"]:
        return None
    return 100.0 * work["draft_s"] / red["busy_s"]


def acceptance_rate(ctx):
    """Drafted tokens that were accepted and emitted, of those drafted over
    the window."""
    c = ctx.get("counters") or {}
    if not c.get("drafted_tokens"):
        return None
    return 100.0 * c.get("accepted_draft_tokens", 0) / c["drafted_tokens"]


def drafts_per_verify(ctx):
    """Tokens drafted for each verify forward over the window: 1.00 while
    every narrow step drafts."""
    c = ctx.get("counters") or {}
    if not c.get("drafted_tokens") or not c.get("target_forwards"):
        return None
    return c["drafted_tokens"] / c["target_forwards"]
