"""A stack that mixes Gated DeltaNet (linear attention) layers with full
attention (Qwen3-Next's ``qwen3_next``): the operations and bytes its frames
need, from the configuration's published keys and the program's own counts,
and the trace's device time under the linear mixer's scopes. The yardstick's
arithmetic, like ``work.py``, ``work_moe.py`` and ``work_mtp.py``: nothing
here reads the program's code.

What the program writes and this reads. A linear layer runs under the
scopes ``gdn_proj`` (the input projections), ``gdn_conv`` (the causal
depthwise convolution over the carried tail), ``gdn_scan`` (the gated delta
rule, chunked in a wide step, the recurrence in a narrow one, with the
normalisation of q and k and the gates), ``gdn_norm_gate`` and ``gdn_out``;
a full layer's output gate under ``attn_gate``. Each frame's
``serve/frame_work`` span carries, beside the counts ``work_moe`` and
``work_layers`` read, ``gdn_positions`` (live positions x linear layers)
and ``gdn_state_rw`` (live rows x linear layers, a step);
``kv_positions_read_layers`` / ``attn_pairs_layers`` count the FULL layers
alone, summed over them. The counters ``recurrent_bytes_in_use_sum`` and
``kv_bytes_in_use_sum`` sum, over the window's frames, what the live slots
hold of recurrent state and of pages.

The recurrence's least work, whatever implements it: a position of one
linear layer and value head decays the state (dk x dv multiplies), reads it
against k (2 dk dv), writes the outer product (2 dk dv) and reads it against
q (2 dk dv): 7 dk dv FLOPs; it reads q, k, v and writes o (bf16); a live row
reads and writes its float32 state once a step. No credit for the chunked
form's extra products.
"""

import os

from perfbench import harness, scope_reduce, trace_reduce

SCOPES = ("gdn_proj", "gdn_conv", "gdn_scan", "gdn_norm_gate", "gdn_out")
SCAN = "gdn_scan"
FRAME_COUNTERS = ("prefill_tokens", "tokens_emitted", "target_forwards",
                  "expert_rows", "attn_pairs_layers", "gdn_positions",
                  "gdn_state_rw")


def sizes(config):
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    layers = config["num_hidden_layers"]
    full = layers // config["full_attention_interval"]
    return {"hk": hk, "hv": hv, "dk": dk, "dv": dv, "full": full,
            "linear": layers - full, "e": config["hidden_size"],
            "channels": 2 * hk * dk + hv * dv}


def linear_mixer_params(config):
    """A Gated DeltaNet mixer's matrices a token is multiplied by: q | k | v
    | z, b | a, and the output projection (the convolution's taps are not a
    product)."""
    s = sizes(config)
    return (s["e"] * (s["channels"] + s["hv"] * s["dv"])
            + s["e"] * 2 * s["hv"] + s["hv"] * s["dv"] * s["e"])


def full_mixer_params(config):
    """A gated attention's matrices: the doubled q_proj, k, v and o."""
    e, d = config["hidden_size"], config["head_dim"]
    h, kvh = config["num_attention_heads"], config["num_key_value_heads"]
    return e * h * 2 * d + 2 * e * kvh * d + h * d * e


def block_params(config):
    """What every token meets of a layer's routed block: the router over the
    PUBLISHED experts and the shared expert with its gate."""
    e = config["hidden_size"]
    router = config.get("n_routed_experts_published", config["num_experts"])
    return (e * router
            + 3 * e * config["shared_expert_intermediate_size"] + e)


def token_flops(config):
    """A live token through every layer's matrices that every token meets;
    its routed experts, its recurrence and its attention's pairs are counted
    apart."""
    s = sizes(config)
    return 2 * (s["linear"] * linear_mixer_params(config)
                + s["full"] * full_mixer_params(config)
                + config["num_hidden_layers"] * block_params(config))


def expert_row_flops(config):
    return 2 * 3 * config["hidden_size"] * config["moe_intermediate_size"]


def recurrence_flops(config):
    """One position of ONE linear layer, every value head: 7 dk dv each."""
    s = sizes(config)
    return 7 * s["dk"] * s["dv"] * s["hv"]


def pair_flops(config):
    """One query x key pair of one full layer: QK^T and PV over every
    query head."""
    return 4 * config["num_attention_heads"] * config["head_dim"]


def head_flops(config):
    return 2 * config["hidden_size"] * config["vocab_size"]


def position_bytes(config, bytes_per_value=2):
    """q, k, v read and o written for one position of one linear layer."""
    s = sizes(config)
    return (s["channels"] + s["hv"] * s["dv"]) * bytes_per_value


def state_bytes(config):
    """One linear layer's float32 state of one row."""
    s = sizes(config)
    return s["hv"] * s["dk"] * s["dv"] * 4


def scan_floor_s(config, peaks, *, positions, state_rw):
    """The least time for the recurrence's work: ``positions`` (live
    positions x linear layers) at ``recurrence_flops``, or their q, k, v, o
    and ``state_rw`` (live rows x linear layers a step) states read and
    written, whichever takes longer. (seconds, "compute" | "memory")."""
    t_flops = positions * recurrence_flops(config) / peaks["bf16_flops"]
    t_bytes = (positions * position_bytes(config)
               + state_rw * 2 * state_bytes(config)) \
        / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), \
        "compute" if t_flops > t_bytes else "memory"


def frame_flops(config, work):
    """FLOPs the model needed for one frame's tokens (``work``: the frame's
    ``serve/frame_work`` counts): its live tokens (prompt tokens consumed
    and decode forwards; none speculated) through the matrices every token
    meets, the rows its held experts got, the recurrence on its live
    positions, the pairs its full layers scored, the head on the rows that
    emitted."""
    live = work["prefill_tokens"] + work["target_forwards"]
    return (live * token_flops(config)
            + work["expert_rows"] * expert_row_flops(config)
            + work["gdn_positions"] * recurrence_flops(config)
            + work["attn_pairs_layers"] * pair_flops(config)
            + work["tokens_emitted"] * head_flops(config))


def gdn_scope_of(path):
    """The innermost of ``SCOPES`` on the op's path, or None."""
    for part in reversed(path.rstrip(":").split("/")):
        part = scope_reduce._WRAPPED.sub("", part)
        if part in SCOPES:
            return part
    return None


def gdn_seconds(trace, lo, hi):
    """Over [lo, hi) of the trace's clock, mean over chips: self seconds of
    the device's operations by the linear mixer's scope. None if no
    operation ran on a device."""
    devices = [line["events"] for plane in trace["planes"]
               if trace_reduce.DEVICE_PLANE.match(plane["name"])
               for line in plane["lines"]
               if line["name"] == trace_reduce.OPS_LINE and line["events"]]
    if not devices:
        return None
    scope_ns = {}
    for events in devices:
        keyed = [((e[0], e[3] if len(e) > 3 else ""), e[1], e[2])
                 for e in events if e[1] < hi and e[1] + e[2] > lo]
        for (_, path), start, self_ns in trace_reduce.self_times(keyed):
            scope = gdn_scope_of(path)
            if scope and lo <= start < hi:
                scope_ns[scope] = scope_ns.get(scope, 0) + self_ns
    return {k: v / len(devices) / 1e9 for k, v in scope_ns.items()}


def serve_reduction(trace, config):
    """The traced frames of a serving run (whole frames that have their work
    in the trace, as ``scope_reduce`` takes them): needed FLOPs, the
    recurrence's counts, and device seconds by the mixer's scope. None where
    the trace has no such frames or their work lacks the mixer's counts (a
    model without linear layers, a program older than they are)."""
    window = trace_reduce.find_span(trace, scope_reduce.WINDOW_SPAN)
    if window is None:
        return None
    frames = scope_reduce.frames_with_work(trace, *window)
    if not frames or any(c not in frames[0][3] for c in FRAME_COUNTERS):
        return None
    return {"frames": len(frames),
            "flops": sum(frame_flops(config, w) for *_, w in frames),
            "positions": sum(w["gdn_positions"] for *_, w in frames),
            "state_rw": sum(w["gdn_state_rw"] for *_, w in frames),
            "scope_s": gdn_seconds(trace, frames[0][0], frames[-1][1])}


_REDUCED = {}


def for_ctx(ctx):
    """The run's reduction, or None: no trace, a configuration without the
    linear mixer's keys, or no counts of it in the trace."""
    if not ctx or not ctx.get("trace") or ctx.get("kind") != "serve" \
            or "linear_num_value_heads" not in ctx.get("config", {}):
        return None
    path = scope_reduce.newest_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        red = _REDUCED[key] = serve_reduction(scope_reduce.load_scoped(path),
                                              ctx["config"])
        if red and red["scope_s"]:
            floor_s, bound = scan_floor_s(
                ctx["config"], device_peaks(), positions=red["positions"],
                state_rw=red["state_rw"])
            harness.log(
                "linear mixers, device seconds by scope: "
                + ", ".join(f"{k} {v:.3f}" for k, v in sorted(
                    red["scope_s"].items(), key=lambda kv: -kv[1]))
                + f"; the recurrence's floor {floor_s * 1e3:.1f} ms "
                f"({bound}-bound: {red['positions']} positions, "
                f"{red['state_rw']} states read and written); needed "
                f"{red['flops'] / 1e12:.2f} TFLOP")
    return _REDUCED[key]


def device_peaks():
    import jax
    from perfbench import peaks
    return peaks.peaks_for(jax.devices()[0].device_kind)


def gdn_share(ctx):
    """Device self time under the linear mixer's scopes over busy, in the
    traced frames."""
    work = for_ctx(ctx)
    red = scope_reduce.for_ctx(ctx) if work else None
    if not red or not work["scope_s"] or not red["busy_s"]:
        return None
    return 100.0 * sum(work["scope_s"].values()) / red["busy_s"]


def scan_roofline(ctx):
    """The recurrence's least time on the traced frames over the device
    time under ``gdn_scan``."""
    work = for_ctx(ctx)
    if not work or not work["scope_s"] or not work["scope_s"].get(SCAN):
        return None
    floor_s, _ = scan_floor_s(ctx["config"], device_peaks(),
                              positions=work["positions"],
                              state_rw=work["state_rw"])
    return 100.0 * floor_s / work["scope_s"][SCAN]


def step_mfu(ctx):
    """The traced frames' needed FLOPs over the device's busy seconds in
    them times the bf16 peak."""
    work = for_ctx(ctx)
    red = scope_reduce.for_ctx(ctx) if work else None
    if not red or not red["busy_s"]:
        return None
    return 100.0 * work["flops"] / (
        red["busy_s"] * device_peaks()["bf16_flops"])


def recurrent_state_share(ctx):
    """Of the bytes the live sequences hold on the device over the window's
    frames, the part that is recurrent state and convolution tail (a slot's
    whatever its context) and not pages."""
    c = ctx.get("counters") or {}
    state, pages = c.get("recurrent_bytes_in_use_sum"), \
        c.get("kv_bytes_in_use_sum")
    if not state or pages is None:
        return None
    return 100.0 * state / (state + pages)
