"""A stack that mixes gated short convolutions with full attention (LFM2's
``lfm2_moe``): the bytes and operations its attending layers need, from the
configuration's published keys and the program's own counts. The yardstick's
arithmetic, like ``work.py``, ``work_moe.py`` and ``work_gdn.py``: nothing
here reads the program's code.

What the program writes and this reads. Each frame's ``serve/frame_work``
span carries, beside the counts ``work_moe`` and ``work_layers`` read,
``conv_positions`` (live positions x conv layers) and
``kv_positions_read_layers`` / ``attn_pairs_layers``, which count the
ATTENDING layers alone, summed over them. The counters
``recurrent_bytes_in_use_sum`` and ``kv_bytes_in_use_sum`` sum, over the
window's frames, what the live slots hold of convolution tails and of pages.

The conv mixer's own device time has no reader: its scopes (``conv_proj``,
``conv_mix``, ``conv_out``) read 0.177 s in PR 51's traced run where the two
matrices' bytes alone need 0.256 s at the HBM's rate: XLA fetches a layer's
matrices behind the operation before it (``copy-start`` / ``copy-done``
without a path), so an operation's own time is not its bytes' time
(PERF.md section 7).
"""

import os

from perfbench import scope_reduce, trace_reduce

FRAME_COUNTERS = ("conv_positions", "kv_positions_read_layers",
                  "attn_pairs_layers")
NARROW_KERNEL = "paged_attn_c1"


def sizes(config):
    kinds = config["layer_types"]
    heads = config["num_attention_heads"]
    return {"e": config["hidden_size"], "taps": config["conv_L_cache"],
            "conv": kinds.count("conv"),
            "full": kinds.count("full_attention"), "h": heads,
            "kvh": config.get("num_key_value_heads") or heads,
            "d": config.get("head_dim") or config["hidden_size"] // heads}


def attention_floor_s(config, peaks, *, positions, pairs, bytes_per_value=2):
    """The least time for the attending layers' paged reads: ``positions``
    (KV positions read, summed over the attending layers) x K and V x KV
    heads x head_dim at the HBM's rate, or ``pairs`` (query x key pairs,
    summed likewise) x heads x head_dim x 4 FLOPs at the bf16 peak."""
    s = sizes(config)
    t_bytes = positions * 2 * s["kvh"] * s["d"] * bytes_per_value \
        / peaks["hbm_bytes_per_s"]
    t_flops = pairs * 4 * s["h"] * s["d"] / peaks["bf16_flops"]
    return max(t_flops, t_bytes), \
        "compute" if t_flops > t_bytes else "memory"


def serve_reduction(trace):
    """The traced frames of a serving run (whole frames that have their work
    in the trace, as ``scope_reduce`` takes them): the narrow frames'
    attention counts. None where the trace has no such frames or their work
    lacks the mixer's counts (a model without conv layers, a program older
    than they are)."""
    window = trace_reduce.find_span(trace, scope_reduce.WINDOW_SPAN)
    if window is None:
        return None
    frames = scope_reduce.frames_with_work(trace, *window)
    if not frames or any(c not in frames[0][3] for c in FRAME_COUNTERS):
        return None
    narrow = [w for *_, w in frames if w["width"] == 1]
    return {"frames": len(frames), "narrow_frames": len(narrow),
            "narrow_kv_positions": sum(w["kv_positions_read_layers"]
                                       for w in narrow),
            "narrow_pairs": sum(w["attn_pairs_layers"] for w in narrow)}


_REDUCED = {}


def device_peaks():
    import jax
    from perfbench import peaks
    return peaks.peaks_for(jax.devices()[0].device_kind)


def for_ctx(ctx):
    """The run's reduction, or None: no trace, a configuration without the
    conv mixer's keys, or no counts of it in the trace."""
    if not ctx or not ctx.get("trace") or ctx.get("kind") != "serve" \
            or "conv_L_cache" not in ctx.get("config", {}):
        return None
    path = scope_reduce.newest_trace()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        _REDUCED[key] = serve_reduction(scope_reduce.load_scoped(path))
    return _REDUCED[key]


def paged_decode_roofline_d64(ctx):
    """The narrow step's paged kernel against the K and V its attending
    layers had to read in the traced narrow frames."""
    work = for_ctx(ctx)
    red = scope_reduce.for_ctx(ctx) if work else None
    kernel_s = (red or {}).get("kernel_s", {}).get(NARROW_KERNEL)
    if not kernel_s or not work["narrow_kv_positions"]:
        return None
    floor_s, _ = attention_floor_s(
        ctx["config"], device_peaks(),
        positions=work["narrow_kv_positions"], pairs=work["narrow_pairs"])
    return 100.0 * floor_s / kernel_s


def conv_tail_share(ctx):
    """Of the bytes the live sequences hold on the device over the window's
    frames, the part that is convolution tails (a slot's whatever its
    context) and not pages."""
    c = (ctx or {}).get("counters") or {}
    tails, pages = c.get("recurrent_bytes_in_use_sum"), \
        c.get("kv_bytes_in_use_sum")
    if not tails or pages is None \
            or "conv_L_cache" not in ctx.get("config", {}):
        return None
    return 100.0 * tails / (tails + pages)
