"""routed experts. The grouped products' share of their roofline in the
traced frames: the least time for the experts' weights those frames' steps
had to read (every expert with at least one row, its three matrices, bf16)
or the rows they had to multiply, whichever is larger, over the device time
under ``moe_experts`` (the three grouped products and the gate). Experts
and rows counted in-graph by the program, per frame."""

from perfbench import peaks, scope_reduce, work_moe


def read(ctx):
    red = work_moe.for_ctx(ctx)
    if not red or not red["scope_s"].get(work_moe.EXPERTS):
        return None
    import jax
    pk = peaks.peaks_for(jax.devices()[0].device_kind)
    floor_s, _ = work_moe.experts_floor_s(
        ctx["config"], pk, expert_rows=red["expert_rows"],
        experts_touched=red["experts_touched"])
    return scope_reduce.share(floor_s, red["scope_s"][work_moe.EXPERTS])
