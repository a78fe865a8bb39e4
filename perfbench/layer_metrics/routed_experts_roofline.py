"""routed experts. ``moe_experts_roofline``'s arithmetic with the width of
ONE expert read from the configuration's ``moe_intermediate_size``, where
the file has that key, and from ``intermediate_size`` where it has not. A
model whose config.json keeps a dense MLP's width under
``intermediate_size`` beside its experts' own (Mellum2: 7168 that no layer
uses, experts of 896) would have ``moe_experts_roofline`` count eight
times the bytes its grouped products read."""

from perfbench import peaks, scope_reduce, work_moe


def read(ctx):
    red = work_moe.for_ctx(ctx)
    if not red or not red["scope_s"].get(work_moe.EXPERTS):
        return None
    import jax
    config = dict(ctx["config"])
    config["intermediate_size"] = config.get("moe_intermediate_size",
                                             config["intermediate_size"])
    pk = peaks.peaks_for(jax.devices()[0].device_kind)
    floor_s, _ = work_moe.experts_floor_s(
        config, pk, expert_rows=red["expert_rows"],
        experts_touched=red["experts_touched"])
    return scope_reduce.share(floor_s, red["scope_s"][work_moe.EXPERTS])
