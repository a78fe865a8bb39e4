"""kernels. The decode kernel's share of its roofline in the traced
narrow frames: the least time for the KV positions its queries had to read
(K and V, every layer and KV head, bf16) or the query x key pairs they had
to score (QK^T and PV), whichever is larger, over the device time of
``paged_attn_c1``. Work counted in-graph by the program, per frame."""

import re

from perfbench import peaks, scope_reduce

KERNEL = re.compile(r"^paged_attn_c(\d+)$")


def roofline(ctx, split, wide):
    red = scope_reduce.for_ctx(ctx)
    if not red:
        return None
    kernel_s = sum(s for name, s in red["kernel_s"].items()
                   if KERNEL.match(name)
                   and (int(KERNEL.match(name).group(1)) > 1) == wide)
    if not kernel_s:
        return None
    import jax
    pk = peaks.peaks_for(jax.devices()[0].device_kind)
    d = ctx["dims"]
    nbytes = red[f"kv_positions_read_{split}"] * d["L"] * 2 * d["KVH"] \
        * d["D"] * 2
    flops = red[f"attn_pairs_{split}"] * d["L"] * d["H"] * d["D"] * 4
    floor_s = max(nbytes / pk["hbm_bytes_per_s"], flops / pk["bf16_flops"])
    return 100.0 * floor_s / kernel_s


def read(ctx):
    return roofline(ctx, "narrow", wide=False)
