"""kernels. ``paged_decode_roofline``'s arithmetic for the chunked-prefill
kernel (``paged_attn_c128``: any chunk width over 1) in the traced wide
frames."""

from perfbench import harness


def read(ctx):
    return harness.load_module(
        "layer_metrics", "paged_decode_roofline").roofline(ctx, "wide",
                                                           wide=True)
