"""kernels. ``paged_decode_roofline_layered``'s arithmetic for the
chunked-prefill kernels (``paged_attn_c128`` and ``paged_attn_ring_c128``:
any chunk width over 1) in the traced wide frames."""

from perfbench import work_layers


def read(ctx):
    return work_layers.roofline(ctx, "wide", wide=True)
