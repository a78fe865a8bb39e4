"""kernels. The narrow step's paged kernel (``paged_attn_c1``) against its
roofline where only SOME layers attend and their heads are 64 lanes wide:
the least time for the K and V of the positions the attending layers read in
the traced narrow frames (``kv_positions_read_layers`` x 2 x KV heads x
head_dim x 2 B at the HBM's rate) or the pairs they scored
(``attn_pairs_layers`` x heads x head_dim x 4 FLOPs), whichever takes longer
(``work_conv.attention_floor_s``), over the kernel's device time.
``paged_decode_roofline`` counts every layer of the stack (``work.dims``'
L) and would read 4.5 times the bytes here. The kernel runs two heads a
128-lane row: no credit for the lanes it multiplies by zeros."""

from perfbench import work_conv


def read(ctx):
    return work_conv.paged_decode_roofline_d64(ctx)
