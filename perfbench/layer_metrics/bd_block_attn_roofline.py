"""kernels. The narrow block step's paged attention against its roofline in
the traced frames a block wide: the least time for the K and V of the
positions its rows read (``kv_positions_read`` x layers x 2 x KV heads x
head_dim x 2 B at the HBM's rate) or the query x key pairs they scored
(``attn_pairs`` x layers x heads x head_dim x 4 FLOPs at the bf16 peak),
whichever takes longer, over the device time of ``paged_attn_c<L>``, the
by-head paged kernel at L query positions a row. Work counted in-graph by
the program, per frame."""

from perfbench import work_bd


def read(ctx):
    return work_bd.block_attn_roofline(ctx)
