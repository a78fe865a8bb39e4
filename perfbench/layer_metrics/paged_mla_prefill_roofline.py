"""kernels. The latent (MLA) paged kernels of the traced WIDE frames
(``paged_attn_mla_c<C>``, C > 1) against their roofline: least time for the
frames' counted work (``latent_positions_read`` rows of 1,152 B and
``latent_pairs`` pairs of 40,960 FLOPs, both summed over the attention
layers by the program; the expanded form's mathematics, no term for
absorption) over the kernels' device seconds (``work_mla.py``)."""

from perfbench import work_mla


def read(ctx):
    return work_mla.roofline(ctx, "wide", wide=True)
