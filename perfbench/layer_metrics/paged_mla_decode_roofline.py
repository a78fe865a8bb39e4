"""kernels. The latent (MLA) paged kernels of the traced NARROW frames
(``paged_attn_mla_c1``, the prediction module's draft, and
``paged_attn_mla_c2``, the stack's two-wide verify) against their roofline:
least time for the rows they had to read (1,152 B each) and the pairs they
had to score (``work_mla.attention_floor_s``; the stack's counts and the
module's, ``latent_positions_read`` + ``mtp_latent_positions_read``) over
the kernels' device seconds (``work_mtp.py``)."""

from perfbench import work_mtp


def read(ctx):
    return work_mtp.decode_roofline(ctx)
