"""frame program. The whole step's share of the chip's bf16 peak in the
traced frames of a model with latent attention and shortcut-connected
layers: the FLOPs their live tokens need (two latent attentions'
projections, two dense FFNs and the router a layer, the experts' rows, the
attention's pairs, the head on emitting rows; ``work_mla.step_flops``) over
busy seconds x peak."""

from perfbench import work_mla


def read(ctx):
    return work_mla.step_mfu(ctx)
