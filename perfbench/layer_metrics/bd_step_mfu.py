"""frame program. The whole step's share of the chip's bf16 peak in the
traced frames of a model that generates by diffusion over blocks: the FLOPs
the algorithm needs for the positions the frames computed (L a row-forward,
a prefill chunk's own: every layer's matrices, k experts' rows a position and
layer, the counted pairs, the head on the L rows of a denoising forward; no
credit for a commit's head; ``work_bd.frame_flops``) over busy seconds x
peak."""

from perfbench import work_bd


def read(ctx):
    return work_bd.step_mfu(ctx)
