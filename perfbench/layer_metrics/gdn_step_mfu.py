"""frame program. The whole step's share of the chip's bf16 peak in the
traced frames of a model with Gated DeltaNet layers: the FLOPs the frames'
live tokens needed (every layer's matrices that a token meets, the held
experts' rows, the recurrence on live positions, the full layers' pairs, the
head on emitting rows; ``work_gdn.frame_flops``) over busy seconds x peak."""

from perfbench import work_gdn


def read(ctx):
    return work_gdn.step_mfu(ctx)
