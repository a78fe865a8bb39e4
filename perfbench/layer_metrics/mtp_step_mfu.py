"""frame program. The whole step's share of the chip's bf16 peak in the
traced frames of a model that drafts for itself with its prediction module:
the FLOPs the MAIN model needs for the tokens the frames ingested or
emitted (every layer's matrices, k experts' rows a routed layer, the
attention's pairs, the head on emitting rows; no credit for the draft or
for a verified position that emitted nothing; ``work_mtp.frame_flops``)
over busy seconds x peak."""

from perfbench import work_mtp


def read(ctx):
    return work_mtp.step_mfu(ctx)
