"""routed experts. The grouped products of a self-drafting model, the
stack's routed layers' and the prediction module's together, against their
roofline in the traced frames: least time for the weights they had to read
(every expert with at least one row, its three matrices of
``moe_intermediate_size``, bf16) or the rows they had to multiply, whichever
is larger, over the device time under ``moe_experts``. ``expert_rows`` +
``mtp_expert_rows`` and ``experts_touched`` + ``mtp_experts_touched``,
counted in-graph by the program, per frame (``work_mtp.py``;
``routed_experts_roofline`` would hold the module's seconds against the
stack's work alone)."""

from perfbench import work_mtp


def read(ctx):
    return work_mtp.experts_roofline(ctx)
