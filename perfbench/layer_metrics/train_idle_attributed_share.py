"""device. ``idle_attributed_share`` of a training cell: gaps under
``train_batch`` and its ``train/<phase>`` spans (a per-layer metric names
one end-to-end metric that it moves, so training has its own)."""

from perfbench import harness


def read(ctx):
    return harness.load_module("layer_metrics",
                               "idle_attributed_share").read(ctx)
