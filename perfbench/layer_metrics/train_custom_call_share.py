"""kernels. ``custom_call_share`` of a training cell (a per-layer metric
names one end-to-end metric that it moves, so training has its own)."""

from perfbench import harness


def read(ctx):
    return harness.load_module("layer_metrics", "custom_call_share").read(ctx)
