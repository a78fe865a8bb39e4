"""train engine / ZeRO. ``scope_coverage`` of a training cell, over its
traced steps."""

from perfbench import harness


def read(ctx):
    return harness.load_module("layer_metrics", "scope_coverage").read(ctx)
