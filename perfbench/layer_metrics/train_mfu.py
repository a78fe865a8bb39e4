"""train engine / ZeRO. Model FLOP/s utilization: the FLOPs forward and
backward need (6*N per token plus the windowed attention's, ``work.py``;
recomputation not counted) for the steps that ended in the window, over
window x chips x the chip's published bf16 peak."""


def read(ctx):
    if not ctx.get("step_ends") or not ctx.get("peaks"):
        return None
    flops = len(ctx["step_ends"]) * ctx["flops_per_step"]
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops"])
