"""Tokens of the steps that ended (block_until_ready) inside the window,
over window x chips."""


def read(ctx):
    if not ctx.get("step_ends"):
        return None
    return (len(ctx["step_ends"]) * ctx["tokens_per_step"]
            / (ctx["window_s"] * ctx["chips"]))
