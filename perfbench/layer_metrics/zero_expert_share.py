"""routed experts. Selections that chose a zero-compute (identity) expert,
of all the live tokens' selections over the window, from the program's
counters: ``zero_expert_selections`` over ``expert_selections``. With
random weights 256 / 768 = 33.3%; a token's compute varies with it."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("expert_selections"):
        return None
    return 100.0 * c.get("zero_expert_selections", 0) \
        / c["expert_selections"]
