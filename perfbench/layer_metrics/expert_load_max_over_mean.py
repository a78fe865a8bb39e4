"""routed experts. How uneven the experts' groups were, from the program's
counters over the window: the largest group of each layer and step, summed
(``expert_rows_max``), over the mean group (``expert_rows`` /
``num_experts``). 1.0 is an even spread."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("expert_rows") or "expert_rows_max" not in c:
        return None
    return c["expert_rows_max"] * ctx["config"]["num_experts"] \
        / c["expert_rows"]
