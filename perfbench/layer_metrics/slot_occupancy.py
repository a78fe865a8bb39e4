"""scheduler / admission. Rows that did work over rows the frames had:
active_row_steps / slot_steps_capacity, both counted in-graph, over the
window."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("slot_steps_capacity"):
        return None
    return 100.0 * c["active_row_steps"] / c["slot_steps_capacity"]
