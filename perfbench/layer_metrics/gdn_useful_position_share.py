"""frame program. Positions the linear layers' states had to move by over
positions the gated delta rule computed for them, from the program's counters
over the window: ``gdn_positions`` / ``gdn_positions_computed``. A wide step
runs the rule's chunked form on the rows that prefill, two a trip x the
chunk's width whatever is live in them, and the one-position recurrence on
every row. Leaves the metric out where the program does not count what it
computed."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("gdn_positions_computed"):
        return None
    return 100.0 * c["gdn_positions"] / c["gdn_positions_computed"]
