"""frame program. Positions the frames' work needed over positions they
computed, from the program's counters over the window: (``prefill_tokens``
+ ``target_forwards``) / ``positions_computed``. A frame computes slots x
width x steps positions whatever is useful in it (ROADMAP S1)."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("positions_computed"):
        return None
    useful = c["prefill_tokens"] + c["target_forwards"]
    return 100.0 * useful / c["positions_computed"]
