"""routed experts. Rows the routed layers' dispatch gathered and their
combine added, over the rows their experts computed, from the program's
counters over the window: ``expert_rows_moved`` / ``expert_rows``. 1 where
the bookkeeping moves what an expert computes and nothing else; the whole
blocks that hold the groups' rows read a little over it, and a program that
moves every selection row (tokens x k, dead positions and other chips'
experts too) reads that many times more. Leaves the metric out where the
program does not count what it moved."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("expert_rows_moved") or not c.get("expert_rows"):
        return None
    return c["expert_rows_moved"] / c["expert_rows"]
