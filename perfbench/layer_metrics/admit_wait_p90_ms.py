"""scheduler / admission. p90 of the program's own ``engine.queue`` spans
(enqueue to admission into a slot, stamped at frame boundaries) that began
in the window."""

from perfbench import clientlog


def read(ctx):
    return clientlog.percentile(ctx.get("queue_waits_ms") or [], 90)
