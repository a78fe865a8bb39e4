"""frame program. Tokens drafted for each verify forward over the window,
from the program's counters: ``drafted_tokens`` over ``target_forwards``.
1.00 while every narrow step drafts; a change that drafts less often is a
change of configuration, not a speed-up."""

from perfbench import work_mtp


def read(ctx):
    return work_mtp.drafts_per_verify(ctx)
