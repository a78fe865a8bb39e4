"""frame program. Drafted tokens that the verify accepted and the request
took, of those drafted over the window, from the program's counters:
``accepted_draft_tokens`` over ``drafted_tokens``. With seeded random
weights ~0: the floor of the mechanism."""

from perfbench import work_mtp


def read(ctx):
    return work_mtp.acceptance_rate(ctx)
