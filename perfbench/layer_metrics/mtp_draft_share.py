"""frame program. Device self time under the scope ``mtp_draft`` (the
prediction module's draft forward, its rows and their commit) over busy,
in the traced frames: what drafting costs, whatever it is worth."""

from perfbench import work_mtp


def read(ctx):
    return work_mtp.draft_share(ctx)
