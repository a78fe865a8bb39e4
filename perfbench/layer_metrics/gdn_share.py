"""frame program. Device self time under the Gated DeltaNet mixer's scopes
(``gdn_proj``, ``gdn_conv``, ``gdn_scan``, ``gdn_norm_gate``, ``gdn_out``)
over device busy time, in the traced frames: what the linear layers cost,
whatever implements them."""

from perfbench import work_gdn


def read(ctx):
    return work_gdn.gdn_share(ctx)
