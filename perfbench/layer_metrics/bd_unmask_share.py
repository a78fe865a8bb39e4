"""frame program. Device self time under the scope ``bd_unmask`` (a
position's confidence over the vocabulary, the selection of what to unmask,
the block's update) over device busy time, in the traced frames: what
choosing costs beside the forward, over logits of L rows a slot."""

from perfbench import work_bd


def read(ctx):
    return work_bd.unmask_share(ctx)
