"""frame program. Of the row-forwards of rows past their prompt, those that
only COMMIT a mask-free block (its K, V kept, its logits unused), from the
program's counters over the window: ``bd_commit_forwards`` /
``target_forwards``. What fusing a block's commit with the next block's first
denoising step would take off the path (1 / (S + 1): 20% at S = 4)."""

from perfbench import work_bd


def read(ctx):
    return work_bd.commit_forward_share(ctx)
