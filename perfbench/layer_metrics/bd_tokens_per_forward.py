"""frame program. Tokens emitted over row-forwards of rows past their
prompt, from the program's counters over the window: ``tokens_emitted`` /
``target_forwards``. A model that generates by diffusion over blocks of L
positions in S denoising steps pays S + 1 forwards a block of L tokens: 0.8
at L = S = 4, less the first blocks' prompt remainders and the last blocks'
cut positions. Leaves the metric out where the program counts no denoising
forwards (a left-to-right model, an older program)."""

from perfbench import work_bd


def read(ctx):
    return work_bd.tokens_per_forward(ctx)
