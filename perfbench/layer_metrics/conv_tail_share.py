"""KV manager. Of the bytes the live sequences hold on the device, summed
over the window's frames, the share that is convolution tails
(``recurrent_bytes_in_use_sum``: a live slot's 2 rows a conv layer, whatever
its context) and not pages (``kv_bytes_in_use_sum``): the number that says
how little a slot of this family costs beside its context."""

from perfbench import work_conv


def read(ctx):
    return work_conv.conv_tail_share(ctx)
