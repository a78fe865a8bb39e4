"""KV manager. Of the bytes the live sequences hold on the device, summed
over the window's frames, the share that is recurrent state and convolution
tail (``recurrent_bytes_in_use_sum``: a live slot's whatever its context)
and not pages (``kv_bytes_in_use_sum``)."""

from perfbench import work_gdn


def read(ctx):
    return work_gdn.recurrent_state_share(ctx)
