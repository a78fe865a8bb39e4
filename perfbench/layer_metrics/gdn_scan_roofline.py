"""kernels. The gated delta rule against its roofline in the traced frames:
the least time for the RECURRENCE's work on the frames' live positions
(``gdn_positions`` x 7 dk dv Hv FLOPs, or their q, k, v, o and the live
rows' float32 states read and written once a step, ``gdn_state_rw``,
whichever takes longer; ``work_gdn.scan_floor_s``) over the device time
under ``gdn_scan``. No credit for the chunked form's extra products, nor for
the dead positions of a chunk; if the scan becomes a kernel of the repo's
own, this is its roofline share."""

from perfbench import work_gdn


def read(ctx):
    return work_gdn.scan_roofline(ctx)
