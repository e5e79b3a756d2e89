"""cam_search_topk_roofline.tail: the least time the chip could take for the
window's top-k kernel calls over the time they took, in percent.  Each
call's least time is counted from the configuration's own rows, cells and
bits (never from an expanded operand) and the call's own query rows; see
``roofline.topk_least_time``."""

from onchip import kernels


def read(ctx):
    return kernels.roofline_pct(ctx)
