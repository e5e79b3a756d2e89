"""topk_kernel_ms.tput: median device time per call of the fused top-k
kernel, found in the trace by its stable name."""

from onchip import kernels


def read(ctx):
    return kernels.median_call_ms(ctx)
