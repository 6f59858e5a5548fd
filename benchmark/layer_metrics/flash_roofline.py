"""Kernels: the three flash kernels together against their roofline: the
least time the chip could take for the calls of one step (the larger of
FLOPs over the bf16 peak and bytes over the HBM peak, harness/flops.py) over
their device time in one step."""

from harness import roofline


def read(trace, run):
    return roofline.flash_share(trace, run, roofline.FLASH_KERNELS)
