"""Kernels: ``_fwd_kernel`` alone against its roofline (see
flash_roofline.py)."""

from harness import roofline


def read(trace, run):
    return roofline.flash_share(trace, run, ("_fwd_kernel",))
