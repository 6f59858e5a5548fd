"""Kernels: ``_bwd_dkv_kernel`` alone against its roofline (see
flash_roofline.py)."""

from harness import roofline


def read(trace, run):
    return roofline.flash_share(trace, run, ("_bwd_dkv_kernel",))
