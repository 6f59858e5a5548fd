"""Kernels: ``_bwd_dkv_blockdiff_kernel`` alone against its roofline: the
least time the chip's peaks allow its traced calls at the block-diffusion
mask's pair count (harness/blockdiff.py) over their device time. None where
the step holds no such kernel."""

from harness import blockdiff


def read(trace, run):
    return blockdiff.share(trace, run, ("_bwd_dkv_blockdiff_kernel",))
