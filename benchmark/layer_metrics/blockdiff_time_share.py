"""Kernels: device time of the three block-diffusion kernels of
``ops/flash_attention.py`` (harness/blockdiff.py has their names) over the
device's busy time. None where the step holds none of them."""

from harness import blockdiff


def read(trace, run):
    return blockdiff.time_share(trace, run)
