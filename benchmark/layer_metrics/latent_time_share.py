"""Kernels: device time of the three latent kernels of
``ops/flash_attention.py`` (harness/latent.py has their names) over the
device's busy time. None where the step holds none of them."""

from harness import latent


def read(trace, run):
    return latent.time_share(trace, run)
