"""Kernels: device time of the three window kernels of
``ops/flash_attention.py`` (harness/window.py has their names) over the
device's busy time. None where the step holds none of them."""

from harness import window


def read(trace, run):
    return window.time_share(trace, run)
