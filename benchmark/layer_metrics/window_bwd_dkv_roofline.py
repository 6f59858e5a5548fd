"""Kernels: ``_bwd_dkv_window_kernel`` alone against its roofline: the least
time the chip's peaks allow its calls of one step at the window's pair count
(harness/window.py) over their device time. None where the step holds no
such kernel."""

from harness import window


def read(trace, run):
    return window.share(trace, run, ("_bwd_dkv_window_kernel",))
