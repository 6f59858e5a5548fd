"""Kernels: ``_bwd_dkv_latent_kernel`` alone against its roofline: the least
time the chip's peaks allow one call at q/k of one width and v of another
(harness/latent.py) x the times the kernel ran in the traced stretch, over
its device time there. None where the step holds no such kernel."""

from harness import latent


def read(trace, run):
    return latent.share(trace, run, "_bwd_dkv_latent_kernel")
