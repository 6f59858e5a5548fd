"""Short-conv operator: ``_mix_fwd_kernel`` (``ops/short_conv.py``) alone against
its roofline: the least time the chip's peaks allow its calls of one step
([B | C | u] read and y written once a call: harness/shortconv.py) over their device time. None where the step
holds no such kernel."""

from harness import shortconv


def read(trace, run):
    return shortconv.kernel_roofline(trace, run, "_mix_fwd_kernel")
