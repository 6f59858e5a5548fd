"""Experts: the grouped matmuls' share of the chip's bf16 peak: the active
experts' FLOPs of a step (the configuration's own count) over the device
seconds under ``moe_experts`` (harness/moe.py): the grouped matmul's share
of its roofline, whoever wrote the kernel."""

from harness import moe


def read(trace, run):
    return moe.experts_mfu(trace, run)
