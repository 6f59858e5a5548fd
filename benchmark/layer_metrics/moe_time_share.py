"""Experts: device self time of the operations under any ``moe_*`` scope of
``parallel/ep.moe_topk``, forward and backward, over the busy time inside
step runs (harness/moe.py has the rules). None where the step has no such
scope."""

from harness import moe


def read(trace, run):
    return moe.time_share(trace, run)
