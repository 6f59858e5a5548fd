"""Short-conv operator: device self time of the operations under any
``shortconv_*`` scope of ``models/lfm2.py``'s gated short convolution,
forward, recomputed forward and backward, over the busy time inside step
runs (harness/shortconv.py has the rules). None where the step has no such
scope."""

from harness import shortconv


def read(trace, run):
    return shortconv.time_share(trace, run)
