"""Model step: device self time of the operations under ``outgate_proj`` and
``outgate_mul`` of ``models/trinity.py``'s attention (the output gate's
projection; its sigmoid and product with the kernels' output), forward,
recomputed forward and backward, over the busy time inside step runs
(harness/outgate.py has the rules). None where the step has no such scope."""

from harness import outgate


def read(trace, run):
    return outgate.time_share(trace, run)
