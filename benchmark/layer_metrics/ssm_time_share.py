"""State-space mixer: device self time of the operations under any ``ssm_*``
scope of ``models/nemotron_h.py``'s Mamba-2 mixer, forward and backward,
over the busy time inside step runs (harness/ssm.py has the rules). None
where the step has no such scope."""

from harness import ssm


def read(trace, run):
    return ssm.time_share(trace, run)
