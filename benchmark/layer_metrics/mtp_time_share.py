"""Model step: device self time of the operations under any ``mtp_*`` scope
of ``models/joyai_flash.py``'s multi-token-prediction module, forward,
recomputed forward and backward, over the busy time inside step runs
(harness/latent.py has the rules). None where the step has no such scope."""

from harness import latent


def read(trace, run):
    return latent.mtp_time_share(trace, run)
