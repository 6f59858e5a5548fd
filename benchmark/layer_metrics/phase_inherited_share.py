"""Device: busy time inside step runs whose phase was inherited from the
operation before (the compiler's own operations carry no scope), over all
busy time inside step runs. None where the step names no phase."""

from harness import phases


def read(trace, run):
    return phases.inherited_share(trace, run)
