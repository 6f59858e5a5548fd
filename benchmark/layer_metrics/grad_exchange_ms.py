"""Collectives: device self time a step under ``phase_grad_exchange``: the
gradient collective and the packing and unpacking around it (which run on
one chip too). None where the step names no phase."""

from harness import phases


def read(trace, run):
    return phases.phase_ms(trace, run, "grad_exchange")
