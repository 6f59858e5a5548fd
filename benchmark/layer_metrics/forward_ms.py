"""Model step: device self time a step under ``phase_forward_backward``,
the forward pass (instructions whose ``op_name`` holds no ``transpose(``),
averaged over chips and step runs. None where the step names no phase."""

from harness import phases


def read(trace, run):
    return phases.phase_ms(trace, run, phases.FORWARD)
