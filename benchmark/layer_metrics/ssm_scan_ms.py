"""State-space mixer: device self time a step under ``ssm_conv``,
``ssm_scan`` and ``ssm_gate_norm``: the causal conv, the chunked scan and
the gated group norm, the part of a mixer that is not a projection
(harness/ssm.py)."""

from harness import ssm


def read(trace, run):
    return ssm.scan_ms(trace, run)
