"""Short-conv operator: device self time a step under ``shortconv_mix``:
the two gates and the taps between the projections, the part of the
operator that is no projection (harness/shortconv.py)."""

from harness import shortconv


def read(trace, run):
    return shortconv.mix_ms(trace, run)
