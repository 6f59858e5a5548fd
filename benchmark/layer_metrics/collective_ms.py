"""Collectives: device time a step spends with an all-reduce, all-gather or
reduce-scatter in flight, averaged over the chips."""

from harness import roofline


def read(trace, run):
    found = roofline.collective_seconds_per_step(trace, run)
    return None if found is None else 1e3 * found[0]
