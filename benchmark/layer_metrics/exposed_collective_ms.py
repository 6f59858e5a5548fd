"""Collectives: the part of ``collective_ms`` during which no compute
operation runs on that chip."""

from harness import roofline


def read(trace, run):
    found = roofline.collective_seconds_per_step(trace, run)
    return None if found is None else 1e3 * found[1]
