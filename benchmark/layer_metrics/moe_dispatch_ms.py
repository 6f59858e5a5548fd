"""Experts: device self time a step under ``moe_router``, ``moe_dispatch``
and ``moe_combine``: routing, sort, gathers and the weighted sum, the
HBM-bound part of the layer (harness/moe.py)."""

from harness import moe


def read(trace, run):
    return moe.dispatch_ms(trace, run)
