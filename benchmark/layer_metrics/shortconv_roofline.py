"""Short-conv operator: the whole operator against its roofline: the least
time the chip's peaks allow a step's operators (the configuration's count of
the two projections' products in every pass and of the bytes no writing can
avoid) over the device seconds under the three ``shortconv_*`` scopes
(harness/shortconv.py), whoever wrote the middle."""

from harness import shortconv


def read(trace, run):
    return shortconv.roofline(trace, run)
