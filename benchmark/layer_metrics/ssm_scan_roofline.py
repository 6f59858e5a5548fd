"""State-space mixer: the chunked scan against its roofline: the least
time the chip's peaks allow a step's scans (the configuration's count of
their products and unavoidable bytes) over the device seconds under
``ssm_scan`` (harness/ssm.py), whoever wrote the scan."""

from harness import ssm


def read(trace, run):
    return ssm.scan_roofline(trace, run)
