"""Model step: device self time a step under ``outgate_mul`` alone, the
output gate's sigmoid and its product with the attention kernels' output,
forward, recomputed forward and backward (harness/outgate.py; the earlier
line ``outgate_ms`` has it by direction beside the bytes the product must
move). None where the step has no such scope."""

from harness import outgate


def read(trace, run):
    return outgate.mul_ms(trace, run)
