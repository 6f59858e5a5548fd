"""Model step: model FLOPs of one step on one chip (harness/flops.py, no
recomputation) over the median device time of the step program, as a share
of the chip's bf16 peak."""

from harness import trace_reduce


def read(trace, run):
    if trace is None or not trace.devices:
        return None
    seconds = trace_reduce.median_step_seconds(trace, run.program)
    flops = run.job.model_flops_per_item * run.items_per_step_per_chip
    return 100.0 * flops / seconds / run.peaks["bf16_flops_per_s"]
