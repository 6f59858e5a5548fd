"""Step wrapper: median gap on the device between two runs of the step
program inside a block."""

import statistics

from harness import trace_reduce


def read(trace, run):
    if trace is None or not trace.devices:
        return None
    gaps = trace_reduce.launch_gaps_seconds(trace, run.program,
                                            run.block_steps)
    return 1e3 * statistics.median(gaps) if gaps else None
