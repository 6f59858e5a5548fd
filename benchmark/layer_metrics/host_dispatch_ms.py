"""Step wrapper: median host time of one ``step(...)`` call (the jit
dispatch and ``timed_step``'s bookkeeping), not a device time."""

import statistics


def read(trace, run):
    if not run.dispatch_seconds:
        return None
    return 1e3 * statistics.median(run.dispatch_seconds)
