"""Step wrapper: median over the traced stretch of an ``hvd.step`` span's
duration less the ``hvd.step.dispatch`` span it contains: what
``metrics.timed_step`` itself costs a call, on the profiler's clock. None
without a device plane or where the program writes no such span."""

import statistics

from harness import program_spans


def read(trace, run):
    spans = program_spans.spans_for(trace)
    own = program_spans.wrapper_self_seconds(trace, spans) if spans else []
    return 1e3 * statistics.median(own) if own else None
