"""Device: 1 - the union of the device-operation intervals over the traced
stretch (first ``bench.block``'s start to the last one's end)."""

from harness import trace_reduce


def read(trace, run):
    if trace is None or not trace.devices:
        return None
    busy_s, window_s = trace_reduce.busy_and_window_seconds(trace)
    return 100.0 * (1.0 - busy_s / window_s)
