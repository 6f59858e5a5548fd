"""Kernels: device time of the Pallas kernels over the device's busy time.
Reads 0 where the router takes XLA attention."""

from harness import trace_reduce


def read(trace, run):
    if trace is None or not trace.devices:
        return None

    def kind(span):
        ins = run.hlo.get(span.name)
        return "kernel" if ins is not None and run.hlo.is_kernel(ins) \
            else "other"
    seconds = trace_reduce.op_seconds_by(trace, kind)
    busy = sum(seconds.values())
    return 100.0 * seconds.get("kernel", 0.0) / busy if busy else None
