"""Model step: share of the device's busy time spent in convolutions and
dots, alone or inside a fusion (by the compiled text's opcodes)."""

from harness import trace_reduce


def read(trace, run):
    if trace is None or not trace.devices:
        return None

    def kind(span):
        ins = run.hlo.get(span.name)
        return "mxu" if ins is not None and run.hlo.is_mxu(ins) else "other"
    seconds = trace_reduce.op_seconds_by(trace, kind)
    busy = sum(seconds.values())
    return 100.0 * seconds.get("mxu", 0.0) / busy if busy else None
