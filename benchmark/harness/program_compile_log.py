"""The program's own compile log, read where it lives.

``horovod_tpu.metrics.compile_log`` (since PR 35) keeps every trace, lowering
and backend compile of the process as a span with its function, its parent
and its cause, beside the spans ``hvd.import`` and ``hvd.init``; the run is
this process, so the readers under ``layer_metrics/`` import it and ask. A
program from before the log has no such module, and one whose ``hvd.init()``
never ran holds no span: ``report`` then returns None and the readers return
None, as ``step_wrapper_self_ms`` does without ``hvd.step`` spans.

The first use in a run prints the earlier line ``compile_log``: the seconds
by stage, the largest top-level and nested functions, the step functions'
part, the program's own spans (``PERF.md`` §5's set-up table) and
``hvd_step_recompiles_total``.

A trace without a device plane is a rehearsal's. Its result line keeps the
set of metrics that the traced rehearsals under ``tests/benchmark/`` compare
exactly, so there the readers print the earlier line and return None, as
the device trace's readers do.
"""

from __future__ import annotations

import json

TOP_FUNCTIONS = 12
TOP_NESTED = 5
_said = []


def module():
    """``horovod_tpu.metrics.compile_log``, or None where the program has
    none."""
    try:
        from horovod_tpu.metrics import compile_log
    except ImportError:
        return None
    return compile_log


def facts(found, log) -> dict:
    """``found`` (``log.report()``) cut to one line's length.
    ``union_of_kept_s`` is what the intervals of the compile spans cover,
    computed from the intervals where the log still keeps every span (else
    None): ``by_stage_s`` and ``by_function_s`` must both come to it."""
    def seconds(entry):
        return sum(v for k, v in entry.items() if k.endswith("_s"))
    functions = sorted(found["functions"].items(),
                       key=lambda kv: -seconds(kv[1]))
    nested = sorted(found["nested"].items(),
                    key=lambda kv: -kv[1]["seconds"])
    by_stage = sum(s["seconds"] for s in found["stages"].values())
    by_function = sum(seconds(e) for e in found["functions"].values())
    return {
        "spans": found["spans"], "kept": found["kept"],
        "stages": found["stages"], "programs": found["programs"],
        "union_s": found["union_s"], "by_stage_s": by_stage,
        "by_function_s": by_function,
        "union_of_kept_s": log.union_seconds(
            s for s in log.spans() if s.stage is not None)
        if found["kept"] == found["spans"] else None,
        "top_functions": dict(functions[:TOP_FUNCTIONS]),
        "other_functions": {
            "count": len(functions[TOP_FUNCTIONS:]),
            "seconds": sum(seconds(e) for _, e in
                           functions[TOP_FUNCTIONS:])},
        "top_nested": dict(nested[:TOP_NESTED]),
        "nested_functions": len(nested),
        "step": found["step"], "program_spans": found["program_spans"]}


def recompiles():
    """``hvd_step_recompiles_total`` over all frameworks, or None where the
    program has no such counter."""
    from horovod_tpu import metrics
    return metrics.snapshot_value(metrics.get_registry().snapshot(),
                                  "hvd_step_recompiles_total")


def report(trace):
    """The log's report, or None where there is no log, no span in it, or
    no device plane in ``trace``."""
    log = module()
    if log is None or not log.spans():
        return None
    found = log.report()
    if not _said:
        _said.append(True)
        print(json.dumps({"compile_log": dict(
            facts(found, log), recompiles=recompiles())}), flush=True)
    return found if trace is not None and trace.devices else None
