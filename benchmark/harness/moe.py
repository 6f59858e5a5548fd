"""The expert layer's parts as device time.

``parallel/ep.moe_topk`` writes its four parts under the named scopes
``moe_router``, ``moe_dispatch``, ``moe_experts`` and ``moe_combine``
(``profiler/annotate.MOE_SCOPES``), inside the step's
``phase_forward_backward``; the compiler keeps the scope in each
instruction's ``op_name``, forward and backward:

    jit(_local_step)/phase_forward_backward/jvp(OlmoeDecoder)/OlmoeBlock_0/OlmoeSparseMoe_0/moe_dispatch/jit(argsort)/sort
    jit(_local_step)/phase_forward_backward/transpose(phase_forward_backward)/jvp(OlmoeDecoder)/OlmoeBlock_0/OlmoeSparseMoe_0/moe_combine/...

Two rules beyond the scope's own word. (1) The TPU compiler lowers each
``jax.lax.ragged_dot`` to Mosaic calls of its own whose ``op_name`` is
``ragged-dot-none`` or ``ragged-dot-metadata`` and nothing else: the scope
is gone. Such an operation counts as ``moe_experts`` (``by_name``). (2) An
operation with neither (the compiler's copies and loop fusions) inherits
the scope, or the lack of one, of the latest earlier operation of the same
step run on that chip that has a ``phase_*`` scope or is a ragged dot: the
rule of ``harness/phases.py``. Both amounts are printed on the earlier line
``moe_ms`` beside the numbers they are part of.

Where the step's text holds no ``moe_*`` scope (a program without the
layer) there is nothing to read and every reader returns None.
"""

from __future__ import annotations

import json
import re

from harness import phases, trace_reduce

SCOPE = re.compile(r"\b(moe_[a-z]+)")
RAGGED_DOT = "ragged-dot"
EXPERTS = "moe_experts"
HBM_BOUND = ("moe_router", "moe_dispatch", "moe_combine")


def is_ragged_dot(ins) -> bool:
    return ins is not None and ins.op_name.startswith(RAGGED_DOT)


def scope_of(ins):
    """(the ``moe_*`` scope or None, whether the instruction says so
    itself): False where it has to inherit."""
    if ins is None:
        return None, False
    if is_ragged_dot(ins):
        return EXPERTS, True
    found = SCOPE.search(ins.op_name)
    if found:
        return found.group(1), True
    return None, phases.phase_of(ins) is not None


def has_scopes(hlo) -> bool:
    return any(SCOPE.search(i.op_name) for i in hlo.instructions.values())


def reduce(trace, hlo, program) -> dict:
    """{"seconds": {scope: device self seconds a step}, "inherited": the
    part of it rule (2) assigned, "by_name": the part rule (1) did,
    "total": self seconds a step of every operation inside step runs},
    averaged over the chips and the step runs."""
    seconds, inherited, by_name, total = {}, {}, 0.0, 0.0
    chips = len(trace.devices)
    for device in trace.devices:
        runs = trace_reduce.step_runs(device, program)
        if not runs:
            continue
        share = 1.0 / (len(runs) * chips)
        timed = sorted(
            trace_reduce.self_seconds(
                trace_reduce.inside_steps(device, program)),
            key=lambda pair: (pair[0].start, -pair[0].end))
        run, latest = 0, None
        for span, spent in timed:
            while run + 1 < len(runs) and span.start >= runs[run].end:
                run, latest = run + 1, None  # the next step run begins afresh
            ins = hlo.get(span.name)
            scope, own = scope_of(ins)
            total += share * spent
            if own:
                latest = scope
            else:
                scope = latest
                if scope:
                    inherited[scope] = inherited.get(scope, 0.0) + \
                        share * spent
            if scope:
                seconds[scope] = seconds.get(scope, 0.0) + share * spent
                if is_ragged_dot(ins):
                    by_name += share * spent
    return {"seconds": seconds, "inherited": inherited, "by_name": by_name,
            "total": total}


_REDUCED = phases.PerTrace()


def reduced(trace, run):
    """This run's reduction, made once; None without a device plane or
    without the scopes. The first use prints the earlier line ``moe_ms``."""
    if trace is None or not trace.devices:
        return None

    def make():
        if not has_scopes(run.hlo):
            return None
        found = reduce(trace, run.hlo, run.program)
        if not found["total"]:
            return None

        def ms(table):
            return {k: 1e3 * v for k, v in sorted(table.items())}
        print(json.dumps({
            "moe_ms": ms(found["seconds"]),
            "inherited_ms": ms(found["inherited"]),
            "ragged_dot_by_name_ms": 1e3 * found["by_name"],
            "moe_total_ms": 1e3 * sum(found["seconds"].values()),
            "busy_in_steps_ms": 1e3 * found["total"]}), flush=True)
        return found
    return _REDUCED.get(trace, make)


def time_share(trace, run):
    found = reduced(trace, run)
    if found is None:
        return None
    return 100.0 * sum(found["seconds"].values()) / found["total"]


def dispatch_ms(trace, run):
    found = reduced(trace, run)
    if found is None:
        return None
    return 1e3 * sum(found["seconds"].get(s, 0.0) for s in HBM_BOUND)


def experts_mfu(trace, run):
    """100 x the experts' useful FLOPs of a step (the configuration's own
    count: the active experts' three products, forward and backward) over
    the device seconds under ``moe_experts`` over the chip's bf16 peak."""
    found = reduced(trace, run)
    facts = run.job.facts
    per_layer = facts.get("moe_train_flops_per_token_per_layer")
    spent = found["seconds"].get(EXPERTS) if found else None
    if not spent or per_layer is None:
        return None
    flops = per_layer * facts["layers"] * run.items_per_step_per_chip
    return 100.0 * flops / spent / run.peaks["bf16_flops_per_s"]
