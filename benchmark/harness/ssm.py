"""The Mamba-2 mixer's parts as device time.

``models/nemotron_h.py`` writes a mixer's five parts under the named scopes
``ssm_in_proj``, ``ssm_conv``, ``ssm_scan`` (``ops/ssd.ssd_chunked``),
``ssm_gate_norm`` and ``ssm_out_proj`` (``profiler/annotate.SSM_SCOPES``),
inside the step's ``phase_forward_backward``; the compiler keeps the scope in
each instruction's ``op_name``, forward, recomputed forward and backward:

    jit(_local_step)/phase_forward_backward/jvp(NemotronHDecoder)/NemotronHBlock_0/NemotronHMamba2Mixer_0/ssm_scan/dot_general
    jit(_local_step)/phase_forward_backward/transpose(jvp(NemotronHDecoder))/NemotronHBlock_0/.../ssm_conv/mul

The rules of ``harness/moe.py``: an operation that names a scope counts
under it; one that names none (the compiler's copies and loop fusions)
inherits the scope, or the lack of one, of the latest earlier operation of
the same step run on that chip that says what it is: one with a ``phase_*``
scope, or a ``ragged-dot`` call (the expert layer's, which carries no scope
and is no part of a mixer). Both amounts are printed on the earlier line
``ssm_ms``.

Where the step's text holds no ``ssm_*`` scope (the parent's programs, every
other configuration) there is nothing to read and every reader returns None.
"""

from __future__ import annotations

import json
import re

from harness import flops, moe, phases, trace_reduce

SCOPE = re.compile(r"\b(ssm_[a-z_]+)")
SCAN = "ssm_scan"
NOT_A_PROJECTION = ("ssm_conv", SCAN, "ssm_gate_norm")


def scope_of(ins):
    """(the ``ssm_*`` scope or None, whether the instruction says so
    itself): False where it has to inherit."""
    if ins is None:
        return None, False
    found = SCOPE.search(ins.op_name)
    if found:
        return found.group(1), True
    return None, moe.is_ragged_dot(ins) or phases.phase_of(ins) is not None


def has_scopes(hlo) -> bool:
    return any(SCOPE.search(i.op_name) for i in hlo.instructions.values())


def reduce(trace, hlo, program) -> dict:
    """{"seconds": {scope: device self seconds a step}, "inherited": the
    part of it the inheritance rule assigned, "total": self seconds a step
    of every operation inside step runs}, averaged over the chips and the
    step runs."""
    seconds, inherited, total = {}, {}, 0.0
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
            scope, own = scope_of(hlo.get(span.name))
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
    return {"seconds": seconds, "inherited": inherited, "total": total}


_REDUCED = phases.PerTrace()


def reduced(trace, run):
    """This run's reduction, made once; None without a device plane or
    without the scopes. The first use prints the earlier line ``ssm_ms``."""
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
            "ssm_ms": ms(found["seconds"]),
            "inherited_ms": ms(found["inherited"]),
            "ssm_total_ms": 1e3 * sum(found["seconds"].values()),
            "busy_in_steps_ms": 1e3 * found["total"]}), flush=True)
        return found
    return _REDUCED.get(trace, make)


def time_share(trace, run):
    found = reduced(trace, run)
    if found is None:
        return None
    return 100.0 * sum(found["seconds"].values()) / found["total"]


def scan_ms(trace, run):
    found = reduced(trace, run)
    if found is None:
        return None
    return 1e3 * sum(found["seconds"].get(s, 0.0) for s in NOT_A_PROJECTION)


def scan_roofline(trace, run):
    """100 x the least seconds the chip's peaks allow one step's scans (the
    configuration's own count of their products and unavoidable bytes,
    forward and backward, every mixer layer) over the device seconds a step
    under ``ssm_scan``, recomputation included."""
    found = reduced(trace, run)
    facts = run.job.facts
    spent = found["seconds"].get(SCAN) if found else None
    if not spent or "ssd_scan_flops_per_layer_step" not in facts:
        return None
    least = facts["ssm_layers"] * flops.roofline_seconds(
        facts["ssd_scan_flops_per_layer_step"],
        facts["ssd_scan_bytes_per_layer_step"], run.peaks)[0]
    return 100.0 * least / spent
