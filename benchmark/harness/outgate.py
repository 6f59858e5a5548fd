"""An attention operator's output gate, and the norms of the branches'
outputs, as device time.

``models/trinity.py`` writes the gate's own projection under ``outgate_proj``
and its sigmoid and product with the kernels' output under ``outgate_mul``
(``profiler/annotate.OUTGATE_SCOPES``), and the norm of each branch's output
with its addition to the stream under ``postnorm_attn`` and ``postnorm_ff``
(``POSTNORM_SCOPES``); the compiler keeps a scope in each instruction's
``op_name``, forward, recomputed forward and backward:

    jit(_local_step)/phase_forward_backward/jvp(TrinityDecoder)/TrinityBlock_1/TrinityAttention_0/outgate_proj/gate_proj/dot_general
    jit(_local_step)/phase_forward_backward/transpose(jvp(TrinityDecoder))/TrinityBlock_3/postnorm_ff/post_mlp_layernorm/mul

The rules of ``harness/ssm.py``: an operation that names one of the four
scopes counts under it; one that names none and no ``phase_*`` scope either
(the compiler's copies and loop fusions) inherits the scope, or the lack of
one, of the latest earlier operation of the same step run on that chip that
says what it is. One pass reads all four, each split by direction
(``harness/attn_parts.way_of``: ``recomputed`` for the forward a block runs
again inside the backward), and prints the earlier lines ``outgate_ms`` and
``postnorm_ms``. ``outgate_ms`` states beside the product's time the bytes it
must move (``Job.facts["outgate_mul_bytes_per_layer_pass"]``: the kernels'
output and the gate read, the product written), as a fact and not as a
share.

**The gate's two names are metrics, the norms' two are a printed line and no
metric.** The compiler makes a norm of a branch's output the epilogue of the
branch's last product and its backward a part of that product's backward
(PERF.md section 5, PR 52): those fusions carry the product's name
(``attn_out_proj``, ``moe_shared``, ``moe_combine``), so what is left under
``postnorm_*`` is the part of the norms that was NOT fused, and a change to
the fusion would move ``attn_proj_ms`` and not this number.

Where the step holds no such scope (the parent's programs, every other
configuration) there is nothing to read and every reader returns None.
"""

from __future__ import annotations

import json
import re

from harness import attn_parts, latent, phases, trace_reduce

SCOPES = re.compile(r"\b((?:outgate|postnorm)_[a-z_]+)")
FAMILIES = ("outgate", "postnorm")
MUL = "outgate_mul"


def reduce(trace, hlo, program) -> dict:
    """{"parts": {scope: {way: device self seconds a step}}, "inherited":
    {scope: the part of it the inheritance rule assigned}, "total": self
    seconds a step of every operation inside step runs}, averaged over the
    chips and the step runs, for the four scopes of ``SCOPES``."""
    parts, inherited, total = {}, {}, 0.0
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
        run, latest = 0, (None, phases.FORWARD)
        for span, spent in timed:
            while run + 1 < len(runs) and span.start >= runs[run].end:
                # the next step run begins afresh
                run, latest = run + 1, (None, phases.FORWARD)
            ins = hlo.get(span.name)
            scope, own = latent.scope_of(ins, SCOPES)
            total += share * spent
            if own:
                latest = (scope, attn_parts.way_of(ins))
            else:
                scope = latest[0]
                if scope:
                    inherited[scope] = inherited.get(scope, 0.0) + \
                        share * spent
            if scope:
                ways = parts.setdefault(scope, {})
                ways[latest[1]] = ways.get(latest[1], 0.0) + share * spent
    return {"parts": parts, "inherited": inherited, "total": total}


def seconds(found: dict, prefix: str) -> float:
    """Seconds a step under the scopes whose names begin with ``prefix``,
    every direction."""
    return sum(spent for scope, ways in found["parts"].items()
               if scope.startswith(prefix) for spent in ways.values())


_REDUCED = phases.PerTrace()


def reduced(trace, run):
    """This run's reduction, made once; None without a device plane or
    without the scopes. The first use prints the earlier lines
    ``outgate_ms`` and ``postnorm_ms``."""
    if trace is None or not trace.devices:
        return None

    def make():
        if not latent.has_scopes(run.hlo, SCOPES):
            return None
        found = reduce(trace, run.hlo, run.program)
        if not found["total"]:
            return None

        def ms(table, family):
            return {k: 1e3 * v for k, v in sorted(table.items())
                    if k.startswith(family)}
        for family in FAMILIES:
            line = {f"{family}_ms": {
                        scope: ms(ways, "")
                        for scope, ways in sorted(found["parts"].items())
                        if scope.startswith(family)},
                    "inherited_ms": ms(found["inherited"], family),
                    f"{family}_total_ms": 1e3 * seconds(found, family),
                    "busy_in_steps_ms": 1e3 * found["total"]}
            nbytes = run.job.facts.get("outgate_mul_bytes_per_layer_pass")
            if family == "outgate" and nbytes is not None:
                line["outgate_mul_bytes_per_layer_pass"] = nbytes
                line["outgate_mul_least_ms_per_layer_pass"] = \
                    1e3 * nbytes / run.peaks["hbm_bytes_per_s"]
            print(json.dumps(line), flush=True)
        return found
    return _REDUCED.get(trace, make)


def time_share(trace, run):
    """Device self time under ``outgate_proj`` + ``outgate_mul``, forward,
    recomputed forward and backward, over the busy time inside step runs."""
    found = reduced(trace, run)
    if found is None:
        return None
    return 100.0 * seconds(found, "outgate") / found["total"]


def mul_ms(trace, run):
    """Device self time a step under ``outgate_mul`` alone."""
    found = reduced(trace, run)
    if found is None:
        return None
    return 1e3 * seconds(found, MUL)
