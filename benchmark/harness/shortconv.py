"""The gated short-convolution operator's parts as device time.

``models/lfm2.py`` writes a ``conv`` layer's operator under the named scopes
``shortconv_in_proj``, ``shortconv_mix`` (the two gates and the taps of
``ops/short_conv.py``, nothing else) and ``shortconv_out_proj``
(``profiler/annotate.SHORTCONV_SCOPES``), inside the step's
``phase_forward_backward``; the compiler keeps the scope in each
instruction's ``op_name``, forward, recomputed forward and backward:

    jit(_local_step)/phase_forward_backward/jvp(Lfm2MoeDecoder)/Lfm2Block_0/Lfm2ShortConv_0/shortconv_in_proj/in_proj/dot_general
    jit(_local_step)/phase_forward_backward/transpose(jvp(Lfm2MoeDecoder))/Lfm2Block_0/.../shortconv_mix/mul

The rules of ``harness/ssm.py``: an operation that names a scope counts
under it (a fusion says what its root instruction was, so gates that the
compiler fused into a projection's product count under that projection);
one that names none (the compiler's copies and loop fusions) inherits the
scope, or the lack of one, of the latest earlier operation of the same step
run on that chip that says what it is: one with a ``phase_*`` scope. Both
amounts are printed on the earlier line ``shortconv_ms``.

The middle's two Pallas kernels (``ops/short_conv.py``: ``_mix_fwd_kernel``,
``_mix_bwd_kernel``) are read from the device trace under their own names,
each against what one call of it has to move (:func:`mix_kernel_cost`;
``Job.facts["shortconv_mix_call"]`` states the shapes: tokens, channels,
taps), times the calls of it the compiled step holds.

Where the step's text holds no ``shortconv_*`` scope and no such kernel (the
parent's programs, every other configuration) there is nothing to read and
every reader returns None.
"""

from __future__ import annotations

import json
import re

from harness import flops, kernels, phases, roofline as roofline_lib, \
    trace_reduce

SCOPE = re.compile(r"\b(shortconv_[a-z_]+)")
MIX = "shortconv_mix"
# kernel -> [tokens, d] arrays of the compute dtype it reads and writes once:
# forward [B | C | u] in and y out; backward those three and dy in, their
# three gradients out
MIX_KERNELS = {"_mix_fwd_kernel": 3 + 1, "_mix_bwd_kernel": 3 + 1 + 3}


def scope_of(ins):
    """(the ``shortconv_*`` scope or None, whether the instruction says so
    itself): False where it has to inherit."""
    if ins is None:
        return None, False
    found = SCOPE.search(ins.op_name)
    if found:
        return found.group(1), True
    return None, phases.phase_of(ins) is not None


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
    without the scopes. The first use prints the earlier line
    ``shortconv_ms``."""
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
            "shortconv_ms": ms(found["seconds"]),
            "inherited_ms": ms(found["inherited"]),
            "shortconv_total_ms": 1e3 * sum(found["seconds"].values()),
            "busy_in_steps_ms": 1e3 * found["total"]}), flush=True)
        return found
    return _REDUCED.get(trace, make)


def time_share(trace, run):
    found = reduced(trace, run)
    if found is None:
        return None
    return 100.0 * sum(found["seconds"].values()) / found["total"]


def mix_ms(trace, run):
    found = reduced(trace, run)
    if found is None:
        return None
    return 1e3 * found["seconds"].get(MIX, 0.0)


def roofline(trace, run):
    """100 x the least seconds the chip's peaks allow one step's operators
    (the configuration's own count: the two projections' products in every
    pass the step makes, and the bytes no writing can avoid, every ``conv``
    layer) over the device seconds a step under all three scopes,
    recomputation included, whoever implements the middle."""
    found = reduced(trace, run)
    if found is None:
        return None
    facts = run.job.facts
    spent = sum(found["seconds"].values())
    if not spent or "shortconv_flops_per_layer_step" not in facts:
        return None
    least = facts["shortconv_layers"] * flops.roofline_seconds(
        facts["shortconv_flops_per_layer_step"],
        facts["shortconv_bytes_per_layer_step"], run.peaks)[0]
    return 100.0 * least / spent


def mix_kernel_cost(kernel: str, tokens: int, channels: int, taps: int,
                    dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) one call of the middle's ``kernel`` needs: every
    ``[tokens, channels]`` array read or written once (the rows a tile reads
    from its neighbours, the taps and their float32 gradient are a
    thousandth of that and a writing could keep them on chip); an element's
    operations are the two gates and a multiply-add a tap forward, and
    backward those again, the transposed taps, the three products of the
    gates' gradients and a multiply-add a tap for the taps' own. Memory-bound
    on every chip there is."""
    per_element = 2 + 2 * taps if kernel == "_mix_fwd_kernel" else \
        (2 + 2 * taps) + 1 + 2 * taps + 3 + 2 * taps
    elements = tokens * channels
    return float(per_element * elements), \
        float(MIX_KERNELS[kernel] * elements * dtype_bytes)


def kernel_roofline(trace, run, name: str):
    """100 x the least seconds the chip's peaks allow one step's calls of
    the middle's kernel ``name`` (its cost x the calls of it the compiled
    step holds, a recomputed forward among them) over their measured device
    seconds in one step. None without a device plane or the kernel."""
    if trace is None or not trace.devices:
        return None
    call = run.job.facts.get("shortconv_mix_call")
    calls = kernels.inventory(run.hlo).get(name)
    if call is None or not calls:
        return None

    def this_kernel(span):
        ins = run.hlo.get(span.name)
        if ins is None or not run.hlo.is_kernel(ins):
            return None
        return name if run.hlo.kernel_name(ins) == name else None
    spent = trace_reduce.op_seconds_by(trace, this_kernel).get(name)
    steps = roofline_lib.steps_traced(trace, run)
    if not spent or not steps:
        return None
    least = calls * flops.roofline_seconds(
        *mix_kernel_cost(name, *call), run.peaks)[0]
    return 100.0 * least / (spent / steps)
