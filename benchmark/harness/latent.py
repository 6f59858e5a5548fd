"""Multi-head latent attention and the multi-token-prediction module as
device time: the latent kernels of ``ops/flash_attention.py``, the operator's
scopes, and the module's.

A ``flash_attention`` call whose values are not as wide as its queries and
keys (``models/joyai_flash.py``: q and k of 192, v of 128) compiles to kernel
functions of their own names (``_fwd_latent_kernel``,
``_bwd_dq_latent_kernel``, ``_bwd_dkv_latent_kernel``), the roles of
``flops.FLASH_PRODUCTS``' three at two widths. ``harness/roofline.flash_share``
prices the causal names by ONE head width; here a call costs what the
mathematics needs at both (:func:`latent_kernel_cost`;
``Job.facts["latent_call"]`` states the shapes: batch, seq, heads, qk_dim,
v_dim), and a kernel's cost is multiplied by **the number of times it ran**
in the traced stretch, counted on the device's own line as
``harness/blockdiff.py`` counts: a block recomputed in backward that keeps
its attention's output runs the forward kernel once, one that does not runs
it twice and is costed twice.

The operator writes its parts under ``mla_q_proj``, ``mla_kv_proj``,
``mla_rope``, ``mla_out_proj`` and its call under ``attn_latent``
(``profiler/annotate.MLA_SCOPES``, ``ATTN_SCOPES``), the module under
``mtp_merge``, ``mtp_block``, ``mtp_head`` (``MTP_SCOPES``); the compiler
keeps a scope in each instruction's ``op_name``, forward, recomputed forward
and backward:

    jit(_local_step)/phase_forward_backward/jvp(JoyaiFlashDecoder)/JoyaiBlock_1/JoyaiLatentAttention_0/mla_q_proj/q_a_proj/dot_general
    jit(_local_step)/phase_forward_backward/transpose(jvp(JoyaiFlashDecoder))/JoyaiMtp_0/mtp_block/JoyaiBlock_0/.../mla_rope/mul

The rules of ``harness/ssm.py``: an operation that names a scope of the
family asked about counts under it (the module's block names an ``mtp_*``
scope AND an ``mla_*`` or ``moe_*`` one: each family reads its own); one
that names none and no ``phase_*`` scope either (the compiler's copies and
loop fusions) inherits the scope, or the lack of one, of the latest earlier
operation of the same step run on that chip that says what it is. Both
amounts are printed on the earlier lines ``latent_ms`` and ``mtp_ms``.

Where the step holds no such kernel and no such scope (the parent's
programs, every other configuration) there is nothing to read and every
reader returns None.
"""

from __future__ import annotations

import json
import re

from harness import flops, phases, trace_reduce

# latent kernel -> the causal kernel whose role it has
LATENT_KERNELS = {"_fwd_latent_kernel": "_fwd_kernel",
                  "_bwd_dq_latent_kernel": "_bwd_dq_kernel",
                  "_bwd_dkv_latent_kernel": "_bwd_dkv_kernel"}
# products a pair, by role, as (over qk_dim, over v_dim): forward s | pv;
# dq: s, dq | dp; dk/dv: s, dk | dp, dv
PRODUCTS = {"_fwd_latent_kernel": (1, 1), "_bwd_dq_latent_kernel": (2, 1),
            "_bwd_dkv_latent_kernel": (2, 2)}
# [batch * heads, seq, .] arrays read and written once, by role, as (q-like
# at qk_dim, key-like at qk_dim, at v_dim), and float32 rows (lse, corr)
ARRAYS = {"_fwd_latent_kernel": ((1, 1, 2), 1),        # q | k | v, o
          "_bwd_dq_latent_kernel": ((2, 1, 2), 2),     # q, dq | k | v, do
          "_bwd_dkv_latent_kernel": ((1, 2, 3), 2)}    # q | k, dk | v, do, dv
OPERATOR = re.compile(r"\b(mla_[a-z_]+|attn_latent)\b")
MODULE = re.compile(r"\b(mtp_[a-z_]+)")


def latent_kernel_cost(kernel: str, batch: int, seq: int, heads: int,
                       qk_dim: int, v_dim: int, dtype_bytes: int = 2
                       ) -> tuple:
    """(FLOPs, HBM bytes) one call of the latent ``kernel`` needs, whoever
    implements it. Over the causal pairs ``seq (seq + 1) / 2`` a head:
    forward ``2 (qk + v)`` FLOPs a pair (s, pv), dq ``2 (2 qk + v)`` (s, dp,
    dq), dk/dv ``2 (2 qk + 2 v)`` (s, dp, dv, dk). Bytes: every array once,
    q and dq at ``qk_dim``, v, o, do and dv at ``v_dim``, the row statistics
    in float32; of a key-like array (k, dk) each head's own part and the
    rotary part, which all heads share, once a sequence and not once a head:
    the rotary part is what the keys have beyond the values' width (the
    family's ``qk_nope_head_dim == v_head_dim``)."""
    rope_dim = qk_dim - v_dim
    over_qk, over_v = PRODUCTS[kernel]
    pairs = batch * heads * flops.attended_pairs(seq, True)
    (q_like, key_like, v_like), rows = ARRAYS[kernel]
    rows_of_heads = batch * heads * seq
    elements = rows_of_heads * (q_like * qk_dim + v_like * v_dim
                                + key_like * (qk_dim - rope_dim)) \
        + key_like * batch * seq * rope_dim
    return (2.0 * (over_qk * qk_dim + over_v * v_dim) * pairs,
            float(elements * dtype_bytes + rows_of_heads * rows * 4))


# -- the three kernels, from the device's own line ----------------------------

def _kernel_of(run):
    def kernel(span):
        ins = run.hlo.get(span.name)
        if ins is None or not run.hlo.is_kernel(ins):
            return None
        name = run.hlo.kernel_name(ins)
        return name if name in LATENT_KERNELS else None
    return kernel


_RUNS = phases.PerTrace()


def runs_and_seconds(trace, run):
    """{kernel: (times it ran, its device seconds)} over the traced stretch,
    averaged over the chips, counted once a trace; None without a device
    plane or without a latent kernel in the trace."""
    if trace is None or not trace.devices:
        return None
    return _RUNS.get(trace, lambda: _count_runs(trace, run))


def _count_runs(trace, run):
    kernel = _kernel_of(run)
    lo, hi = trace_reduce.stretch(trace)
    found = {}
    for device in trace.devices:
        for span, seconds in trace_reduce.self_seconds(device.ops):
            if span.end <= lo or span.start >= hi:
                continue
            name = kernel(span)
            if name is not None:
                ran, spent = found.get(name, (0, 0.0))
                found[name] = (ran + 1, spent + seconds)
    chips = len(trace.devices)
    return {name: (ran / chips, spent / chips)
            for name, (ran, spent) in found.items()} or None


def share(trace, run, name):
    """100 x the least seconds the chip's peaks allow the traced calls of
    the latent kernel ``name`` (its cost x the times it ran) over their
    measured device seconds."""
    found = runs_and_seconds(trace, run)
    call = run.job.facts.get("latent_call") if found else None
    if call is None or name not in found:
        return None
    ran, spent = found[name]
    least = ran * flops.roofline_seconds(
        *latent_kernel_cost(name, *call), run.peaks)[0]
    return 100.0 * least / spent if spent else None


def time_share(trace, run):
    """Device time of the three latent kernels over the device's busy
    time."""
    if trace is None or not trace.devices:
        return None
    kernel = _kernel_of(run)
    seconds = trace_reduce.op_seconds_by(
        trace, lambda span: "latent" if kernel(span) else "other")
    busy = sum(seconds.values())
    if not busy or "latent" not in seconds:
        return None
    return 100.0 * seconds["latent"] / busy


# -- the operator's and the module's scopes ------------------------------------

def scope_of(ins, pattern):
    """(the scope of ``pattern``'s family or None, whether the instruction
    says so itself): False where it has to inherit."""
    if ins is None:
        return None, False
    found = pattern.search(ins.op_name)
    if found:
        return found.group(1), True
    return None, phases.phase_of(ins) is not None


def has_scopes(hlo, pattern) -> bool:
    return any(pattern.search(i.op_name) for i in hlo.instructions.values())


def reduce(trace, hlo, program, pattern) -> dict:
    """{"seconds": {scope: device self seconds a step}, "inherited": the
    part of it the inheritance rule assigned, "total": self seconds a step
    of every operation inside step runs}, averaged over the chips and the
    step runs, for the scopes ``pattern`` finds."""
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
            scope, own = scope_of(hlo.get(span.name), pattern)
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


_REDUCED = {"latent": phases.PerTrace(), "mtp": phases.PerTrace()}
_PATTERNS = {"latent": OPERATOR, "mtp": MODULE}


def reduced(trace, run, family: str):
    """This run's reduction for ``family`` (``"latent"``: the operator's
    four scopes and its call; ``"mtp"``: the module's three), made once;
    None without a device plane or without the scopes. The first use prints
    the earlier line ``latent_ms`` or ``mtp_ms``."""
    if trace is None or not trace.devices:
        return None
    pattern = _PATTERNS[family]

    def make():
        if not has_scopes(run.hlo, pattern):
            return None
        found = reduce(trace, run.hlo, run.program, pattern)
        if not found["total"]:
            return None

        def ms(table):
            return {k: 1e3 * v for k, v in sorted(table.items())}
        print(json.dumps({
            f"{family}_ms": ms(found["seconds"]),
            "inherited_ms": ms(found["inherited"]),
            f"{family}_total_ms": 1e3 * sum(found["seconds"].values()),
            "busy_in_steps_ms": 1e3 * found["total"]}), flush=True)
        return found
    return _REDUCED[family].get(trace, make)


def attention_roofline(trace, run):
    """100 x the least seconds the chip's peaks allow one step's latent
    attention operators (the configuration's own count: the five
    projections' products in every pass the step makes and the kernels'
    costs, against the bytes no writing can avoid, every operator) over the
    device seconds a step under the four ``mla_*`` scopes and
    ``attn_latent``, recomputation included, whichever way the key is
    built."""
    found = reduced(trace, run, "latent")
    if found is None:
        return None
    facts = run.job.facts
    spent = sum(found["seconds"].values())
    if not spent or "latent_attention_flops_per_layer_step" not in facts:
        return None
    least = facts["latent_layers"] * flops.roofline_seconds(
        facts["latent_attention_flops_per_layer_step"],
        facts["latent_attention_bytes_per_layer_step"], run.peaks)[0]
    return 100.0 * least / spent


def mtp_time_share(trace, run):
    """Device self time under any ``mtp_*`` scope, forward, recomputed
    forward and backward, over the busy time inside step runs."""
    found = reduced(trace, run, "mtp")
    if found is None:
        return None
    return 100.0 * sum(found["seconds"].values()) / found["total"]
