"""The parts of an attention operator that are not its kernels, and the
head, as device time.

Every model writes its attention operator's parts under one set of names
(``profiler/annotate.ATTN_PART_SCOPES``: ``attn_qkv_proj``, ``attn_qk_norm``,
``attn_rope``, ``attn_out_proj`` by the model; ``attn_kernel_io``,
``attn_self_block``, ``attn_merge`` by ``ops/flash_attention.py`` around its
kernels' calls) and its head under ``head_logits`` and ``head_loss``
(``HEAD_SCOPES``). The latent operator keeps its ``mla_*`` names, the
multi-token-prediction module's head ``mtp_head``, a diffusion objective's
loss ``diffusion_loss``: :data:`GROUPS` puts them beside the shared names.
A part never encloses another part, so the first name an ``op_name`` holds
is its only one:

    jit(_local_step)/phase_forward_backward/jvp(GptDecoder)/EncoderBlock_3/FlashSelfAttention_0/attn_qkv_proj/query/dot_general
    jit(_local_step)/phase_forward_backward/transpose(phase_forward_backward)/jvp(SdarMoeDecoder)/SdarBlock_1/SdarAttention_0/attn_blockdiff/attn_kernel_io/transpose
    jit(_local_step)/phase_forward_backward/jvp(head_loss)/reduce_max

The three metrics are sums over ``harness/latent.reduce`` under
:data:`PARTS` (the rules of ``harness/ssm.py``: an operation that names a
part counts under it; one that names none and no ``phase_*`` scope either
inherits the part, or the lack of one, of the latest earlier operation of the
same step run on that chip that says what it is). A second pass over the
same spans, by the same rule, makes what the pattern alone cannot: each
part's time by direction (``phases.direction``; the recomputed forward apart
where the text marks it with ``rematted_computation``), the time of the
``copy``, ``copy-start`` / ``copy-done``, ``transpose`` and ``convert``
operations each part was assigned, the attention kernels' time, what the
attention modules hold under no part, and, where the step writes no
``head_loss`` at all (the GPT cells' loss is the configuration's, not the
model's), the operations of ``phases.LOSS_BLOCK`` as ``head_loss``. All of it
is printed on the earlier line ``attn_parts_ms``.

Where the step's text holds no name of the two shared families (the parent's
programs) there is nothing to read and every reader returns None.
"""

from __future__ import annotations

import json
import re
import time

from harness import blockdiff, flops, latent, phases, trace_reduce, window

GROUPS = {
    "projections": ("attn_qkv_proj", "attn_out_proj", "mla_q_proj",
                    "mla_kv_proj", "mla_out_proj"),
    "outside": ("attn_qk_norm", "attn_rope", "mla_rope", "attn_kernel_io",
                "attn_self_block", "attn_merge"),
    "head": ("head_logits", "head_loss", "mtp_head", "diffusion_loss"),
}
_NAMES = [name for names in GROUPS.values() for name in names]
PARTS = re.compile(r"\b(%s)\b" % "|".join(_NAMES))
# the names this vocabulary brought (the two shared families' prefixes): a
# step that holds none is from before it
SHARED = re.compile(r"\b(%s)\b" % "|".join(
    name for name in _NAMES if name.startswith(("attn_", "head_"))))
HEAD_LOSS = "head_loss"
WRITES_HEAD_LOSS = re.compile(rf"\b({HEAD_LOSS})\b")
ATTENTION_KERNELS = (*flops.FLASH_PRODUCTS, *window.WINDOW_KERNELS,
                     *blockdiff.BLOCKDIFF_KERNELS, *latent.LATENT_KERNELS)
ATTENTION_MODULE = re.compile(r"/\w*Attention(?:_\d+)?/")
COPIES = ("copy", "copy-start", "copy-done", "transpose", "convert")
RECOMPUTED = "recomputed"
REMAT_MARK = "rematted_computation"


def way_of(ins) -> str:
    """``forward``, ``backward``, or ``recomputed`` for the forward pass a
    block runs again inside the backward."""
    if REMAT_MARK in ins.op_name:
        return RECOMPUTED
    return phases.direction(ins)


def second_pass(trace, hlo, program, loss_block_is_head: bool) -> dict:
    """What :func:`latent.reduce` under :data:`PARTS` cannot say, over the
    same spans by the same rule; seconds a step, averaged over the chips
    and the step runs. ``parts``: {part: {way: s}}; ``inherited`` and
    ``copies``: {part: s}; ``kernels``: {attention kernel: s};
    ``attention_modules``: the operations that name an attention module,
    and ``unnamed``, those of them that name no part and are no kernel."""
    parts, inherited, copies, kernels = {}, {}, {}, {}
    modules = unnamed = 0.0
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
            part, own = latent.scope_of(ins, PARTS)
            if own and part is None and loss_block_is_head and \
                    phases.block_of(ins) == phases.LOSS_BLOCK:
                part = HEAD_LOSS
            if own:
                latest = (part, way_of(ins))
            else:
                part, _ = latest
                if part:
                    inherited[part] = inherited.get(part, 0.0) + \
                        share * spent
            if part:
                ways = parts.setdefault(part, {})
                ways[latest[1]] = ways.get(latest[1], 0.0) + share * spent
                if ins is not None and ins.opcode in COPIES:
                    copies[part] = copies.get(part, 0.0) + share * spent
            if not own:
                continue
            kernel = hlo.kernel_name(ins) if hlo.is_kernel(ins) else None
            if kernel not in ATTENTION_KERNELS:
                kernel = None
            if kernel:
                kernels[kernel] = kernels.get(kernel, 0.0) + share * spent
            if ATTENTION_MODULE.search(ins.op_name):
                modules += share * spent
                if not (part or kernel):
                    unnamed += share * spent
    return {"parts": parts, "inherited": inherited, "copies": copies,
            "kernels": kernels, "attention_modules": modules,
            "unnamed": unnamed}


def reduce(trace, hlo, program) -> dict:
    """{"seconds": {part: device self seconds a step}, "inherited", "total":
    as :func:`latent.reduce` gives them under :data:`PARTS`, with the
    ``loss`` block as ``head_loss`` where the step writes none; "detail":
    :func:`second_pass`'s}."""
    found = latent.reduce(trace, hlo, program, PARTS)
    loss_block_is_head = not latent.has_scopes(hlo, WRITES_HEAD_LOSS)
    detail = second_pass(trace, hlo, program, loss_block_is_head)
    if loss_block_is_head and HEAD_LOSS in detail["parts"]:
        found["seconds"][HEAD_LOSS] = sum(
            detail["parts"][HEAD_LOSS].values())
        if HEAD_LOSS in detail["inherited"]:
            found["inherited"][HEAD_LOSS] = detail["inherited"][HEAD_LOSS]
    return {**found, "detail": detail,
            "loss_from": "loss block" if loss_block_is_head else HEAD_LOSS}


def group_seconds(found: dict) -> dict:
    return {group: sum(found["seconds"].get(name, 0.0) for name in names)
            for group, names in GROUPS.items()}


_REDUCED = phases.PerTrace()


def reduced(trace, run):
    """This run's reduction, made once; None without a device plane or
    where the step holds no name of the shared families. The first use
    prints the earlier line ``attn_parts_ms``."""
    if trace is None or not trace.devices:
        return None

    def make():
        if not latent.has_scopes(run.hlo, SHARED):
            return None
        began = time.perf_counter()
        found = reduce(trace, run.hlo, run.program)
        if not found["total"]:
            return None
        say(found, run.hlo, time.perf_counter() - began)
        return found
    return _REDUCED.get(trace, make)


def say(found: dict, hlo, reduction_s: float):
    def ms(table):
        return {k: 1e3 * v for k, v in sorted(table.items())}
    detail = found["detail"]
    # a text without the mark does not tell a recomputed forward from the
    # backward: two ways then, and the line says so
    marked = any(REMAT_MARK in i.op_name for i in hlo.instructions.values())
    print(json.dumps({
        "attn_parts_ms": {part: ms(ways)
                          for part, ways in sorted(detail["parts"].items())},
        "recomputed_forward_marked": marked,
        "inherited_ms": ms(found["inherited"]),
        "copies_ms": ms(detail["copies"]),
        "attention_kernels_ms": ms(detail["kernels"]),
        "groups_ms": ms(group_seconds(found)),
        "head_loss_from": found["loss_from"],
        "attention_modules_ms": 1e3 * detail["attention_modules"],
        "attention_modules_unnamed_ms": 1e3 * detail["unnamed"],
        "busy_in_steps_ms": 1e3 * found["total"],
        "reduction_s": reduction_s}), flush=True)


def group_ms(trace, run, group: str):
    """Device self time a step under the parts of ``group``
    (:data:`GROUPS`), forward, recomputed forward and backward."""
    found = reduced(trace, run)
    if found is None:
        return None
    return 1e3 * group_seconds(found)[group]
