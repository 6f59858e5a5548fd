"""``nemotron3n-t8192`` at its real size, compiled for one described TPU v5e
(``tpu_compile_cases.py``): one compile a module, read by every test here.
"""

import re

import pytest

from tpu_compile_cases import (  # noqa: F401
    _benchmark_on_path, _compiled_cell, _kernel_calls, _parts_hold,
    _row_scatters, no_persistent_cache, topo)


@pytest.fixture(scope="module")
def nemotron_cell(topo):
    """``nemotron3n-t8192``: nine layers at the published widths, 8192
    tokens, blocks M and E recomputed, through
    ``dp.make_stateful_train_step``."""
    return _compiled_cell(topo, "nemotron3n-t8192")


def test_nemotron_cell_fits_one_v5e_at_full_size(nemotron_cell):
    job, traffic, compiled = nemotron_cell
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    # full size: 8.0 GB of arguments, and the step's temporaries are 2.66 GB
    # since the head walks its rows in chunks (``ops/head_loss.py``)
    assert 10.5e9 < total < 15.0e9, total
    # 667 M parameters and AdamW's moments at 12 bytes
    assert memory.argument_size_in_bytes == pytest.approx(8.0e9, rel=2e-3)
    recorded = traffic["memory_analysis"]
    assert recorded["argument_bytes"] == memory.argument_size_in_bytes
    # the record is PR 30's program, whose expert layers worked all 49 152
    # pairs: the step's temporaries may shrink, they may not outgrow it
    assert memory.temp_size_in_bytes <= 1.02 * recorded["temp_bytes"]


def test_nemotron_cell_holds_its_kernels_and_scopes(nemotron_cell):
    """Three flash kernels (the attention block keeps its activations) and
    the scan's kernels once a mixer layer and pass they are traced for: the
    forward twice a layer (the pass itself and the recomputation, which
    also writes the chunks' end states) and the backward once, every one
    under ``ssm_scan``, the backward's under ``transpose(jvp(...))``; and
    no call of the compiler's own: a share's walk multiplies by XLA's
    batched product, ``[8, 640, k] x [8, k, n]`` over a tile's eight slots
    of 640 rows, eight times in each of four expert layers whose forward is
    recomputed (the two projections forward, recomputed, towards the rows
    and towards the matrices), every one under ``moe_experts``: two loops a
    layer, not an unrolling and not a fast path beside a fallback, and no
    ``ragged-dot`` call (32 of them, and 12 of their metadata, before PR
    34). Every ``ssm_*`` scope and ``moe_shared`` in the text. The rows of
    pairs sent elsewhere are gone: the ``k T`` = 49 152 pairs still index
    vectors (the sort keys, the router weights' gradient), and no array
    has that many rows of hidden or expert width; nor does any array hold a
    chunk's [128, 128] decays a head (``ssd_chunked`` wrote [1, 64, 8, 8,
    128, 128]). Since PR 42 the mixer's conv and gated norm are kernels
    too (``ops/ssm_ends.py``), under ``ssm_conv`` and ``ssm_gate_norm``."""
    from horovod_tpu.parallel import ep
    from horovod_tpu.profiler.annotate import MOE_SCOPES, SSM_SCOPES
    job, _, compiled = nemotron_cell
    text = compiled.as_text()
    calls, op_names = _kernel_calls(text)
    mixers, expert_layers = job.facts["ssm_layers"], 4
    assert mixers == 4
    assert calls == {
        "_fwd_kernel": 1, "_bwd_dq_kernel": 1, "_bwd_dkv_kernel": 1,
        "_ssd_fwd_kernel": 2 * mixers, "_ssd_bwd_kernel": mixers,
        # the mixer's two ends (PR 42): the conv a call for each of x, B
        # and C, the gated norm one; forward, recomputed, backward
        "_conv_fwd_kernel": 3 * 2 * mixers, "_conv_bwd_kernel": 3 * mixers,
        "_norm_fwd_kernel": 2 * mixers, "_norm_bwd_kernel": mixers,
        # a live tile's rows back to their tokens: the weighted rows
        # forward and the rows' gradient backward, once a layer each (the
        # recomputed forward walk's result is needed by nothing, and goes)
        "_add_rows_kernel": 2 * expert_layers}
    way_back = op_names["_add_rows_kernel"]
    assert sorted("transpose(jvp(" in name for name in way_back) == \
        [False] * expert_layers + [True] * expert_layers
    assert all(("moe_dispatch" if "transpose(jvp(" in name
                else "moe_combine") in name for name in way_back)
    assert "ragged-dot" not in text and not _row_scatters(text)
    slot = ep.share_slot_rows(6 * 8192, 128)
    assert slot == 640 and ep.share_tile_rows(6 * 8192, 8, 128) == 8 * slot
    products = re.findall(
        r"= f32(\[8,\d+,\d+\])\S* convolution\([^\n]*"
        r"moe_experts\)*/esk,ekn->esn/dot_general", text)
    assert len(products) == 8 * expert_layers
    # 2688 x 1856 are no whole 128s: the slots' side of the shape rule,
    # and no grouped-matmul kernel in the step (``calls`` above)
    assert ep.share_product((2688, 1856)) == "slots"
    assert sorted(set(products)) == sorted(
        f"[8,{a},{b}]" for a, b in [(slot, 1856), (slot, 2688),
                                    (1856, 2688), (2688, 1856)])
    for kernel, scope in (("_ssd_fwd_kernel", "ssm_scan"),
                          ("_ssd_bwd_kernel", "ssm_scan"),
                          ("_conv_fwd_kernel", "ssm_conv"),
                          ("_conv_bwd_kernel", "ssm_conv"),
                          ("_norm_fwd_kernel", "ssm_gate_norm"),
                          ("_norm_bwd_kernel", "ssm_gate_norm")):
        assert all(scope in name for name in op_names[kernel]), kernel
        if "bwd" in kernel:
            assert all("transpose(jvp(" in name
                       for name in op_names[kernel]), kernel
    for scope in SSM_SCOPES + MOE_SCOPES:
        assert scope in text, scope
    pairs = 6 * 8192
    assert re.search(rf"\[{pairs}\]", text)
    assert not re.search(rf"\[{pairs},\d", text)
    assert not re.search(r"\[1,64,8,8,128,128\]", text)
    opcodes = re.findall(r"[\s)]([a-z\-]+)\(", text)
    assert "all-reduce" not in opcodes  # one chip exchanges nothing


def _entry_instructions(text):
    """(the text's index, the instructions of its entry computation that
    are no bookkeeping)."""
    _benchmark_on_path()
    from harness import hlo_text
    hlo = hlo_text.HloIndex(text)
    entry = re.search(r"^ENTRY\s+%?([\w.\-]+)", text, re.M).group(1)
    free = {"bitcast", "get-tuple-element", "tuple", "parameter", "constant"}
    return hlo, [i for i in hlo.bodies[entry] if i.opcode not in free]


def _scope_bytes(text, scopes, positions=8192):
    """{scope: bytes in + out} of the entry computation's instructions whose
    ``op_name`` holds the scope: each instruction's results and its distinct
    operands, whole (a fusion that reads a slice of an operand is counted as
    reading all of it: an upper bound). But a kernel works one run of
    channels of its sequences (arrays whose last axis is the ``positions``):
    the in-projection's whole output is an operand it addresses a run of,
    a ``dx`` several calls fill is a result it writes a run of. Each such
    array of a kernel is counted at the smallest of them."""
    hlo, instructions = _entry_instructions(text)
    from harness import hlo_text

    def arrays(shape):   # (elements, bytes an element, is a sequence)
        return [(hlo_text.shape_bytes(f"s8[{dims}]"),
                 hlo_text.DTYPE_BYTES[dtype],
                 dims.endswith(f",{positions}"))
                for dtype, dims in hlo_text._ARRAY.findall(shape)
                if dtype in hlo_text.DTYPE_BYTES]
    total = dict.fromkeys(scopes, 0)
    for ins in instructions:
        scope = next((s for s in scopes if s in ins.op_name), None)
        if scope is None:
            continue
        operands = ins.attributes.split("(", 1)[1].split("), ")[0]
        moved = arrays(ins.shape)
        for name in set(re.findall(r"%([\w.\-]+)", operands)):
            moved += arrays(hlo.instructions[name].shape)
        run = min((n for n, _, sequence in moved if sequence), default=0)
        total[scope] += sum(
            (min(n, run) if sequence and hlo.is_kernel(ins) else n) * size
            for n, size, sequence in moved)
    return total


def _activation_copies(text):
    """The entry instructions that only move a sequence's activations
    (8192 positions by some thousand channels): none is wanted beside a
    kernel."""
    instructions = _entry_instructions(text)[1]
    from harness import hlo_text
    return [(i.name, i.shape) for i in instructions
            if i.opcode in ("slice", "copy", "pad", "concatenate")
            and hlo_text.shape_bytes(i.shape) > 8192 * 1024]


def test_nemotron_cell_moves_the_two_ends_once_a_pass(nemotron_cell):
    """Under ``ssm_conv`` + ``ssm_gate_norm`` the step's instructions read
    and write under 8 GB (18.6 before PR 42; 4 layers x (two forward passes
    and a backward) of x, y, z, their gradients and the results once each
    are 5.8). Nothing writes the norm's statistics out a channel
    (``f32[8192,8,512]``), the gated product in float32, or a cotangent a
    tap of the conv (a tuple of four ``bf16[1,8192,6144]``); and no
    ``slice`` copies a run of the in-projection's output for a kernel: they
    read it in place."""
    _, _, compiled = nemotron_cell
    text = compiled.as_text()
    moved = _scope_bytes(text, ("ssm_conv", "ssm_gate_norm"))
    assert 4e9 < sum(moved.values()) < 8e9, moved
    assert "f32[8192,8,512]" not in text
    assert "f32[1,8192,4096]" not in text
    assert not re.search(
        r"\((bf16\[1,8192,6144\]\S*, ){3}bf16\[1,8192,6144\]", text)
    hlo = _entry_instructions(text)[0]
    copies = [found for found in _activation_copies(text)
              if "ssm_" in hlo.instructions[found[0]].op_name]
    assert not copies, copies


def test_nemotron_cell_names_its_attention_parts_and_its_head(nemotron_cell):
    """Attention without positions or norms: the projections, what
    surrounds the kernels' calls, the head and the loss. The model writes
    no kind; a kernel's call carries no part."""
    _parts_hold(nemotron_cell[2].as_text(),
                ("attn_qkv_proj", "attn_kernel_io", "attn_out_proj",
                 "head_logits", "head_loss"))
