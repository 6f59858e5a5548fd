"""``smallthinker-t16384`` at its real size, compiled for one described TPU v5e
(``tpu_compile_cases.py``): one compile a module, read by every test here.
"""

import re

import pytest

from tpu_compile_cases import (  # noqa: F401
    _compiled_cell, _kernel_calls, _parts_hold, _row_scatters,
    no_persistent_cache, topo)


@pytest.fixture(scope="module")
def smallthinker_cell(topo):
    """``smallthinker-t16384``: eight layers at the published widths,
    16 384 tokens, every block recomputed but for its attention's output,
    through ``dp.make_train_step``."""
    return _compiled_cell(topo, "smallthinker-t16384")


def test_smallthinker_cell_fits_one_v5e_at_full_size(smallthinker_cell):
    job, traffic, compiled = smallthinker_cell
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 4e9 < total < 15.0e9, total
    # 643.85 M parameters and AdamW's moments at 12 bytes
    assert memory.argument_size_in_bytes == pytest.approx(7.726e9, rel=1e-3)
    recorded = traffic["memory_analysis"]
    assert recorded["argument_bytes"] == memory.argument_size_in_bytes
    assert memory.temp_size_in_bytes <= 1.02 * recorded["temp_bytes"]


def test_smallthinker_cell_holds_causal_and_window_kernels_side_by_side(
        smallthinker_cell):
    """The two full layers under the causal kernels' names and the six
    window layers under the window kernels', each name once a layer: the
    blocks are recomputed, but the attention's output and row statistics are
    kept by name, so no forward kernel runs twice (``"blocks"`` would hold
    4 and 12). Every causal call under ``attn_full`` and every window call
    under ``attn_window``, the backward's under ``transpose(jvp(...))``; the
    routers under ``moe_router`` before their layer's attention; the share
    walks its pairs by XLA's batched product over eight slots of 2304 rows
    (1.5 x 6 x 16 384 / 64), no ``ragged-dot`` and no grouped-matmul
    kernel: experts of 2560 x 768 are under the width at which a walk takes
    the kernels over live blocks (``ep.share_product``; PR 47 measured them
    0.6% slower here); one chip exchanges nothing."""
    from horovod_tpu.parallel import ep
    job, _, compiled = smallthinker_cell
    text = compiled.as_text()
    calls, op_names = _kernel_calls(text)
    assert calls == {
        "_fwd_kernel": 2, "_bwd_dq_kernel": 2, "_bwd_dkv_kernel": 2,
        "_fwd_window_kernel": 6, "_bwd_dq_window_kernel": 6,
        "_bwd_dkv_window_kernel": 6, "_add_rows_kernel": 2 * 8,
        "_rotary_kernel": 2 * 6}
    assert job.flash_layers == 2 and job.facts["window_layers"] == 6
    way_back = op_names.pop("_add_rows_kernel")
    assert sum("moe_combine" in name and "transpose(" not in name
               for name in way_back) == 8
    assert sum("moe_dispatch" in name and "transpose(jvp(" in name
               for name in way_back) == 8
    assert not _row_scatters(text)
    # rotary (PR 51): q and k of a window layer through one call, in the
    # block's forward and in its recomputation; the backward is XLA's
    turned = op_names.pop("_rotary_kernel")
    assert all("attn_window/attn_rope" in name for name in turned)
    assert sum("rematted_computation" in name for name in turned) == 6
    for kernel, names in op_names.items():
        scope = "attn_window" if "window" in kernel else "attn_full"
        assert all(scope in name for name in names), kernel
        backward = [("transpose(jvp(" in name) for name in names]
        assert all(backward) if "bwd" in kernel else not any(backward)
    full = {name.split("SmallThinkerBlock_")[1][0]
            for name in op_names["_fwd_kernel"]}
    windowed = {name.split("SmallThinkerBlock_")[1][0]
                for name in op_names["_fwd_window_kernel"]}
    assert full == {"0", "4"} and windowed == set("123567")
    assert "ragged-dot" not in text
    slot = ep.share_slot_rows(6 * 16384, 64)
    assert slot == 2304 and ep.share_tile_rows(6 * 16384, 8, 64) == 8 * slot
    assert re.search(rf"= f32\[8,{slot},768\]\S* convolution\([^\n]*"
                     r"moe_experts\)*/esk,ekn->esn/dot_general", text)
    assert ep.share_product((2560, 768)) == "slots"
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "attn_full", "attn_window"):
        assert scope in text, scope
    opcodes = re.findall(r"[\s)]([a-z\-]+)\(", text)
    assert "all-reduce" not in opcodes


def test_smallthinker_cell_names_its_attention_parts_and_its_head(
        smallthinker_cell):
    """The projections, rotary (the window layers'), what surrounds the
    kernels' calls, the head and the loss; no per-head norm in this model.
    Each kernel's call under its kind and no part."""
    _parts_hold(smallthinker_cell[2].as_text(),
                ("attn_qkv_proj", "attn_rope", "attn_kernel_io",
                 "attn_out_proj", "head_logits", "head_loss"),
                "attn_full|attn_window")
