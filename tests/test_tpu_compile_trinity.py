"""``trinity-t16384`` at its real size, compiled for one described TPU v5e
(``tpu_compile_cases.py``): one compile a module, read by every test here.
"""

import re

import pytest

from tpu_compile_cases import (  # noqa: F401
    _compiled_cell, _kernel_calls, _parts_hold, _products_by_blocks,
    _row_scatters, _scopes_hold, _unfused, no_persistent_cache, topo)

SEQ = 16384


@pytest.fixture(scope="module")
def trinity_cell(topo):
    """``trinity-t16384``: published layers 1-5 at the published widths,
    16 of 128 experts held, 16 384 tokens, every block recomputed but for
    its attention's output, through ``dp.make_stateful_train_step``."""
    return _compiled_cell(topo, "trinity-t16384")


def test_trinity_cell_fits_one_v5e_at_full_size(trinity_cell):
    job, traffic, compiled = trinity_cell
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 4e9 < total < 15.0e9, total
    # 705.47 M parameters and AdamW's moments at 12 bytes
    assert memory.argument_size_in_bytes == pytest.approx(8.466e9, rel=1e-3)
    recorded = traffic["memory_analysis"]
    assert recorded["argument_bytes"] == memory.argument_size_in_bytes
    assert memory.temp_size_in_bytes <= 1.02 * recorded["temp_bytes"]


def test_trinity_cell_holds_causal_and_window_kernels_side_by_side(
        trinity_cell):
    """The one full layer under the causal kernels' names and the four
    sliding layers under the window kernels', each name once a layer: the
    blocks are recomputed, but the attention's output and row statistics are
    kept by name, so no forward kernel runs twice, and the gate is
    recomputed around the kept output. q at 32 heads, k and v at their own
    4. The share walks its pairs by the grouped-matmul kernels over live row
    blocks (experts of 2048 x 1024: ``ep.share_product``), the forward walk
    once more in each block's recomputation: the feed-forward's output is
    normed before it joins the stream, and that norm's backward reads it.
    One chip exchanges nothing."""
    from horovod_tpu.parallel import ep
    job, _, compiled = trinity_cell
    text = compiled.as_text()
    calls, op_names = _kernel_calls(text)
    assert calls == {
        "_fwd_kernel": 1, "_bwd_dq_kernel": 1, "_bwd_dkv_kernel": 1,
        "_fwd_window_kernel": 4, "_bwd_dq_window_kernel": 4,
        "_bwd_dkv_window_kernel": 4, "_add_rows_kernel": 3 * 4,
        "_gmm_kernel": 12 * 4, "_gmm_dw_kernel": 3 * 4,
        "_rotary_kernel": 2 * 4}
    assert job.flash_call == (1, SEQ, 32, 128, True)
    assert job.flash_layers == 1 and job.facts["window_layers"] == 4
    way_back = op_names.pop("_add_rows_kernel")
    assert sum("moe_combine" in name for name in way_back) == 2 * 4
    assert sum("moe_dispatch" in name and "transpose(jvp(" in name
               for name in way_back) == 4
    assert not _row_scatters(text)
    _products_by_blocks(op_names, "TrinityMoE_0", layers=4, matrices=3,
                        recomputed=True)
    # rotary: q and k of a sliding layer through one call, in the block's
    # forward and in its recomputation; the backward is XLA's
    turned = op_names.pop("_rotary_kernel")
    assert all("attn_window/attn_rope" in name for name in turned)
    assert sum("rematted_computation" in name for name in turned) == 4
    for kernel, names in op_names.items():
        scope = "attn_window" if "window" in kernel else "attn_full"
        assert all(scope in name for name in names), kernel
        assert not any("rematted_computation" in name for name in names)
        backward = [("transpose(jvp(" in name) for name in names]
        assert all(backward) if "bwd" in kernel else not any(backward)
    full = {name.split("TrinityBlock_")[1][0]
            for name in op_names["_fwd_kernel"]}
    windowed = {name.split("TrinityBlock_")[1][0]
                for name in op_names["_fwd_window_kernel"]}
    assert full == {"2"} and windowed == set("0134")
    hlo, _ = _unfused(text)
    forward = next(i for i in hlo.kernels()
                   if hlo.kernel_name(i) == "_fwd_window_kernel")
    # q [32, T, 128], k and v [4, T, 128] as the kernel takes them
    assert re.search(rf"bf16\[32,{SEQ},128\]", forward.attributes)
    assert len(re.findall(rf"bf16\[4,{SEQ},128\]", forward.attributes)) >= 2
    assert "ragged-dot" not in text and "esk,ekn->esn" not in text
    slot = ep.share_slot_rows(8 * SEQ, 128)
    assert slot == 1536 and ep.share_tile_rows(8 * SEQ, 16, 128) == 16 * slot
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "moe_shared", "attn_full", "attn_window"):
        assert scope in text, scope
    opcodes = re.findall(r"[\s)]([a-z\-]+)\(", text)
    assert "all-reduce" not in opcodes


def test_trinity_cell_names_its_attention_parts_and_its_head(trinity_cell):
    """The projections, the per-head norms, rotary (the sliding layers'),
    what surrounds the kernels' calls, the head and the loss. Each kernel's
    call under its kind and no part."""
    _parts_hold(trinity_cell[2].as_text(),
                ("attn_qkv_proj", "attn_qk_norm", "attn_rope",
                 "attn_kernel_io", "attn_out_proj", "head_logits",
                 "head_loss"), "attn_full|attn_window")


def test_trinity_cell_names_the_gate_and_the_post_norms(trinity_cell):
    """The two families no other model has, in every one of the five blocks,
    forward, recomputed and backward: the gate's projection and its product
    are recomputed around the kept output; neither family shares an
    ``op_name`` with an attention part."""
    from horovod_tpu.profiler import annotate
    text = trinity_cell[2].as_text()
    _scopes_hold(text, annotate.OUTGATE_SCOPES, layers=5)
    _scopes_hold(text, annotate.POSTNORM_SCOPES, layers=5)
