"""``sdar-t8192-bd4`` at its real size, compiled for one described TPU v5e
(``tpu_compile_cases.py``): one compile a module, read by every test here.
"""

import re

import pytest

from tpu_compile_cases import (  # noqa: F401
    _compiled_cell, _kernel_calls, _parts_hold, _row_scatters,
    no_persistent_cache, topo)


@pytest.fixture(scope="module")
def sdar_cell(topo):
    """``sdar-t8192-bd4``: the configuration's layers at the published
    widths, 8192 data tokens as 16 384 rows a layer, every block recomputed
    but for its attention calls' outputs, through ``dp.make_train_step``."""
    return _compiled_cell(topo, "sdar-t8192-bd4")


def test_sdar_cell_fits_one_v5e_at_full_size(sdar_cell):
    job, traffic, compiled = sdar_cell
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 4e9 < total < 15.0e9, total
    # the parameters and AdamW's moments at 12 bytes
    layers = job.facts["layers"]
    parameters = layers * 94638336 + 2 * 18992 * 2048 + 2048
    assert memory.argument_size_in_bytes == pytest.approx(12 * parameters,
                                                          rel=1e-3)
    recorded = traffic["memory_analysis"]
    assert recorded["argument_bytes"] == memory.argument_size_in_bytes
    assert memory.temp_size_in_bytes <= 1.02 * recorded["temp_bytes"]


def test_sdar_cell_holds_the_block_mask_kernels_and_no_score_array(
        sdar_cell):
    """Two calls of each role a layer (the clean queries' and the noised
    queries', both over the clean keys), every one under ``attn_blockdiff``,
    the backward's under ``transpose(jvp(...))``; the attention calls'
    outputs are kept by name, so no forward kernel runs twice. No call under
    a name of ``flops.FLASH_PRODUCTS`` or of the window kernels (the job
    names no flash shapes: ``harness/kernels.unasked`` would fail the run).
    No array of the step has [2L, 2L] or [L, L] elements a head: the mask
    and the scores exist in VMEM tiles alone (a noised block on itself is
    ``[.., 2048, 4, 8, 4, 4]``). The share walks by XLA's batched product
    over sixteen slots of 1536 rows (experts of 2048 x 768 are under the
    width at which a walk takes the kernels over live blocks,
    ``ep.share_product``); one chip exchanges nothing."""
    from horovod_tpu.parallel import ep
    job, _, compiled = sdar_cell
    text = compiled.as_text()
    layers = job.facts["layers"]
    calls, op_names = _kernel_calls(text)
    assert calls == {"_fwd_blockdiff_kernel": 2 * layers,
                     "_bwd_dq_blockdiff_kernel": 2 * layers,
                     "_bwd_dkv_blockdiff_kernel": 2 * layers,
                     "_add_rows_kernel": 2 * layers,
                     "_rotary_kernel": 2 * layers}
    assert job.flash_call is None
    op_names.pop("_add_rows_kernel")
    # rotary (PR 51): q and k of both streams through one call a layer, in
    # the block's forward and in its recomputation; the backward is XLA's
    turned = op_names.pop("_rotary_kernel")
    assert all("attn_blockdiff/attn_rope" in name for name in turned)
    assert sum("rematted_computation" in name for name in turned) == layers
    for kernel, names in op_names.items():
        assert all("attn_blockdiff" in name for name in names), kernel
        backward = [("transpose(jvp(" in name) for name in names]
        assert all(backward) if "bwd" in kernel else not any(backward)
        assert {name.split("SdarBlock_")[1][0] for name in names} == \
            set(map(str, range(layers)))
    seq = job.facts["seq_len"]
    for shape in set(re.findall(r"= \w+\[([\d,]+)\]", text)):
        dims = [int(d) for d in shape.split(",")]
        assert sum(d in (seq, 2 * seq) for d in dims) < 2, shape
    assert "ragged-dot" not in text and not _row_scatters(text)
    slot = ep.share_slot_rows(8 * 16384, 128)
    assert slot == 1536 and ep.share_tile_rows(8 * 16384, 16, 128) == 16 * slot
    assert re.search(rf"= f32\[16,{slot},768\]\S* convolution\([^\n]*"
                     r"moe_experts\)*/esk,ekn->esn/dot_general", text)
    assert ep.share_product((2048, 768)) == "slots"
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "attn_blockdiff", "diffusion_loss"):
        assert scope in text, scope
    opcodes = re.findall(r"[\s)]([a-z\-]+)\(", text)
    assert "all-reduce" not in opcodes


def test_sdar_cell_names_its_attention_parts_and_its_head(sdar_cell):
    """Every shared part an attention operator can have, once each: the
    model's four, what ``flash_attention`` does around the six calls a
    layer, the noised block on itself and the merge; the head's logits (the
    loss stays ``diffusion_loss``). Each kernel's call under
    ``attn_blockdiff`` and no part."""
    _parts_hold(sdar_cell[2].as_text(),
                ("attn_qkv_proj", "attn_qk_norm", "attn_rope",
                 "attn_kernel_io", "attn_self_block", "attn_merge",
                 "attn_out_proj", "head_logits"), "attn_blockdiff")
