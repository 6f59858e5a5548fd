"""``lfm2-t16384`` at its real size, compiled for one described TPU v5e
(``tpu_compile_cases.py``): one compile a module, read by every test here.
"""

import re

import pytest

from tpu_compile_cases import (  # noqa: F401
    _compiled_cell, _kernel_calls, _parts_hold, _products_by_blocks,
    _row_scatters, _unfused, no_persistent_cache, topo)

SEQ, HIDDEN = 16384, 2048


@pytest.fixture(scope="module")
def lfm2_cell(topo):
    """``lfm2-t16384``: published layers 1-5 at the published widths,
    16 384 tokens, every block recomputed but for its attention's output,
    through ``dp.make_stateful_train_step``."""
    return _compiled_cell(topo, "lfm2-t16384")


def test_lfm2_cell_fits_one_v5e_at_full_size(lfm2_cell):
    job, traffic, compiled = lfm2_cell
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 4e9 < total < 15.0e9, total
    # 507.82 M parameters and AdamW's moments at 12 bytes
    assert memory.argument_size_in_bytes == pytest.approx(6.094e9, rel=1e-3)
    recorded = traffic["memory_analysis"]
    assert recorded["workload"] == "lfm2-t16384"
    assert recorded["argument_bytes"] == memory.argument_size_in_bytes
    assert memory.temp_size_in_bytes <= 1.02 * recorded["temp_bytes"]


def test_lfm2_cell_holds_the_causal_kernels_on_grouped_heads_of_64(
        lfm2_cell):
    """The one attention layer under the causal kernels' names, each once:
    the blocks are recomputed, but the attention's output and row statistics
    are kept by name, so the forward kernel does not run twice (``"blocks"``
    would hold 2). q at 32 heads of 64, k and v at their own 8: a group of
    4, nothing repeated in HBM. Every call under ``attn_full`` of
    ``Lfm2Block_1``; the share walks tiles of eight slots of 3072 rows (1.5 x
    4 x 16 384 / 32), the largest any share has run, and its experts (2048 x
    1792: whole 128s, the kernels' side of ``ep.share_product``) multiply
    the tile's live row blocks by ``ops/grouped_matmul.py``'s kernels, all
    under ``moe_experts``: a layer's three products forward, and in the
    backward walk the three again, their three transposes towards the rows
    (``_gmm_kernel`` x 9) and the three towards the matrices
    (``_gmm_dw_kernel`` x 3); no ``dot_general`` over ``[8, 3072, .]`` is
    left. The rows go back to their tokens through ``_add_rows_kernel``; no
    ``ragged-dot``, no scatter of rows, no collective on one chip."""
    from horovod_tpu.parallel import ep
    job, _, compiled = lfm2_cell
    text = compiled.as_text()
    calls, op_names = _kernel_calls(text)
    assert calls == {"_fwd_kernel": 1, "_bwd_dq_kernel": 1,
                     "_bwd_dkv_kernel": 1, "_add_rows_kernel": 2 * 4,
                     "_gmm_kernel": 9 * 4, "_gmm_dw_kernel": 3 * 4,
                     "_mix_fwd_kernel": 2 * 4, "_mix_bwd_kernel": 4}
    assert job.flash_call == (1, SEQ, 32, 64, True) and job.flash_layers == 1
    way_back = op_names.pop("_add_rows_kernel")
    assert sum("moe_combine" in name and "transpose(" not in name
               for name in way_back) == 4
    assert sum("moe_dispatch" in name and "transpose(jvp(" in name
               for name in way_back) == 4
    assert not _row_scatters(text)
    _products_by_blocks(op_names, "Lfm2SparseMoe_0", layers=4, matrices=3)
    # the middle of each of the four conv layers: its forward kernel twice
    # (the pass and the block's recomputation), its backward once
    middles = op_names.pop("_mix_fwd_kernel"), op_names.pop("_mix_bwd_kernel")
    for names in middles:
        assert all("Lfm2ShortConv_0/shortconv_mix" in name for name in names)
        assert {name.split("Lfm2Block_")[1][0] for name in names} == \
            set("0234")
    assert all("transpose(" in name for name in middles[1])
    for kernel, names in op_names.items():
        assert all("attn_full" in name and "Lfm2Block_1/" in name
                   for name in names), kernel
    hlo, _ = _unfused(text)
    forward = next(i for i in hlo.kernels()
                   if hlo.kernel_name(i) == "_fwd_kernel")
    # q [32, T, 64], k and v [8, T, 64] as the kernel takes them
    assert re.search(rf"bf16\[32,{SEQ},64\]", forward.attributes)
    assert len(re.findall(rf"bf16\[8,{SEQ},64\]", forward.attributes)) >= 2
    assert "ragged-dot" not in text
    slot = ep.share_slot_rows(4 * SEQ, 32)
    assert slot == 3072 and ep.share_tile_rows(4 * SEQ, 8, 32) == 8 * slot
    assert "esk,ekn->esn" not in text
    assert ep.share_product((HIDDEN, 1792)) == "blocks"
    from horovod_tpu.profiler.annotate import SHORTCONV_SCOPES
    for scope in (*SHORTCONV_SCOPES, "moe_router", "moe_dispatch",
                  "moe_experts", "moe_combine", "attn_full"):
        assert scope in text, scope
    opcodes = re.findall(r"[\s)]([a-z\-]+)\(", text)
    assert "all-reduce" not in opcodes


def test_lfm2_cell_leaves_positions_where_the_projections_leave_them(
        lfm2_cell):
    """No ``[heads, T, T]`` array anywhere (the one ``[T, T]`` is the logits:
    the vocabulary's slice is as long as the sequence, 1 x 16 384 x 16 384
    in float32); and nothing the step runs one by one
    copies, transposes or pads a ``[T, 3d]`` activation: the only unfused
    instructions of that shape are the in-projection's products, the fusions
    around them and the middle's kernels, positions major as the projection
    wrote them."""
    _, _, compiled = lfm2_cell
    text = compiled.as_text()
    squares = set(re.findall(rf"\w+\[((?:\d+,)*){SEQ},{SEQ}\]", text))
    assert squares <= {"", "1,"}, squares
    _, unfused = _unfused(text)
    wide = [ins for ins in unfused
            if re.search(rf"\[(?:1,)?(?:{SEQ},{3 * HIDDEN}|"
                         rf"{3 * HIDDEN},{SEQ})\]", ins.shape)]
    assert wide  # the in-projection's output is there
    moved = [(ins.opcode, ins.shape) for ins in wide
             if ins.opcode in ("copy", "transpose", "pad", "copy-start",
                               "copy-done")]
    assert not moved, moved
    # positions stay major ({2,1,0} of [1, T, 3d], {1,0} of [T, 3d])
    assert not [ins.shape for ins in wide
                if re.search(rf"\[(?:1,)?{3 * HIDDEN},{SEQ}\]", ins.shape)]
    assert all(re.search(r"\{(?:2,)?1,0[:}]", ins.shape) for ins in wide
               if ins.opcode in ("fusion", "convolution")), \
        [ins.shape for ins in wide]


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_the_middles_kernels_compile_alone_at_the_cells_shapes(topo,
                                                               direction):
    """``ops/short_conv.py``'s two calls at [1, 16 384, 3 x 2048] for the
    described chip: Mosaic takes the rolls down a tile's rows, the 16-row
    blocks beside a tile and the room the calls ask VMEM for."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops import short_conv
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    bcu = arg((1, SEQ, 3 * HIDDEN), jnp.bfloat16)
    w = arg((3, HIDDEN), jnp.float32)
    if direction == "forward":
        text = short_conv._mix_forward_call.lower(
            bcu, w, dtype=jnp.dtype(jnp.bfloat16),
            interpret=False).compile().as_text()
        kernel = "_mix_fwd_kernel"
    else:
        text = short_conv._mix_backward_call.lower(
            bcu, arg((1, SEQ, HIDDEN), jnp.bfloat16), w,
            interpret=False).compile().as_text()
        kernel = "_mix_bwd_kernel"
    calls, _ = _kernel_calls(text)
    assert calls == {kernel: 1}
    # nothing moves an activation around the call (the taps' gradient, 64
    # tiles x 3 x 2048 float32, may change its layout on the way out)
    _, unfused = _unfused(text)
    assert not [ins.shape for ins in unfused
                if ins.opcode in ("copy", "transpose", "pad")
                and str(SEQ) in ins.shape]


def test_lfm2_cell_names_its_attention_parts_and_its_head(lfm2_cell):
    """The one attention layer: projections, per-head norms, rotary, what
    surrounds the three calls; the tied head and the loss. Each kernel's
    call under ``attn_full`` and no part."""
    _parts_hold(lfm2_cell[2].as_text(),
                ("attn_qkv_proj", "attn_qk_norm", "attn_rope",
                 "attn_kernel_io", "attn_out_proj", "head_logits",
                 "head_loss"), "attn_full")
