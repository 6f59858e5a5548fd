"""``joyai-t8192`` at its real size, compiled for one described TPU v5e
(``tpu_compile_cases.py``): one compile a module, read by every test here;
and the three flash kernels alone at q/k of 192 against v of 128.
"""

import re

import pytest

from tpu_compile_cases import (  # noqa: F401
    KERNELS, _compiled_cell, _kernel_calls, _kernel_text, _parts_hold,
    _row_scatters, no_persistent_cache, topo)

SEQ, HEADS, QK, V = 8192, 32, 192, 128
LATENT_KERNELS = {"forward": "_fwd_latent_kernel",
                  "dq": "_bwd_dq_latent_kernel",
                  "dkv": "_bwd_dkv_latent_kernel"}


@pytest.fixture(scope="module")
def joyai_cell(topo):
    """``joyai-t8192``: published layers 0-4 and the multi-token-prediction
    module at the published widths, 8192 tokens, every block recomputed but
    for its attention's output, through ``dp.make_stateful_train_step``."""
    return _compiled_cell(topo, "joyai-t8192")


def test_joyai_cell_fits_one_v5e_at_full_size(joyai_cell):
    job, traffic, compiled = joyai_cell
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    assert 4e9 < total < 15.0e9, total
    # 680.44 M parameters and AdamW's moments at 12 bytes
    assert memory.argument_size_in_bytes == pytest.approx(8.165e9, rel=1e-3)
    recorded = traffic["memory_analysis"]
    assert recorded["workload"] == "joyai-t8192"
    assert recorded["argument_bytes"] == memory.argument_size_in_bytes
    assert memory.temp_size_in_bytes <= 1.02 * recorded["temp_bytes"]


def test_joyai_cell_holds_the_latent_kernels_and_none_of_the_causal_names(
        joyai_cell):
    """Six latent-attention operators (five layers and the module's block),
    each kernel once an operator: the blocks are recomputed, but an
    attention's output and row statistics are kept by name, so the forward
    kernel does not run twice (``"blocks"`` would hold 12). q and k at 32
    heads of 192, v at 32 of 128. Every call under ``attn_latent``, one of
    each kernel inside the module's ``mtp_block``. None of the causal names,
    which the benchmark prices by one head width. The five shares walk tiles
    of sixteen slots of 384 rows (1.5 x 8 x 8192 / 256), the thinnest any
    share has run, by the batched product (2048 x 768: ``ep.share_product``
    says ``"slots"``, no grouped-matmul kernel), and the rows go back to
    their tokens through ``_add_rows_kernel``; no ``ragged-dot``, no scatter
    of rows, no collective on one chip."""
    from horovod_tpu.parallel import ep
    from horovod_tpu.profiler.annotate import MLA_SCOPES, MTP_SCOPES
    job, _, compiled = joyai_cell
    text = compiled.as_text()
    calls, op_names = _kernel_calls(text)
    assert calls == {"_fwd_latent_kernel": 6, "_bwd_dq_latent_kernel": 6,
                     "_bwd_dkv_latent_kernel": 6, "_add_rows_kernel": 2 * 5}
    assert job.flash_call is None and job.flash_layers == 0
    assert job.facts["latent_call"] == [1, SEQ, HEADS, QK, V]
    way_back = op_names.pop("_add_rows_kernel")
    assert sum("moe_combine" in name and "transpose(" not in name
               for name in way_back) == 5
    assert sum("moe_dispatch" in name and "transpose(jvp(" in name
               for name in way_back) == 5
    assert not _row_scatters(text)
    for kernel, names in op_names.items():
        assert all("attn_latent" in name for name in names), kernel
        assert sum("JoyaiMtp_0/mtp_block" in name for name in names) == 1
        stack = {re.search(r"JoyaiBlock_(\d)", name).group(1)
                 for name in names if "JoyaiMtp_0" not in name}
        assert stack == set("01234"), (kernel, stack)
    from harness import hlo_text  # on the path since _kernel_calls
    hlo = hlo_text.HloIndex(text)
    forward = next(i for i in hlo.kernels()
                   if hlo.kernel_name(i) == "_fwd_latent_kernel")
    # q and k [32, T, 192], v [32, T, 128] as the kernel takes them
    assert len(re.findall(rf"bf16\[{HEADS},{SEQ},{QK}\]",
                          forward.attributes)) >= 2
    assert re.search(rf"bf16\[{HEADS},{SEQ},{V}\]", forward.attributes)
    assert "ragged-dot" not in text
    slot = ep.share_slot_rows(8 * SEQ, 256)
    assert slot == 384 and ep.share_tile_rows(8 * SEQ, 16, 256) == 16 * slot
    assert ep.share_product((2048, 768)) == "slots"
    for scope in (*MLA_SCOPES, *MTP_SCOPES, "attn_latent", "moe_router",
                  "moe_dispatch", "moe_experts", "moe_combine", "moe_shared"):
        assert scope in text, scope
    opcodes = re.findall(r"[\s)]([a-z\-]+)\(", text)
    assert "all-reduce" not in opcodes


def test_joyai_cell_holds_no_square_of_scores(joyai_cell):
    """No ``[heads, T, T]`` array anywhere: the scores exist a tile at a time
    in VMEM. (The one ``[T, T]`` is no square of scores: the key-value
    up-projection's output is 32 heads x (128 + 128) = 8192 wide, as long as
    the sequence.) The two logits are ``[T, 16160]`` float32."""
    _, _, compiled = joyai_cell
    text = compiled.as_text()
    squares = set(re.findall(rf"\w+\[((?:\d+,)*){SEQ},{SEQ}\]", text))
    assert squares <= {"", "1,"}, squares
    assert HEADS * (QK - 64 + V) == SEQ
    assert re.search(rf"f32\[(?:1,)?{SEQ},16160\]", text)


@pytest.mark.parametrize("kernel", list(KERNELS))
def test_latent_kernel_compiles_for_v5e_under_its_own_name(topo, kernel):
    """The three bodies at the cell's shapes, q/k of 192 (two 128-lane
    registers a row in VMEM, which the call's own limit counts) against v of
    128: one custom call each, named by the latent kernel's function and
    never by a name of ``flops.FLASH_PRODUCTS``, whose readers price a call
    by one head width."""
    text = _kernel_text(topo, kernel, SEQ, QK, True, heads=HEADS, v_dim=V)
    calls, _ = _kernel_calls(text)
    assert calls == {LATENT_KERNELS[kernel]: 1}


def test_joyai_cell_names_what_surrounds_its_kernels_and_its_head(joyai_cell):
    """The operator keeps its ``mla_*`` names; of the shared ones the step
    holds what ``flash_attention`` writes inside ``attn_latent`` and the
    main head's two (the module's stays ``mtp_head``). Each kernel's call
    under ``attn_latent`` and no part."""
    text = joyai_cell[2].as_text()
    _parts_hold(text, ("attn_kernel_io", "head_logits", "head_loss"),
                "attn_latent")
    for scope in ("mla_q_proj", "mla_kv_proj", "mla_rope", "mla_out_proj",
                  "mtp_head"):
        assert scope in text, scope
