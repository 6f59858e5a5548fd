"""The kernels' own compiles for a described TPU v5e 2x2, with no chip attached
(``tpu_compile_cases.py`` says what such a compile shows): each kernel alone
at the cells' shapes, a second or two a case. The steps are
``test_tpu_compile_steps.py``'s, each full-size cell a file of its own
(``test_tpu_compile_sdar.py``, ``_smallthinker.py``, ``_nemotron.py``).
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops import flash_attention as fa

from tpu_compile_cases import (  # noqa: F401
    KERNELS, _kernel_calls, _kernel_text, _unfused, no_persistent_cache,
    topo)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("seq,head_dim", [(1024, 64), (8192, 64),
                                          (8192, 128), (2048, 64),
                                          (4096, 128), (16384, 128)])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_flash_kernel_compiles_for_v5e(topo, kernel, seq, head_dim, causal):
    """(16384, 128) holds 4 MiB each of k and v (dk/dv: q and do) twice:
    past the compiler's default scoped VMEM, so the call asks for its own
    (``fa._vmem_params``); the shorter ones ask for nothing, as before."""
    text = _kernel_text(topo, kernel, seq, head_dim, causal)
    assert text.count("tpu_custom_call") == 1, text.count("tpu_custom_call")


@pytest.mark.parametrize("head_dim", [64, 128])
def test_flash_attention_lowered_for_v5e_holds_the_kernels_unpatched(
        topo, head_dim):
    """``flash_attention`` as a model calls it, forward and gradient, with
    nothing patched and no ``interpret`` passed: lowered for the described
    chip it holds the three Mosaic kernels, not interpret mode's loops (the
    platform lowered for decides, ``ops/kernel_call.py``; this process's
    default backend is the CPU)."""
    assert jax.default_backend() == "cpu"
    x = jax.ShapeDtypeStruct((1, 2048, 4, head_dim), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32))
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls, _ = _kernel_calls(text)
    assert calls == {"_fwd_kernel": 1, "_bwd_dq_kernel": 1,
                     "_bwd_dkv_kernel": 1}
    assert " while(" not in text


WINDOW_KERNELS = {"forward": "_fwd_window_kernel",
                  "dq": "_bwd_dq_window_kernel",
                  "dkv": "_bwd_dkv_window_kernel"}


@pytest.mark.parametrize("seq,head_dim,window", [
    (16384, 128, 4096), (8192, 64, 1000), (2048, 128, 1)])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_window_kernel_compiles_for_v5e_under_its_own_name(
        topo, kernel, seq, head_dim, window):
    """The window's loop bounds and second mask compile for the chip (the
    new cell's shape first), one custom call each, and the compiled text
    names the call by the window kernel's function: never by a name of
    ``flops.FLASH_PRODUCTS``, whose readers cost a call at the causal pair
    count."""
    text = _kernel_text(topo, kernel, seq, head_dim, True, window)
    calls, _ = _kernel_calls(text)
    assert calls == {WINDOW_KERNELS[kernel]: 1}


GROUPED = {  # heads, key heads, head width, positions, the mask
    "smallthinker_window": (28, 4, 128, 16384, dict(window=4096)),
    "smallthinker_full": (28, 4, 128, 16384, dict()),
    "sdar": (32, 4, 128, 8192, dict(block_mask=(4, "lt"))),
    "nemotron": (32, 2, 128, 8192, dict()),
    "heads_of_64": (12, 4, 64, 2048, dict()),
}


@pytest.mark.parametrize("cell", list(GROUPED))
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_grouped_heads_kernel_compiles_for_v5e(topo, kernel, cell):
    """k and v at their own heads, found by ``bh // group`` in the index
    maps, compile for the chip at the grouped cells' shapes (a group of 7 is
    no power of two): one custom call each, and no array of a key head
    repeated to the query heads exists beside it (the dk/dv kernel's own
    results, one a query head, are the only ones of that shape)."""
    heads, kv_heads, head_dim, seq, mask = GROUPED[cell]
    text = _kernel_text(topo, kernel, seq, head_dim, True, heads=heads,
                        kv_heads=kv_heads, **mask)
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"= bf16\[[0-9,]+\]\S* broadcast\(",
                         text.split("ENTRY")[1])


BLOCKDIFF_KERNELS = {"forward": "_fwd_blockdiff_kernel",
                     "dq": "_bwd_dq_blockdiff_kernel",
                     "dkv": "_bwd_dkv_blockdiff_kernel"}


@pytest.mark.parametrize("seq,head_dim,block_mask", [
    (8192, 128, (4, "le")), (8192, 128, (4, "lt")), (2048, 64, (16, "lt")),
    (1024, 128, (3, "le"))],
    ids=["sdar_clean", "sdar_noised", "g16_heads_of_64", "g3"])
@pytest.mark.parametrize("kernel", list(KERNELS))
def test_blockdiff_kernel_compiles_for_v5e_under_its_own_name(
        topo, kernel, seq, head_dim, block_mask):
    """The block mask's row-against-column compare and its loop bounds
    compile for the chip (the new cell's two calls first; a block length
    that is no power of two: the edge is ``floor((q + 0.5) / G)`` in
    float32), one custom call each, named by the block-diffusion kernel's
    function: never by a name of ``flops.FLASH_PRODUCTS`` nor of the window
    kernels, whose readers cost a call at their own pair counts."""
    text = _kernel_text(topo, kernel, seq, head_dim, True,
                        block_mask=block_mask)
    calls, _ = _kernel_calls(text)
    assert calls == {BLOCKDIFF_KERNELS[kernel]: 1}


@pytest.mark.parametrize("rows_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16-rows", "float32-rows"])
@pytest.mark.parametrize("tokens,d,tile", [
    (16384, 2560, 18432), (8192, 2688, 5120)],
    ids=["smallthinker-t16384", "nemotron3n-t8192"])
def test_rows_to_tokens_kernel_compiles_for_v5e(topo, tokens, d, tile,
                                                rows_dtype):
    """A live tile's way back at both share cells' shapes (eight slots; 20
    and 21 lanes of 128): one custom call, the float32 result aliased to
    the operand it adds to (no copy of ``[T, d]`` beside the kernel), the
    tokens and weights of a tile and the job list in scalar memory, and the
    job list made without a sort or a scatter."""
    from horovod_tpu.ops import rows_to_tokens as rt
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(
        lambda out, rows, weight, at, fresh: rt.add_rows_at_tokens(
            out, rows, weight, at, 8, fresh), donate_argnums=0).lower(
        arg((tokens, d), jnp.float32), arg((tile, d), rows_dtype),
        arg((tile,), jnp.float32), arg((tile,), jnp.int32),
        arg((), jnp.bool_)).compile()
    text = compiled.as_text()
    calls, _ = _kernel_calls(text)
    assert calls == {"_add_rows_kernel": 1}
    opcodes = set(re.findall(r"[\s)]([a-z\-]+)\(", text))
    assert not opcodes & {"sort", "scatter", "while"}, opcodes
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == memory.output_size_in_bytes \
        == tokens * d * 4
    assert memory.temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("batch,seq,heads,kv_heads", [
    (2, 8192, 32, 4), (1, 16384, 28, 4), (2, 4096, 16, 16)],
    ids=["sdar-t8192-bd4", "smallthinker-t16384", "olmoe-t4096"])
def test_rotary_compiles_for_v5e(topo, batch, seq, heads, kv_heads):
    """q and k of a call at the three cells with heads of 128 (``sdar``'s
    two streams are its batch): the forward ONE kernel call for both arrays
    and no float32 array of q's size beside it; the backward no kernel: the
    same rotation in ``jax.numpy``, which never holds a float32 half of q
    (the halves it swaps are bf16)."""
    from horovod_tpu.ops.rotary import rotary
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(count):
        return jax.ShapeDtypeStruct((batch, seq, count, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def float32_of(text, least):
        """The float32 arrays of at least ``least`` elements that the
        program writes out."""
        return [shape for ins in _unfused(text)[1]
                for shape in re.findall(r"f32\[([\d,]+)\]", ins.shape)
                if math.prod(int(d) for d in shape.split(",")) >= least]
    size = batch * seq * heads * 128
    forward = jax.jit(lambda q, k: rotary((q, k), 1e6)).lower(
        arg(heads), arg(kv_heads)).compile().as_text()
    assert _kernel_calls(forward)[0] == {"_rotary_kernel": 1}
    assert not float32_of(forward, size)
    backward = jax.jit(lambda q, k, dq, dk: jax.vjp(
        lambda q, k: rotary((q, k), 1e6), q, k)[1]((dq, dk))).lower(
        arg(heads), arg(kv_heads), arg(heads), arg(kv_heads)) \
        .compile().as_text()
    assert not _kernel_calls(backward)[0]
    assert not float32_of(backward, size // 2)


ENDS = {  # channels of the array, of the run, where the run starts
    "conv_6144": ("conv", 6144, 6144, 0, jnp.bfloat16),
    "conv_x_in_place": ("conv", 10304, 4096, 4096, jnp.bfloat16),
    "conv_C_in_place": ("conv", 10304, 1024, 9216, jnp.bfloat16),
    "conv_x_float32": ("conv", 10304, 4096, 4096, jnp.float32),
    "norm_4096": ("norm", 4096, 4096, 0, jnp.bfloat16),
    "norm_z_in_place": ("norm", 10304, 4096, 0, jnp.bfloat16),
    "norm_z_float32": ("norm", 10304, 4096, 0, jnp.float32),
}


@pytest.mark.parametrize("case", list(ENDS))
def test_mixer_end_kernels_compile_for_v5e(topo, case):
    """The conv's and the gated norm's forward and backward alone at the
    cell's shapes, 8192 positions: on the whole of an array and on a run
    of the in-projection's output where it lies, in bf16 and in float32 (a
    tile is as many bytes either way, or the backward's five float32 tiles,
    each held twice, pass the kernel's VMEM). One custom call a pass (the
    arguments of a program of their own come row-major, so what surrounds
    the kernels is the cell test's to say)."""
    from horovod_tpu.ops import ssm_ends as se
    stage, wide, channels, at, dtype = ENDS[case]
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    x = arg((1, 8192, wide), dtype)
    y = arg((1, 8192, channels), dtype)
    vector = arg((channels,), jnp.float32)
    if stage == "conv":
        def loss(x, w, b, cotangent):
            return jnp.sum(se.causal_conv_silu(x, w, b, at=at) * cotangent)
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2))).lower(
            x, arg((4, channels), jnp.float32), vector, y).compile()
        expected = {"_conv_fwd_kernel": 1, "_conv_bwd_kernel": 1}
    else:
        def loss(y, z, scale, cotangent):
            return jnp.sum(se.gated_group_norm(y, z, scale, 8, at=at)
                           * cotangent)
        compiled = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2))).lower(y, x, vector, y).compile()
        expected = {"_norm_fwd_kernel": 1, "_norm_bwd_kernel": 1}
    calls, _ = _kernel_calls(compiled.as_text())
    assert calls == expected
