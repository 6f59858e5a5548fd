"""Whole steps and the expert layer compiled for a described TPU v5e 2x2
(``tpu_compile_cases.py``): the DP and ZeRO-1 steps of a two-layer decoder at
GPT-2 small widths on one and on four chips, and ``ep.moe_topk`` at OLMoE's
widths.
"""

import functools
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from tpu_compile_cases import (_kernel_calls, _parts_hold,  # noqa: F401
                               no_persistent_cache, topo)


@pytest.fixture(scope="module")
def gpt2_width_step_text(topo):
    """``text(chips)``: the compiled text of one whole ``dp.make_train_step``
    of a two-layer decoder at GPT-2 small widths, T = 1024 and 8 sequences
    per chip, on the first ``chips`` described devices. Compiled once each."""
    from horovod_tpu.models import GptSmall
    from horovod_tpu.parallel import dp, mesh as mesh_lib, zero

    model = GptSmall().clone(layers=2)
    opt = optax.adamw(1e-4)

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]).mean(), {}

    @functools.lru_cache(maxsize=None)
    def text(chips, sharded_update=False):
        mesh = mesh_lib.data_parallel_mesh(topo.devices[:chips])

        def on_mesh(tree, spec):
            sharding = NamedSharding(mesh, spec)
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sharding), tree)

        tokens = jax.ShapeDtypeStruct((8 * chips, model.max_len), jnp.int32)
        params = jax.eval_shape(model.init, jax.random.key(0),
                                tokens)["params"]
        step = dp.make_train_step(loss_fn, opt, mesh,
                                  sharded_update=sharded_update)
        if sharded_update:
            opt_state = on_mesh(jax.eval_shape(
                lambda p: zero.sharded_opt_init(opt, p, mesh), params),
                P(dp.DP_AXES))
        else:
            opt_state = on_mesh(jax.eval_shape(opt.init, params), P())
        return step.lower(
            on_mesh(params, P()), opt_state,
            on_mesh({"tokens": tokens, "labels": tokens}, P(dp.DP_AXES)),
            on_mesh(jax.eval_shape(lambda: jax.random.key(1)), P()),
        ).compile().as_text()

    return text, model


EXCHANGE = "phase_grad_exchange"   # dp.py's scope around the gradient exchange


def _under_exchange(text, *opcodes):
    """Lines of instructions with one of ``opcodes`` traced under the
    gradient exchange's scope."""
    kinds = "|".join(re.escape(o) for o in opcodes)
    return [line for line in text.splitlines() if EXCHANGE in line
            and re.search(rf"[\s)](?:{kinds})\(", line)]


def test_gpt2_width_dp_step_compiles_for_four_v5e(gpt2_width_step_text):
    """The kernels inside a real step, and the gradient all-reduce."""
    text, model = gpt2_width_step_text
    assert text(4).count("tpu_custom_call") == 3 * model.layers
    assert "all-reduce" in text(4)


def test_one_chip_step_has_nothing_to_exchange(gpt2_width_step_text):
    """Over a group of one the compiler removes the leaf-by-leaf all-reduce
    and nothing is left of the exchange: no packing, no instruction at all."""
    text, model = gpt2_width_step_text
    assert text(1).count("tpu_custom_call") == 3 * model.layers
    assert "all-reduce" not in text(1)
    assert EXCHANGE not in text(1)
    assert "phase_optimizer_update" in text(1)   # the scopes are there
    # nor anything of the asynchronous exchange (PR 29): no option got there
    assert "async_collective_fusion" not in text(1)
    assert "async_collective_name" not in text(1)


@pytest.mark.parametrize("chips", [1, 4])
def test_gpt2_width_step_names_its_attention_parts_and_its_head(
        gpt2_width_step_text, chips):
    """``FlashSelfAttention`` has neither norms nor positions of its own: the
    projections, what surrounds the kernels' calls, and the tied head's
    logits (the loss is the caller's, under no name). The model writes no
    kind; a kernel's call carries no part."""
    _parts_hold(gpt2_width_step_text[0](chips),
                ("attn_qkv_proj", "attn_kernel_io", "attn_out_proj",
                 "head_logits"))


def test_four_chip_exchange_is_all_reduces_and_no_packing(
        gpt2_width_step_text):
    """The leaves go to the wire in their own layouts: the combiner's
    all-reduces, and no relayout into a flat buffer or back."""
    text, _ = gpt2_width_step_text
    assert _under_exchange(text(4), "all-reduce")
    assert not _under_exchange(text(4), "reshape", "copy", "concatenate",
                               "dynamic-update-slice")


def _entry(text):
    """The lines of the entry computation, in the order they run."""
    return re.search(r"^ENTRY .*?\{\n(.*?)^\}", text, re.S | re.M).group(
        1).splitlines()


def _operands(line):
    """The float32 arrays an all-reduce's result holds (a tuple's: all),
    scalars apart: the combiner may take the loss's all-reduce along."""
    result = line.split(" all-reduce(")[0].split(" = ", 1)[1]
    assert not re.search(r"\b(?:bf16|f16)\[", result), result
    return len(re.findall(r"\bf32\[\d", result))


def test_four_chip_exchange_rides_inside_the_update(gpt2_width_step_text):
    """With ``dp.ASYNC_EXCHANGE_COMPILER_OPTIONS`` on the step's jit, the
    all-reduce of one operand (the tied embedding's gradient, which the
    combiner leaves alone) is gone from the entry computation: pieces of it
    sit in ``%async_collective_fusion`` computations that loop fusions of
    the optimizer update call. What is left blocking is variadic (the
    combiner's groups of the blocks' leaves, PERF.md §6, PR 29). Every
    gradient leaf is still reduced once, in float32."""
    text, model = gpt2_width_step_text
    entry = _entry(text(4))
    hosts = [i for i, line in enumerate(entry)
             if "calls=%async_collective_fusion" in line]
    assert len(hosts) > 8, len(hosts)
    assert all("kind=kLoop" in entry[i] for i in hosts)
    # the pieces ride among the update's own fusions, not after them
    assert "phase_optimizer_update" in " ".join(entry[hosts[0]:hosts[-1]])
    # one exchange, cut up: every piece is the same f32[vocab, hidden]
    pieces = re.findall(r"^\s*%[\w.\-]+ = (\S+) all-reduce\(.*"
                        r"async_collective_fusion_config", text(4), re.M)
    assert len(pieces) >= len(hosts)
    assert {p.split("{")[0] for p in pieces} == {
        f"f32[{model.vocab},{model.hidden}]"}
    blocking = [line for line in entry if " all-reduce(" in line
                and EXCHANGE in line]
    assert blocking and all(_operands(line) > 1 for line in blocking)
    leaves = len(jax.tree_util.tree_leaves(jax.eval_shape(
        model.init, jax.random.key(0),
        jnp.zeros((1, model.max_len), jnp.int32))["params"]))
    assert sum(map(_operands, blocking)) + 1 == leaves
    assert not _under_exchange(text(4), "reshape", "copy", "concatenate",
                               "dynamic-update-slice")


@pytest.mark.parametrize("chips,engaged", [(1, "no"), (4, "yes")])
def test_mesh_decides_the_compile_options(topo, chips, engaged):
    """Several TPU chips take ``dp.ASYNC_EXCHANGE_COMPILER_OPTIONS``; a mesh
    of one is compiled as before PR 29, with no option: the same program.
    The registry counts either."""
    from horovod_tpu.metrics.registry import get_registry
    from horovod_tpu.parallel import dp, mesh as mesh_lib
    built = get_registry().counter("hvd_async_exchange_steps_total",
                                   engaged=engaged)
    before = built.value
    options = dp.exchange_compiler_options(
        mesh_lib.data_parallel_mesh(topo.devices[:chips]))
    assert options == (dp.ASYNC_EXCHANGE_COMPILER_OPTIONS
                       if chips > 1 else None)
    assert built.value == before + 1


def test_zero1_step_compiles_for_four_v5e_with_the_options(
        gpt2_width_step_text):
    """The options govern every program of a four-chip mesh: ZeRO-1's
    reduce-scatter (an all-reduce and a slice on a 2x2) still builds."""
    text, model = gpt2_width_step_text
    zero1 = text(4, sharded_update=True)
    assert zero1.count("tpu_custom_call") == 3 * model.layers
    assert "all-reduce" in zero1 and "phase_param_gather" in zero1


# -- the expert layer at OLMoE's widths ----------------------------------------

@pytest.fixture(scope="module")
def olmoe_layer_text(topo):
    """Forward and backward of ``ep.moe_topk`` at the published widths
    (8192 tokens of 2048, top-8 of 64 experts of 1024, bf16), compiled for
    one described chip."""
    from horovod_tpu.parallel import ep
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, router, gate, up, down):
        out, stats = ep.moe_topk(x, router, gate, up, down, 8)
        return out.astype(jnp.float32).sum() + stats.router_z_loss

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        arg((8192, 2048)), arg((2048, 64), jnp.float32),
        arg((64, 2048, 1024)), arg((64, 2048, 1024)),
        arg((64, 1024, 2048))).compile().as_text()


def test_expert_layer_compiles_to_grouped_matmul_kernels(olmoe_layer_text):
    """The nine products of a full load are the repo's own kernels
    (``ops/grouped_matmul.py``): ``_gmm_kernel`` six times (the three
    projections forward and towards the rows) and ``_gmm_dw_kernel`` three
    (towards the matrices), each under ``moe_experts`` in its ``op_name``,
    the backward's under ``transpose(jvp(...))``: what
    ``benchmark/harness/moe.py`` reads by the scope. No call of the
    compiler's own: a ``ragged_dot`` was Mosaic calls named ``ragged-dot-*``
    (nine and two of metadata before PR 36), paced by the (group, tile)
    pairs they visited. The rows are the ``k T`` pairs and a block of
    padding an expert: 576 blocks of 128."""
    from horovod_tpu.parallel import ep
    calls, op_names = _kernel_calls(olmoe_layer_text)
    assert calls == {"_gmm_kernel": 6, "_gmm_dw_kernel": 3}
    assert "ragged-dot" not in olmoe_layer_text
    for kernel, names in op_names.items():
        assert all("moe_experts" in name for name in names), kernel
    assert all("transpose(jvp(" in name
               for name in op_names["_gmm_dw_kernel"])
    assert sum("transpose(jvp(" in name
               for name in op_names["_gmm_kernel"]) == 3
    rows = ep.grouped_blocks_built(8 * 8192, 64) * ep.SHARE_BLOCK_ROWS
    assert rows == 73728
    assert re.search(rf"bf16\[{rows},2048\]", olmoe_layer_text)
    assert re.search(rf"bf16\[{rows},1024\]", olmoe_layer_text)


def test_expert_layer_moves_rows_by_gathers_alone(olmoe_layer_text):
    """Dispatch and combine, forward and backward: no scatter."""
    opcodes = re.findall(r"[\s)]([a-z\-]+)\(", olmoe_layer_text)
    assert "scatter" not in opcodes
    assert "gather" in opcodes and "sort" in opcodes
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine"):
        assert scope in olmoe_layer_text
