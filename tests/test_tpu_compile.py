"""Compiles for a described TPU v5e 2x2, with no chip attached.

The TPU compiler is installed beside JAX and compiles for a topology that is
described, not attached. It refuses what interpret mode cannot see: a slice
not aligned to the tiling, a kernel that wants more VMEM than it may use, a
program that does not fit the device. Nothing runs, so these cases say
nothing about results or times. A compile that passes is not a chip run.

Code that asks ``jax.default_backend()`` sees the CPU here, so the kernels
get ``interpret=False`` from the test.
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import (NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from horovod_tpu.ops import flash_attention as fa  # noqa: E402

HEADS = 12
BLOCK = 512  # flash_attention's default block for these lengths


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except (RuntimeError, NotImplementedError, ImportError) as e:
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; the next one would warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _forward(causal, scale, q, k, v, o, lse, do, off, window=None,
             block_mask=None):
    return fa._flash_fwd(q, k, v, off, off, causal, scale, BLOCK, BLOCK,
                         False, window, block_mask)[:2]


def _backward(pick, causal, scale, q, k, v, o, lse, do, off, window=None,
              block_mask=None):
    # the two backward kernels share one function; the one whose outputs
    # are dropped is dead code to the compiler
    grads = fa._flash_bwd(causal, scale, BLOCK, BLOCK, False, window,
                          block_mask, (q, k, v, o, lse, off, off), (do, None))
    return pick(grads)


KERNELS = {
    "forward": _forward,
    "dq": functools.partial(_backward, lambda g: g[0]),
    "dkv": functools.partial(_backward, lambda g: g[1:3]),
}


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


def _kernel_text(topo, kernel, seq, head_dim, causal, window=None,
                 block_mask=None, heads=HEADS, kv_heads=None):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = arg((1, seq, heads, head_dim), jnp.bfloat16)
    kv = arg((1, seq, kv_heads or heads, head_dim), jnp.bfloat16)
    lse = arg((heads, 1, seq), jnp.float32)
    off = arg((1,), jnp.float32)
    fn = functools.partial(KERNELS[kernel], causal, head_dim ** -0.5,
                           window=window, block_mask=block_mask)
    return jax.jit(fn).lower(x, kv, kv, x, lse, x, off).compile().as_text()


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

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "flash_attention", functools.partial(
            fa.flash_attention, interpret=False))
        yield text, model


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


def _benchmark_on_path():
    """``benchmark/`` importable: its ``harness`` names a step's kernels."""
    import sys
    benchmark = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark")
    if benchmark not in sys.path:
        sys.path.insert(0, benchmark)


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


# -- the Nemotron-H cell at its real size ----------------------------------------

def _compiled_cell(topo, workload):
    """(job, traffic, compiled): a cell as ``benchmark/compile_check.py``
    compiles it: the configuration's own job at its real size through
    ``dp.make_*train_step(donate=True)`` for one described chip."""
    _benchmark_on_path()
    from harness import spec as spec_lib
    from horovod_tpu.parallel import dp, mesh as mesh_lib
    spec = spec_lib.load()
    cell = spec_lib.workload(spec, workload)
    traffic = spec_lib.traffic(cell["traffic"])
    config, builder = spec_lib.config(spec, cell["config"])
    job = spec_lib.load_module(builder).build(config, traffic)
    mesh = mesh_lib.data_parallel_mesh(topo.devices[:1])

    def on_mesh(tree, partition):
        sharding = NamedSharding(mesh, partition)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)
    key = jax.eval_shape(lambda: jax.random.key(0))
    params, state = jax.eval_shape(job.init, key)
    batch = jax.eval_shape(functools.partial(job.make_batch, n=1), key)
    make = dp.make_stateful_train_step if job.stateful else \
        dp.make_train_step
    step = make(job.loss_fn, job.optimizer, mesh, donate=True)
    arguments = [on_mesh(params, P()),
                 on_mesh(jax.eval_shape(job.optimizer.init, params), P())]
    if job.stateful:
        arguments.append(on_mesh(state, P()))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fa, "flash_attention", functools.partial(
            fa.flash_attention, interpret=False))
        compiled = step.lower(*arguments, on_mesh(batch, P(dp.DP_AXES)),
                              on_mesh(key, P())).compile()
    return job, traffic, compiled


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
    assert 10.67e9 < total < 15.0e9, total
    # 667 M parameters and AdamW's moments at 12 bytes
    assert memory.argument_size_in_bytes == pytest.approx(8.0e9, rel=2e-3)
    recorded = traffic["memory_analysis"]
    assert recorded["argument_bytes"] == memory.argument_size_in_bytes
    # the record is PR 30's program, whose expert layers worked all 49 152
    # pairs: the step's temporaries may shrink, they may not outgrow it
    assert memory.temp_size_in_bytes <= 1.02 * recorded["temp_bytes"]


def _kernel_calls(text):
    """{kernel: count} of the step's ``tpu_custom_call``s as the benchmark
    names them (``harness.kernels.inventory``: a Pallas kernel by its
    function, the compiler's grouped matmuls by their one-word ``op_name``),
    and {kernel: the ``op_name`` of each of its calls}."""
    _benchmark_on_path()
    from harness import hlo_text, kernels
    hlo = hlo_text.HloIndex(text)
    op_names = {}
    for ins in hlo.kernels():
        op_names.setdefault(hlo.kernel_name(ins), []).append(ins.op_name)
    return kernels.inventory(hlo), op_names


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


def _row_scatters(text):
    """The shapes of the scatters of rows under an expert layer's scopes:
    a share's walk has none (its one scatter is of a scalar a pair)."""
    return [shape for shape in re.findall(
        r"= \w+(\[[\d,]*\])\S* scatter\([^\n]*moe_", text) if "," in shape]


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


# -- the SmallThinker cell at its real size ----------------------------------------

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
    kernel; one chip exchanges nothing."""
    from horovod_tpu.parallel import ep
    job, _, compiled = smallthinker_cell
    text = compiled.as_text()
    calls, op_names = _kernel_calls(text)
    assert calls == {
        "_fwd_kernel": 2, "_bwd_dq_kernel": 2, "_bwd_dkv_kernel": 2,
        "_fwd_window_kernel": 6, "_bwd_dq_window_kernel": 6,
        "_bwd_dkv_window_kernel": 6, "_add_rows_kernel": 2 * 8}
    assert job.flash_layers == 2 and job.facts["window_layers"] == 6
    way_back = op_names.pop("_add_rows_kernel")
    assert sum("moe_combine" in name and "transpose(" not in name
               for name in way_back) == 8
    assert sum("moe_dispatch" in name and "transpose(jvp(" in name
               for name in way_back) == 8
    assert not _row_scatters(text)
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
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "attn_full", "attn_window"):
        assert scope in text, scope
    opcodes = re.findall(r"[\s)]([a-z\-]+)\(", text)
    assert "all-reduce" not in opcodes


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
    over sixteen slots of 1536 rows; one chip exchanges nothing."""
    from horovod_tpu.parallel import ep
    job, _, compiled = sdar_cell
    text = compiled.as_text()
    layers = job.facts["layers"]
    calls, op_names = _kernel_calls(text)
    assert calls == {"_fwd_blockdiff_kernel": 2 * layers,
                     "_bwd_dq_blockdiff_kernel": 2 * layers,
                     "_bwd_dkv_blockdiff_kernel": 2 * layers,
                     "_add_rows_kernel": 2 * layers}
    assert job.flash_call is None
    op_names.pop("_add_rows_kernel")
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
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "attn_blockdiff", "diffusion_loss"):
        assert scope in text, scope
    opcodes = re.findall(r"[\s)]([a-z\-]+)\(", text)
    assert "all-reduce" not in opcodes
