"""What the described-chip compile tests share (``test_tpu_compile*.py``).

The TPU compiler is installed beside JAX and compiles for a topology that is
described, not attached. It refuses what interpret mode cannot see: a slice
not aligned to the tiling, a kernel that wants more VMEM than it may use, a
program that does not fit the device. Nothing runs, so these cases say
nothing about results or times. A compile that passes is not a chip run.

Every kernel module under ``horovod_tpu/ops/`` picks Mosaic or interpret mode
by the platform a program is lowered for (``ops/kernel_call.py``), so a
compile for the described chip holds the real kernels with nothing patched.

No test is collected from this file. A test file imports the two fixtures by
name (``topo``; ``no_persistent_cache``, ``autouse`` in the module that
imports it): the topology is described when a test of that file first asks
for it, never at import.
"""

import functools
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or it logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
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
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip; the next one would warn."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


# the three calls alone: ``_flash_fwd`` and ``_flash_bwd`` are handed
# ``interpret=False`` themselves (no ``flash_attention`` above them)
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


def _kernel_text(topo, kernel, seq, head_dim, causal, window=None,
                 block_mask=None, heads=HEADS, kv_heads=None, v_dim=None):
    """``v_dim``: the values' (and the output's) width where it is not the
    queries' and keys' ``head_dim`` (latent attention)."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    v_dim = v_dim or head_dim
    q = arg((1, seq, heads, head_dim), jnp.bfloat16)
    o = arg((1, seq, heads, v_dim), jnp.bfloat16)
    k = arg((1, seq, kv_heads or heads, head_dim), jnp.bfloat16)
    v = arg((1, seq, kv_heads or heads, v_dim), jnp.bfloat16)
    lse = arg((heads, 1, seq), jnp.float32)
    off = arg((1,), jnp.float32)
    fn = functools.partial(KERNELS[kernel], causal, head_dim ** -0.5,
                           window=window, block_mask=block_mask)
    return jax.jit(fn).lower(q, k, v, o, lse, o, off).compile().as_text()


def _benchmark_on_path():
    """``benchmark/`` importable: its ``harness`` names a step's kernels."""
    import sys
    benchmark = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark")
    if benchmark not in sys.path:
        sys.path.insert(0, benchmark)


def _unfused(text):
    """(the text's index, the instructions the program runs one by one:
    those of no fused computation)."""
    _benchmark_on_path()
    from harness import hlo_text
    hlo = hlo_text.HloIndex(text)
    fused = {body for ins in hlo.instructions.values()
             if ins.opcode == "fusion" for body in ins.calls}
    return hlo, [ins for ins in hlo.instructions.values()
                 if ins.computation not in fused]


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


def _parts_hold(text, parts, kind=None):
    """Holds a compiled step's ``op_name``s to the rules of the attention
    operator's parts and the head's (``profiler/annotate.ATTN_PART_SCOPES``,
    ``HEAD_SCOPES``): each of ``parts``, and no other, is named forward and
    in the transposed pass; no ``op_name`` holds two parts; a flash kernel's
    call is under no part, and under ``kind`` (a pattern over
    ``ATTN_SCOPES``) where the model writes one."""
    from horovod_tpu.profiler import annotate
    part = re.compile(r"\b(%s)\b" % "|".join(
        annotate.ATTN_PART_SCOPES + annotate.HEAD_SCOPES))
    op_names = set(re.findall(r'op_name="([^"]*)"', text))
    found = {False: set(), True: set()}
    for name in op_names:
        named = part.findall(name)
        assert len(named) <= 1, name
        if named:
            found["transpose(" in name].add(named[0])
    assert found[False] == found[True] == set(parts)
    flash = [name for kernel, names in _kernel_calls(text)[1].items()
             if re.match(r"_(fwd|bwd_dq|bwd_dkv)_", kernel) for name in names]
    assert flash
    for name in flash:
        assert not part.search(name), name
        assert kind is None or re.search(kind, name), name


def _row_scatters(text):
    """The shapes of the scatters of rows under an expert layer's scopes:
    a share's walk has none (its one scatter is of a scalar a pair)."""
    return [shape for shape in re.findall(
        r"= \w+(\[[\d,]*\])\S* scatter\([^\n]*moe_", text) if "," in shape]


def _products_by_blocks(op_names, module, layers, matrices,
                        recomputed=False):
    """Takes the grouped-matmul kernels out of ``op_names`` and holds them
    to a walk whose product is the kernels over live row blocks
    (``ep.share_product``): in each of ``layers`` expert layers, every call
    under ``module`` and ``moe_experts`` inside the walk's loop; the
    ``matrices`` products forward, and in the backward walk the products
    again, their transposes towards the rows and, ``_gmm_dw_kernel``, the
    transposes towards the matrices. ``recomputed``: the block's
    recomputation runs the forward walk once more, because something after
    the experts keeps their output alive in backward (a norm of the
    feed-forward's output, ``models/trinity.py``); where nothing does, the
    compiler drops the recomputed walk."""
    products, towards = op_names.pop("_gmm_kernel"), \
        op_names.pop("_gmm_dw_kernel")
    for name in products + towards:
        assert module in name and re.search(
            r"while/body/[\w(]*moe_experts", name), name
    backward = ["transpose(jvp(" in name for name in products]
    assert backward.count(False) == matrices * layers
    assert backward.count(True) == (3 if recomputed else 2) * matrices \
        * layers
    assert sum("rematted_computation" in name for name in products) == \
        (matrices * layers if recomputed else 0)
    assert len(towards) == matrices * layers
    assert all("transpose(jvp(" in name for name in towards)


def _scopes_hold(text, scopes, layers):
    """Holds a compiled step's ``op_name``s to a family of scopes of its own
    (``profiler/annotate.FAMILIES``): each of ``scopes`` is named in every
    one of the ``layers`` blocks forward, in the block's recomputation and
    in the transposed pass, and no ``op_name`` holds two of them or one of
    them beside an attention part."""
    from horovod_tpu.profiler import annotate
    mine = re.compile(r"\b(%s)\b" % "|".join(scopes))
    part = re.compile(r"\b(%s)\b" % "|".join(annotate.ATTN_PART_SCOPES))
    found = {}
    for name in set(re.findall(r'op_name="([^"]*)"', text)):
        named = mine.findall(name)
        assert len(set(named)) <= 1 and not (named and part.search(name)), \
            name
        if named:
            way = "recomputed" if "rematted_computation" in name else \
                "backward" if "transpose(" in name else "forward"
            block = re.search(r"Block_(\d+)", name).group(1)
            found.setdefault((named[0], way), set()).add(block)
    assert found == {(scope, way): {str(i) for i in range(layers)}
                     for scope in scopes
                     for way in ("forward", "recomputed", "backward")}


def _compiled_cell(topo, workload):
    """(job, traffic, compiled): a cell as ``benchmark/compile_check.py``
    compiles it: the configuration's own job at its real size through
    ``dp.make_*train_step(donate=True)`` for one described chip."""
    _benchmark_on_path()
    from harness import spec as spec_lib
    from horovod_tpu.parallel import dp, mesh as mesh_lib
    # A helper traced for an earlier cell of this process is cached with
    # that trace's frames, and a kernel's bytecode then carries the earlier
    # cell's kernel names beside its own (``HloIndex.kernel_name`` picks the
    # rarest; two bodies alike tie): which cells share a worker is the
    # scheduler's choice, so every cell starts from empty caches.
    jax.clear_caches()
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
    compiled = step.lower(*arguments, on_mesh(batch, P(dp.DP_AXES)),
                          on_mesh(key, P())).compile()
    return job, traffic, compiled
