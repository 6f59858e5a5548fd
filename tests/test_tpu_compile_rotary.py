"""The rotary operator (``ops/rotary.py``) inside whole training steps
compiled for one described TPU v5e (``tpu_compile_cases.py``): a tiny SDAR
and a tiny SmallThinker decoder with heads of 128, each through
``dp.make_train_step`` with its blocks recomputed.

What the operator brings to a step, forward, recomputed and backward, names
the part ``attn_rope``; the forward is the kernel (no split, no join); the
backward is the same rotation in ``jax.numpy`` and joins the two swapped
halves in the activation's own dtype: no float32 half is ever cut, padded
or joined (what ``jax.grad`` of the plain ``jax.numpy`` rotation made of its
split and its concatenate), and the forward cuts and joins nothing.
"""

import functools
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_compile_cases import (_benchmark_on_path,  # noqa: F401
                               _kernel_calls, no_persistent_cache, topo)

BATCH, SEQ = 2, 256
PART = "attn_rope"


def _sdar():
    from horovod_tpu.models import SdarTiny, sdar_loss, sdar_noise
    model = SdarTiny(layers=1, heads=4, kv_heads=2, head_dim=128,
                     remat="blocks_keep_attention")
    tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)
    batch = {"x0": tokens, **jax.eval_shape(functools.partial(
        sdar_noise, block=model.block_length, mask_id=model.vocab - 1),
        jax.random.key(1), tokens)}

    def loss_fn(params, batch, rng):
        logits, stats = model.apply({"params": params}, batch["xt"],
                                    batch["x0"])
        return sdar_loss(logits, batch, stats)
    return model, (tokens, tokens), batch, loss_fn


def _smallthinker():
    from horovod_tpu.models import SmallThinkerTiny, smallthinker_loss
    # a window layer with rotary between two full layers without positions
    model = SmallThinkerTiny(
        heads=4, kv_heads=2, head_dim=128, rope_layout=(0, 1, 0),
        sliding_window_layout=(0, 1, 0), remat="blocks_keep_attention")
    tokens = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32)

    def loss_fn(params, batch, rng):
        logits, stats = model.apply({"params": params}, batch["tokens"])
        return smallthinker_loss(logits, batch["labels"], stats)
    return model, (tokens,), {"tokens": tokens, "labels": tokens}, loss_fn


MODELS = {"sdar": _sdar, "smallthinker": _smallthinker}


@pytest.fixture(scope="module")
def step_text(topo):
    """``text(model)``: the compiled text of one whole ``dp.make_train_step``
    of the tiny model on one described chip; compiled once each."""
    from horovod_tpu.parallel import dp, mesh as mesh_lib
    mesh = mesh_lib.data_parallel_mesh(topo.devices[:1])

    def on_mesh(tree, spec):
        sharding = NamedSharding(mesh, spec)
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    @functools.lru_cache(maxsize=None)
    def text(name):
        model, inputs, batch, loss_fn = MODELS[name]()
        opt = optax.adamw(1e-4)
        params = jax.eval_shape(model.init, jax.random.key(0),
                                *inputs)["params"]
        step = dp.make_train_step(loss_fn, opt, mesh)
        return step.lower(
            on_mesh(params, P()), on_mesh(jax.eval_shape(opt.init, params),
                                          P()),
            on_mesh(batch, P(dp.DP_AXES)),
            on_mesh(jax.eval_shape(lambda: jax.random.key(1)), P()),
        ).compile().as_text()
    return text


def _instructions(text):
    """Every instruction of the text, those inside fusions too, that names
    the part: (opcode, result shape, op_name)."""
    _benchmark_on_path()
    from harness import hlo_text
    return [(ins.opcode, ins.shape, ins.op_name)
            for ins in hlo_text.HloIndex(text).instructions.values()
            if re.search(rf"\b{PART}\b", ins.op_name)]


@pytest.mark.parametrize("model", sorted(MODELS))
def test_every_operation_of_the_rotary_names_the_part(step_text, model):
    """Forward, recomputed forward and the operator's own backward: the
    kernel's two calls a rotary layer under the part (the block's forward
    and its recomputation), none in the backward; the backward's operations
    under ``transpose(jvp(...))`` and the part."""
    text = step_text(model)
    calls, op_names = _kernel_calls(text)
    assert calls["_rotary_kernel"] == 2
    first, again = sorted(op_names["_rotary_kernel"], key=len)
    for name in (first, again):
        assert re.search(rf"\b{PART}\b", name), name
    assert "rematted_computation" in again and \
        "rematted_computation" not in first
    found = _instructions(text)
    ways = {"forward": [], "recomputed": [], "backward": []}
    for opcode, shape, name in found:
        way = "recomputed" if "rematted_computation" in name else \
            "backward" if "transpose(" in name else "forward"
        ways[way].append((opcode, shape))
    assert all(ways.values()), {way: len(ops) for way, ops in ways.items()}
    # what the operator's own functions trace (the kernel's jitted call,
    # ``jnp.roll``'s) is nowhere outside the part
    ours = re.findall(r'op_name="([^"]*(?:_rotary_call|_roll_static)[^"]*)"',
                      text)
    assert ours and all(re.search(rf"\b{PART}\b", name) for name in ours)


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_part_cuts_and_joins_no_float32_halves(step_text, model):
    """The forward and its recomputation are the kernel: nothing under the
    part there cuts, joins or pads. The backward joins the two swapped
    halves of a cotangent once, in bf16 (a ``concatenate``, which the
    compiler may write as pads under a maximum): never a float32 half."""
    cut_or_joined = [(opcode, shape, name)
                     for opcode, shape, name in _instructions(step_text(model))
                     if opcode in ("pad", "concatenate", "slice")]
    assert cut_or_joined
    for opcode, shape, name in cut_or_joined:
        assert shape.startswith("bf16["), (opcode, shape)
        assert "transpose(" in name and "rematted_computation" not in name, \
            (opcode, name)
