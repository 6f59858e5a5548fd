"""The attention operator's parts and the head under their names
(``profiler/annotate.ATTN_PART_SCOPES``, ``HEAD_SCOPES``), at the tiny widths
the models' own test files use.

Every model's attention writes the same names around its projections, its
per-head norms and rotary; ``ops/flash_attention.py`` names what it does
around its kernels' calls; the head and the loss name themselves. The names
are metadata: loss, gradients and three steps' parameters do not change by a
bit with every scope of ``annotate``'s taken away. The full-size steps are
held to the same rules on their described compiles
(``tests/test_tpu_compile*.py``)."""

import contextlib
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import (GptDecoder, JoyaiFlashTiny, Lfm2Tiny,
                                NemotronHTiny, OlmoeDecoder, SdarTiny,
                                SmallThinkerTiny, TrinityTiny,
                                joyai_flash_loss, lfm2_loss, nemotron_h_loss,
                                olmoe_loss, sdar_loss, sdar_noise,
                                smallthinker_loss, trinity_loss)
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.profiler import annotate

PARTS = annotate.ATTN_PART_SCOPES + annotate.HEAD_SCOPES
PART = re.compile(r"\b(%s)\b" % "|".join(PARTS))
# a name stack, not a source file's path (``ops/head_loss.py`` is one)
NAME_STACK = re.compile(r'"(?!/)([^"]*(?:attn_|head_)[^"]*)"')
PROJECTIONS = ("attn_qkv_proj", "attn_out_proj")
BATCH, SEQ = 2, 32


def _tokens(vocab):
    return jax.random.randint(jax.random.key(7), (BATCH, SEQ), 0, vocab)


def _stateless(model, loss_of):
    tokens = _tokens(model.vocab)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]

    def loss(p):
        return loss_of(model.apply({"params": p}, tokens), tokens)
    return params, loss


def _stateful(model, loss_of):
    tokens = _tokens(model.vocab)
    variables = jax.jit(model.init)(jax.random.key(0), tokens)
    state = variables["router_state"]

    def loss(p):
        return loss_of(model, p, state, tokens)[0]
    return variables["params"], loss


def _gpt():
    model = GptDecoder(vocab=256, layers=1, hidden=32, heads=4, mlp_dim=64,
                       max_len=SEQ)
    return _stateless(model, lambda logits, tokens: (
        optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.roll(tokens, -1, axis=1)).mean()))


def _olmoe():
    model = OlmoeDecoder(vocab=256, layers=1, hidden=32, heads=4, experts=8,
                         experts_per_token=2, expert_dim=16)
    return _stateless(model, lambda out, tokens: olmoe_loss(
        out[0], jnp.roll(tokens, -1, axis=1), out[1], 2)[0])


def _smallthinker():
    # a full layer without positions and a window layer with rotary
    model = SmallThinkerTiny(rope_layout=(0, 1), sliding_window_layout=(0, 1))
    return _stateless(model, lambda out, tokens: (
        smallthinker_loss(out[0], jnp.roll(tokens, -1, axis=1), out[1])[0]))


def _sdar():
    model = SdarTiny(layers=1)
    x0 = _tokens(model.vocab)
    noised = {"x0": x0, **jax.jit(functools.partial(
        sdar_noise, block=model.block_length, mask_id=model.vocab - 1))(
            jax.random.key(1), x0)}
    params = jax.jit(model.init)(jax.random.key(0), x0, x0)["params"]

    def loss(p):
        logits, stats = model.apply({"params": p}, noised["xt"], x0)
        return sdar_loss(logits, noised, stats)[0]
    return params, loss


def _next_token(loss_fn):
    return lambda model, p, state, tokens: loss_fn(
        model, p, state, tokens, jnp.roll(tokens, -1, axis=1))


# model -> (its parameters and loss, the parts its step writes at a test's
# length: below the router's threshold ``attn_kernel_io`` is the repeat of
# the key heads, which a model with equal heads does not have)
MODELS = {
    "gpt": (_gpt, (*PROJECTIONS, "head_logits")),
    "olmoe": (_olmoe, (*PROJECTIONS, "attn_qk_norm", "attn_rope",
                       "head_logits", "head_loss")),
    "nemotron_h": (
        lambda: _stateful(NemotronHTiny(pattern="*E"),
                          _next_token(nemotron_h_loss)),
        (*PROJECTIONS, "attn_kernel_io", "head_logits", "head_loss")),
    "lfm2": (
        lambda: _stateful(Lfm2Tiny(layer_types=("conv", "full_attention")),
                          _next_token(lfm2_loss)),
        (*PROJECTIONS, "attn_qk_norm", "attn_rope", "attn_kernel_io",
         "head_logits", "head_loss")),
    "smallthinker": (_smallthinker, (*PROJECTIONS, "attn_rope",
                                     "attn_kernel_io", "head_logits",
                                     "head_loss")),
    "sdar": (_sdar, (*PROJECTIONS, "attn_qk_norm", "attn_rope",
                     "attn_kernel_io", "head_logits")),
    "joyai_flash": (
        lambda: _stateful(JoyaiFlashTiny(num_layers=1), joyai_flash_loss),
        ("head_logits", "head_loss")),
    # the gate and the post-norms are families of their own: no part
    "trinity": (
        lambda: _stateful(TrinityTiny(layer_types=("sliding_attention",
                                                   "full_attention")),
                          _next_token(trinity_loss)),
        (*PROJECTIONS, "attn_qk_norm", "attn_rope", "attn_kernel_io",
         "head_logits", "head_loss")),
}


def _kernels():
    """No model: the kernels' own path at a length a test interprets, a
    grouped causal call and a block-diffusion pass."""
    keys = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(keys[0], (1, 256, 4, 64), jnp.bfloat16)
    k, v = (jax.random.normal(key, (1, 256, 2, 64), jnp.bfloat16)
            for key in keys[1:])

    def loss(p):
        o = fa.attention(p["q"], p["k"], p["v"], causal=True,
                         min_flash_seq=128)
        streams = fa.blockdiff_attention(p["q"], p["k"], p["v"], 4,
                                         min_flash_seq=128)
        return jnp.sum(o.astype(jnp.float32) ** 2) + \
            jnp.sum(streams.astype(jnp.float32) ** 2)
    return {"q": q, "k": k, "v": v}, loss


CASES = {**MODELS, "kernels": (_kernels, ("attn_kernel_io",
                                           "attn_self_block", "attn_merge"))}


def _train(params, loss, steps=3):
    """(loss and gradients at the start, the parameters after ``steps`` of
    AdamW, the lowered step's name stacks); the step is traced anew."""
    optimizer = optax.adamw(1e-3)

    @jax.jit
    def step(p, opt_state):
        value, grads = jax.value_and_grad(loss)(p)
        updates, opt_state = optimizer.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, value, grads
    opt_state = optimizer.init(params)
    lowered = step.lower(params, opt_state)  # traced once for both uses
    stacks = set(NAME_STACK.findall(lowered.as_text(debug_info=True)))
    compiled = lowered.compile()
    first = None
    for _ in range(steps):
        params, opt_state, value, grads = compiled(params, opt_state)
        first = first or (value, grads)
    return jax.tree_util.tree_map(np.asarray, (first, params)), stacks


@pytest.mark.parametrize("case", list(CASES))
def test_the_names_change_no_value_and_each_part_is_written_once(
        monkeypatch, case):
    """Forward and in the transposed pass alike every part the case writes
    is in a name stack, no stack holds two parts, and with ``annotate``'s
    one scope function handing out the null context the stacks hold none and
    every number is the same bit for bit."""
    make, parts = CASES[case]
    params, loss = make()
    named, stacks = _train(params, loss)
    for stack in stacks:
        assert len(PART.findall(stack)) <= 1, stack
    found = {PART.search(s).group(1) for s in stacks if PART.search(s)}
    assert found == set(parts)
    backward = {PART.search(s).group(1) for s in stacks
                if PART.search(s) and "transpose(" in s}
    # the repeat of the key heads (the kernels' case apart) has a backward,
    # the sum over a group, as every part has one
    assert backward == set(parts)
    monkeypatch.setattr(annotate, "collective_scope",
                        lambda name: contextlib.nullcontext())
    bare, bare_stacks = _train(params, loss)
    assert not any(PART.search(s) for s in bare_stacks)
    for got, want in zip(jax.tree_util.tree_leaves(named),
                         jax.tree_util.tree_leaves(bare)):
        assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("family", list(annotate.FAMILIES))
def test_a_family_takes_its_names_and_refuses_every_other(family):
    """One checking function for all families: a name of the family's tuple
    is written into the traced operations' name stack, any other name, a
    name of another family among them, is an error that says which names
    there are."""
    what, names = annotate.FAMILIES[family]
    scope = functools.partial(annotate.family_scope, family)
    others = [n for other, (_, theirs) in annotate.FAMILIES.items()
              if other != family for n in theirs]
    for unknown in (f"{family}_everything", *others):
        with pytest.raises(ValueError, match=f"unknown {what} scope") as e:
            scope(unknown)
        assert names[0] in str(e.value)

    def f(x):
        with scope(names[-1]):
            return x * 2.0
    assert f"{names[-1]}/mul" in jax.jit(f).lower(
        jnp.ones(4)).as_text(debug_info=True)
    # the name bound to the family is the same function
    bound = {"attn_part": annotate.attn_part_scope}.get(
        family, getattr(annotate, f"{family}_scope"))
    assert (bound.func, bound.args) == (annotate.family_scope, (family,))


def test_the_two_new_families_share_no_name_with_a_kind_or_a_phase():
    assert not set(PARTS) & set(annotate.ATTN_SCOPES)
    assert all(n.startswith("attn_") for n in annotate.ATTN_PART_SCOPES)
    assert all(n.startswith("head_") for n in annotate.HEAD_SCOPES)
    assert not any(n.startswith(("hvd_", annotate.PHASE_PREFIX))
                   for n in PARTS)
