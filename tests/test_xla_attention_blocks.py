"""``ops/flash_attention.xla_attention`` in row blocks: under a causal mask
the queries go in blocks of ``XLA_CAUSAL_BLOCK`` rows, each against the keys
its mask shows it. Held to a dense float32 reference under a boolean mask
written here by hand (``xla_attention`` is other test files' reference, so
nothing of ``ops/`` is called for it), output and all three gradients; the
plan's counts against that mask's sum; and the traced program itself: no
``[T, T]`` array, the products' sizes, the counter."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flash_cases import assert_close, equations, out_and_grads, qkv

from horovod_tpu.ops import flash_attention as fa

BLOCK = fa.XLA_CAUSAL_BLOCK
HEADS, WIDTH = 2, 32

# the call's mask keywords, by name: a window narrower than a block and one
# of 2.5 blocks; both edges of a block mask
MASKS = {
    "causal": {},
    "window_narrow": {"window": BLOCK // 2 - 14},
    "window_2.5_blocks": {"window": 5 * BLOCK // 2},
    "blocks_le": {"block_mask": (4, "le")},
    "blocks_lt": {"block_mask": (4, "lt")},
}
# four blocks; 2.5 blocks (the last one short); at most one block
LENGTHS = (4 * BLOCK, 5 * BLOCK // 2, 3 * BLOCK // 4)


def mask_by_hand(t, window=None, block_mask=None):
    """[t, t] bool, rows the queries: the call's mask, entry by entry."""
    seen = np.zeros((t, t), bool)
    for i in range(t):
        for j in range(t):
            if block_mask is not None:
                group, edge = block_mask
                seen[i, j] = j // group <= i // group if edge == "le" \
                    else j // group < i // group
            else:
                seen[i, j] = j <= i and (window is None or i - j < window)
    return seen


def dense_reference(q, k, v, seen):
    """Attention under the boolean mask ``seen`` in float32, key heads
    repeated to the query heads; a row that sees no key gives 0."""
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(seen[None, None], s, -jnp.inf)
    top = jnp.where(seen.any(-1), s.max(-1), 0.0)[..., None]
    e = jnp.where(seen[None, None], jnp.exp(s - top), 0.0)
    p = e / jnp.maximum(e.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@functools.lru_cache(maxsize=None)
def reference(mask, t, dtype=jnp.float32, kv_heads=HEADS):
    """``(inputs in dtype, dout, out, grads)``: the dense reference, in
    float32, on the inputs as ``dtype`` rounds them."""
    q, _, dout = qkv(11, (1, t, HEADS, WIDTH), jnp.float32)
    _, k, v = qkv(12, (1, t, kv_heads, WIDTH), jnp.float32)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    seen = jnp.asarray(mask_by_hand(t, **MASKS[mask]))
    out, grads = out_and_grads(
        lambda q, k, v: dense_reference(q, k, v, seen), q, k, v, dout)
    return (q, k, v), dout, out, grads


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("t", LENGTHS)
@pytest.mark.parametrize("mask", list(MASKS))
def test_blocks_match_the_dense_mask(mask, t, dtype):
    """Output and the gradients of q, k, v, whatever the blocks' edges."""
    (q, k, v), dout, want, want_grads = reference(mask, t, dtype)
    got, grads = out_and_grads(
        lambda q, k, v: fa.xla_attention(q, k, v, causal=True, **MASKS[mask]),
        q, k, v, dout)
    assert got.dtype == dtype and got.shape == q.shape
    assert_close(got, want, dtype)
    for g, w in zip(grads, want_grads):
        assert_close(g, w, dtype)


def test_rows_that_see_no_key_are_zeros_with_zero_gradients():
    """Under ``"lt"`` a query of block 0 sees nothing: output 0, and
    nothing flows to its query row."""
    (q, k, v), dout, _, _ = reference("blocks_lt", LENGTHS[0])
    got, (dq, _, _) = out_and_grads(
        lambda q, k, v: fa.xla_attention(q, k, v, causal=True,
                                         block_mask=(4, "lt")), q, k, v, dout)
    assert not np.asarray(got[:, :4]).any() and np.asarray(got[:, 4:]).any()
    assert not np.asarray(dq[:, :4]).any()
    # a whole row block with no key: blocks of as many positions as it has
    whole = fa.xla_attention(q, k, v, causal=True, block_mask=(BLOCK, "lt"))
    assert not np.asarray(whole[:, :BLOCK]).any()
    assert np.isfinite(np.asarray(whole)).all()


@pytest.mark.parametrize("mask", ["causal", "window_narrow"])
def test_grouped_heads_through_the_router(mask):
    """``attention`` below its threshold repeats the key heads and takes
    the blocks; dk and dv come back at the key heads."""
    t = LENGTHS[1]
    (q, k, v), dout, want, want_grads = reference(mask, t, kv_heads=1)
    got, grads = out_and_grads(
        lambda q, k, v: fa.attention(q, k, v, causal=True, **MASKS[mask]),
        q, k, v, dout)
    assert_close(got, want, jnp.float32)
    for g, w, x in zip(grads, want_grads, (q, k, v)):
        assert g.shape == x.shape
        assert_close(g, w, jnp.float32)


@pytest.mark.parametrize("block, share", [(64, 56.25), (128, 62.5),
                                          (256, 75.0), (512, 100.0)])
def test_score_plan_at_512(block, share):
    """What a causal call of 512 computes, by the size of its blocks, as a
    share of ``T^2``; 50.1% of it is visible."""
    plan = fa.xla_score_plan(512, 512, block, True)
    assert plan == {"computed": int(share * 512 * 512 / 100),
                    "visible": 512 * 513 // 2}


@pytest.mark.parametrize("t", LENGTHS)
@pytest.mark.parametrize("mask", list(MASKS))
def test_score_plan_sees_what_the_mask_sees(mask, t):
    """``visible`` is the dense mask's sum, and every visible score lies in
    its row block's key range: ``computed`` counts those ranges."""
    seen = mask_by_hand(t, **MASKS[mask])
    plan = fa.xla_score_plan(t, t, BLOCK, True, **MASKS[mask])
    assert plan["visible"] == seen.sum()
    inside = np.zeros_like(seen)
    for r0, r1, k0, k1 in fa._xla_blocks(t, t, BLOCK, True,
                                         MASKS[mask].get("window"),
                                         MASKS[mask].get("block_mask")):
        inside[r0:r1, k0:k1] = True
    assert not (seen & ~inside).any()
    assert plan["computed"] == inside.sum() >= plan["visible"]


def test_score_plan_without_a_mask_is_the_whole_product():
    assert fa.xla_score_plan(96, 160, BLOCK, False) == \
        {"computed": 96 * 160, "visible": 96 * 160}


def _score_products(fn, *args):
    """Entries of every product of ``fn``'s jaxpr whose output has no side
    a head wide (the scores and their gradient), and every shape it
    holds."""
    scores, shapes = [], set()
    for eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr):
        shapes.update(tuple(v.aval.shape) for v in eqn.outvars)
        if eqn.primitive.name == "dot_general" \
                and WIDTH not in eqn.outvars[0].aval.shape[-2:]:
            scores.append(int(np.prod(eqn.outvars[0].aval.shape)))
    return scores, shapes


def _loss(attend):
    return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32))


@pytest.mark.parametrize("mask", list(MASKS))
def test_the_dead_work_is_gone_from_the_program(mask):
    """Forward and backward of a causal call of four blocks hold no
    ``[.., T, T]`` array, and the scores and their gradient are as many
    entries as the plan computes."""
    t, batch = 4 * BLOCK, 3
    q, k, v = qkv(13, (batch, t, HEADS, WIDTH), jnp.bfloat16)
    scores, shapes = _score_products(
        jax.grad(_loss(functools.partial(fa.xla_attention, causal=True,
                                         **MASKS[mask])), argnums=(0, 1, 2)),
        q, k, v)
    assert not [s for s in shapes if s[-2:] == (t, t)]
    plan = fa.xla_score_plan(t, t, BLOCK, True, **MASKS[mask])
    assert sum(scores) == 2 * plan["computed"] * batch * HEADS
    assert plan["computed"] < t * t * (0.63 if mask != "blocks_le" else 0.64)


def _parents_writing(q, k, v, causal=False):
    """``xla_attention`` as it stood before the blocks: one product pair
    over the whole ``[T, T]``."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


@pytest.mark.parametrize("t", [BLOCK // 2, 4 * BLOCK])
def test_a_bidirectional_call_is_the_program_it_was(t):
    """No causal mask, no blocks: the jaxpr of the one dense product pair,
    token for token, at any length."""
    q, k, v = qkv(14, (2, t, HEADS, WIDTH), jnp.bfloat16)
    grad = functools.partial(jax.grad, argnums=(0, 1, 2))
    assert str(jax.make_jaxpr(grad(_loss(fa.xla_attention)))(q, k, v)) == \
        str(jax.make_jaxpr(grad(_loss(_parents_writing)))(q, k, v))


@pytest.mark.parametrize("t", [BLOCK // 2, BLOCK])
def test_a_causal_call_of_one_block_is_the_dense_product(t):
    """At or under one block: one product pair over ``[T, T]`` forward, and
    the parent's result bit for bit, output and gradients."""
    q, k, v = qkv(15, (2, t, HEADS, WIDTH), jnp.bfloat16)
    causal = functools.partial(fa.xla_attention, causal=True)
    scores, _ = _score_products(causal, q, k, v)
    assert scores == [2 * HEADS * t * t]
    dout = qkv(16, q.shape, jnp.float32)[0]
    got, grads = out_and_grads(causal, q, k, v, dout)
    want, want_grads = out_and_grads(
        functools.partial(_parents_writing, causal=True), q, k, v, dout)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    for g, w in zip(grads, want_grads):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("mask", ["causal", "window_narrow", "blocks_lt"])
def test_counter_reads_the_plan_of_what_was_traced(mask):
    """``hvd_xla_attention_scores_total{kind}`` after one trace: the plan x
    batch x heads."""
    from horovod_tpu.metrics.registry import get_registry

    def read():
        return {kind: get_registry().counter(
            "hvd_xla_attention_scores_total", kind=kind).value
            for kind in ("computed", "visible")}

    t, batch = 4 * BLOCK, 3
    q, k, v = qkv(17, (batch, t, HEADS, WIDTH), jnp.bfloat16)
    before = read()
    jax.make_jaxpr(functools.partial(fa.xla_attention, causal=True,
                                     **MASKS[mask]))(q, k, v)
    plan = fa.xla_score_plan(t, t, BLOCK, True, **MASKS[mask])
    assert {kind: n - before[kind] for kind, n in read().items()} == \
        {kind: n * batch * HEADS for kind, n in plan.items()}
    if mask == "causal":
        assert plan["computed"] / plan["visible"] == \
            pytest.approx(1.25, abs=0.01)
