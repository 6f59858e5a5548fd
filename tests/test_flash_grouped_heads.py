"""Grouped-query attention in the flash kernels: k and v at their own heads,
a key head read once for its group, nothing repeated in HBM (interpret mode
on CPU; ``flash_cases.py`` holds what the flash test files share)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flash_cases import assert_close, out_and_grads
from horovod_tpu.ops.flash_attention import flash_attention


def _calls(group):
    from horovod_tpu.metrics.registry import get_registry
    return get_registry().counter("hvd_flash_calls_total",
                                  kv_group=str(group)).value


# the mask's arguments, the shard's offsets (traced, under jit) or None,
# the dtype
GROUPED_CASES = {
    "causal": (dict(), None, jnp.float32),
    "causal_bf16": (dict(), None, jnp.bfloat16),
    "causal_offsets": (dict(), (96.0, 0.0), jnp.float32),
    "window": (dict(window=100), None, jnp.float32),
    "window_offsets": (dict(window=300), (256.0, 256.0), jnp.float32),
    "block_le": (dict(block_mask=(4, "le")), None, jnp.float32),
    "block_lt": (dict(block_mask=(4, "lt")), None, jnp.float32),
}


@pytest.mark.parametrize("case", list(GROUPED_CASES))
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("group,kv_heads", [(1, 2), (4, 2), (7, 2), (16, 1)],
                         ids=["mha", "group4", "group7", "group16"])
def test_flash_grouped_heads_match_repeated_keys(group, kv_heads, head_dim,
                                                 case):
    """``kv_heads`` key heads for ``group`` times as many query heads, both
    head widths (the two orientations of the dk/dv sums): (o, lse) and dq of
    the call on k and v at their own heads equal, bit for bit, those of the
    same call on ``_repeat_kv``-repeated keys (the program every call was
    before), a cotangent on lse too; dk and dv are that call's, one a query
    head, summed over each group in float32 (the repeat's own gradient would
    add them up in the arrays' dtype); and, where no shard offsets are in
    play, all agree with ``xla_attention``'s (the lse's cotangent is an
    argument: at zero the same compiled program gives the gradient XLA's
    output alone has)."""
    from horovod_tpu.ops import flash_attention as fa
    mask, offsets, dtype = GROUPED_CASES[case]
    heads, seq, d = group * kv_heads, 256, head_dim
    rng = np.random.RandomState(41)
    q = jnp.asarray(rng.randn(1, seq, heads, d), dtype)
    k, v = (jnp.asarray(rng.randn(1, seq, kv_heads, d), dtype)
            for _ in range(2))
    dout = jnp.asarray(rng.randn(*q.shape), dtype)
    dlse = jnp.asarray(rng.randn(1, heads, seq), jnp.float32)
    offsets = tuple(jnp.float32(x) for x in offsets or ())

    def loss(q, k, v, dlse, *offs):
        o, lse = flash_attention(
            q, k, v, causal=True, interpret=True, block_q=128, block_k=64,
            return_lse=True, **dict(zip(("q_offset", "k_offset"), offs)),
            **mask)
        # a dead row's lse is NEG_INF: keep it out of the sum's rounding
        live = jnp.where(lse > -1e29, lse, 0.0)
        return (jnp.sum(o.astype(jnp.float32) * dout)
                + jnp.sum(live * dlse)), (o, lse)
    run = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))

    def summed(x):  # a query head's dk or dv: float32 over each group
        return jnp.sum(x.astype(jnp.float32).reshape(
            1, seq, kv_heads, group, d), axis=3).astype(dtype)

    before = _calls(group)
    got, (o, lse) = run(q, k, v, dlse, *offsets)
    assert _calls(group) == before + 1
    want, (o_rep, lse_rep) = run(q, *fa._repeat_kv(q, k, v), dlse,
                                 *offsets)  # kv_group="1"
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_rep))
    np.testing.assert_array_equal(np.asarray(lse), np.asarray(lse_rep))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    for g, w, like in zip(got[1:], want[1:], (k, v)):
        assert g.shape == like.shape and g.dtype == dtype
        assert_close(g, summed(w), dtype)
    if offsets or dtype != jnp.float32:
        return

    def xla(q, k, v):
        return fa.xla_attention(q, *fa._repeat_kv(q, k, v), causal=True,
                                **mask)
    o_xla, want = out_and_grads(xla, q, k, v, dout)
    got, (o, _) = run(q, k, v, jnp.zeros_like(dlse))  # like XLA's: no lse
    assert_close(o, o_xla, dtype)
    for g, w in zip(got, want):
        assert_close(g, w, dtype)


def test_flash_refuses_heads_that_are_no_groups():
    q = jnp.zeros((1, 128, 6, 64))
    for kv_heads, v_heads in ((4, 4), (2, 3)):
        with pytest.raises(ValueError, match="no multiple"):
            flash_attention(q, jnp.zeros((1, 128, kv_heads, 64)),
                            jnp.zeros((1, 128, v_heads, 64)), interpret=True)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold, but
    for the kernels' own bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("head_dim", [64, 128])
def test_attention_hands_the_kernels_k_and_v_at_their_own_heads(head_dim):
    """The mechanism itself, SmallThinker's heads (4 key heads for 28): in
    the gradient through ``attention`` all three kernel calls take k and v
    as ``[B * 4, T, D]`` beside q's ``[B * 28, T, D]``, the dk/dv call
    writes ``[B * 28, T, D]``, nothing writes an array the size of k out
    once a query head, no transpose is of a k or v repeated to q's size
    (those of q's size are q, do, o and the results'), and the call counts
    under ``kv_group="7"``."""
    from horovod_tpu.ops.flash_attention import attention
    seq, heads, kv_heads = 256, 28, 4
    q = jnp.zeros((1, seq, heads, head_dim), jnp.bfloat16)
    k = v = jnp.zeros((1, seq, kv_heads, head_dim), jnp.bfloat16)

    def loss(q, k, v):
        o = attention(q, k, v, causal=True, min_flash_seq=seq, interpret=True)
        return jnp.sum(o.astype(jnp.float32))
    before = _calls(7)
    eqns = list(_eqns(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1, 2)))(q, k, v).jaxpr))
    assert _calls(7) == before + 1
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 3
    for call in calls:  # q_off, k_off, q, k, v, ...
        assert [x.aval.shape for x in call.invars[2:5]] == [
            (heads, seq, head_dim)] + 2 * [(kv_heads, seq, head_dim)]
        # a grid row finds its key head by a division; everything else by
        # the index maps a call has always had, which compute nothing
        assert [len(m.index_map_jaxpr.jaxpr.eqns) > 0 for m in
                call.params["grid_mapping"].block_mappings[2:5]] == \
            [False, True, True]
    assert [x.aval.shape for x in calls[2].outvars] == \
        2 * [(heads, seq, head_dim)]

    def sized(e, n):
        return e.invars and getattr(e.invars[0].aval, "size", 0) == n
    assert not [e for e in eqns if e.primitive.name == "broadcast_in_dim"
                and sized(e, k.size)]
    # q forward; q, do, o backward; o, dq back: and none of k's or v's
    assert len([e for e in eqns if e.primitive.name == "transpose"
                and sized(e, q.size)]) == 6


@pytest.mark.parametrize("head_dim", [64, 128])
def test_equal_heads_call_the_kernels_as_they_always_were(head_dim):
    """With as many key heads as query heads (the GPT cells, OLMoE) no index
    map of any of the three calls computes anything: block ``(bh, i, 0)`` or
    ``(bh, 0, 0)``, the program those cells had; and no sum follows the
    dk/dv call."""
    x = jnp.zeros((2, 256, 3, head_dim), jnp.bfloat16)
    eqns = list(_eqns(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(x, x, x).jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 3
    for call in calls:
        assert not any(m.index_map_jaxpr.jaxpr.eqns for m in
                       call.params["grid_mapping"].block_mappings)
    after = eqns[eqns.index(calls[2]) + 1:]
    assert "reduce_sum" not in [e.primitive.name for e in after]
