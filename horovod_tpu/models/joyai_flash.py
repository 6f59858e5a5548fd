"""Decoder LM with multi-head latent attention, fine-grained sparse experts
beside a shared one, and a multi-token-prediction module (JoyAI-LLM-Flash; the
DeepSeek-V3 family's layer, arXiv:2412.19437).

JoyAI-LLM-Flash 48B-A2.7B (``jdopensource/JoyAI-LLM-Flash`` ``config.json``,
``model_type`` ``joyai_llm_flash``). Layer ``i`` is

    a   = x + attn(input_layernorm(x))
    out = a + ff_i(post_attention_layernorm(a))

with the residual in the activations' dtype, RMSNorm, no bias anywhere.

**``attn``, multi-head latent attention** (:class:`JoyaiLatentAttention`),
``heads`` heads whose queries and keys are ``qk_nope_dim + qk_rope_dim`` wide
(128 + 64) and whose values are ``v_dim`` (128):

    c_q          = q_a_layernorm(x W_qa)            d -> q_lora_rank
    q            = c_q W_qb                         -> heads x (nope | rope)
    [c_kv | k_r] = x W_kva                          d -> kv_lora_rank + rope
    [k_nope | v] = kv_a_layernorm(c_kv) W_kvb       -> heads x (nope + v_dim)

``k_r`` is ONE rotary key for all heads. Rotary (``rope_theta``, unscaled)
turns the pairs ``(2i, 2i+1)`` of each head's ``q_rope`` and of ``k_r``
(``rope_interleave``). The two projections that end in rotary columns
(``W_qb``, ``W_kva``) hold their matrices as published and hand the product
those columns with the pairs' first members before their second
(:func:`_pairs_as_halves`: a reordering of the MATRIX's columns, so no
activation is shuffled; a reshape of ``[T, 32, 64]`` to pairs cost the TPU
whole copies in layouts of two lanes, ``PERF.md`` §6), and the rotation is the
rotate-half of ``ops/rotary.rotary``, as the published code permutes before
its own: the same order for q and k, so every score is the one of the
interleaved order. A head's key is ``[k_nope | k_r]``; scores ``q . k /
sqrt(nope + rope)``, causal softmax, ``o = p v``, ``o W_o``. The key is built
by a broadcast of ``k_r`` to the heads and a concatenation, once a layer and
pass (``PERF.md`` §6 has what it costs), and the call goes through the
length-routed ``ops/flash_attention.attention`` under ``attn_latent``: at or
above the crossover q and k of 192 against v of 128 run the flash kernels
under ``_fwd_latent_kernel``, ``_bwd_dq_latent_kernel``,
``_bwd_dkv_latent_kernel``. The parts around the call run under
``profiler/annotate.MLA_SCOPES``.

**``ff_i``**: the first ``first_k_dense`` layers a dense SwiGLU of
``dense_dim``; every later one (:class:`JoyaiMoE`) ``shared(x) + sum_k w_k
expert_k(x)``: the shared expert one SwiGLU of ``shared_experts x
expert_dim`` under ``moe_shared``, the routed ones ``experts`` SwiGLUs of
``expert_dim`` through ``parallel/ep.moe_dropless``. Scores ``s = sigmoid(x
W_r)`` in float32; the ``experts_per_token`` chosen by ``s + expert_bias``
(``noaux_tc``: the bias moves the choice alone; ``n_group = topk_group = 1``,
no group limits it); weights ``s`` at the chosen over their sum
(``norm_topk_prob``) times ``routed_scale``: ``ep.route_sigmoid_topk`` as it
is (its sum + 1e-20 where the published code adds 1e-20 too).
``experts_held = (first, count)`` makes a layer one chip's share of an
expert-parallel deployment: it routes over all ``experts`` and holds, and
computes, ``count`` of them; ``None`` holds all. The router, its bias rule and
state, the experts' stack and the SwiGLU are ``models/lfm2.py``'s modules:
``router_state`` holds each sparse layer's ``expert_bias`` and ``load``, and
a call with the collection mutable begins with ``b <- b + bias_update_rate *
sign(mean(load) - load)``.

A final RMSNorm (``norm``) and an untied head (``lm_head``), float32 logits.

**The multi-token-prediction module** (:class:`JoyaiMtp`;
``mtp_layers = num_nextn_predict_layers``, 0 or 1; DeepSeek-V3 report §2.2).
With ``g_i`` the stack's final-normed output at position ``i``:

    u_i      = W_eh [enorm(Emb(t_{i+1})) ; hnorm(g_i)]       2d -> d
    z        = Block(u)      one more sparse layer, causal, positions 0..T-1
    logits'_i = Head(shared_head_norm(z_i))

``Emb`` and ``Head`` are the main model's own embedding and head, used a
second time and not copied: the embedding's gradient is the sum of two
gathers and the head's of two products. ``t_{i+1}`` at the last position is
the roll's ``t_0``; no loss reads that position or the one before it, and the
block is causal. The parts run under ``profiler/annotate.MTP_SCOPES``.

:func:`joyai_flash_loss` is

    mean_{i <= T-2} CE(logits_i, t_{i+1})
        + mtp_lambda * mean_{i <= T-3} CE(logits'_i, t_{i+2})

over all T rows under a mask (no slice of a logits array).

Modules keep the class's name first (``JoyaiBlock_3/JoyaiLatentAttention_0``,
``JoyaiMtp_0/JoyaiBlock_0``), which is how a device trace tells them apart;
what they hold is named as the published checkpoint names it. ``remat`` is
the blocks' recomputation policy, ``models/smallthinker.REMAT_POLICIES``'
names. Every matrix and the embedding start normal 0.02, norms at 1.

The repo's dtype policy: float32 parameters; ``dtype`` (bf16) activations
and matmul inputs with float32 accumulation; float32 for router logits and
scores, the norms' statistics, the rotary angles, the softmax's statistics,
both logits and the loss.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.lfm2 import (ROUTER_STATE, Lfm2Experts, Lfm2Mlp,
                                     Lfm2Router, linear)
from horovod_tpu.models.olmoe import INIT
from horovod_tpu.models.smallthinker import REMAT_POLICIES
from horovod_tpu.ops.flash_attention import attention
from horovod_tpu.ops.head_loss import head_cross_entropy
from horovod_tpu.ops.rotary import rotary
from horovod_tpu.parallel import ep
from horovod_tpu.profiler.annotate import (attn_scope, head_scope, mla_scope,
                                           moe_scope, mtp_scope)


def _pairs_as_halves(features: int, width: int, rope: int, dtype, name: str
                     ) -> nn.Dense:
    """A projection without bias whose output is groups of ``width`` columns
    that each end in ``rope`` rotary ones: those leave as ``[first members |
    second members]`` of their pairs ``(2i, 2i+1)``. The matrix is stored as
    published; its columns are reordered on the way to the product (the same
    products summed in the same order as reordering the output would give),
    which costs a pass over the matrix and nothing over the activations."""
    def dot_general(x, kernel, dimension_numbers, precision=None,
                    preferred_element_type=None):
        groups = kernel.reshape(kernel.shape[0], -1, width)
        pairs = groups[..., width - rope:]
        halves = jnp.concatenate(
            [groups[..., :width - rope], pairs[..., 0::2], pairs[..., 1::2]],
            axis=-1).reshape(kernel.shape)
        return jax.lax.dot_general(
            x, halves, dimension_numbers, precision=precision,
            preferred_element_type=preferred_element_type)
    return nn.Dense(features, use_bias=False, dtype=dtype, kernel_init=INIT,
                    dot_general=dot_general, name=name)


class JoyaiLatentAttention(nn.Module):
    heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    rope_theta: float
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, t, hidden = x.shape
        nope, rope = self.qk_nope_dim, self.qk_rope_dim
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        with mla_scope("mla_q_proj"):
            c_q = norm(name="q_a_layernorm")(
                linear(self.q_lora_rank, self.dtype, "q_a_proj")(x))
            q = _pairs_as_halves(
                self.heads * (nope + rope), nope + rope, rope, self.dtype,
                "q_b_proj")(c_q).reshape(b, t, self.heads, nope + rope)
        with mla_scope("mla_kv_proj"):
            latent = _pairs_as_halves(
                self.kv_lora_rank + rope, self.kv_lora_rank + rope, rope,
                self.dtype, "kv_a_proj_with_mqa")(x)
            c_kv = norm(name="kv_a_layernorm")(
                latent[..., :self.kv_lora_rank])
            kv = linear(self.heads * (nope + self.v_dim), self.dtype,
                        "kv_b_proj")(c_kv).reshape(
                            b, t, self.heads, nope + self.v_dim)
        with mla_scope("mla_rope"):
            # one rotary key, turned once and handed to every head
            k_r = rotary(latent[..., None, self.kv_lora_rank:],
                         self.rope_theta)
            q = jnp.concatenate(
                [q[..., :nope], rotary(q[..., nope:], self.rope_theta)],
                axis=-1)
            k = jnp.concatenate(
                [kv[..., :nope],
                 jnp.broadcast_to(k_r, (b, t, self.heads, rope))], axis=-1)
            v = kv[..., nope:]
        with attn_scope("attn_latent"):
            o = attention(q, k, v, causal=True,
                          sm_scale=(nope + rope) ** -0.5)
        with mla_scope("mla_out_proj"):
            return linear(hidden, self.dtype, "o_proj")(
                o.reshape(b, t, self.heads * self.v_dim))


class JoyaiMoE(nn.Module):
    """``shared(x) + sum_k w_k expert_k(x)`` over the experts held here."""
    experts: int
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    routed_scale: float = 2.5
    bias_update_rate: float = 1e-3
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        held = self.experts_held[1] if self.experts_held else self.experts
        route, load = Lfm2Router(
            self.experts, self.experts_per_token, self.routed_scale,
            self.bias_update_rate, name="gate")(d)
        weights = Lfm2Experts(held, self.expert_dim, name="experts")(d)
        out, stats = ep.moe_dropless(
            x.reshape(-1, d).astype(self.dtype), route, ep.swiglu_expert,
            tuple(w.astype(self.dtype) for w in weights),
            held=self.experts_held)
        if load is not None:
            load.value = stats.expert_tokens.astype(jnp.float32)
        with moe_scope("moe_shared"):
            shared = Lfm2Mlp(self.shared_dim, self.dtype,
                             name="shared_experts")(x)
        return out.reshape(x.shape) + shared


class JoyaiBlock(nn.Module):
    """``a = x + attn(input_layernorm(x))``, then ``a +
    ff(post_attention_layernorm(a))``; ``attention`` and ``feed_forward``
    construct the layer's two halves."""
    attention: Any
    feed_forward: Any
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        x = x + self.attention()(norm(name="input_layernorm")(x))
        return x + self.feed_forward()(
            norm(name="post_attention_layernorm")(x))


def _count_module():
    """Monitoring, at trace time like ``flash_attention._count_call``: one
    count a multi-token-prediction module traced."""
    from horovod_tpu.metrics.registry import get_registry
    get_registry().counter(
        "hvd_mtp_modules_total",
        "multi-token-prediction modules traced").inc()


class JoyaiMtp(nn.Module):
    """``shared_head_norm(Block(W_eh [enorm(next_embedding) ; hnorm(g)]))``:
    the multi-token-prediction module up to the shared head. ``block``
    constructs its sparse layer."""
    block: Any
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, g, next_embedding):
        _count_module()
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        with mtp_scope("mtp_merge"):
            u = linear(g.shape[-1], self.dtype, "eh_proj")(jnp.concatenate(
                [norm(name="enorm")(next_embedding), norm(name="hnorm")(g)],
                axis=-1))
        with mtp_scope("mtp_block"):
            z = self.block(name="JoyaiBlock_0")(u)
        with mtp_scope("mtp_head"):
            return norm(name="shared_head_norm")(z)


class JoyaiFlashDecoder(nn.Module):
    """Causal LM: embedding -> ``num_layers`` blocks -> RMSNorm -> head, and
    behind them the multi-token-prediction module on the same embedding and
    head. Returns float32 ``(logits, mtp_logits)`` [B, T, vocab] each
    (``mtp_logits`` None with ``mtp_layers`` 0), or with ``head=False`` the
    two normed hidden states [B, T, hidden] they are the products of
    (:func:`joyai_flash_loss` runs the head itself); apply with
    ``mutable=["router_state"]`` to train the expert biases."""

    num_layers: int = 40
    first_k_dense: int = 1
    mtp_layers: int = 1
    mtp_lambda: float = 0.3
    vocab: int = 129280
    hidden: int = 2048
    heads: int = 32
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_dim: int = 128
    dense_dim: int = 7168
    experts: int = 256
    experts_per_token: int = 8
    expert_dim: int = 768
    shared_experts: int = 1
    routed_scale: float = 2.5
    bias_update_rate: float = 1e-3
    rope_theta: float = 32e6
    experts_held: Optional[Tuple[int, int]] = None
    eps: float = 1e-6
    remat: str = ""
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, head: bool = True):
        if self.mtp_layers not in (0, 1):
            raise ValueError(
                f"mtp_layers {self.mtp_layers}: the published configuration "
                "has one multi-token-prediction module; 0 leaves it out")
        if self.remat and self.remat not in REMAT_POLICIES:
            raise ValueError(f"remat {self.remat!r} is none of "
                             f"{['', *REMAT_POLICIES]}")
        attn = functools.partial(
            JoyaiLatentAttention, self.heads, self.q_lora_rank,
            self.kv_lora_rank, self.qk_nope_dim, self.qk_rope_dim,
            self.v_dim, self.rope_theta, self.eps, self.dtype)
        dense = functools.partial(Lfm2Mlp, self.dense_dim, self.dtype,
                                  name="mlp")
        sparse = functools.partial(
            JoyaiMoE, self.experts, self.experts_per_token, self.expert_dim,
            self.shared_experts * self.expert_dim, self.routed_scale,
            self.bias_update_rate, self.experts_held, self.dtype)
        block = JoyaiBlock
        if self.remat:
            block = nn.remat(block, policy=REMAT_POLICIES[self.remat])
        # the rows are gathered in float32 and cast after: both gathers'
        # parts of the embedding's gradient add up in float32
        embed = nn.Embed(self.vocab, self.hidden, dtype=jnp.float32,
                         embedding_init=INIT, name="embed_tokens")
        # bf16 inputs, float32 out of the accumulators: no bf16 logits
        head_of = nn.Dense(
            self.vocab, use_bias=False, dtype=self.dtype, kernel_init=INIT,
            dot_general=functools.partial(
                jax.lax.dot_general, preferred_element_type=jnp.float32),
            name="lm_head")
        x = embed(tokens).astype(self.dtype)
        for i in range(self.num_layers):
            # named here: nn.remat's class would name itself otherwise
            x = block(attn, dense if i < self.first_k_dense else sparse,
                      self.eps, self.dtype, name=f"JoyaiBlock_{i}")(x)
        g = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm")(x)
        z = None
        if self.mtp_layers:
            with mtp_scope("mtp_merge"):
                next_embedding = embed(jnp.roll(tokens, -1, axis=1)) \
                    .astype(self.dtype)
            z = JoyaiMtp(functools.partial(block, attn, sparse, self.eps,
                                           self.dtype),
                         self.eps, self.dtype, name="JoyaiMtp_0")(
                             g, next_embedding)
        if not head:
            return g, z
        with head_scope("head_logits"):
            logits = head_of(g)
        with mtp_scope("mtp_head"):
            return logits, None if z is None else head_of(z)


# The decoder's defaults ARE the published geometry (50.19 B parameters with
# the module, 48.94 B without; 2.7 B active a token): the preset is its name.
JoyaiLlmFlash = JoyaiFlashDecoder


def JoyaiFlashTiny(**kw) -> JoyaiFlashDecoder:
    """The dense layer, two sparse layers and the module at widths a CPU
    trains in seconds; q/k (16 + 8) and v (16) still differ."""
    sizes = dict(num_layers=3, vocab=256, hidden=32, heads=4, q_lora_rank=24,
                 kv_lora_rank=16, qk_nope_dim=16, qk_rope_dim=8, v_dim=16,
                 dense_dim=64, experts=8, experts_per_token=2, expert_dim=16,
                 rope_theta=1e4)
    return JoyaiFlashDecoder(**{**sizes, **kw})


MTP_HEAD = (functools.partial(mtp_scope, "mtp_head"),) * 2


def joyai_flash_loss(model: JoyaiFlashDecoder, params, router_state, tokens):
    """Mean next-token cross-entropy plus ``model.mtp_lambda`` times the
    module's mean second-next-token cross-entropy; no auxiliary term:
    balance is the bias rule's. Returns ``(loss, (new router_state, aux))``
    as ``dp.make_stateful_train_step`` takes them; ``aux["expert_tokens"]``
    is this step's load, float32 [sparse layers, experts], the stack's in
    layer order and the module's last; ``aux["next_token_loss"]`` and
    ``aux["mtp_loss"]`` the two means.

    Door A of ``ops/head_loss.py``, twice over the one shared ``lm_head``:
    the loss holds the model and its parameters, so it stops the decoder
    before its head and hands each hidden state and the kernel to
    ``head_cross_entropy`` (the module's under ``mtp_head``). A head's mean
    is of ``logits_i`` against ``t_{i+ahead}`` over the ``T - ahead``
    positions that have such a token: all T rows are computed alike and the
    rest weigh 0 (no slice of a hidden state)."""
    (hidden, mtp_hidden), new_state = model.apply(
        {"params": params, ROUTER_STATE: router_state}, tokens, head=False,
        mutable=[ROUTER_STATE])
    b, t = tokens.shape

    def mean_ce(hidden, ahead: int, *scopes):
        has_label = jnp.broadcast_to(jnp.arange(t) < t - ahead, (b, t))
        return head_cross_entropy(
            hidden.reshape(b * t, -1), params["lm_head"]["kernel"],
            jnp.roll(tokens, -ahead, axis=1).reshape(b * t),
            has_label.reshape(b * t).astype(jnp.float32), *scopes) \
            / (b * (t - ahead))
    loss = next_token = mean_ce(hidden, 1)
    aux = {"next_token_loss": next_token}
    if mtp_hidden is not None:
        aux["mtp_loss"] = mean_ce(mtp_hidden, 2, MTP_HEAD)
        loss = loss + model.mtp_lambda * aux["mtp_loss"]
    new_state = new_state.get(ROUTER_STATE, {})  # none without a sparse layer
    aux["expert_tokens"] = jnp.stack(_expert_loads(new_state)) \
        if new_state else jnp.zeros((0, model.experts))
    return loss, (new_state, aux)


def _expert_loads(router_state) -> list:
    """The sparse layers' ``load`` [experts] out of a ``router_state``, the
    stack's in layer order (a tree's keys come sorted as text) and the
    module's last."""
    stack = sorted((name for name in router_state
                    if name.startswith("JoyaiBlock_")),
                   key=lambda name: int(name.rsplit("_", 1)[1]))
    layers = [router_state[name] for name in stack]
    if "JoyaiMtp_0" in router_state:
        layers.append(router_state["JoyaiMtp_0"]["JoyaiBlock_0"])
    return [layer["JoyaiMoE_0"]["gate"]["load"] for layer in layers]
