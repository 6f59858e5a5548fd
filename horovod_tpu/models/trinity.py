"""Sparse-expert decoder LM whose attention output is gated, whose layers mix
window and full attention, and whose branches are normed before they join the
stream (Trinity; ``model_type`` ``afmoe``).

Trinity-Mini 26B-A3B (``arcee-ai/Trinity-Mini`` ``config.json``; the public
``modeling_afmoe.py`` and Arcee's report). ``x_0 = Emb(t) * sqrt(hidden)``
(``mup_enabled``). Layer ``l`` with input ``x``:

    h   = input_layernorm(x)
    q   = q_norm(heads(h W_q))        RMSNorm over each head's own head_dim
    k   = k_norm(heads(h W_k))        values, one weight vector for all heads
    v   = heads(h W_v)
    g   = h W_g                       [T, heads * head_dim]: the layer's gate
    o   = Attn_l(q, k, v)             query head j on key head j // group
    a   = x + post_attention_layernorm((o * sigmoid(g)) W_o)
    out = a + post_mlp_layernorm(ff_l(pre_mlp_layernorm(a)))

Four RMSNorms a layer (weight x ``x / rms(x)``, no unit offset, ``eps``), no
bias anywhere. **The gate** is a fifth projection of the layer's normed
input, as wide as the queries; its sigmoid multiplies the attention's output
before ``o_proj``. ``Attn_l`` by ``layer_types[l]``:

- ``sliding_attention``: rotary on q and k (rotate-half, ``rope_theta``,
  unscaled, ``ops/rotary.rotary``), mask ``0 <= i - j < window``, under
  ``attn_window``;
- ``full_attention``: **no position signal at all**, mask ``j <= i``, under
  ``attn_full``.

Both go through the length-routed ``ops/flash_attention.attention`` with k
and v at their own heads. ``ff_l``: the first ``num_dense_layers`` layers a
dense SwiGLU of ``dense_dim`` (:func:`swiglu`); every later one
(:class:`TrinityMoE`) ``shared(u) + sum_k w_k expert_k(u)``, the shared
expert one SwiGLU of ``shared_experts x expert_dim`` under ``moe_shared`` and
added unweighted, the routed ones SwiGLUs of ``expert_dim`` through
``parallel/ep.moe_dropless``. The router in float32: ``s = sigmoid(u W_r)``,
the ``experts_per_token`` largest of ``s + expert_bias`` (the bias moves the
choice alone; one group), weights ``s`` at the chosen over their sum + 1e-20
(``route_norm``) times ``route_scale``: ``ep.route_sigmoid_topk`` as it is.
``experts_held = (first, count)`` makes a sparse layer one chip's share of an
expert-parallel deployment: it routes over all ``experts`` and holds, and
computes, ``count`` of them; ``None`` holds all. The router with its bias
rule and state is ``models/lfm2.Lfm2Router``: ``router_state`` holds each
sparse layer's ``expert_bias`` and ``load``, and a call with the collection
mutable begins with ``b <- b + load_balance_coeff * sign(mean(load) -
load)``.

A final RMSNorm (``norm``) and an untied head (``lm_head``), float32 logits.

The parts no other model has run under names of their own
(``profiler/annotate.OUTGATE_SCOPES``: ``outgate_proj`` the gate's
projection, ``outgate_mul`` its sigmoid and the product; ``POSTNORM_SCOPES``:
``postnorm_attn``, ``postnorm_ff``), the rest under the shared attention-part
and head names. Modules keep the class's name first
(``TrinityBlock_3/TrinityAttention_0``); what they hold is named as the
published checkpoint names it. ``remat`` is the blocks' recomputation policy,
``models/smallthinker.REMAT_POLICIES``' names: under
``blocks_keep_attention`` the kernels' output is kept and the gate's
projection, sigmoid and product are recomputed around it. Every matrix and
the embedding start normal 0.02, norms at 1.

The repo's dtype policy: float32 parameters; ``dtype`` (bf16) activations
and matmul inputs with float32 accumulation, the gate's sigmoid and product
among them; float32 for router logits and scores, the norms' statistics, the
rotary angles, logits and loss.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.lfm2 import (Lfm2Experts, Lfm2Mlp, Lfm2Router, linear,
                                     lfm2_loss, routed_swiglu)
from horovod_tpu.models.olmoe import INIT
from horovod_tpu.models.smallthinker import REMAT_POLICIES
from horovod_tpu.ops.flash_attention import attention
from horovod_tpu.ops.rotary import rotary
from horovod_tpu.profiler.annotate import (attn_part_scope, attn_scope,
                                           head_scope, moe_scope,
                                           outgate_scope, postnorm_scope)

LAYER_TYPES = ("sliding_attention", "full_attention")
PERIOD = ("sliding_attention",) * 3 + ("full_attention",)  # as published


class TrinityAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: Optional[float]  # None: the layer has no positions
    window: Optional[int]        # None: causal over the whole context
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, t, hidden = x.shape

        def heads_of(name, count):
            return linear(count * self.head_dim, self.dtype, name)(x) \
                .reshape(b, t, count, self.head_dim)
        with attn_part_scope("attn_qkv_proj"):
            q, k, v = (heads_of("q_proj", self.heads),
                       heads_of("k_proj", self.kv_heads),
                       heads_of("v_proj", self.kv_heads))
        with outgate_scope("outgate_proj"):
            gate = linear(self.heads * self.head_dim, self.dtype,
                          "gate_proj")(x)
        # over each head's own head_dim values, one weight vector for all
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        with attn_part_scope("attn_qk_norm"):
            q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
        with attn_scope("attn_full" if self.window is None
                        else "attn_window"):
            if self.rope_theta is not None:
                with attn_part_scope("attn_rope"):
                    q, k = rotary((q, k), self.rope_theta)
            o = attention(q, k, v, causal=True, window=self.window)
        with outgate_scope("outgate_mul"):
            o = o.reshape(gate.shape) * jax.nn.sigmoid(gate)
        with attn_part_scope("attn_out_proj"):
            return linear(hidden, self.dtype, "o_proj")(o)


SWIGLU_NAMES = ("gate_proj", "up_proj", "down_proj")  # as published


def swiglu(width: int, dtype, name: str) -> Lfm2Mlp:
    """``down_proj(silu(gate_proj x) * up_proj x)``: the dense feed-forward,
    and a sparse layer's shared expert."""
    return Lfm2Mlp(width, dtype, SWIGLU_NAMES, name=name)


class TrinityMoE(nn.Module):
    """``shared(x) + sum_k w_k expert_k(x)`` over the experts held here."""
    experts: int
    experts_per_token: int
    expert_dim: int
    shared_dim: int
    route_scale: float = 2.826
    load_balance_coeff: float = 1e-3
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        held = self.experts_held[1] if self.experts_held else self.experts
        out = routed_swiglu(
            x, Lfm2Router(self.experts, self.experts_per_token,
                          self.route_scale, self.load_balance_coeff,
                          name="router"),
            Lfm2Experts(held, self.expert_dim, ("gate", "up", "down"),
                        name="experts"),
            self.experts_held, self.dtype)
        with moe_scope("moe_shared"):
            shared = swiglu(self.shared_dim, self.dtype, "shared_experts")(x)
        return out.reshape(x.shape) + shared


class TrinityBlock(nn.Module):
    """``a = x + post_attention_layernorm(attn(input_layernorm(x)))``, then
    ``a + post_mlp_layernorm(ff(pre_mlp_layernorm(a)))``; ``attention`` and
    ``feed_forward`` construct the layer's two halves."""
    attention: Any
    feed_forward: Any
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        branch = self.attention()(norm(name="input_layernorm")(x))
        with postnorm_scope("postnorm_attn"):
            x = x + norm(name="post_attention_layernorm")(branch)
        branch = self.feed_forward()(norm(name="pre_mlp_layernorm")(x))
        with postnorm_scope("postnorm_ff"):
            return x + norm(name="post_mlp_layernorm")(branch)


class TrinityDecoder(nn.Module):
    """Causal LM: scaled embedding -> one block a layer of ``layer_types``
    -> RMSNorm -> untied head. Returns float32 logits [B, T, vocab], or with
    ``head=False`` the normed hidden state [B, T, hidden] they are the
    product of (:data:`trinity_loss` runs the head itself); apply with
    ``mutable=["router_state"]`` to train the expert biases."""

    layer_types: Tuple[str, ...] = PERIOD * 8
    num_dense_layers: int = 2
    vocab: int = 200192
    hidden: int = 2048
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    dense_dim: int = 6144
    experts: int = 128
    experts_per_token: int = 8
    expert_dim: int = 1024
    shared_experts: int = 1
    route_scale: float = 2.826
    load_balance_coeff: float = 1e-3
    window: int = 2048
    rope_theta: float = 1e4
    experts_held: Optional[Tuple[int, int]] = None
    eps: float = 1e-5
    remat: str = ""
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, head: bool = True):
        unknown = set(self.layer_types) - set(LAYER_TYPES)
        if unknown or not self.layer_types:
            raise ValueError(f"a layer's attention is one of {LAYER_TYPES}; "
                             f"layer_types names {sorted(unknown)}")
        if self.remat and self.remat not in REMAT_POLICIES:
            raise ValueError(f"remat {self.remat!r} is none of "
                             f"{['', *REMAT_POLICIES]}")
        def attention_with(rope_theta, window):
            return functools.partial(
                TrinityAttention, self.heads, self.kv_heads, self.head_dim,
                rope_theta, window, self.eps, self.dtype)
        attentions = {
            "sliding_attention": attention_with(self.rope_theta, self.window),
            "full_attention": attention_with(None, None),
        }
        dense = functools.partial(swiglu, self.dense_dim, self.dtype, "mlp")
        sparse = functools.partial(
            TrinityMoE, self.experts, self.experts_per_token,
            self.expert_dim, self.shared_experts * self.expert_dim,
            self.route_scale, self.load_balance_coeff, self.experts_held,
            self.dtype)
        block = TrinityBlock
        if self.remat:
            block = nn.remat(block, policy=REMAT_POLICIES[self.remat])
        # the rows are gathered and scaled in float32 and cast after, so the
        # embedding's gradient adds up in float32
        embed = nn.Embed(self.vocab, self.hidden, dtype=jnp.float32,
                         embedding_init=INIT, name="embed_tokens")
        x = (embed(tokens) * self.hidden ** 0.5).astype(self.dtype)
        for i, kind in enumerate(self.layer_types):
            # named here: nn.remat's class would name itself otherwise
            x = block(attentions[kind],
                      dense if i < self.num_dense_layers else sparse,
                      self.eps, self.dtype, name=f"TrinityBlock_{i}")(x)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm")(x)
        if not head:
            return x
        # bf16 inputs, float32 out of the accumulators: no bf16 logits
        with head_scope("head_logits"):
            return nn.Dense(
                self.vocab, use_bias=False, dtype=self.dtype,
                kernel_init=INIT, dot_general=functools.partial(
                    jax.lax.dot_general, preferred_element_type=jnp.float32),
                name="lm_head")(x)


# The decoder's defaults ARE the published geometry (26.12 B parameters,
# about 3 B active a token): the preset is its name.
TrinityMini = TrinityDecoder


def TrinityTiny(**kw) -> TrinityDecoder:
    """A leading dense layer and one period of sparse ones at widths a CPU
    trains in seconds; the window is shorter than a test's sequence."""
    sizes = dict(layer_types=("sliding_attention",) + PERIOD,
                 num_dense_layers=1, vocab=256, hidden=32, heads=4,
                 kv_heads=2, head_dim=8, dense_dim=64, experts=16,
                 experts_per_token=2, expert_dim=16, window=24)
    return TrinityDecoder(**{**sizes, **kw})


# Mean next-token cross-entropy, no auxiliary term (balance is the bias
# rule's): ``(loss, (new router_state, {"expert_tokens": this step's load,
# float32 [sparse layers, experts]}))`` as ``dp.make_stateful_train_step``
# takes them. ``lfm2_loss``'s door A of ``ops/head_loss.py`` on the untied
# head's kernel [hidden, vocab].
trinity_loss = functools.partial(lfm2_loss, router=("TrinityMoE_0", "router"),
                                 head=("lm_head", "kernel"))
