"""Hybrid short-convolution / attention decoder LM with sparse experts (LFM2):
a layer's operator and its feed-forward are chosen from two lists that know
nothing of each other.

LFM2-8B-A1B (``LiquidAI/LFM2-8B-A1B`` ``config.json``, ``model_type``
``lfm2_moe``; transformers' ``modeling_lfm2_moe.py``). Layer ``i`` is

    h   = x + op_i(RMSNorm(x))           operator_norm
    out = h + ff_i(RMSNorm(h))           ffn_norm

with the residual in the activations' dtype.

**The operator**, by ``layer_types[i]``:

- ``conv``, the gated short convolution (:class:`Lfm2ShortConv`):
  ``[B | C | u] = in_proj(x)`` (``d -> 3d``, split in that order, no bias);
  ``g = B * u``; ``c_t = sum_j w[j] g_{t-K+1+j}`` a channel, depthwise and
  causal over ``K = conv_L_cache`` positions with zeros before the sequence,
  no bias (``conv_bias`` false) and **no activation**; ``y = C * c``;
  ``out_proj(y)`` (``d -> d``). No position signal, and no state longer than
  ``K - 1`` tokens. The middle is ``ops/short_conv.py``; the three parts run
  under ``profiler/annotate.SHORTCONV_SCOPES``.
- ``full_attention`` (:class:`Lfm2Attention`): ``q`` in ``heads`` heads and
  ``k``, ``v`` in ``kv_heads`` heads of ``head_dim``, no bias; an RMSNorm
  over each head's own ``head_dim`` values of q and of k (``q_layernorm``,
  ``k_layernorm``: one weight vector for all heads), then rotary
  (rotate-half, ``rope_theta``, ``ops/rotary.rotary``), causal
  ``softmax(q k^T / sqrt(head_dim)) v``
  with query head ``j`` on key head ``j // (heads / kv_heads)`` through the
  length-routed ``ops/flash_attention.attention`` under ``attn_full``, k and
  v at their own heads; ``out_proj``.

**The feed-forward**, by ``i < num_dense_layers``:

- dense (:class:`Lfm2Mlp`): ``w2(silu(w1 x) * w3 x)`` of width
  ``intermediate_size``;
- sparse (:class:`Lfm2SparseMoe`, ``parallel/ep.moe_dropless``): scores
  ``s = sigmoid(x W_r)`` in float32 over all ``experts``; the choice is the
  top ``k`` of ``s + expert_bias`` (``use_expert_bias``: the bias moves the
  choice and nothing else); the weights are ``s`` at the chosen, over their
  sum (``norm_topk_prob``), times ``routed_scale``: ``ep.route_sigmoid_topk``
  as it is. **A departure**: the published code divides by the sum + 1e-6,
  ``route_sigmoid_topk`` by the sum + 1e-20; at four scores near 0.5 the two
  weights differ by 5e-7 of themselves, below what float32 tells apart in a
  loss. Expert ``e`` is ``w2_e(silu(w1_e x) * w3_e x)``
  (``ep.swiglu_expert``); no shared expert. ``experts_held = (first, count)``
  makes the layer one chip's share of an expert-parallel deployment: it
  routes over all ``experts`` and holds, and computes, ``count`` of them;
  ``None`` holds all.

**The state.** ``expert_bias`` is trained by a rule and not by a gradient
(the auxiliary-loss-free balancing of arXiv:2408.15664, as
``models/nemotron_h.py`` has it), so it is the model's state and no
parameter: the collection ``router_state`` holds, for each sparse layer's
``gate``, ``expert_bias`` and ``load``, the pairs each expert was sent in the
previous step (zeros before the first). With the collection mutable a call
begins with ``b <- b + bias_update_rate * sign(mean(load) - load)`` and
leaves its own ``load`` as float32, which
``dp.make_stateful_train_step``'s state sync averages over chips. Without it
mutable (evaluation) the bias is used as it stands.

Embedding -> blocks -> RMSNorm (``embedding_norm``) -> **the embedding's own
rows as the head** (``tie_word_embeddings``), float32 logits: the
embedding's gradient is the sum of the gather's and the head product's.
Modules keep the class's name first (``Lfm2Block_3/Lfm2ShortConv_0``),
which is how a device trace tells the kinds apart. ``remat`` is the blocks'
recomputation policy, ``models/smallthinker.REMAT_POLICIES``' names.

Every matrix and the embedding start normal 0.02, the conv's taps uniform in
+-1/sqrt(K) (torch's ``Conv1d`` default for one input channel a group),
every norm's scale at 1.

The repo's dtype policy: float32 parameters; ``dtype`` (bf16) activations
and matmul inputs with float32 accumulation; float32 for router logits and
scores, the norms' statistics, the gates and the taps' sum of the short
convolution, the rotary angles, logits and loss.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.olmoe import INIT
from horovod_tpu.models.smallthinker import REMAT_POLICIES
from horovod_tpu.ops.flash_attention import attention
from horovod_tpu.ops.head_loss import head_cross_entropy
from horovod_tpu.ops.rotary import rotary
from horovod_tpu.ops.short_conv import gated_short_conv
from horovod_tpu.parallel import ep
from horovod_tpu.profiler.annotate import (attn_part_scope, attn_scope,
                                           head_scope, shortconv_scope)

OPERATORS = ("conv", "full_attention")
ROUTER_STATE = "router_state"
# the published stack: six periods, the last two one ``conv`` shorter
LFM2_8B_A1B_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


def linear(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, kernel_init=INIT,
                    name=name)


def _taps_init(key, shape, dtype=jnp.float32):
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Lfm2ShortConv(nn.Module):
    """``out_proj(C * conv(B * u))``, ``[B | C | u] = in_proj(x)``; the taps
    ``conv`` [K, d] in float32, the last on the position itself."""
    taps: int = 3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        w = self.param("conv", _taps_init, (self.taps, d), jnp.float32)
        with shortconv_scope("shortconv_in_proj"):
            bcu = linear(3 * d, self.dtype, "in_proj")(x)
        with shortconv_scope("shortconv_mix"):
            y = gated_short_conv(bcu, w, self.dtype)
        with shortconv_scope("shortconv_out_proj"):
            return linear(d, self.dtype, "out_proj")(y)


class Lfm2Attention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
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
        # over each head's own head_dim values, one weight vector for all
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        with attn_part_scope("attn_qk_norm"):
            q, k = norm(name="q_layernorm")(q), norm(name="k_layernorm")(k)
        with attn_scope("attn_full"):
            with attn_part_scope("attn_rope"):
                q, k = rotary((q, k), self.rope_theta)
            o = attention(q, k, v, causal=True)
        with attn_part_scope("attn_out_proj"):
            return linear(hidden, self.dtype, "out_proj")(
                o.reshape(b, t, self.heads * self.head_dim))


SWIGLU_NAMES = ("w1", "w3", "w2")  # gate, up, down as this checkpoint has them


class Lfm2Mlp(nn.Module):
    """``w2(silu(w1 x) * w3 x)``: the dense feed-forward. ``names`` are the
    gate's, the up- and the down-projection's: another checkpoint's model
    (``models/trinity.py``) names its own."""
    width: int
    dtype: Any = jnp.bfloat16
    names: Tuple[str, str, str] = SWIGLU_NAMES

    @nn.compact
    def __call__(self, x):
        w1, w3, w2 = self.names
        gate = linear(self.width, self.dtype, w1)(x)
        up = linear(self.width, self.dtype, w3)(x)
        return linear(x.shape[-1], self.dtype, w2)(jax.nn.silu(gate) * up)


class Lfm2Router(nn.Module):
    """``gate``: the router's matrix, and in ``router_state`` the expert
    bias with the load its rule reads. Returns the routing function
    ``parallel/ep.moe_dropless`` takes and where to leave the load (None
    outside training)."""
    experts: int
    experts_per_token: int
    routed_scale: float
    bias_update_rate: float

    @nn.compact
    def __call__(self, hidden: int):
        weight = self.param("weight", INIT, (hidden, self.experts),
                            jnp.float32)
        zeros = functools.partial(jnp.zeros, (self.experts,), jnp.float32)
        bias = self.variable(ROUTER_STATE, "expert_bias", zeros)
        load = self.variable(ROUTER_STATE, "load", zeros)
        training = self.is_mutable_collection(ROUTER_STATE) and \
            not self.is_initializing()
        if training:
            bias.value = bias.value + self.bias_update_rate * jnp.sign(
                load.value.mean() - load.value)
        route = functools.partial(
            ep.route_sigmoid_topk, w_router=weight, bias=bias.value,
            k=self.experts_per_token, scale=self.routed_scale)
        return route, load if training else None


class Lfm2Experts(nn.Module):
    """The routed SwiGLU experts held here, stacked: ``w1``, ``w3``
    [held, d, f], ``w2`` [held, f, d], under ``names``."""
    held: int
    width: int
    names: Tuple[str, str, str] = SWIGLU_NAMES

    @nn.compact
    def __call__(self, hidden: int):
        w1, w3 = (self.param(name, INIT, (self.held, hidden, self.width),
                             jnp.float32) for name in self.names[:2])
        return w1, w3, self.param(self.names[2], INIT,
                                  (self.held, self.width, hidden),
                                  jnp.float32)


def routed_swiglu(x, router: Lfm2Router, experts: Lfm2Experts, held, dtype):
    """``sum_k w_k expert_k(x)`` over the experts held here as [tokens, d],
    through ``parallel/ep.moe_dropless``; the step's load is left where
    ``router`` says. For a module's compact ``__call__``, which makes and
    names the two."""
    d = x.shape[-1]
    route, load = router(d)
    weights = experts(d)
    out, stats = ep.moe_dropless(
        x.reshape(-1, d).astype(dtype), route, ep.swiglu_expert,
        tuple(w.astype(dtype) for w in weights), held=held)
    if load is not None:
        load.value = stats.expert_tokens.astype(jnp.float32)
    return out


class Lfm2SparseMoe(nn.Module):
    experts: int
    experts_per_token: int
    expert_dim: int
    routed_scale: float = 1.0
    bias_update_rate: float = 1e-3
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        held = self.experts_held[1] if self.experts_held else self.experts
        return routed_swiglu(
            x, Lfm2Router(self.experts, self.experts_per_token,
                          self.routed_scale, self.bias_update_rate,
                          name="gate"),
            Lfm2Experts(held, self.expert_dim, name="experts"),
            self.experts_held, self.dtype).reshape(x.shape)


class Lfm2Block(nn.Module):
    """``h = x + op(operator_norm(x)); h + ff(ffn_norm(h))``; ``operator``
    and ``feed_forward`` construct the layer's two halves."""
    operator: Any
    feed_forward: Any
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        x = x + self.operator()(norm(name="operator_norm")(x))
        return x + self.feed_forward()(norm(name="ffn_norm")(x))


class Lfm2MoeDecoder(nn.Module):
    """Causal LM: embedding -> one block a layer of ``layer_types`` ->
    RMSNorm -> the embedding's rows as the head. Returns float32 logits
    [B, T, vocab], or with ``head=False`` the normed hidden state [B, T,
    hidden] they are the product of (a loss that runs the head itself,
    :func:`lfm2_loss`); apply with ``mutable=["router_state"]`` to train the
    expert biases."""

    layer_types: Tuple[str, ...] = LFM2_8B_A1B_LAYER_TYPES
    num_dense_layers: int = 2
    vocab: int = 65536
    hidden: int = 2048
    conv_taps: int = 3
    heads: int = 32
    kv_heads: int = 8
    head_dim: int = 64
    dense_dim: int = 7168
    experts: int = 32
    experts_per_token: int = 4
    expert_dim: int = 1792
    routed_scale: float = 1.0
    bias_update_rate: float = 1e-3
    rope_theta: float = 1e6
    experts_held: Optional[Tuple[int, int]] = None
    eps: float = 1e-5
    remat: str = ""
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens, head: bool = True):
        unknown = set(self.layer_types) - set(OPERATORS)
        if unknown or not self.layer_types:
            raise ValueError(f"a layer's operator is one of {OPERATORS}; "
                             f"layer_types names {sorted(unknown)}")
        if self.remat and self.remat not in REMAT_POLICIES:
            raise ValueError(f"remat {self.remat!r} is none of "
                             f"{['', *REMAT_POLICIES]}")
        operators = {
            "conv": functools.partial(Lfm2ShortConv, self.conv_taps,
                                      self.dtype),
            "full_attention": functools.partial(
                Lfm2Attention, self.heads, self.kv_heads, self.head_dim,
                self.rope_theta, self.eps, self.dtype),
        }
        dense = functools.partial(Lfm2Mlp, self.dense_dim, self.dtype)
        sparse = functools.partial(
            Lfm2SparseMoe, self.experts, self.experts_per_token,
            self.expert_dim, self.routed_scale, self.bias_update_rate,
            self.experts_held, self.dtype)
        block = Lfm2Block
        if self.remat:
            block = nn.remat(block, policy=REMAT_POLICIES[self.remat])
        # the rows are gathered in float32 and cast after, so the gather's
        # part of the embedding's gradient adds up in float32 as the head's
        embed = nn.Embed(self.vocab, self.hidden, dtype=jnp.float32,
                         embedding_init=INIT, name="embed_tokens")
        x = embed(tokens).astype(self.dtype)
        for i, kind in enumerate(self.layer_types):
            # named here: nn.remat's class would name itself otherwise
            x = block(operators[kind],
                      dense if i < self.num_dense_layers else sparse,
                      self.eps, self.dtype, name=f"Lfm2Block_{i}")(x)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                       name="embedding_norm")(x)
        if not head:
            return x
        # bf16 inputs, float32 out of the accumulators: no bf16 logits
        with head_scope("head_logits"):
            return jnp.einsum("btd,vd->btv", x,
                              embed.embedding.astype(self.dtype),
                              preferred_element_type=jnp.float32)


def Lfm2_8B_A1B(**kw) -> Lfm2MoeDecoder:
    """LFM2-8B-A1B geometry (8.3 B parameters, 1.5 B active a token): 24
    layers, 18 short-convolution and 6 attention, 2 dense and 22 sparse."""
    return Lfm2MoeDecoder(**kw)


def Lfm2Tiny(**kw) -> Lfm2MoeDecoder:
    """Both operators and both feed-forwards at widths a CPU trains in
    seconds: a leading dense ``conv`` layer and one period of sparse ones."""
    sizes = dict(layer_types=("conv", "full_attention", "conv", "conv",
                              "conv"),
                 num_dense_layers=1, vocab=256, hidden=32, heads=4,
                 kv_heads=2, head_dim=8, dense_dim=64, experts=8,
                 experts_per_token=2, expert_dim=16, rope_theta=1e4)
    return Lfm2MoeDecoder(**{**sizes, **kw})


def lfm2_loss(model, params, router_state, tokens, labels,
              router=("Lfm2SparseMoe_0", "gate"),
              head=("embed_tokens", "embedding")):
    """Mean next-token cross-entropy, no auxiliary term: balance is the
    bias rule's. Returns ``(loss, (new router_state, aux))`` as
    ``dp.make_stateful_train_step`` takes them; ``aux["expert_tokens"]`` is
    this step's load, float32 [sparse layers, experts]. ``router`` is where
    a block of ``model`` keeps its :class:`Lfm2Router`, ``head`` where
    ``params`` keep the head's matrix.

    Door A of ``ops/head_loss.py``: the loss holds the model and its
    parameters, so it stops the decoder before its head and hands the hidden
    state and the matrix to ``head_cross_entropy``; no [B T, vocab] array
    stands between the forward and the backward pass."""
    hidden, new_state = model.apply(
        {"params": params, ROUTER_STATE: router_state}, tokens, head=False,
        mutable=[ROUTER_STATE])
    rows = labels.size
    loss = head_cross_entropy(
        hidden.reshape(rows, -1), params[head[0]][head[1]],
        labels.reshape(rows), jnp.ones(rows, jnp.float32)) / rows
    new_state = new_state.get(ROUTER_STATE, {})  # none without a sparse layer
    # <Block>_<i>, in layer order (a tree's keys come sorted as text)
    blocks = sorted(new_state, key=lambda name: int(name.rsplit("_", 1)[1]))
    loads = [new_state[b][router[0]][router[1]]["load"] for b in blocks]
    return loss, (new_state, {"expert_tokens": jnp.stack(loads)
                              if loads else jnp.zeros((0, model.experts))})
