"""Sparse-expert decoder LM whose layers mix window and full attention
(SmallThinker): the router reads the layer's input, before attention.

SmallThinker-21BA3B-Instruct (``PowerInfer/SmallThinker-21BA3B-Instruct``
``config.json``; arXiv:2507.20984). Every layer ``l`` with input ``x`` is

    routing = top-k of r = x W_r, weights softmax(r[chosen])   the router
                                              reads x ITSELF, before attention
    x1  = x  + W_o Attn_l(RMSNorm(x))
    out = x1 + sum_chosen w_e W_down,e (relu(W_gate,e h2) * W_up,e h2),
                                              h2 = RMSNorm(x1)

with no dense feed-forward and no shared expert. Attention: ``q`` in
``heads`` heads and ``k``, ``v`` in ``kv_heads`` heads of ``head_dim``, no
bias and no q/k norm, query head ``j`` on key head ``j // (heads /
kv_heads)``, through the length-routed ``ops/flash_attention.attention``. Two
per-layer flags make the layers differ: ``rope_layout[l] = 1`` gives q and k
rotary positions (rotate-half, ``ops/rotary.rotary``), 0 gives the layer
**no position signal**; ``sliding_window_layout[l] = 1`` narrows the causal
mask to ``0 <= i - j < window``, 0 keeps it causal. The published layouts
are ``0,1,1,1`` repeated: one full layer without positions, three window
layers with rotary. The attention call runs under the named scope
``attn_full`` or ``attn_window`` (``profiler/annotate.ATTN_SCOPES``), so a
device trace tells the two kinds of layer apart. Experts:
``parallel/ep.moe_dropless`` with the :class:`~horovod_tpu.parallel.ep.Routing`
that ``ep.moe_routing`` made of the layer's input under
``ep.route_topk_softmax``, and ``ep.reglu_expert``; ``experts_held =
(first, count)`` makes the layer one chip's share of an expert-parallel
deployment (it routes over all ``experts`` and computes ``count`` of them),
``None`` holds all.

Embedding -> blocks -> RMSNorm -> untied head with float32 logits. Modules
keep the class's name first (``SmallThinkerBlock_3/SmallThinkerAttention_0``);
inside them the names are the published checkpoint's as far as remembered
(``input_layernorm``, ``q_proj`` .. ``o_proj``, ``post_attention_layernorm``,
``primary_router``, ``experts`` with ``gate``, ``up`` and ``down``).

Every matrix and the embedding start normal 0.02 (``models/olmoe.INIT``);
``residual_out_std`` gives the two matrices that write into the residual
stream (``o_proj``, the experts' ``down``) a width of their own. The
routers read that stream un-normed, so what the branches add to it decides
the routing: at 0.02 throughout, the prefix averages that attention writes
at initialisation are a vector all tokens share, and from the second layer
on most tokens choose the same six experts (``PERF.md`` §6, PR 38).

``remat`` is the recomputation policy of the blocks (``nn.remat``): ``""``
keeps every activation, ``"blocks"`` recomputes each block whole in
backward, ``"blocks_keep_attention"`` recomputes it but for the attention's
output and row statistics (``ops/flash_attention.FLASH_RESIDUALS``, kept by
name), so the forward kernels run once a step.

The repo's dtype policy: float32 parameters, ``dtype`` (bf16) activations
and matmul inputs with float32 accumulation; router logits and weights, the
norms' statistics, the rotary angles, the logits and the loss in float32.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from horovod_tpu.models.olmoe import INIT
from horovod_tpu.ops.flash_attention import FLASH_RESIDUALS, attention
from horovod_tpu.ops.head_loss import cross_entropy
from horovod_tpu.ops.rotary import rotary
from horovod_tpu.parallel import ep
from horovod_tpu.profiler.annotate import (attn_part_scope, attn_scope,
                                           head_scope)

REMAT_POLICIES = {
    "blocks": None,  # nothing saved inside a block
    "blocks_keep_attention":
        jax.checkpoint_policies.save_only_these_names(*FLASH_RESIDUALS),
}


def _dense(features: int, dtype, name: str, init=INIT) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, kernel_init=init,
                    name=name)


def _out_init(std: Optional[float]):
    """The initializer of the two matrices that write into the residual
    stream (``o_proj``, the experts' ``down``); ``None`` is ``INIT``."""
    return INIT if std is None else nn.initializers.normal(stddev=std)


class SmallThinkerAttention(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: Optional[float]  # None: the layer has no positions
    window: Optional[int]        # None: causal over the whole context
    residual_out_std: Optional[float] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, t, hidden = x.shape

        def heads_of(name, count):
            return _dense(count * self.head_dim, self.dtype, name)(x) \
                .reshape(b, t, count, self.head_dim)
        with attn_part_scope("attn_qkv_proj"):
            q, k, v = (heads_of("q_proj", self.heads),
                       heads_of("k_proj", self.kv_heads),
                       heads_of("v_proj", self.kv_heads))
        with attn_scope("attn_full" if self.window is None
                        else "attn_window"):
            if self.rope_theta is not None:
                with attn_part_scope("attn_rope"):
                    q, k = rotary((q, k), self.rope_theta)
            o = attention(q, k, v, causal=True, window=self.window)
        with attn_part_scope("attn_out_proj"):
            return _dense(hidden, self.dtype, "o_proj",
                          _out_init(self.residual_out_std))(
                o.reshape(b, t, self.heads * self.head_dim))


class SmallThinkerRouter(nn.Module):
    """``primary_router``: the routing of the rows it is given."""
    experts: int
    experts_per_token: int

    @nn.compact
    def __call__(self, x) -> ep.Routing:
        weight = self.param("weight", INIT, (x.shape[-1], self.experts),
                            jnp.float32)
        return ep.moe_routing(functools.partial(
            ep.route_topk_softmax, w_router=weight,
            k=self.experts_per_token), x.reshape(-1, x.shape[-1]))


class SmallThinkerExperts(nn.Module):
    """The ReGLU experts held here, stacked: ``gate``, ``up`` [held, d, f],
    ``down`` [held, f, d], over a routing made elsewhere."""
    held: int
    expert_dim: int
    experts_held: Optional[Tuple[int, int]] = None
    residual_out_std: Optional[float] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, routing: ep.Routing):
        d, f = x.shape[-1], self.expert_dim
        gate, up = (self.param(name, INIT, (self.held, d, f), jnp.float32)
                    for name in ("gate", "up"))
        down = self.param("down", _out_init(self.residual_out_std),
                          (self.held, f, d), jnp.float32)
        out, stats = ep.moe_dropless(
            x.reshape(-1, d).astype(self.dtype), routing, ep.reglu_expert,
            tuple(w.astype(self.dtype) for w in (gate, up, down)),
            held=self.experts_held)
        return out.reshape(x.shape), stats


class SmallThinkerBlock(nn.Module):
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    experts_per_token: int
    expert_dim: int
    rope_theta: Optional[float]
    window: Optional[int]
    experts_held: Optional[Tuple[int, int]] = None
    eps: float = 1e-6
    residual_out_std: Optional[float] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        # the router reads the layer's input, not a normed or later stream
        routing = SmallThinkerRouter(self.experts, self.experts_per_token,
                                     name="primary_router")(x)
        x = x + SmallThinkerAttention(
            self.heads, self.kv_heads, self.head_dim, self.rope_theta,
            self.window, self.residual_out_std, self.dtype)(
                norm(name="input_layernorm")(x))
        held = self.experts_held[1] if self.experts_held else self.experts
        out, stats = SmallThinkerExperts(
            held, self.expert_dim, self.experts_held, self.residual_out_std,
            self.dtype, name="experts")(
                norm(name="post_attention_layernorm")(x), routing)
        return x + out, stats


PERIOD = (0, 1, 1, 1)  # the published layouts, both: full, window x 3


class SmallThinkerDecoder(nn.Module):
    """Causal LM: embedding -> one block a layer of the layouts -> RMSNorm
    -> untied head. Returns (float32 logits [B, T, vocab],
    :class:`ep.MoeStats` with a leading layer axis)."""

    vocab: int = 151936
    hidden: int = 2560
    heads: int = 28
    kv_heads: int = 4
    head_dim: int = 128
    experts: int = 64
    experts_per_token: int = 6
    expert_dim: int = 768
    rope_layout: Tuple[int, ...] = PERIOD * 13
    sliding_window_layout: Tuple[int, ...] = PERIOD * 13
    window: int = 4096
    rope_theta: float = 1.5e6
    experts_held: Optional[Tuple[int, int]] = None
    eps: float = 1e-6
    residual_out_std: Optional[float] = None
    remat: str = ""
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens):
        if len(self.rope_layout) != len(self.sliding_window_layout) or \
                not self.rope_layout:
            raise ValueError(
                f"rope_layout {self.rope_layout} and sliding_window_layout "
                f"{self.sliding_window_layout} name each layer once")
        if self.remat and self.remat not in REMAT_POLICIES:
            raise ValueError(f"remat {self.remat!r} is none of "
                             f"{['', *REMAT_POLICIES]}")
        block = SmallThinkerBlock
        if self.remat:
            block = nn.remat(block, policy=REMAT_POLICIES[self.remat])
        x = nn.Embed(self.vocab, self.hidden, dtype=self.dtype,
                     embedding_init=INIT)(tokens)
        stats = []
        for i, (rope, windowed) in enumerate(zip(
                self.rope_layout, self.sliding_window_layout)):
            # named here: nn.remat's class would name itself otherwise
            x, layer_stats = block(
                self.heads, self.kv_heads, self.head_dim, self.experts,
                self.experts_per_token, self.expert_dim,
                self.rope_theta if rope else None,
                self.window if windowed else None, self.experts_held,
                self.eps, self.residual_out_std, self.dtype,
                name=f"SmallThinkerBlock_{i}")(x)
            stats.append(layer_stats)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm")(x)
        # bf16 inputs, float32 out of the accumulators: no bf16 logits
        with head_scope("head_logits"):
            logits = nn.Dense(
                self.vocab, use_bias=False, dtype=self.dtype,
                kernel_init=INIT, dot_general=functools.partial(
                    jax.lax.dot_general, preferred_element_type=jnp.float32),
                name="LmHead")(x)
        return logits, jax.tree_util.tree_map(
            lambda *leaves: jnp.stack(leaves), *stats)


def SmallThinker21BA3B(**kw) -> SmallThinkerDecoder:
    """SmallThinker-21BA3B-Instruct geometry (21.5 B parameters, 3 B active
    a token): 52 layers, 13 full without positions, 39 window-4096 with
    rotary."""
    return SmallThinkerDecoder(**kw)


def SmallThinkerTiny(**kw) -> SmallThinkerDecoder:
    """One period at widths a CPU trains in seconds; the window is shorter
    than a test's sequence."""
    sizes = dict(vocab=256, hidden=32, heads=4, kv_heads=2, head_dim=8,
                 experts=8, experts_per_token=2, expert_dim=16,
                 rope_layout=PERIOD, sliding_window_layout=PERIOD, window=24,
                 rope_theta=1e4)
    return SmallThinkerDecoder(**{**sizes, **kw})


def smallthinker_loss(logits: jax.Array, labels: jax.Array,
                      stats: ep.MoeStats):
    """Mean next-token cross-entropy and no auxiliary term (the published
    config names none). Returns (loss, aux) as ``dp.make_train_step`` takes
    them; ``aux["expert_tokens"]`` is the step's load, int32 [layers, E].

    Door B of ``ops/head_loss.py``: the loss is handed the logits (its
    caller runs the model), so the operator is the row function over the
    whole array."""
    with head_scope("head_loss"):
        loss = cross_entropy(logits, labels, jnp.ones(labels.shape,
                                                      jnp.float32)) \
            / labels.size
    return loss, {"expert_tokens": stats.expert_tokens}
