"""Sparse-expert decoder LM (OLMoE): the block that is not ``EncoderBlock``.

OLMoE-1B-7B (arXiv:2409.02060; ``allenai/OLMoE-1B-7B-0125-Instruct``
``config.json``, ``model_type`` ``olmoe``). Per layer

    h = x + Attn(RMSNorm(x))        y = h + MoE(RMSNorm(h))

Attention: ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)`` (the norm runs
over the whole projection, before the split into heads), ``v = W_v x``,
rotary positions (rotate-half) on q and k through the one rotary operator
the five rotary models share (``ops/rotary.rotary``: q and k of a call in one
pass, the backward its own, the rotation by the negated angles), the
length-routed attention op the dense models share
(``ops/flash_attention.attention``), ``W_o``. MoE: the
dropless top-k layer of ``parallel/ep.moe_topk`` with gated (SwiGLU) experts
of three matrices. A final RMSNorm and an untied head; no bias anywhere.
Modules keep flax's own names (``OlmoeBlock_0/OlmoeAttention_0``,
``Embed_0``, ``LmHead``): the class's name first, which is how a device
trace tells the blocks apart (``op_name`` carries the module path); the
projections and norms inside them are named as the published checkpoint
names them (``q_proj``, ``q_norm``, ``gate_proj``, ``router``).

The dense models' dtype policy: float32 parameters, ``dtype`` (bf16)
activations and matmul inputs with float32 accumulation; router logits and
softmax, the norms' statistics, the rotary angles and rotation
(``ops/rotary.py``, forward and backward), the logits and the loss in
float32.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from horovod_tpu.ops.flash_attention import attention
from horovod_tpu.ops.rotary import rotary
from horovod_tpu.parallel import ep
from horovod_tpu.profiler.annotate import attn_part_scope, head_scope

INIT = nn.initializers.normal(stddev=0.02)  # transformers' initializer_range


class OlmoeAttention(nn.Module):
    heads: int
    rope_theta: float = 10000.0
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        if d % self.heads:
            raise ValueError(f"hidden dim {d} must be divisible by "
                             f"heads ({self.heads})")
        proj = functools.partial(nn.Dense, d, use_bias=False,
                                 dtype=self.dtype, kernel_init=INIT)
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)

        def heads_of(name, normed=True):
            with attn_part_scope("attn_qkv_proj"):
                y = proj(name=f"{name}_proj")(x)
            if normed:
                with attn_part_scope("attn_qk_norm"):
                    y = norm(name=f"{name}_norm")(y)
            return y.reshape(b, t, self.heads, d // self.heads)
        q, k, v = heads_of("q"), heads_of("k"), heads_of("v", normed=False)
        with attn_part_scope("attn_rope"):
            q, k = rotary((q, k), self.rope_theta)
        o = attention(q, k, v, causal=True)
        with attn_part_scope("attn_out_proj"):
            return proj(name="o_proj")(o.reshape(b, t, d))


class OlmoeSparseMoe(nn.Module):
    """Router and experts of one layer; ``parallel/ep.moe_topk`` over all
    the tokens of the call."""
    experts: int
    experts_per_token: int
    expert_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x) -> Tuple[jax.Array, ep.MoeStats]:
        d, e, f = x.shape[-1], self.experts, self.expert_dim
        router = self.param("router", INIT, (d, e), jnp.float32)
        gate, up = (self.param(name, INIT, (e, d, f), jnp.float32)
                    for name in ("gate_proj", "up_proj"))
        down = self.param("down_proj", INIT, (e, f, d), jnp.float32)
        out, stats = ep.moe_topk(
            x.reshape(-1, d).astype(self.dtype), router,
            gate.astype(self.dtype), up.astype(self.dtype),
            down.astype(self.dtype), self.experts_per_token)
        return out.reshape(x.shape), stats


class OlmoeBlock(nn.Module):
    heads: int
    experts: int
    experts_per_token: int
    expert_dim: int
    rope_theta: float = 10000.0
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        x = x + OlmoeAttention(self.heads, self.rope_theta, self.eps,
                               self.dtype)(norm(name="input_layernorm")(x))
        out, stats = OlmoeSparseMoe(
            self.experts, self.experts_per_token, self.expert_dim,
            self.dtype)(norm(name="post_attention_layernorm")(x))
        return x + out, stats


class OlmoeDecoder(nn.Module):
    """Causal LM: embedding -> N sparse-expert blocks -> RMSNorm -> untied
    head. Returns (float32 logits [B, T, vocab], :class:`ep.MoeStats` with
    a leading layer axis)."""

    vocab: int = 50304
    layers: int = 16
    hidden: int = 2048
    heads: int = 16
    experts: int = 64
    experts_per_token: int = 8
    expert_dim: int = 1024
    rope_theta: float = 10000.0
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(self.vocab, self.hidden, dtype=self.dtype,
                     embedding_init=INIT)(tokens)
        stats = []
        for _ in range(self.layers):
            x, layer_stats = OlmoeBlock(
                self.heads, self.experts, self.experts_per_token,
                self.expert_dim, self.rope_theta, self.eps, self.dtype)(x)
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


def Olmoe1B7B(**kw) -> OlmoeDecoder:
    """OLMoE-1B-7B geometry (6.9 B parameters, 1.3 B active a token)."""
    return OlmoeDecoder(layers=16, hidden=2048, heads=16, experts=64,
                        experts_per_token=8, expert_dim=1024, **kw)


def olmoe_loss(logits: jax.Array, labels: jax.Array, stats: ep.MoeStats,
               experts_per_token: int, load_balancing_coef: float = 0.01,
               router_z_coef: float = 0.001):
    """Mean next-token cross-entropy + ``load_balancing_coef`` x the
    load-balancing loss + ``router_z_coef`` x the router z-loss (OLMoE,
    arXiv:2409.02060). Returns (loss, aux) as ``dp.make_train_step`` takes
    them: ``expert_tokens`` (int32 [layers, E], summed over chips) and the
    two auxiliary losses (averaged)."""
    with head_scope("head_loss"):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        balance = ep.load_balancing_loss(stats.expert_tokens,
                                         stats.router_prob_mean,
                                         experts_per_token)
        z = stats.router_z_loss.mean()
        loss = ce + load_balancing_coef * balance + router_z_coef * z
    return loss, {"expert_tokens": stats.expert_tokens,
                  "load_balancing_loss": balance, "router_z_loss": z}
