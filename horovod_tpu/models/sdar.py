"""Sparse-expert decoder LM trained on a block-diffusion objective (SDAR):
two streams of every sequence through every layer, told apart by attention
alone.

SDAR-30B-A3B-Chat (``JetLM/SDAR-30B-A3B-Chat`` ``config.json``, ``model_type``
``sdar_moe``; arXiv:2510.06303), whose objective is BD3-LM's block diffusion
with an absorbing mask state (arXiv:2503.09573). A sequence ``x0`` of ``L``
tokens is cut into blocks of ``G``, ``b(i) = i // G``; every block draws a
noise level ``t_b`` and each of its tokens becomes the mask token with
probability ``t_b``, which gives ``xt`` (:func:`sdar_noise`). **Both ``xt``
and ``x0`` go through the model in one pass**, ``2L`` rows a sequence (the
noised stream first), and every layer with input ``x`` is

    h   = RMSNorm(x)
    q, k, v = h W_q, h W_k, h W_v            heads / kv_heads heads of head_dim
    q, k = RMSNorm over each head's head_dim   (q_norm, k_norm: per head)
    q, k = rotary(q, k) at the row's position IN ITS SEQUENCE (both: 0..L-1;
              ops/rotary.rotary over [2B, L, ...], one call for both, the
              backward its own)
    x1  = x + W_o Attn(q, k, v)   under the block-diffusion mask:
              xt on xt: b(k) == b(q);  xt on x0: b(k) < b(q);
              x0 on x0: b(k) <= b(q);  x0 on xt: never
    out = x1 + sum_chosen w_e W_down,e (silu(W_gate,e h2) * W_up,e h2),
              h2 = RMSNorm(x1), the top k of softmax(h2 W_r) renormalised

with no bias, no dense feed-forward and no shared expert. Position-wise code
(norms, projections, experts) sees ``2L`` rows and knows no stream; only
:class:`SdarAttention` is told them, and hands
``ops/flash_attention.blockdiff_attention`` the rows under the named scope
``attn_blockdiff`` (``profiler/annotate.ATTN_SCOPES``). The top k of the
softmax renormalised over the chosen (``norm_topk_prob``) is the softmax of
the chosen logits: ``parallel/ep.route_topk_softmax``, with
``ep.swiglu_expert`` in ``ep.moe_dropless``; ``experts_held = (first,
count)`` makes the layer one chip's share of an expert-parallel deployment.
Then RMSNorm and an untied head over the **noised stream's rows alone**, with
float32 logits; :func:`sdar_loss` is the cross-entropy of ``x0`` at the
masked positions, weighted ``1 / t_b``, over all ``B L`` positions.

The layers are alike, and written out one by one (``SdarBlock_0`` ...). A
scanned stack (``nn.scan``, the parameters stacked) was tried and is not
kept: the block would be traced and compiled once, but the backward loop
holds every layer's gradient until the optimizer runs after it, where the
unrolled step updates a layer's weights as soon as their gradients exist; at
five layers of the published widths the described compile counts 9.36 GB of
temporaries scanned against 4.87 GB unrolled (``PERF.md`` §6, PR 40), and six
layers scanned do not fit the chip. ``remat`` is the blocks' recomputation
policy, ``models/smallthinker.REMAT_POLICIES``' names.

Every matrix and the embedding start normal 0.02 (``models/olmoe.INIT``),
every norm's scale at 1. What that start does to the routers, and where the
benchmark's cell starts instead, is ``PERF.md`` §6, PR 40: the masked
positions, a quarter of the rows, share one embedding, and under random
weights nothing but attention's prefix averages tells them apart.

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
from horovod_tpu.models.smallthinker import REMAT_POLICIES
from horovod_tpu.ops.flash_attention import blockdiff_attention
from horovod_tpu.ops.head_loss import cross_entropy
from horovod_tpu.ops.rotary import rotary
from horovod_tpu.parallel import ep
from horovod_tpu.profiler.annotate import (attn_part_scope, attn_scope,
                                           diffusion_scope, head_scope)


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, kernel_init=INIT,
                    name=name)


class SdarAttention(nn.Module):
    """Attention over the two streams of a sequence, ``x`` [B, 2L, hidden]
    with the noised stream first."""
    heads: int
    kv_heads: int
    head_dim: int
    block_length: int
    rope_theta: float
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, rows, hidden = x.shape

        def heads_of(name, count):
            return _dense(count * self.head_dim, self.dtype, name)(x) \
                .reshape(b, rows, count, self.head_dim)
        with attn_part_scope("attn_qkv_proj"):
            q, k, v = (heads_of("q_proj", self.heads),
                       heads_of("k_proj", self.kv_heads),
                       heads_of("v_proj", self.kv_heads))
        # over each head's own head_dim values, one weight vector for all
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        with attn_part_scope("attn_qk_norm"):
            q, k = norm(name="q_norm")(q), norm(name="k_norm")(k)
        with attn_scope("attn_blockdiff"):
            def streams(y):  # positions 0..L-1 in either stream
                return y.reshape(b * 2, rows // 2, *y.shape[2:])
            with attn_part_scope("attn_rope"):
                q, k = (y.reshape(b, rows, *y.shape[2:]) for y in rotary(
                    (streams(q), streams(k)), self.rope_theta))
            o = blockdiff_attention(q, k, v, self.block_length)
        with attn_part_scope("attn_out_proj"):
            return _dense(hidden, self.dtype, "o_proj")(
                o.reshape(b, rows, self.heads * self.head_dim))


class SdarSparseMoe(nn.Module):
    """``router`` [d, E] over all experts and the SwiGLU experts held here,
    stacked: ``gate_proj``, ``up_proj`` [held, d, f], ``down_proj``
    [held, f, d]."""
    experts: int
    experts_per_token: int
    expert_dim: int
    experts_held: Optional[Tuple[int, int]] = None
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x) -> Tuple[jax.Array, ep.MoeStats]:
        d, f = x.shape[-1], self.expert_dim
        held = self.experts_held[1] if self.experts_held else self.experts
        router = self.param("router", INIT, (d, self.experts), jnp.float32)
        gate, up = (self.param(name, INIT, (held, d, f), jnp.float32)
                    for name in ("gate_proj", "up_proj"))
        down = self.param("down_proj", INIT, (held, f, d), jnp.float32)
        out, stats = ep.moe_dropless(
            x.reshape(-1, d).astype(self.dtype),
            functools.partial(ep.route_topk_softmax, w_router=router,
                              k=self.experts_per_token),
            ep.swiglu_expert,
            tuple(w.astype(self.dtype) for w in (gate, up, down)),
            held=self.experts_held)
        return out.reshape(x.shape), stats


class SdarBlock(nn.Module):
    """One layer over the ``2L`` rows: (the layer's output, its
    :class:`ep.MoeStats`)."""
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    experts_per_token: int
    expert_dim: int
    block_length: int
    rope_theta: float
    experts_held: Optional[Tuple[int, int]] = None
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        norm = functools.partial(nn.RMSNorm, epsilon=self.eps,
                                 dtype=self.dtype)
        x = x + SdarAttention(
            self.heads, self.kv_heads, self.head_dim, self.block_length,
            self.rope_theta, self.eps, self.dtype)(
                norm(name="input_layernorm")(x))
        out, stats = SdarSparseMoe(
            self.experts, self.experts_per_token, self.expert_dim,
            self.experts_held, self.dtype)(
                norm(name="post_attention_layernorm")(x))
        return x + out, stats


class SdarMoeDecoder(nn.Module):
    """Block-diffusion LM: embedding of both streams -> one block a layer
    -> RMSNorm -> untied head over the noised stream. ``(xt, x0)``, each
    [B, L] int32, give (float32 logits [B, L, vocab] of the noised stream,
    :class:`ep.MoeStats` with a leading layer axis)."""

    vocab: int = 151936
    layers: int = 48
    hidden: int = 2048
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    experts: int = 128
    experts_per_token: int = 8
    expert_dim: int = 768
    block_length: int = 4
    rope_theta: float = 1e6
    experts_held: Optional[Tuple[int, int]] = None
    eps: float = 1e-6
    remat: str = ""
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, xt, x0):
        if xt.shape != x0.shape or xt.shape[1] % self.block_length:
            raise ValueError(
                f"the noised stream {xt.shape} and the clean one {x0.shape} "
                f"are one batch in whole blocks of {self.block_length}")
        if self.remat and self.remat not in REMAT_POLICIES:
            raise ValueError(f"remat {self.remat!r} is none of "
                             f"{['', *REMAT_POLICIES]}")
        block = SdarBlock
        if self.remat:
            block = nn.remat(block, policy=REMAT_POLICIES[self.remat])
        seq = xt.shape[1]
        # the rows are gathered in float32 and cast after: the mask token's
        # row then sums the gradient of a quarter of all rows in float32,
        # where a bf16 gather's transpose adds them up in bf16
        x = nn.Embed(self.vocab, self.hidden, dtype=jnp.float32,
                     embedding_init=INIT)(
                         jnp.concatenate([xt, x0], axis=1)).astype(self.dtype)
        stats = []
        for i in range(self.layers):
            # named here: nn.remat's class would name itself otherwise
            x, layer_stats = block(
                self.heads, self.kv_heads, self.head_dim, self.experts,
                self.experts_per_token, self.expert_dim, self.block_length,
                self.rope_theta, self.experts_held, self.eps, self.dtype,
                name=f"SdarBlock_{i}")(x)
            stats.append(layer_stats)
        stats = jax.tree_util.tree_map(lambda *leaves: jnp.stack(leaves),
                                       *stats)
        x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype, name="norm")(
            x[:, :seq])
        # bf16 inputs, float32 out of the accumulators: no bf16 logits
        with head_scope("head_logits"):
            logits = nn.Dense(
                self.vocab, use_bias=False, dtype=self.dtype,
                kernel_init=INIT, dot_general=functools.partial(
                    jax.lax.dot_general, preferred_element_type=jnp.float32),
                name="LmHead")(x)
        return logits, stats


def Sdar30BA3B(**kw) -> SdarMoeDecoder:
    """SDAR-30B-A3B-Chat geometry (30.5 B parameters, 3.3 B active a
    token): 48 layers alike."""
    return SdarMoeDecoder(**kw)


def SdarTiny(**kw) -> SdarMoeDecoder:
    """Two layers at widths a CPU trains in seconds; ``q`` is wider than the
    hidden size, as published."""
    sizes = dict(vocab=256, layers=2, hidden=32, heads=8, kv_heads=2,
                 head_dim=8, experts=8, experts_per_token=2, expert_dim=16,
                 block_length=4, rope_theta=1e4)
    return SdarMoeDecoder(**{**sizes, **kw})


NOISE_FLOOR = 1e-3  # the least noise level of a block: 1 / t stays finite


def sdar_noise(key: jax.Array, tokens: jax.Array, block: int, mask_id: int
               ) -> dict:
    """The noised stream of ``tokens`` [B, L] (``x0``): a level ``t``
    uniform on [``NOISE_FLOOR``, 1] for each block of ``block`` positions,
    every token of the block replaced by ``mask_id`` with probability ``t``.
    Returns ``xt`` (int, as ``tokens``), ``masked`` (bool [B, L]: where
    ``xt`` holds the mask) and ``weight`` (float32 [B, L]: ``1 / t`` of the
    position's block, the linear schedule's weight of its loss term)."""
    b, seq = tokens.shape
    if seq % block:
        raise ValueError(f"{seq} tokens are no whole blocks of {block}")
    with diffusion_scope("diffusion_noise"):
        key_level, key_mask = jax.random.split(key)
        level = jnp.repeat(jax.random.uniform(
            key_level, (b, seq // block), jnp.float32, NOISE_FLOOR, 1.0),
            block, axis=1)
        masked = jax.random.uniform(key_mask, (b, seq), jnp.float32) < level
        return {"xt": jnp.where(masked, jnp.asarray(mask_id, tokens.dtype),
                                tokens),
                "masked": masked, "weight": 1.0 / level}


def sdar_loss(logits: jax.Array, batch: dict, stats: ep.MoeStats):
    """``(1 / (B L)) sum over the masked positions of (1 / t) CE(logits,
    x0)``: ``logits`` [B, L, vocab] are the noised stream's, position ``i``
    predicts token ``i`` (no shift), ``batch`` holds ``x0`` beside
    :func:`sdar_noise`'s ``masked`` and ``weight``. No auxiliary term (the
    published config names none). Returns (loss, aux) as
    ``dp.make_train_step`` takes them: ``expert_tokens`` is the step's load,
    int32 [layers, E], ``masked_tokens`` the positions the loss is over.

    Door B of ``ops/head_loss.py``: the loss is handed the logits (its
    caller runs the model); the schedule's weight at the masked positions
    and 0 elsewhere are the operator's row weights."""
    with diffusion_scope("diffusion_loss"):
        loss = cross_entropy(
            logits, batch["x0"],
            jnp.where(batch["masked"], batch["weight"], 0.0)) \
            / batch["masked"].size
        return loss, {"expert_tokens": stats.expert_tokens,
                      "masked_tokens": jnp.sum(batch["masked"],
                                               dtype=jnp.int32)}
