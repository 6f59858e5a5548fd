"""Transformer encoder (BERT family) — the second benchmark flagship.

Parity target: the reference benchmarks BERT-Large pretraining with tensor
fusion + fp16 gradient compression (reference: docs/benchmarks.rst:67-83
protocol; BASELINE.md config 3). From-scratch flax.linen, TPU-first: bf16
activations on the MXU with fp32 params, static shapes, bias-free layernorm
residual blocks in the pre-LN arrangement XLA fuses cleanly.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu.ops.flash_attention import attention
from horovod_tpu.profiler.annotate import attn_part_scope


class FlashSelfAttention(nn.Module):
    """Self-attention whose core is the length-routed attention op
    (ops/flash_attention.py): same q/k/v/out projection geometry as
    ``nn.MultiHeadDotProductAttention``. At/above the crossover
    (HOROVOD_FLASH_MIN_SEQ, default 1024) the Pallas flash kernel runs and
    the [T, T] score matrix never touches HBM; below it the router uses
    plain XLA dot attention, under a causal mask in row blocks that stop at
    the diagonal (measured at 512: ``PERF.md`` §6, PR 49; where the two
    paths cross has not been measured). Bidirectional (BERT) by default;
    set ``causal`` for decoder use."""

    heads: int
    dtype: Any = jnp.bfloat16
    causal: bool = False

    @nn.compact
    def __call__(self, x, deterministic=True):
        d = x.shape[-1]
        if d % self.heads:
            raise ValueError(f"hidden dim {d} must be divisible by "
                             f"heads ({self.heads})")
        head_dim = d // self.heads
        proj = dict(features=(self.heads, head_dim), dtype=self.dtype)
        with attn_part_scope("attn_qkv_proj"):
            q = nn.DenseGeneral(name="query", **proj)(x)
            k = nn.DenseGeneral(name="key", **proj)(x)
            v = nn.DenseGeneral(name="value", **proj)(x)
        o = attention(q, k, v, causal=self.causal)
        with attn_part_scope("attn_out_proj"):
            return nn.DenseGeneral(features=d, axis=(-2, -1),
                                   dtype=self.dtype, name="out")(o)


class EncoderBlock(nn.Module):
    """Pre-LN transformer block; ``causal=True`` makes it a decoder block
    (the GPT family reuses it with that flag)."""

    hidden: int
    heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    use_flash: bool = False
    causal: bool = False

    @nn.compact
    def __call__(self, x, mask=None, deterministic=True):
        h = nn.LayerNorm(dtype=self.dtype)(x)
        if self.use_flash:
            if mask is not None:
                raise ValueError("use_flash supports mask=None (full "
                                 "bidirectional) or causal only")
            h = FlashSelfAttention(heads=self.heads, dtype=self.dtype,
                                   causal=self.causal)(
                                       h, deterministic=deterministic)
        else:
            if self.causal:
                if mask is not None:
                    raise ValueError("causal=True builds its own mask")
                mask = nn.make_causal_mask(jnp.ones((1, x.shape[1])))
            h = nn.MultiHeadDotProductAttention(
                num_heads=self.heads, dtype=self.dtype,
                deterministic=deterministic)(h, h, mask=mask)
        x = x + h
        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = nn.Dense(self.mlp_dim, dtype=self.dtype)(h)
        h = nn.gelu(h)
        h = nn.Dense(self.hidden, dtype=self.dtype)(h)
        return x + h


class BertEncoder(nn.Module):
    """Masked-LM encoder: embeddings -> N blocks -> tied-ish LM head."""

    vocab: int = 30522
    layers: int = 12
    hidden: int = 768
    heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 512
    dtype: Any = jnp.bfloat16
    use_flash: bool = False

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True):
        pos = jnp.arange(tokens.shape[1])[None, :]
        embed = nn.Embed(self.vocab, self.hidden, dtype=self.dtype)
        x = embed(tokens)
        x = x + nn.Embed(self.max_len, self.hidden,
                         dtype=self.dtype)(pos)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        for _ in range(self.layers):
            x = EncoderBlock(self.hidden, self.heads, self.mlp_dim,
                             self.dtype, use_flash=self.use_flash)(
                                 x, deterministic=deterministic)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        # LM head tied to the input embedding (BERT geometry)
        logits = embed.attend(x)
        logits = logits + self.param("lm_bias", nn.initializers.zeros,
                                     (self.vocab,), jnp.float32)
        return logits.astype(jnp.float32)


def BertBase(**kw) -> BertEncoder:
    return BertEncoder(layers=12, hidden=768, heads=12, mlp_dim=3072, **kw)


def BertLarge(**kw) -> BertEncoder:
    return BertEncoder(layers=24, hidden=1024, heads=16, mlp_dim=4096, **kw)
