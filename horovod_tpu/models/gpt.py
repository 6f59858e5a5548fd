"""Decoder-only transformer LM (GPT family) — the causal counterpart of
the BERT flagship.

The reference benchmarks encoder pretraining only (docs/benchmarks.rst
protocol); a causal LM is where the Pallas flash kernel's traced loop
bound pays off (future k-blocks cost zero MXU work; its speed against
XLA dot attention is not measured on today's code). Same TPU-first
recipe as the encoder: bf16 activations on the MXU, fp32 params, pre-LN
residual blocks, static shapes.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax.numpy as jnp

from horovod_tpu.models.transformer import EncoderBlock
from horovod_tpu.profiler.annotate import head_scope


class GptDecoder(nn.Module):
    """Causal LM: embeddings -> N decoder blocks -> tied LM head."""

    vocab: int = 50257
    layers: int = 12
    hidden: int = 768
    heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 1024
    dtype: Any = jnp.bfloat16
    use_flash: bool = True

    @nn.compact
    def __call__(self, tokens, deterministic: bool = True):
        pos = jnp.arange(tokens.shape[1])[None, :]
        embed = nn.Embed(self.vocab, self.hidden, dtype=self.dtype)
        x = embed(tokens)
        x = x + nn.Embed(self.max_len, self.hidden, dtype=self.dtype)(pos)
        for _ in range(self.layers):
            x = EncoderBlock(self.hidden, self.heads, self.mlp_dim,
                             self.dtype, use_flash=self.use_flash,
                             causal=True)(x, deterministic=deterministic)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        with head_scope("head_logits"):
            return embed.attend(x).astype(jnp.float32)


def GptSmall(**kw) -> GptDecoder:
    """GPT-2 small geometry (124M params)."""
    return GptDecoder(layers=12, hidden=768, heads=12, mlp_dim=3072, **kw)


def GptMedium(**kw) -> GptDecoder:
    """GPT-2 medium geometry (350M params)."""
    return GptDecoder(layers=24, hidden=1024, heads=16, mlp_dim=4096, **kw)
