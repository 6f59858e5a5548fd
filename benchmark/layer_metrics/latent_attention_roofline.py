"""Latent attention: the whole operator against its roofline: the least time
the chip's peaks allow a step's operators (the configuration's count of the
five projections' products in every pass, the kernels' costs and the bytes no
writing can avoid) over the device seconds under the four ``mla_*`` scopes
and ``attn_latent`` (harness/latent.py), however the key reaches the
kernels."""

from harness import latent


def read(trace, run):
    return latent.attention_roofline(trace, run)
