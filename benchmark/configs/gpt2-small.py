"""GPT-2 small: the job the program trains, and its plain float32 reference.

``build(config, traffic)`` returns a ``harness.job.Job``. The job's half is
what a user writes: the repo's flax model, an optax optimizer, a loss. The
reference's half is this file's own: the same architecture in plain
``jax.numpy`` and float32, no flax module, no kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax

from harness import flops
from harness.job import Job, Tolerance

# bf16 keeps 8 significand bits: one rounding is off by at most 2**-9
# relative. Along a 12-layer residual path some hundred roundings compound
# as their root sum: about 10 x 2**-9 = 2% on a gradient leaf. The loss is a
# mean over thousands of tokens of float32 logits, so its error averages
# down and 2**-8 holds it. A precision one step lower (fp8's 2**-4, or bf16
# accumulation in the matmuls) is 16 times coarser and fails both.
TOLERANCE = Tolerance(
    loss_rtol=2.0 ** -8, grad_rel_l2=8 * 2.0 ** -8,
    reason="bf16 activations against float32: 2**-8 on the averaged loss, "
           "8 x 2**-8 relative L2 on a gradient leaf after 12 residual "
           "layers; fp8 or bf16 accumulation would be 16 times off")

REFERENCE_QUERY_BLOCK = 1024  # rows of the score matrix the reference holds


def build(config: dict, traffic: dict) -> Job:
    from horovod_tpu.models import GptSmall

    seq = int(traffic["seq_len"])
    layers = int(config["n_layer"])
    hidden, heads = int(config["n_embd"]), int(config["n_head"])
    mlp, vocab = int(config["n_inner"]), int(config["vocab_size"])
    positions = max(int(config["n_positions"]), seq)
    model = GptSmall(vocab=vocab, max_len=positions)
    if (model.hidden, model.heads, model.mlp_dim) != (hidden, heads, mlp):
        raise ValueError("GptSmall is not at the configuration's widths")
    if layers != model.layers:  # the rehearsal's depth
        model = model.clone(layers=layers)
    opt = config["optimizer"]
    optimizer = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                            eps=opt["eps"], weight_decay=opt["weight_decay"])

    def init(key):
        tokens = jnp.zeros((1, seq), jnp.int32)
        return model.init(key, tokens)["params"], None

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]).mean()
        return loss, {}

    def make_batch(key, n):
        tokens = jax.random.randint(key, (n, seq), 0, vocab, jnp.int32)
        return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    from horovod_tpu.ops.flash_attention import flash_min_seq
    flash = seq >= flash_min_seq()
    per_chip = int(traffic["per_chip_batch"])
    return Job(
        unit="tokens", items_per_example=seq, stateful=False, init=init,
        loss_fn=loss_fn, optimizer=optimizer, make_batch=make_batch,
        model_flops_per_item=flops.gpt_train_flops_per_token(
            layers, hidden, mlp, vocab, seq, causal=True),
        reference_loss=functools.partial(reference_loss, layers=layers,
                                         heads=heads),
        check_leaves=(
            ("Embed_0", "embedding"),
            ("EncoderBlock_0", "FlashSelfAttention_0", "query", "kernel"),
            ("EncoderBlock_0", "Dense_0", "kernel"),
            (f"EncoderBlock_{layers - 1}", "FlashSelfAttention_0", "value",
             "kernel"),
            (f"EncoderBlock_{layers - 1}", "Dense_1", "kernel"),
            ("LayerNorm_0", "scale"),
        ),
        sample_examples=int(traffic.get("reference_examples", 1)),
        tolerance=TOLERANCE,
        flash_call=(per_chip, seq, heads, hidden // heads, True)
        if flash else None,
        flash_layers=layers if flash else 0,
        facts={"layers": layers, "hidden": hidden, "heads": heads,
               "mlp": mlp, "vocab": vocab, "positions": positions,
               "seq_len": seq, "attention": "flash" if flash else "xla"})


# -- the plain reference ------------------------------------------------------

def _layer_norm(x, p, eps=1e-6):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1 + jnp.tanh(
        jnp.sqrt(2 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _causal_attention(q, k, v):
    """[B, T, H, D] each. Scores are taken ``REFERENCE_QUERY_BLOCK`` query
    rows at a time, each block recomputed in the backward pass, so that one
    sequence of 8192 fits beside the job's own state."""
    b, t, h, d = q.shape
    block = min(REFERENCE_QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence {t} is not a multiple of {block}")
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def rows(args):
        start, qb = args  # qb: [B, block, H, D]
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        query_pos = start + jnp.arange(block)
        s = jnp.where(query_pos[:, None] >= key_pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    blocks = q.reshape(b, t // block, block, h, d).swapaxes(0, 1)
    out = jax.lax.map(rows, (jnp.arange(0, t, block), blocks))
    return out.swapaxes(0, 1).reshape(b, t, h, d)


def _block(x, p):
    a = p["FlashSelfAttention_0"]
    h = _layer_norm(x, p["LayerNorm_0"])
    q, k, v = (jnp.einsum("btc,chd->bthd", h, a[n]["kernel"]) + a[n]["bias"]
               for n in ("query", "key", "value"))
    o = _causal_attention(q, k, v)
    x = x + jnp.einsum("bthd,hdc->btc", o, a["out"]["kernel"]) \
        + a["out"]["bias"]
    h = _layer_norm(x, p["LayerNorm_1"])
    h = _gelu_new(h @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"])
    return x + h @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"]


def reference_loss(params, model_state, batch, *, layers, heads):
    """Mean next-token cross-entropy of the pre-LN decoder, float32
    throughout, every matmul at the highest precision."""
    del model_state, heads  # the head count is in the kernels' shapes
    with jax.default_matmul_precision("highest"):
        tokens = batch["tokens"]
        table = params["Embed_0"]["embedding"].astype(jnp.float32)
        x = table[tokens] + \
            params["Embed_1"]["embedding"][:tokens.shape[1]][None]
        for i in range(layers):
            x = jax.checkpoint(_block)(x, params[f"EncoderBlock_{i}"])
        x = _layer_norm(x, params["LayerNorm_0"])
        logits = x @ table.T
        picked = jnp.take_along_axis(
            logits, batch["labels"][..., None], axis=-1)[..., 0]
        return (jax.nn.logsumexp(logits, axis=-1) - picked).mean()
