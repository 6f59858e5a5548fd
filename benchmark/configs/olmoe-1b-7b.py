"""OLMoE-1B-7B: the job the program trains, and its plain float32 reference.

``build(config, traffic)`` returns a ``harness.job.Job``. The job's half is
what a user writes: the repo's flax model (``models/olmoe.py``, whose expert
layer is ``parallel/ep.moe_topk``), an optax optimizer, the model's loss. The
reference's half is this file's own and shares no code with either: the same
equations in plain ``jax.numpy`` and float32, every expert computed densely
for every token and masked by the top-k choice (no sort, no grouped matmul,
no kernel), attention as explicit scores, the same loss with both auxiliary
terms. ``olmoe_train_flops_per_token`` and ``moe_train_flops_per_token`` are
the configuration's own operation counts (``harness/flops.py`` knows dense
decoders only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax

from harness.flops import TRAIN_OVER_FORWARD, attended_pairs
from harness.job import Job, Tolerance

# One layer, so few roundings compound, but the router is a discontinuity
# the dense models do not have. bf16 activations move a router logit by
# about 2**-9 relative; where a token's 8th and 9th logits lie closer than
# that, program and reference choose different experts for the slot (about
# 2% of the tokens by the spacing of 64 logits of spread 0.9), and that
# token's output changes by one expert's weighted contribution, not by a
# rounding. Every gradient leaf is a sum of incoherent per-token terms and
# sees the square root of the share of rows that moved: on the chip every
# leaf reads 4.2-7.4% over 22 seeds, the loss (a mean over 4096 tokens)
# 7.7e-6 to 1.2e-4 (PERF.md §6, PR 26).
#
# The loss's limit is 2.75 times its largest sound reading. A mean over
# tokens resolves no precision; the limit is there for a missing term:
# without the z-loss the loss moves by 1.8e-3, five times the limit. The
# gradients' limit lies between the sound readings and what the program
# gives with the inputs of attention and of the expert layer rounded to
# three significand bits (float8_e4m3's), which has to fail and does:
# 13.5-19.9% on every leaf (forward rounding only; its loss reads 5e-5 to
# 3.7e-4).
#
# What this check does NOT hold the program to is the float32 the
# configuration states for the router: bf16 router logits read 1.0-1.2
# times the policy's errors (4.7-7.5% on every leaf) and pass. The guard of
# the router's precision is the CPU test of the router's choices on equal
# inputs (tests/test_olmoe.py::test_bf16_router_logits_fail_the_policy_
# limits); PERF.md §7 says which edit of run.py would bring it to the chip.
TOLERANCE = Tolerance(
    loss_rtol=3.3e-4, grad_rel_l2=0.10,
    reason="bf16 activations against float32 through a top-8-of-64 router: "
           "near-ties send ~2% of the tokens' 8th slot to another expert, "
           "which every gradient leaf sees (4.2-7.4% over 22 seeds on the "
           "chip, limit 10%; inputs of three significand bits fail); the "
           "loss is a mean over tokens (1.2e-4 at most, limit 3.3e-4; a "
           "missing z-loss moves it 1.8e-3). NOT covered: the router's "
           "float32 (bf16 logits pass here; tests/test_olmoe.py guards it)")

# what the TPU compiler made of one layer's ragged_dots, forward and
# backward, when this file was written: Mosaic calls of its own
# (``ragged-dot-*``). A described fact, no limit: ``correct`` does not read
# it (harness/kernels.py says what it asks), tests/test_olmoe.py does
RAGGED_DOT_CALLS = 11
REFERENCE_QUERY_BLOCK = 1024  # rows of the score matrix the reference holds


def moe_train_flops_per_token(hidden: int, expert_dim: int,
                              experts_per_token: int) -> float:
    """The expert matmuls of one layer, forward and backward: three
    products of hidden x expert_dim multiply-adds in each of the token's
    active experts."""
    return float(TRAIN_OVER_FORWARD * 2 * experts_per_token
                 * 3 * hidden * expert_dim)


def olmoe_train_flops_per_token(layers: int, hidden: int, expert_dim: int,
                                experts: int, experts_per_token: int,
                                vocab: int, seq: int) -> float:
    """Forward + backward matrix work of one token: in each layer q, k, v
    and out (4 h^2), the router (h E), the active experts' three products,
    QK^T and PV over the causal pairs; the untied head (h vocab). The
    embedding is a gather; the experts a token does not visit are no work."""
    attention = 2 * 2 * attended_pairs(seq, causal=True) * hidden / seq
    layer = 2 * (4 * hidden * hidden + hidden * experts) + attention \
        + moe_train_flops_per_token(hidden, expert_dim, experts_per_token) \
        / TRAIN_OVER_FORWARD
    return float(TRAIN_OVER_FORWARD * (layers * layer + 2 * hidden * vocab))


def build(config: dict, traffic: dict) -> Job:
    from horovod_tpu.models import OlmoeDecoder, olmoe_loss
    from horovod_tpu.ops.flash_attention import flash_min_seq

    seq = int(traffic["seq_len"])
    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"seq_len {seq} is past the published context")
    layers = int(config["num_layers"])
    hidden, heads = int(config["hidden_size"]), \
        int(config["num_attention_heads"])
    experts, k = int(config["num_experts"]), \
        int(config["num_experts_per_tok"])
    expert_dim, vocab = int(config["intermediate_size"]), \
        int(config["vocab_size"])
    if config["num_key_value_heads"] != heads or config["norm_topk_prob"] \
            or config["tie_word_embeddings"] or config["attention_bias"] \
            or config["clip_qkv"] is not None \
            or config["hidden_act"] != "silu":
        raise ValueError("OlmoeDecoder is MHA, silu, untied, without "
                         "biases, clipping or renormalised top-k weights")
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    coefs = config["loss"]
    balance_coef = float(coefs["load_balancing_coef"])
    z_coef = float(coefs["router_z_coef"])
    model = OlmoeDecoder(
        vocab=vocab, layers=layers, hidden=hidden, heads=heads,
        experts=experts, experts_per_token=k, expert_dim=expert_dim,
        rope_theta=theta, eps=eps)
    opt = config["optimizer"]
    optimizer = optax.adamw(opt["learning_rate"], b1=opt["b1"], b2=opt["b2"],
                            eps=opt["eps"], weight_decay=opt["weight_decay"])

    def init(key):
        tokens = jnp.zeros((1, seq), jnp.int32)
        return model.init(key, tokens)["params"], None

    def loss_fn(params, batch, rng):
        logits, stats = model.apply({"params": params}, batch["tokens"])
        return olmoe_loss(logits, batch["labels"], stats, k, balance_coef,
                          z_coef)

    def make_batch(key, n):
        tokens = jax.random.randint(key, (n, seq), 0, vocab, jnp.int32)
        return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    flash = seq >= flash_min_seq()
    per_chip = int(traffic["per_chip_batch"])
    last = f"OlmoeBlock_{layers - 1}"
    moe_flops = moe_train_flops_per_token(hidden, expert_dim, k)
    return Job(
        unit="tokens", items_per_example=seq, stateful=False, init=init,
        loss_fn=loss_fn, optimizer=optimizer, make_batch=make_batch,
        model_flops_per_item=olmoe_train_flops_per_token(
            layers, hidden, expert_dim, experts, k, vocab, seq),
        reference_loss=functools.partial(
            reference_loss, layers=layers, heads=heads, k=k, eps=eps,
            theta=theta, balance_coef=balance_coef, z_coef=z_coef),
        check_leaves=(
            ("OlmoeBlock_0", "OlmoeSparseMoe_0", "router"),
            ("OlmoeBlock_0", "OlmoeSparseMoe_0", "gate_proj"),
            ("OlmoeBlock_0", "OlmoeSparseMoe_0", "up_proj"),
            (last, "OlmoeSparseMoe_0", "down_proj"),
            ("OlmoeBlock_0", "OlmoeAttention_0", "q_norm", "scale"),
            (last, "OlmoeAttention_0", "v_proj", "kernel"),
            ("Embed_0", "embedding"),
            ("LmHead", "kernel"),
        ),
        sample_examples=int(traffic.get("reference_examples", 1)),
        tolerance=TOLERANCE,
        flash_call=(per_chip, seq, heads, hidden // heads, True)
        if flash else None,
        flash_layers=layers if flash else 0,
        # described, not required (harness/job.py): the three flash kernels
        # of each layer and the compiler's calls for its ragged_dots
        expected_custom_calls=(3 * flash + RAGGED_DOT_CALLS) * layers,
        facts={"layers": layers, "hidden": hidden, "heads": heads,
               "head_dim": hidden // heads, "experts": experts,
               "experts_per_token": k, "expert_dim": expert_dim,
               "vocab": vocab, "seq_len": seq, "tied_head": False,
               "attention": "flash" if flash else "xla",
               "moe_train_flops_per_token_per_layer": moe_flops})


# -- the plain reference ------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate_half(x, theta):
    """[B, T, H, D]: pairs (i, i + D/2) turned by position x theta^(-2i/D)."""
    t, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2) / d)
    angle = (jnp.arange(t)[:, None] * freq[None, :])[None, :, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)], -1)


def _causal_attention(q, k, v):
    """[B, T, H, D] each, explicit scores, ``REFERENCE_QUERY_BLOCK`` query
    rows at a time against the whole context."""
    b, t, h, d = q.shape
    block = min(REFERENCE_QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence {t} is not a multiple of {block}")
    key_pos = jnp.arange(t)

    @jax.checkpoint
    def rows(args):
        start, qb = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * d ** -0.5
        query_pos = start + jnp.arange(block)
        s = jnp.where(query_pos[:, None] >= key_pos[None, :], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)

    blocks = q.reshape(b, t // block, block, h, d).swapaxes(0, 1)
    out = jax.lax.map(rows, (jnp.arange(0, t, block), blocks))
    return out.swapaxes(0, 1).reshape(b, t, h, d)


def _dense_experts(x, p, k):
    """Every expert for every token, weighted by the router's probability
    where the expert is among the token's top k and by zero elsewhere.
    x: [T, d]. Returns (out [T, d], chosen [T, k], counts [E], mean
    probabilities [E], mean squared logsumexp of the logits)."""
    logits = x @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    weights, chosen = jax.lax.top_k(probs, k)
    n_experts = probs.shape[-1]
    picked = chosen[:, :, None] == jnp.arange(n_experts)[None, None, :]
    gate = jnp.sum(jnp.where(picked, weights[:, :, None], 0.0), axis=1)

    @jax.checkpoint
    def expert(args):
        w_gate, w_up, w_down, g = args
        return g[:, None] * ((jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down)

    out = jax.lax.map(expert, (p["gate_proj"], p["up_proj"], p["down_proj"],
                               gate.T)).sum(axis=0)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return out, chosen, picked.sum(axis=(0, 1)), probs.mean(axis=0), z


def _block(x, p, *, heads, k, eps, theta):
    b, t, d = x.shape
    a = p["OlmoeAttention_0"]
    h = _rms_norm(x, p["input_layernorm"]["scale"], eps)
    q = _rms_norm(h @ a["q_proj"]["kernel"], a["q_norm"]["scale"], eps)
    key = _rms_norm(h @ a["k_proj"]["kernel"], a["k_norm"]["scale"], eps)
    v = h @ a["v_proj"]["kernel"]
    q, key, v = (y.reshape(b, t, heads, d // heads) for y in (q, key, v))
    o = _causal_attention(_rotate_half(q, theta), _rotate_half(key, theta),
                          v)
    x = x + o.reshape(b, t, d) @ a["o_proj"]["kernel"]
    h = _rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    out, chosen, counts, probs, z = _dense_experts(h.reshape(b * t, d),
                                                   p["OlmoeSparseMoe_0"], k)
    return x + out.reshape(b, t, d), (chosen, counts, probs, z)


def reference_forward(params, batch, *, layers, heads, k, eps, theta,
                      balance_coef, z_coef):
    """(loss, routing) in float32, every matmul at the highest precision.
    ``routing``: the experts each token chose, [layers, tokens, k], and the
    pairs each expert received, [layers, E]."""
    with jax.default_matmul_precision("highest"):
        tokens = batch["tokens"]
        x = params["Embed_0"]["embedding"].astype(jnp.float32)[tokens]
        routing = []
        for i in range(layers):
            x, found = jax.checkpoint(functools.partial(
                _block, heads=heads, k=k, eps=eps, theta=theta))(
                    x, params[f"OlmoeBlock_{i}"])
            routing.append(found)
        chosen, counts, probs, z = (jnp.stack(r) for r in zip(*routing))
        x = _rms_norm(x, params["norm"]["scale"], eps)
        logits = x @ params["LmHead"]["kernel"]
        picked = jnp.take_along_axis(
            logits, batch["labels"][..., None], axis=-1)[..., 0]
        ce = (jax.nn.logsumexp(logits, axis=-1) - picked).mean()
        # transformers' load_balancing_loss_func: the layers' tokens taken
        # together; E x sum over experts of selected share x mean probability
        n_experts = counts.shape[-1]
        share = counts.sum(axis=0) / (layers * tokens.size)
        balance = n_experts * jnp.sum(share * probs.mean(axis=0))
        loss = ce + balance_coef * balance + z_coef * z.mean()
        return loss, {"chosen": chosen, "expert_tokens": counts}


def reference_loss(params, model_state, batch, **sizes):
    del model_state
    return reference_forward(params, batch, **sizes)[0]
