"""Trinity-Mini, one chip's share of published layers 1-5: the job the program
trains, its plain float32 reference, and its operation counts.

``build(config, traffic)`` returns a ``harness.job.Job``. The job's half is
what a user writes: the repo's flax model (``models/trinity.py``: output-gated
grouped-query attention with per-head q/k norm through
``ops/flash_attention.attention``, window layers with rotary beside full
layers without positions, four norms a layer, the dense SwiGLU feed-forward,
the expert share of ``parallel/ep.moe_dropless`` beside a shared expert), an
optax optimizer, the model's loss, through ``dp.make_stateful_train_step``
because the routers' expert biases are state.

The reference's half is this file's own and shares no code with ``models/``,
``ops/`` or ``parallel/ep.py``: the equations of the configuration's
``assumed`` in plain ``jax.numpy`` and float32 at the highest matmul
precision. The embedding's rows times ``sqrt(hidden)``; per-head q/k norm and
rotate-half rotary are written out here; attention is explicit scores under
an explicit mask (``0 <= i - j`` on a full layer, ``0 <= i - j < window`` on
a sliding one), ``REFERENCE_QUERY_BLOCK`` query rows at a time against the
whole context with the key heads repeated; the gate is a fifth product of the
normed input whose sigmoid multiplies the attention's output; a branch's
output is normed before it is added; the experts are computed densely for
every token and masked by the choice (no sort, no grouped matmul), **over the
same held experts only**, beside the shared one; the cross-entropy in blocks
of rows over the same vocabulary slice; the same bias rule. Departures from
the published code (``modeling_afmoe.py``), each in the program and in the
reference alike:

- the held experts' part of the sum goes on to the next layer, not all 128
  experts' (``deployment``); the vocabulary is its first 25 024 rows;
- ``expert_bias`` is moved by the rule of ``assumed.expert_bias_rule`` at the
  start of a training call; the published code holds it as a buffer and
  leaves its training to the trainer.

``trinity_forward_flops_per_token`` is the configuration's own model FLOP
count (``harness/flops.py`` knows dense decoders only); the sliding layers'
scores count ``harness/window.window_pairs``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import optax

from harness.flops import TRAIN_OVER_FORWARD, attended_pairs
from harness.job import Job, Tolerance
from harness.window import window_pairs

# The readings of each limit, through run.py's own comparison on the chip
# (my chip runs, PR 52; PERF.md §6): relative L2 of a gradient leaf against
# the float32 reference and relative error of the loss; the largest leaf of a
# seed. Sound: the program, 9 seeds of ``reference_control.py`` and the checks
# of 29 whole runs: 38 seeds. Control: the reference one precision below the
# stated one (float8_e4m3's 3 mantissa bits into every product, bf16 into
# the routers; ``control_job``), 9 seeds. Router: the float32 reference with
# every router on the layer's normed input, before attention, where
# ``smallthinker-21b-a3b``'s reads (``router_control_job``,
# ``reference_router.py``), 2 seeds: with the branches at 1% of the stream
# (``cell_start``) the mildest fault a port can make.
#                                  sound        control        router
#   leaves off the routers' path   2.65-3.31%   10.08-12.17%   5.22-5.26%
#     (attention with its gate and q/k norms, the four norms, the dense and
#      the shared feed-forward, embedding, head)        limit 5.4%
#   the held experts' matrices     7.81-9.37%   10.20-10.89%   14.94-14.97%
#                                                       limit 11.5%
#   the routers' weights           9.7-13.1%    12.4-14.4%     18.8-19.5%
#                                                       limit 16%
#   the loss                       9e-8-6.8e-6  2.8e-5-1.1e-4  9e-8-2.7e-7
#                                                       limit 1.7e-5
# The control is never correct: it fails the first limit (1.63 x over the
# largest sound reading, 1.87 x under the smallest control one) and the loss
# (2.5 x over, 1.64 x under) on every seed. On the routers' path the
# precision below gives no upper reading: four sigmoid routers choose 8 of
# 128, bf16 activations move a score by about 2**-9 relative, and where a
# token's 8th and 9th scores lie closer than that program and reference send
# the slot to different experts, which the held experts' gradients and the
# routers' see; the control's float8 products and bf16 router move hardly
# more slots than that (its experts read 1.09 x the largest sound seed, its
# routers inside the sound range), and a limit squeezed between would fail a
# sound seed of the driver's sooner than a fault. Those two limits lie
# between the sound readings and the ROUTER's: 1.23 x over and 1.30 x under
# (experts), 1.22 x over and 1.18 x under (routers); the control passes them
# and a router on another stream fails both. A router on the UN-NORMED stream
# reads 3.9-4.0% on the routers and under 3% elsewhere (2 seeds,
# ``router_reads="stream"``): at these widths the stream's root mean square
# is 0.905, by accident the norm's, and no limit of this cell holds it;
# ``tests/test_trinity.py`` does. With the post-norms' scales at 1 (the
# model's own start, before ``cell_start``; 7 seeds, PR 52's first call) the
# three classes read 6.7-8.1% / 16.4-18.6% / 20.2-27.9% against a control of
# 29.4-34.3% / 50.7-54.5% / 62.0-78.7%.
TOLERANCE = Tolerance(
    loss_rtol=1.7e-5, grad_rel_l2=0.054,
    grad_rel_l2_under={"router": 0.16, "experts": 0.115},
    reason="bf16 activations against float32 through five output-gated "
           "attention operators and four sigmoid top-8-of-128 routers over a "
           "share of 16 experts (some 1040 rows each): near-ties move a few "
           "rows of a held expert, which its gradient and the router's see. "
           "38 sound seeds on the chip; the same equations one precision "
           "lower on 9 (float8_e4m3's 3 mantissa bits into every product, "
           "bf16 into the routers; reference_control.py); every router on "
           "the layer's input on 2 (reference_router.py). Leaves off the "
           "routers' path 2.65-3.31% against 10.08-12.17% lower, limit 5.4%; "
           "the loss 6.8e-6 at most against 2.8e-5 at least, limit 1.7e-5: "
           "the lower precision fails both on every seed. The held experts "
           "7.81-9.37% (lower precision 10.20-10.89%: 1.09 x, no room "
           "between) against 14.9% under the other router, limit 11.5%; the "
           "routers' weights 9.7-13.1% (lower precision 12.4-14.4%: inside) "
           "against 18.8-19.5%, limit 16%")

REFERENCE_QUERY_BLOCK = 64    # rows of scores, and of logits, held at once
SLIDING, FULL = "sliding_attention", "full_attention"


# -- operation counts ------------------------------------------------------------

def trinity_forward_flops_per_token(
        layer_types, num_dense_layers: int, hidden: int, heads: int,
        kv_heads: int, head_dim: int, dense_dim: int, experts: int,
        experts_per_token: int, held: int, expert_dim: int,
        shared_experts: int, vocab: int, seq: int, window: int) -> dict:
    """Forward matrix work of one token by part, in FLOPs. An attention
    operator: q, k, v and o; the gate's projection, as wide as q; QK^T and
    PV over the pairs its mask leaves (causal on a full layer, the window's
    on a sliding one). The dense feed-forward: three products. A sparse one:
    the router over all experts, the shared expert, and the held experts'
    three products for the ``k held / experts`` pairs a token sends them
    under a uniform router (the rows a share really sees are
    data-dependent). The sliced head. The embedding is a gather; norms,
    rotary, the gate's sigmoid and product are element-wise."""
    q_dim = heads * head_dim
    parts = {
        "attention_projections": 2.0 * hidden * (2 * q_dim + 2 * kv_heads
                                                 * head_dim),
        "gate_projection": 2.0 * hidden * q_dim,
        "full_scores": 2.0 * 2 * attended_pairs(seq, True) * q_dim / seq,
        "window_scores": 2.0 * 2 * window_pairs(seq, window) * q_dim / seq,
        "dense_feed_forward": 2.0 * 3 * hidden * dense_dim,
        "router": 2.0 * hidden * experts,
        "shared_expert": 2.0 * 3 * hidden * shared_experts * expert_dim,
        "held_experts": 2.0 * 3 * hidden * expert_dim
        * experts_per_token * held / experts,
        "head": 2.0 * hidden * vocab,
    }
    layers = len(layer_types)
    windows = sum(1 for kind in layer_types if kind == SLIDING)
    dense = min(num_dense_layers, layers)
    return {
        "projections": layers * parts["attention_projections"],
        "gate_projections": layers * parts["gate_projection"],
        "full_attention": (layers - windows) * parts["full_scores"],
        "window_attention": windows * parts["window_scores"],
        "dense": dense * parts["dense_feed_forward"],
        "experts": (layers - dense) * (
            parts["router"] + parts["shared_expert"]
            + parts["held_experts"]),
        "head": parts["head"],
        "parts": parts,
    }


KINDS = ("projections", "gate_projections", "full_attention",
         "window_attention", "dense", "experts", "head")
POST_NORMS = ("post_attention_layernorm", "post_mlp_layernorm")
POST_NORM_START = 0.01


def cell_start(params):
    """Where the cell's weights start, from the model's own initialisation
    (every matrix normal 0.02, every norm's scale 1); the configuration's
    ``assumed.initialisation`` says why. The two norms of a block that norm a
    BRANCH's output start at ``POST_NORM_START`` and not at 1: a post-norm
    brings whatever its branch computes to a mean square of 1, and over
    random weights what attention computes is an average of values that
    neighbouring positions share, so at 1 half of the stream a router reads
    is a vector common to thousands of tokens, the routers send their
    favourite experts 3-6 times a balanced share, and which chip holds those
    is an accident of the seed that decides the walk's tiles and the step
    time (PERF.md §6, PR 52). With the branches small a token's state is its
    own embedding and the tokens spread evenly, as over a trained router they
    do."""
    return {name: {key: {"scale": leaf["scale"] * POST_NORM_START}
                   if key in POST_NORMS else leaf
                   for key, leaf in block.items()}
            if name.startswith("TrinityBlock_") else block
            for name, block in params.items()}


def build(config: dict, traffic: dict) -> Job:
    from horovod_tpu.models import TrinityDecoder, trinity_loss
    from horovod_tpu.ops.flash_attention import flash_min_seq

    seq = int(traffic["seq_len"])
    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"seq_len {seq} is past the published context")
    layer_types = tuple(config["layer_types"])
    layers = int(config["num_layers"])
    if len(layer_types) != layers:
        raise ValueError(f"layer_types {layer_types} has not num_layers = "
                         f"{layers} entries")
    if config["score_func"] != "sigmoid" or not config["route_norm"] \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["rope_scaling"] is not None \
            or config["tie_word_embeddings"] or not config["mup_enabled"] \
            or config["hidden_act"] != "silu" \
            or config["model_type"] != "afmoe":
        raise ValueError(
            "TrinityDecoder is a scaled embedding, unscaled rotary on the "
            "sliding layers, sigmoid top-k SwiGLU experts in one group "
            "renormalised under an expert bias, and an untied head")
    held = (int(config["experts_held"]["first"]), int(config["num_experts"]))
    sizes = dict(
        num_dense_layers=int(config["num_dense_layers"]),
        hidden=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        dense_dim=int(config["intermediate_size"]),
        experts=int(config["experts_held"]["of"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_dim=int(config["moe_intermediate_size"]),
        shared_experts=int(config["num_shared_experts"]),
        vocab=int(config["vocab_size"]))
    window = int(config["sliding_window"])
    theta = float(config["rope_theta"])
    eps = float(config["rms_norm_eps"])
    scale = float(config["route_scale"])
    rate = float(config["load_balance_coeff"])
    recompute = config["recompute"]["policy"]
    model = TrinityDecoder(
        layer_types=layer_types, route_scale=scale, load_balance_coeff=rate,
        window=window, rope_theta=theta, experts_held=held, eps=eps,
        remat=recompute, **sizes)
    opt = config["optimizer"]
    warmup = int(opt["warmup_steps"])

    def learning_rate(step):  # linear warm-up to the peak, then constant
        return opt["learning_rate"] * jnp.minimum(1.0, (step + 1) / warmup)
    optimizer = optax.adamw(learning_rate, b1=opt["b1"], b2=opt["b2"],
                            eps=opt["eps"], weight_decay=opt["weight_decay"])

    def init(key):
        variables = model.init(key, jnp.zeros((1, seq), jnp.int32))
        return cell_start(variables["params"]), variables["router_state"]

    def loss_fn(params, model_state, batch, rng):
        return trinity_loss(model, params, model_state, batch["tokens"],
                            batch["labels"])

    def make_batch(key, n):
        tokens = jax.random.randint(key, (n, seq), 0, sizes["vocab"],
                                    jnp.int32)
        return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    flash = seq >= flash_min_seq()
    per_chip = int(traffic["per_chip_batch"])
    forward = trinity_forward_flops_per_token(
        layer_types, held=held[1], seq=seq, window=window, **sizes)
    fulls = [i for i, kind in enumerate(layer_types) if kind == FULL]
    windows = [i for i, kind in enumerate(layer_types) if kind == SLIDING]
    sparse = list(range(min(sizes["num_dense_layers"], layers), layers))

    def block(i, *leaf):
        return (f"TrinityBlock_{i}",) + leaf

    def attention(i, name, leaf="kernel"):
        return block(i, "TrinityAttention_0", name, leaf)
    check_leaves = [
        *(attention(i, name) for i in (fulls[0], windows[-1])
          for name in ("q_proj", "k_proj", "gate_proj", "o_proj")),
        attention(0, "gate_proj"), attention(0, "v_proj"),
        attention(fulls[0], "q_norm", "scale"),
        attention(windows[-1], "k_norm", "scale"),
        block(0, "mlp", "gate_proj", "kernel"),
        block(0, "mlp", "down_proj", "kernel"),
        block(0, "post_attention_layernorm", "scale"),
        block(sparse[0], "post_mlp_layernorm", "scale"),
        block(sparse[-1], "post_attention_layernorm", "scale"),
        block(sparse[-1], "pre_mlp_layernorm", "scale"),
        block(sparse[0], "TrinityMoE_0", "router", "weight"),
        block(sparse[-1], "TrinityMoE_0", "router", "weight"),
        block(sparse[0], "TrinityMoE_0", "experts", "gate"),
        block(sparse[len(sparse) // 2], "TrinityMoE_0", "experts", "up"),
        block(sparse[-1], "TrinityMoE_0", "experts", "down"),
        block(sparse[0], "TrinityMoE_0", "shared_experts", "up_proj",
              "kernel"),
        ("embed_tokens", "embedding"), ("lm_head", "kernel"),
        ("norm", "scale")]
    facts = {
        # every block; moe_experts_mfu multiplies its per-layer count by it
        "layers": layers, "layer_types": list(layer_types), **sizes,
        "window": window, "experts_held": list(held), "seq_len": seq,
        "tied_head": False, "recompute": recompute,
        "post_norm_start": POST_NORM_START,
        "attention": "flash" if flash else "xla",
        "forward_mflops_per_token": {k: forward[k] / 1e6 for k in KINDS},
        "full_layers": len(fulls), "window_layers": len(windows),
        # the held experts' three products, forward and backward, for the
        # pairs a uniform router sends them, of the sparse layers, spread
        # over every block (harness/moe.experts_mfu multiplies by "layers")
        "moe_train_flops_per_token_per_layer":
            TRAIN_OVER_FORWARD * forward["parts"]["held_experts"]
            * len(sparse) / layers,
        # what the gate's product must move a pass: the kernels' output and
        # the gate read, the product written (harness/outgate.py states it
        # beside the measured time, as a fact and not as a share)
        "outgate_mul_bytes_per_layer_pass":
            3 * per_chip * seq * sizes["heads"] * sizes["head_dim"] * 2}
    if flash:
        facts["window_call"] = [per_chip, seq, sizes["heads"],
                                sizes["head_dim"], window]
    return Job(
        unit="tokens", items_per_example=seq, stateful=True, init=init,
        loss_fn=loss_fn, optimizer=optimizer, make_batch=make_batch,
        model_flops_per_item=float(
            TRAIN_OVER_FORWARD * sum(forward[k] for k in KINDS)),
        reference_loss=functools.partial(
            reference_loss, layer_types=layer_types, window=window,
            theta=theta, held=held, eps=eps, scale=scale, rate=rate,
            **{k: sizes[k] for k in ("num_dense_layers", "heads", "kv_heads",
                                     "head_dim", "experts_per_token")}),
        # a short stack (the rehearsal) names a layer twice
        check_leaves=tuple(dict.fromkeys(check_leaves)),
        sample_examples=int(traffic.get("reference_examples", 1)),
        tolerance=TOLERANCE,
        # the full layers alone run under the names of flops.FLASH_PRODUCTS
        flash_call=(per_chip, seq, sizes["heads"], sizes["head_dim"], True)
        if flash else None,
        flash_layers=len(fulls) if flash else 0,
        facts=facts)


# -- the plain reference ------------------------------------------------------

# Mantissa bits a matrix product's inputs keep. ``None`` is the reference:
# float32 throughout. The control computes the same equations one precision
# below what the configuration's ``dtype_policy`` states: float8_e4m3's 3
# bits where it states bf16's 7 (every product's inputs but the router's),
# bf16's 7 where it states float32 (the router's logits).
BELOW_BF16_BITS = 3
BELOW_FLOAT32_BITS = 7


def _kept(x, bits):
    """``x`` rounded to ``bits`` explicit mantissa bits (to nearest, ties to
    even) at float32's range, which is what a scaled float8 tensor keeps;
    the rounding is passed straight through in backward, so a product's
    gradients are those of its rounded inputs, accumulated in float32."""
    if bits is None:
        return x
    drop = 23 - bits
    i = jax.lax.bitcast_convert_type(x, jnp.uint32)
    i = (i + jnp.uint32((1 << (drop - 1)) - 1) + ((i >> drop) & 1)) \
        & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
    return x + jax.lax.stop_gradient(
        jax.lax.bitcast_convert_type(i, jnp.float32) - x)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rotate_half(x, theta):
    """[B, T, H, D] at positions 0 .. T-1: pairs (x_i, x_{i + D/2}) turned
    by ``t theta^(-2i/D)``."""
    t, d = x.shape[1], x.shape[-1]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _masked_attention(q, k, v, window, sliding, bits=None):
    """[B, T, H, D] each, explicit scores and an explicit mask,
    ``REFERENCE_QUERY_BLOCK`` query rows at a time against the whole
    context: ``0 <= i - j``, and ``i - j < window`` where the layer is
    ``sliding`` (a flag that may be traced: the layers share this code)."""
    b, t, h, d = q.shape
    block = min(REFERENCE_QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence {t} is not a multiple of {block}")
    key_pos = jnp.arange(t)
    k, v = _kept(k, bits), _kept(v, bits)

    @jax.checkpoint
    def rows(args):
        start, qb = args
        s = jnp.einsum("bqhd,bkhd->bhqk", _kept(qb, bits), k) * d ** -0.5
        ahead = (start + jnp.arange(block))[:, None] - key_pos[None, :]
        seen = (ahead >= 0) & ((ahead < window) | jnp.logical_not(sliding))
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          _kept(jax.nn.softmax(s, axis=-1), bits), v)

    blocks = q.reshape(b, t // block, block, h, d).swapaxes(0, 1)
    out = jax.lax.map(rows, (jnp.arange(0, t, block), blocks))
    return out.swapaxes(0, 1).reshape(b, t, h, d)


def _attention(x, p, *, heads, kv_heads, head_dim, theta, window, sliding,
               eps, bits, gated=True):
    """The module text's attention operator on the normed input ``x``: per-head
    q/k norm, rotary and the window on a ``sliding`` layer alone, the gate
    on the output before ``o_proj``."""
    b, t, _ = x.shape
    x = _kept(x, bits)
    q, k, v = ((x @ _kept(p[name]["kernel"], bits)).reshape(b, t, n, head_dim)
               for name, n in (("q_proj", heads), ("k_proj", kv_heads),
                               ("v_proj", kv_heads)))
    q = _rms_norm(q, p["q_norm"]["scale"], eps)
    k = _rms_norm(k, p["k_norm"]["scale"], eps)
    q, k = (jnp.where(sliding, _rotate_half(a, theta), a) for a in (q, k))
    k, v = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (k, v))
    o = _masked_attention(q, k, v, window, sliding, bits).reshape(
        b, t, heads * head_dim)
    if gated:
        o = o * jax.nn.sigmoid(x @ _kept(p["gate_proj"]["kernel"], bits))
    return _kept(o, bits) @ _kept(p["o_proj"]["kernel"], bits)


def _swiglu(x, w_gate, w_up, w_down, bits):
    w_gate, w_up, w_down = (_kept(w, bits) for w in (w_gate, w_up, w_down))
    return _kept(jax.nn.silu(x @ w_gate) * (x @ w_up), bits) @ w_down


def _feed_forward(x, p, *, bits):
    """A dense SwiGLU: a leading layer's, and a sparse layer's shared
    expert."""
    return _swiglu(_kept(x, bits), p["gate_proj"]["kernel"],
                   p["up_proj"]["kernel"], p["down_proj"]["kernel"], bits)


def _routing(x, w_router, bias, experts_per_token, scale, bits=None):
    """[T, E] float32: the sigmoid scores of a token's chosen experts over
    their sum, times ``scale``, zero elsewhere; the choice is the top k of
    score + bias, and the bias is in nothing else. And the choice [T, k],
    and the pairs each expert was sent [E]."""
    scores = jax.nn.sigmoid(
        _kept(x.reshape(-1, x.shape[-1]), bits) @ _kept(w_router, bits))
    chosen = jax.lax.top_k(jax.lax.stop_gradient(scores + bias),
                           experts_per_token)[1]
    picked = (chosen[:, :, None] == jnp.arange(scores.shape[-1])).any(axis=1)
    dense = jnp.where(picked, scores, 0.0)
    return dense / (dense.sum(-1, keepdims=True) + 1e-20) * scale, chosen, \
        picked.sum(axis=0).astype(jnp.float32)


def _experts(x, p, dense, held, bits):
    """The held SwiGLU experts for every token, weighted by ``dense`` [T, E]
    (zero where the expert is not among the token's chosen)."""
    b, t, d = x.shape
    tokens = _kept(x.reshape(b * t, d), bits)
    first, count = held

    @jax.checkpoint
    def expert(args):
        w_gate, w_up, w_down, g = args
        return g[:, None] * _swiglu(tokens, w_gate, w_up, w_down, bits)

    # one expert at a time into one sum: no [experts, T, d] stack
    out, _ = jax.lax.scan(
        lambda total, args: (total + expert(args), None),
        jnp.zeros_like(tokens),
        (p["gate"], p["up"], p["down"], dense[:, first:first + count].T))
    return out.reshape(b, t, d)


def _cross_entropy(x, w_head, labels, bits=None):
    """Mean next-token cross-entropy, ``REFERENCE_QUERY_BLOCK`` positions
    of float32 logits at a time."""
    d = x.shape[-1]
    rows, w_head = _kept(x.reshape(-1, d), bits), _kept(w_head, bits)
    block = min(REFERENCE_QUERY_BLOCK, rows.shape[0])
    if rows.shape[0] % block:
        raise ValueError(f"{rows.shape[0]} positions are not a multiple "
                         f"of {block}")

    @jax.checkpoint
    def block_sum(args):
        h, y = args
        logits = h @ w_head
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return (jax.nn.logsumexp(logits, axis=-1) - picked).sum()
    sums = jax.lax.map(block_sum, (rows.reshape(-1, block, d),
                                   labels.reshape(-1, block)))
    return sums.sum() / rows.shape[0]


def _layer(x, p, state, *, sliding, sparse, window, theta, held, eps, scale,
           rate, heads, kv_heads, head_dim, experts_per_token, bits,
           router_bits, gated=True, post_norms=True, router_reads="pre_mlp"):
    """One layer of the module text's equations: (the layer's output, its
    new state, the experts each token chose [T, k] or None). ``gated`` and
    ``post_norms`` False, and a router that reads the layer's normed input
    (``router_reads`` ``"layer_input"``: before attention, where a sibling
    architecture's reads) or the un-normed stream (``"stream"``) and not
    the pre-MLP norm's output, are what the model is NOT, for the tests and
    the reading (``router_control_job``) that tell them apart."""
    def after(branch, name):
        return _rms_norm(branch, p[name]["scale"], eps) if post_norms \
            else branch
    h = _rms_norm(x, p["input_layernorm"]["scale"], eps)
    x = x + after(jax.checkpoint(functools.partial(
        _attention, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        theta=theta, window=window, sliding=sliding, eps=eps, bits=bits,
        gated=gated))(h, p["TrinityAttention_0"]),
        "post_attention_layernorm")
    u = _rms_norm(x, p["pre_mlp_layernorm"]["scale"], eps)
    feed_forward = jax.checkpoint(functools.partial(_feed_forward, bits=bits))
    if not sparse:
        return x + after(feed_forward(u, p["mlp"]), "post_mlp_layernorm"), \
            None, None
    moe, router = p["TrinityMoE_0"], state["TrinityMoE_0"]["router"]
    bias = router["expert_bias"] + rate * jnp.sign(
        router["load"].mean() - router["load"])
    seen = {"pre_mlp": u, "layer_input": h, "stream": x}[router_reads]
    dense, chosen, load = _routing(seen, moe["router"]["weight"], bias,
                                   experts_per_token, scale, router_bits)
    routed = jax.checkpoint(functools.partial(
        _experts, held=held, bits=bits))(u, moe["experts"], dense)
    out = x + after(routed + feed_forward(u, moe["shared_experts"]),
                    "post_mlp_layernorm")
    return out, {"TrinityMoE_0": {"router": {
        "expert_bias": bias, "load": load}}}, chosen


def reference_forward(params, model_state, batch, *, layer_types,
                      num_dense_layers, lowered=False, embedding_scale=None,
                      **sizes):
    """(loss, new model state, the experts each token chose [T, k] for each
    sparse layer, the final norm's output [B, T, d]) in float32, every
    matmul at the highest precision.
    ``lowered`` is the control, never the reference: the inputs of every
    product rounded to the precision below the one ``dtype_policy`` states
    for them (``BELOW_BF16_BITS``, the router's ``BELOW_FLOAT32_BITS``). Each
    layer is recomputed in backward from its input; the run of sparse layers
    is one scanned body that picks its weights out of the run by the layer's
    number and takes the layer's kind as data: written out layer by layer,
    the gradient of this function would be a program several times the size
    in the compile cache (PERF.md §6, PR 38). ``embedding_scale`` other than
    ``sqrt(hidden)`` is what the model is NOT, for the test that tells them
    apart."""
    bits = BELOW_BF16_BITS if lowered else None
    router_bits = BELOW_FLOAT32_BITS if lowered else None
    new_state = {}
    layers = len(layer_types)
    dense = min(num_dense_layers, layers)

    def body(sliding, sparse):
        return functools.partial(_layer, sliding=sliding, sparse=sparse,
                                 bits=bits, router_bits=router_bits, **sizes)
    with jax.default_matmul_precision("highest"):
        embedding = params["embed_tokens"]["embedding"].astype(jnp.float32)
        if embedding_scale is None:
            embedding_scale = embedding.shape[-1] ** 0.5
        x = embedding[batch["tokens"]] * embedding_scale
        for i in range(dense):
            x, _, _ = jax.checkpoint(body(layer_types[i] == SLIDING, False))(
                x, params[f"TrinityBlock_{i}"], {})
        names = [f"TrinityBlock_{i}" for i in range(dense, layers)]
        chosen = []
        if names:
            trees = ([params[name] for name in names],
                     [model_state[name] for name in names])
            slides = jnp.asarray([kind == SLIDING
                                  for kind in layer_types[dense:]])

            @jax.checkpoint
            def layer(x, i):
                # one layer's copy of the weights at a time, no stack
                p, state = (jax.tree_util.tree_map(
                    lambda *leaves: jax.lax.select_n(i, *leaves), *tree)
                    if len(names) > 1 else tree[0] for tree in trees)
                out, layer_state, layer_chosen = body(slides[i], True)(
                    x, p, state)
                return out, (layer_state, layer_chosen)

            x, (stacked, run_chosen) = jax.lax.scan(
                layer, x, jnp.arange(len(names)))
            for j, name in enumerate(names):
                new_state[name] = jax.tree_util.tree_map(
                    lambda leaf: leaf[j], stacked)
            chosen.extend(run_chosen)
        x = _rms_norm(x, params["norm"]["scale"], sizes["eps"])
        loss = _cross_entropy(x, params["lm_head"]["kernel"],
                              batch["labels"], bits)
        return loss, new_state, chosen, x


def reference_loss(params, model_state, batch, **sizes):
    return reference_forward(params, model_state, batch, **sizes)[0]


def _in_the_programs_place(job: Job, **what) -> Job:
    def loss_fn(params, model_state, batch, rng):
        return job.reference_loss(params, model_state, batch, **what), \
            (model_state, ())
    return dataclasses.replace(job, loss_fn=loss_fn)


def control_job(job: Job) -> Job:
    """``job`` with the lowered reference in the program's place: what
    ``benchmark/reference_control.py`` hands the harness's own comparison,
    which has to call it not correct (``TOLERANCE`` has the readings)."""
    return _in_the_programs_place(job, lowered=True)


def router_control_job(job: Job) -> Job:
    """``job`` with the float32 reference in the program's place, every
    router reading the layer's normed input and not the pre-MLP norm's
    output, and nothing else changed: the upper reading of the routers'
    limit, which the precision below does not give (``TOLERANCE``).
    ``benchmark/reference_router.py`` hands it to the harness's own
    comparison."""
    return _in_the_programs_place(job, router_reads="layer_input")

