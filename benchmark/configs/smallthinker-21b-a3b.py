"""SmallThinker-21BA3B-Instruct, one chip's share of two periods: the job
the program trains, its plain float32 reference, and its operation counts.

``build(config, traffic)`` returns a ``harness.job.Job``. The job's half is
what a user writes: the repo's flax model (``models/smallthinker.py``: the
window and the causal flash kernels of ``ops/flash_attention.py`` side by
side, a routing made from the layer's input by ``parallel/ep.moe_routing``,
the expert share of ``parallel/ep.moe_dropless``), an optax optimizer, the
model's loss, through ``dp.make_train_step``. The reference's half is this
file's own and shares no code with either: the equations of the
configuration's ``assumed`` and ``deployment`` in plain ``jax.numpy`` and
float32 at the highest matmul precision. Its mask is explicit
(``0 <= i - j`` on a full layer, ``0 <= i - j < window`` on a window layer),
attention is scores in blocks of query rows against the whole context with
the key heads repeated, rotary is written out here, the router reads the
layer's input, the experts are computed densely for every token and masked
by the choice (no sort, no grouped matmul), **over the same held experts
only** and over the same vocabulary slice, the cross-entropy in blocks of
rows.

``smallthinker_forward_flops_per_token`` is the configuration's own model
FLOP count (``harness/flops.py`` knows dense decoders only); the window
layers' scores count ``harness/window.window_pairs``.
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

# Both readings of each limit, through run.py's own comparison on the chip
# (``benchmark/reference_control.py``; PERF.md §6, PR 38): relative L2 of a
# gradient leaf and relative error of the loss.
#
# Sound, the program against the reference, 23 seeds (19 whole runs of the
# cell, 4 of the tool): the leaves off the routers' path 1.18-1.35% (mean
# 1.23, s.d. 0.04: what bf16 does to every token alike); the held experts'
# matrices 7.5-9.1% (mean 8.3, s.d. 0.41) and the routers' weights 6.4-9.5%
# (mean 8.1, s.d. 0.82): top-6 of 64 logits whose spread is 0.02, so bf16
# activations move near-ties between experts, a few of an expert's 1536
# rows move, and its gradient and the router's see it (OLMoE reads
# 4.2-7.4%, Nemotron-H 22.9-32.4%, same cause); the loss 3.7e-7 to 1.2e-5.
#
# The control, ``control_job``: the reference itself with the inputs of
# every product kept to 3 mantissa bits (float8_e4m3's, where the
# configuration states bf16's 7) and the router's to 7 (bf16's, where it
# states float32), in the program's place, 4 seeds: off the routers' path
# 10.20-10.23%, the experts 17.8-18.4%, the routers 11.4-13.3%, the loss
# 6.7e-5 to 1.0e-4. Not correct on every seed, by two of the four limits.
#
# The limits. Off the routers' path 4%: 3.0 x the largest sound reading, 2.5
# x under the control's smallest. The experts 13%: 1.43 x the largest sound
# reading, 11 standard deviations above the sound mean, and 1.37 x under the
# control's smallest. The routers' weights 20%: the control reads only
# 1.2-1.4 x the sound largest there and a limit between them would lie 3
# standard deviations from the sound mean, so one seed in some hundred
# would fail it; the limit stays where a router that reads another stream
# fails it (4970-5050%, the held experts then 46%) and the control passes
# it. The loss 3.3e-4, the accepted expert cells' limit: 27 x the largest
# sound reading and 3 x over the control, which the loss does not tell from
# the policy; it is there for a missing term (a layer, an expert, the
# softmax over the chosen).
# Wrong programs that fail, each on every seed (``_chipcheck/tol38.py``,
# 4-6 seeds): a window off by one block of 512 reads 18.9-22.0% on the q
# and k projections; the router on the stream after attention as above.
TOLERANCE = Tolerance(
    loss_rtol=3.3e-4, grad_rel_l2=0.04,
    grad_rel_l2_under={"primary_router": 0.2, "experts": 0.13},
    reason="bf16 activations against float32 through eight top-6-of-64 "
           "routers over a share of 8 experts (1536 rows each): near-ties "
           "move a few rows of an expert, which its gradient and the "
           "router's see. 23 seeds on the chip against the same equations "
           "one precision lower (float8_e4m3's 3 mantissa bits into every "
           "product, bf16 into the router; reference_control.py): leaves "
           "off the routers' path 1.18-1.35% against 10.2%, limit 4%; the "
           "held experts 7.5-9.1% against 17.8-18.4%, limit 13%; the "
           "routers' weights 6.4-9.5% against 11.4-13.3%, limit 20% (no "
           "limit fits between: it is for a router on another stream, "
           "4970%); the loss 1.2e-5 at most against 6.7e-5, limit 3.3e-4 "
           "(for a missing term)")

REFERENCE_QUERY_BLOCK = 64    # rows of scores, and of logits, held at once


# -- operation counts ------------------------------------------------------------

def smallthinker_forward_flops_per_token(
        window_layout, hidden: int, heads: int, kv_heads: int,
        head_dim: int, experts: int, experts_per_token: int, held: int,
        expert_dim: int, vocab: int, seq: int, window: int) -> dict:
    """Forward matrix work of one token by part, in FLOPs. A layer: q, k, v
    and o; QK^T and PV over the pairs its mask leaves (causal on a full
    layer, the window's on a window layer); the router over all experts;
    the held experts' three products for the ``k held / experts`` pairs a
    token sends them under a uniform router (the rows a share really sees
    are data-dependent). The sliced head. The embedding is a gather; norms,
    rotary and gates are element-wise."""
    q_dim = heads * head_dim
    parts = {
        "attention_projections": 2.0 * hidden * (2 * q_dim + 2 * kv_heads
                                                 * head_dim),
        "full_scores": 2.0 * 2 * attended_pairs(seq, True) * q_dim / seq,
        "window_scores": 2.0 * 2 * window_pairs(seq, window) * q_dim / seq,
        "router": 2.0 * hidden * experts,
        "held_experts": 2.0 * 3 * hidden * expert_dim
        * experts_per_token * held / experts,
        "head": 2.0 * hidden * vocab,
    }
    layers = len(window_layout)
    windows = sum(1 for w in window_layout if w)
    return {
        "projections": layers * parts["attention_projections"],
        "full_attention": (layers - windows) * parts["full_scores"],
        "window_attention": windows * parts["window_scores"],
        "router": layers * parts["router"],
        "experts": layers * parts["held_experts"],
        "head": parts["head"],
        "parts": parts,
    }


KINDS = ("projections", "full_attention", "window_attention", "router",
         "experts", "head")


def build(config: dict, traffic: dict) -> Job:
    from horovod_tpu.models import SmallThinkerDecoder, smallthinker_loss
    from horovod_tpu.ops.flash_attention import flash_min_seq

    seq = int(traffic["seq_len"])
    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"seq_len {seq} is past the published context")
    rope_layout = tuple(int(x) for x in config["rope_layout"])
    window_layout = tuple(int(x) for x in config["sliding_window_layout"])
    layers = int(config["num_layers"])
    if len(rope_layout) != layers or len(window_layout) != layers:
        raise ValueError(f"the layouts {rope_layout} and {window_layout} "
                         f"have not num_layers = {layers} entries")
    if not config["moe_primary_router_apply_softmax"] \
            or config["tie_word_embeddings"] \
            or config["rope_scaling"] is not None:
        raise ValueError(
            "SmallThinkerDecoder is a softmax over the chosen logits (a "
            "norm_topk_prob after it is the identity), an untied head and "
            "unscaled rotary")
    held = (int(config["experts_held"]["first"]),
            int(config["moe_num_primary_experts"]))
    sizes = dict(
        hidden=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        experts=int(config["experts_held"]["of"]),
        experts_per_token=int(config["moe_num_active_primary_experts"]),
        expert_dim=int(config["moe_ffn_hidden_size"]),
        vocab=int(config["vocab_size"]))
    window = int(config["sliding_window_size"])
    theta = float(config["rope_theta"])
    eps = float(config["rms_norm_eps"])
    recompute = config["recompute"]["policy"]
    model = SmallThinkerDecoder(
        rope_layout=rope_layout, sliding_window_layout=window_layout,
        window=window, rope_theta=theta, experts_held=held, eps=eps,
        residual_out_std=float(config["initializer"]["residual_out_std"]),
        remat=recompute, **sizes)
    opt = config["optimizer"]
    warmup = int(opt["warmup_steps"])

    def learning_rate(step):  # linear warm-up to the peak, then constant
        return opt["learning_rate"] * jnp.minimum(1.0, (step + 1) / warmup)
    optimizer = optax.adamw(learning_rate, b1=opt["b1"], b2=opt["b2"],
                            eps=opt["eps"], weight_decay=opt["weight_decay"])

    def init(key):
        return model.init(key, jnp.zeros((1, seq), jnp.int32))["params"], \
            None

    def loss_fn(params, batch, rng):
        logits, stats = model.apply({"params": params}, batch["tokens"])
        return smallthinker_loss(logits, batch["labels"], stats)

    def make_batch(key, n):
        tokens = jax.random.randint(key, (n, seq), 0, sizes["vocab"],
                                    jnp.int32)
        return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    flash = seq >= flash_min_seq()
    per_chip = int(traffic["per_chip_batch"])
    forward = smallthinker_forward_flops_per_token(
        window_layout, held=held[1], seq=seq, window=window, **sizes)
    fulls = [i for i, w in enumerate(window_layout) if not w]
    windows = [i for i, w in enumerate(window_layout) if w]

    def block(i, *leaf):
        return (f"SmallThinkerBlock_{i}",) + leaf

    def attention(i, name):
        return block(i, "SmallThinkerAttention_0", name, "kernel")
    check_leaves = [attention(i, name) for i in (fulls[0], windows[-1])
                    for name in ("q_proj", "k_proj")]
    check_leaves += [
        block(0, "primary_router", "weight"),
        block(layers - 1, "primary_router", "weight"),
        *(block(windows[0], "experts", name)
          for name in ("gate", "up", "down")),
        ("Embed_0", "embedding"), ("LmHead", "kernel")]
    facts = {"layers": layers, "rope_layout": list(rope_layout),
             "sliding_window_layout": list(window_layout), **sizes,
             "window": window, "experts_held": list(held), "seq_len": seq,
             "tied_head": False, "recompute": recompute,
             "attention": "flash" if flash else "xla",
             "forward_mflops_per_token": {
                 k: forward[k] / 1e6 for k in KINDS},
             "full_layers": len(fulls), "window_layers": len(windows),
             # the held experts' three products, forward and backward, for
             # the pairs a uniform router sends them (moe_experts_mfu's)
             "moe_train_flops_per_token_per_layer":
                 TRAIN_OVER_FORWARD * forward["parts"]["held_experts"]}
    if flash:
        facts["window_call"] = [per_chip, seq, sizes["heads"],
                                sizes["head_dim"], window]
    return Job(
        unit="tokens", items_per_example=seq, stateful=False, init=init,
        loss_fn=loss_fn, optimizer=optimizer, make_batch=make_batch,
        model_flops_per_item=float(
            TRAIN_OVER_FORWARD * sum(forward[k] for k in KINDS)),
        reference_loss=functools.partial(
            reference_loss, rope_layout=rope_layout,
            window_layout=window_layout, window=window, theta=theta,
            held=held, eps=eps, **{k: sizes[k] for k in (
                "heads", "kv_heads", "head_dim", "experts_per_token")}),
        check_leaves=tuple(check_leaves),
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


def _masked_attention(q, k, v, window, bits=None, windowed=True):
    """[B, T, H, D] each, explicit scores and an explicit mask,
    ``REFERENCE_QUERY_BLOCK`` query rows at a time against the whole
    context: ``0 <= i - j``, and ``i - j < window`` where there is one and
    the layer is ``windowed`` (a traced flag: the layers share this code)."""
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
        seen = ahead >= 0
        if window is not None:
            seen &= (ahead < window) | jnp.logical_not(windowed)
        s = jnp.where(seen, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          _kept(jax.nn.softmax(s, axis=-1), bits), v)

    blocks = q.reshape(b, t // block, block, h, d).swapaxes(0, 1)
    out = jax.lax.map(rows, (jnp.arange(0, t, block), blocks))
    return out.swapaxes(0, 1).reshape(b, t, h, d)


def _attention(x, p, *, heads, kv_heads, head_dim, theta, window, rope,
               windowed, bits):
    b, t, _ = x.shape
    x = _kept(x, bits)
    q, k, v = ((x @ _kept(p[name]["kernel"], bits)).reshape(b, t, n, head_dim)
               for name, n in (("q_proj", heads), ("k_proj", kv_heads),
                               ("v_proj", kv_heads)))
    q, k = (jnp.where(rope, _rotate_half(a, theta), a) for a in (q, k))
    k, v = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (k, v))
    return _kept(_masked_attention(q, k, v, window, bits, windowed).reshape(
        b, t, heads * head_dim), bits) @ _kept(p["o_proj"]["kernel"], bits)


def _routing(x, w_router, experts_per_token, bits=None):
    """[T, E] float32: the softmax of a token's chosen logits at the chosen
    experts, zero elsewhere; and the choice [T, k]."""
    logits = _kept(x.reshape(-1, x.shape[-1]), bits) @ _kept(w_router, bits)
    chosen = jax.lax.top_k(jax.lax.stop_gradient(logits),
                           experts_per_token)[1]
    picked = (chosen[:, :, None] == jnp.arange(logits.shape[-1])).any(axis=1)
    return jax.nn.softmax(jnp.where(picked, logits, -jnp.inf), axis=-1), \
        chosen


def _experts(x, p, dense, held, bits):
    """The held ReGLU experts for every token, weighted by ``dense`` [T, E]
    (zero where the expert is not among the token's chosen)."""
    b, t, d = x.shape
    tokens = _kept(x.reshape(b * t, d), bits)
    first, count = held

    @jax.checkpoint
    def expert(args):
        w_gate, w_up, w_down, g = args
        w_gate, w_up, w_down = (_kept(w, bits) for w in (w_gate, w_up,
                                                          w_down))
        return g[:, None] * (_kept(
            jnp.maximum(tokens @ w_gate, 0.0) * (tokens @ w_up), bits)
            @ w_down)

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


def _layer(x, p, *, rope, windowed, window, theta, held, eps, heads,
           kv_heads, head_dim, experts_per_token, router_reads, bits,
           router_bits):
    """One layer of the module text's equations: (the layer's output, the
    experts each token chose [T, k])."""
    router = functools.partial(
        _routing, w_router=p["primary_router"]["weight"],
        experts_per_token=experts_per_token, bits=router_bits)
    if router_reads == "input":
        dense, chosen = router(x)
    x = x + jax.checkpoint(functools.partial(
        _attention, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        theta=theta, window=window, rope=rope, windowed=windowed,
        bits=bits))(
            _rms_norm(x, p["input_layernorm"]["scale"], eps),
            p["SmallThinkerAttention_0"])
    h2 = _rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    if router_reads != "input":
        dense, chosen = router(h2)
    return x + jax.checkpoint(functools.partial(
        _experts, held=held, bits=bits))(h2, p["experts"], dense), chosen


def reference_forward(params, batch, *, rope_layout, window_layout,
                      router_reads="input", lowered=False, **sizes):
    """(loss, the experts each token chose [T, k] for each layer) in
    float32, every matmul at the highest precision. ``lowered`` is the
    control, never the reference: the inputs of every product rounded to
    the precision below the one ``dtype_policy`` states for them
    (``BELOW_BF16_BITS``, the router's ``BELOW_FLOAT32_BITS``). Each layer is
    recomputed in backward from its input (float32 at 16 384 x 2560 is 168
    MB a tensor: what eight layers would keep is more than the step's own
    temporaries), and the layers are one scanned body whose two flags are
    data: written out eight times the gradient of this function compiled to
    78 MB of the compile cache and, with the program's 71 and the step's
    76, a run's entries outgrew the 192 MiB the chip machine allows, so that
    no run ever found one (PERF.md §6, PR 38); scanned it is 14 MB.
    ``router_reads`` ``"input"`` is the model: the router reads the layer's
    input ``x``; ``"after_attention"`` is what the model is NOT (the router
    on the normed stream the experts see), for the test that tells them
    apart."""
    bits = BELOW_BF16_BITS if lowered else None
    router_bits = BELOW_FLOAT32_BITS if lowered else None
    blocks = [params[f"SmallThinkerBlock_{i}"]
              for i in range(len(rope_layout))]
    flags = jnp.asarray([rope_layout, window_layout], bool)

    @jax.checkpoint
    def layer(x, i):
        # this layer's weights picked out of the eight, one layer's copy at
        # a time: a stack of all of them would be 2.2 GB of temporaries more
        # than the step's, and ``peak_hbm_gb`` would read the check's
        p = jax.tree_util.tree_map(
            lambda *leaves: jax.lax.select_n(i, *leaves), *blocks)
        return _layer(x, p, rope=flags[0, i], windowed=flags[1, i],
                      bits=bits, router_bits=router_bits,
                      router_reads=router_reads, **sizes)

    with jax.default_matmul_precision("highest"):
        x = params["Embed_0"]["embedding"].astype(jnp.float32)[
            batch["tokens"]]
        x, chosen = jax.lax.scan(layer, x, jnp.arange(len(blocks)))
        x = _rms_norm(x, params["norm"]["scale"], sizes["eps"])
        return _cross_entropy(x, params["LmHead"]["kernel"],
                              batch["labels"], bits), chosen


def reference_loss(params, model_state, batch, **sizes):
    return reference_forward(params, batch, **sizes)[0]


def control_job(job: Job) -> Job:
    """``job`` with the lowered reference in the program's place: what
    ``benchmark/reference_control.py`` hands the harness's own comparison,
    which has to call it not correct (``TOLERANCE`` has the readings)."""
    def loss_fn(params, batch, rng):
        return job.reference_loss(params, None, batch, lowered=True), ()
    return dataclasses.replace(job, loss_fn=loss_fn)
