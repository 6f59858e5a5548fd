"""SDAR-30B-A3B-Chat on its block-diffusion objective, one chip's share of
six layers: the job the program trains, its plain float32 reference, and its
operation counts.

``build(config, traffic)`` returns a ``harness.job.Job``. The job's half is
what a user writes: the repo's flax model (``models/sdar.py``: both streams
of a sequence as ``2L`` rows, ``ops/flash_attention.blockdiff_attention``,
per-head q/k norm, the expert share of ``parallel/ep.moe_dropless``), an
optax optimizer, the model's loss, through ``dp.make_train_step``. The batch
holds the noise (``xt``, ``masked``, ``weight`` beside ``x0``), made once by
the program's ``sdar_noise`` from the batch's key: the harness hands the
reference no key, so both sides read the same noise from the batch.

The reference's half is this file's own and shares no code with either: the
equations of the configuration's ``assumed`` and ``deployment`` in plain
``jax.numpy`` and float32 at the highest matmul precision. Its mask is an
explicit boolean array over the ``2L`` rows, built from block indices and
stream flags (:func:`_mask_rows`), attention is explicit scores,
``REFERENCE_QUERY_BLOCK`` query rows at a time against all ``2L`` keys with
the key heads repeated, rotary at a row's position in its sequence is written
out here, the experts are computed densely for every row and masked by the
choice (no sort, no grouped matmul), **over the same held experts only** and
over the same vocabulary slice, the weighted cross-entropy of the masked
positions in blocks of rows.

``sdar_forward_flops_per_token`` is the configuration's own model FLOP count
(``harness/flops.py`` knows causal decoders only), a **data** token's: the
layers run over ``2L`` rows, the head over ``L``, attention over the ``L^2 +
L G`` pairs a head and sequence that the mask leaves.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import optax

from harness.flops import TRAIN_OVER_FORWARD
from harness.job import Job, Tolerance

# Both readings of each limit, through run.py's own comparison on the chip
# (``benchmark/reference_control.py`` and whole runs of the cell; my chip run,
# PR 40; PERF.md §6): relative L2 of a gradient leaf against the float32
# reference and relative error of the loss. Sound: the program, 10 seeds.
# Control: the reference one precision below the stated one, 3 seeds.
#   leaves off the routers' path   sound 0.35-1.23% (q/k projections and norms
#     (q_proj, k_proj, q_norm,     0.84-1.23, embedding 0.46-0.52, head
#      k_norm, embedding, head)    0.35-0.39); control 8.5-11.3% on q/k, 2.8-
#                                  3.2% on embedding and head.      limit 4%
#   the held experts' matrices     sound 1.9-6.5%; control 11.3-14.4%.
#                                                                   limit 13%
#   the routers' weights           sound 1.6-13.2% (13.2 and 10.1 once each,
#                                  the other eighteen under 6.8); control
#                                  11.2-21.4%.                      limit 30%
#   the loss                       sound 1.4e-6 to 4.1e-5; control 8.0e-5 to
#                                  2.5e-4.        limit 3.3e-4 (the harness's
#                                  accepted cells': eight times the largest)
# The control is never correct: every q/k leaf fails the 4% every time, the
# experts' 13% two seeds in three. The leaves on the routers' path have a
# heavy tail over seeds, and no limit separates the routers' sound readings
# from the control's: a position masked at a noise level near 1e-3 weighs up
# to 1000 in the loss where the mean weight is 7, a batch holds such a
# position or not by chance of the seed, and when the bf16 state of its row
# flips a near-tie of the router it is most of that leaf's gradient. The
# routers' 30% leaves the tail room (a run of the benchmark that reads
# ``correct`` false refuses a PR) and still refuses a router fed another
# stream (hundreds of percent, PERF.md §6, PR 38).
TOLERANCE = Tolerance(
    loss_rtol=3.3e-4, grad_rel_l2=0.04,
    grad_rel_l2_under={"router": 0.30, "gate_proj": 0.13, "up_proj": 0.13,
                       "down_proj": 0.13},
    reason="bf16 activations against float32 through six top-8-of-128 "
           "routers over a share of 16 experts: near-ties move a few rows "
           "of an expert, which its gradient and the router's see, and a "
           "row masked at a low noise level weighs up to 1000 in the loss")

REFERENCE_QUERY_BLOCK = 64    # rows of scores, and of logits, held at once


# -- operation counts ------------------------------------------------------------

def blockdiff_pairs(seq: int, block: int) -> int:
    """(query, key) pairs the block-diffusion mask leaves in one head of
    one sequence: clean on clean ``b(k) <= b(q)``, noised on clean ``b(k) <
    b(q)``, a noised block on itself: ``seq^2 + seq block``."""
    blocks = seq // block
    clean = block * block * blocks * (blocks + 1) // 2
    past = block * block * blocks * (blocks - 1) // 2
    return clean + past + blocks * block * block


def sdar_forward_flops_per_token(
        layers: int, hidden: int, heads: int, kv_heads: int, head_dim: int,
        experts: int, experts_per_token: int, held: int, expert_dim: int,
        vocab: int, seq: int, block: int,
        routed_rows: float = 2.0) -> dict:
    """Forward matrix work of one **data** token by part, in FLOPs. Each of
    its two rows (noised, clean) takes a layer's q, k, v and o and the
    router over all experts; ``routed_rows`` of them take the held experts'
    three products for the ``k held / experts`` pairs a row sends them under
    a uniform router (2: both rows; at the cell's start the rows that hold
    the mask token go to experts held elsewhere, which leaves ``2 - E[t]``;
    the rows a share really sees are data-dependent); QK^T and PV over the
    mask's pairs; the sliced head over the noised row alone. The embedding is
    a gather; norms, rotary and gates are element-wise."""
    q_dim = heads * head_dim
    parts = {
        "row_projections": 2.0 * hidden * (2 * q_dim
                                           + 2 * kv_heads * head_dim),
        "scores": 2.0 * 2 * blockdiff_pairs(seq, block) * q_dim / seq,
        "row_router": 2.0 * hidden * experts,
        "row_held_experts": 2.0 * 3 * hidden * expert_dim
        * experts_per_token * held / experts,
        "head": 2.0 * hidden * vocab,
    }
    return {
        "projections": layers * 2 * parts["row_projections"],
        "attention": layers * parts["scores"],
        "router": layers * 2 * parts["row_router"],
        "experts": layers * routed_rows * parts["row_held_experts"],
        "head": parts["head"],
        "parts": parts,
    }


KINDS = ("projections", "attention", "router", "experts", "head")


def cell_start(params, held, mask_id: int, std: float, out_std: float):
    """Where the cell's weights start, from the model's own initialisation
    (every matrix normal ``std``); the configuration's
    ``assumed.initialisation`` says why. (1) The two matrices that write into
    the residual stream, ``o_proj`` and the experts' ``down_proj``, at
    ``out_std``: the stream the routers read stays the row's embedding, so
    the data tokens' rows choose their experts by their token, evenly. (2)
    The mask token's row of the embedding, which a quarter of all rows
    share and which therefore sends them all to the same eight experts of a
    layer, points away from the router columns of the experts held here, in
    every layer, at the embedding's own size: those eight are then experts
    that other chips hold. Which chip holds them is an accident of the seed
    under random weights and would decide the step time (a held one of them
    is sent 4096 rows more, four tiles of the walk for one)."""
    first, count = held
    blocks = {name: block for name, block in params.items()
              if name.startswith("SdarBlock_")}
    away = -sum(block["SdarSparseMoe_0"]["router"][:, first:first + count]
                .sum(axis=1) for block in blocks.values())
    away = away * (std / jnp.sqrt(jnp.mean(away * away)))
    out = dict(params)
    out["Embed_0"] = {"embedding": params["Embed_0"]["embedding"]
                      .at[mask_id].set(away)}
    for name, block in blocks.items():
        attention = dict(block["SdarAttention_0"])
        attention["o_proj"] = {
            "kernel": attention["o_proj"]["kernel"] * (out_std / std)}
        moe = dict(block["SdarSparseMoe_0"])
        moe["down_proj"] = moe["down_proj"] * (out_std / std)
        out[name] = {**block, "SdarAttention_0": attention,
                     "SdarSparseMoe_0": moe}
    return out


def build(config: dict, traffic: dict) -> Job:
    from horovod_tpu.models import SdarMoeDecoder, sdar_loss, sdar_noise
    from horovod_tpu.ops.flash_attention import flash_min_seq

    seq = int(traffic["seq_len"])
    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"seq_len {seq} is past the published context")
    if not config["norm_topk_prob"] or config["tie_word_embeddings"] \
            or config["rope_scaling"] is not None \
            or config["use_sliding_window"] or config["mlp_only_layers"] \
            or config["decoder_sparse_step"] != 1 \
            or config["attention_bias"] or config["hidden_act"] != "silu":
        raise ValueError(
            "SdarMoeDecoder is top-k renormalised SiLU-gated experts in "
            "every layer, an untied head, unscaled rotary, no window, no "
            "bias")
    held = (int(config["experts_held"]["first"]), int(config["num_experts"]))
    block = int(config["block_length"])
    mask_id = int(config["mask_token_id"])
    sizes = dict(
        layers=int(config["num_layers"]),
        hidden=int(config["hidden_size"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]),
        experts=int(config["experts_held"]["of"]),
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_dim=int(config["moe_intermediate_size"]),
        vocab=int(config["vocab_size"]))
    if mask_id != sizes["vocab"] - 1:
        raise ValueError("the mask token is the slice's last row")
    theta = float(config["rope_theta"])
    eps = float(config["rms_norm_eps"])
    recompute = config["recompute"]["policy"]
    model = SdarMoeDecoder(block_length=block, rope_theta=theta,
                           experts_held=held, eps=eps, remat=recompute,
                           **sizes)
    start = config["initializer"]
    opt = config["optimizer"]
    warmup = int(opt["warmup_steps"])

    def learning_rate(step):  # linear warm-up to the peak, then constant
        return opt["learning_rate"] * jnp.minimum(1.0, (step + 1) / warmup)
    optimizer = optax.adamw(learning_rate, b1=opt["b1"], b2=opt["b2"],
                            eps=opt["eps"], weight_decay=opt["weight_decay"])

    def init(key):
        tokens = jnp.zeros((1, seq), jnp.int32)
        return cell_start(model.init(key, tokens, tokens)["params"], held,
                          mask_id, float(start["std"]),
                          float(start["residual_out_std"])), None

    def loss_fn(params, batch, rng):
        logits, stats = model.apply({"params": params}, batch["xt"],
                                    batch["x0"])
        return sdar_loss(logits, batch, stats)

    def make_batch(key, n):
        key_tokens, key_noise = jax.random.split(key)
        x0 = jax.random.randint(key_tokens, (n, seq), 0, mask_id, jnp.int32)
        return {"x0": x0, **sdar_noise(key_noise, x0, block, mask_id)}

    flash = seq >= flash_min_seq()
    per_chip = int(traffic["per_chip_batch"])
    # the noise level of a block is uniform on [NOISE_FLOOR, 1]: that share
    # of the noised stream's rows holds the mask token (cell_start)
    routed_rows = 2.0 - (1e-3 + 1.0) / 2
    forward = sdar_forward_flops_per_token(
        held=held[1], seq=seq, block=block, routed_rows=routed_rows, **sizes)

    def of_layer(i, *leaf):
        return (f"SdarBlock_{i}",) + leaf
    last = sizes["layers"] - 1
    check_leaves = [
        *(of_layer(i, "SdarAttention_0", name, "kernel")
          for i in (0, last) for name in ("q_proj", "k_proj")),
        of_layer(0, "SdarAttention_0", "q_norm", "scale"),
        of_layer(last, "SdarAttention_0", "k_norm", "scale"),
        # not the last layer's experts: only the masked rows of the noised
        # stream reach the loss from there, and at the cell's start those
        # rows' experts are held elsewhere (cell_start): no gradient at all
        of_layer(0, "SdarSparseMoe_0", "router"),
        of_layer(last - 1, "SdarSparseMoe_0", "router"),
        *(of_layer(last // 2, "SdarSparseMoe_0", name)
          for name in ("gate_proj", "up_proj", "down_proj")),
        ("Embed_0", "embedding"), ("LmHead", "kernel")]
    facts = {**sizes, "block_length": block, "mask_token_id": mask_id,
             "experts_held": list(held), "seq_len": seq,
             "rows_per_layer": 2 * seq, "tied_head": False,
             "recompute": recompute,
             "residual_out_std": start["residual_out_std"],
             "attention": "flash" if flash else "xla",
             "forward_mflops_per_token": {
                 k: forward[k] / 1e6 for k in KINDS},
             "routed_rows_per_token": routed_rows,
             # the held experts' three products, forward and backward, for
             # the pairs a uniform router sends them from the rows of a data
             # token that do not hold the mask token (moe_experts_mfu's)
             "moe_train_flops_per_token_per_layer":
                 TRAIN_OVER_FORWARD * routed_rows
                 * forward["parts"]["row_held_experts"]}
    if flash:  # harness/blockdiff.py: batch, seq, heads, head_dim, block
        facts["blockdiff_call"] = [per_chip, seq, sizes["heads"],
                                   sizes["head_dim"], block]
    return Job(
        unit="tokens", items_per_example=seq, stateful=False, init=init,
        loss_fn=loss_fn, optimizer=optimizer, make_batch=make_batch,
        model_flops_per_item=float(
            TRAIN_OVER_FORWARD * sum(forward[k] for k in KINDS)),
        reference_loss=functools.partial(
            reference_loss, block=block, theta=theta, held=held, eps=eps,
            **{k: sizes[k] for k in ("heads", "kv_heads", "head_dim",
                                     "experts_per_token")}),
        # two layers (the rehearsal) name one router twice
        check_leaves=tuple(dict.fromkeys(check_leaves)),
        sample_examples=int(traffic.get("reference_examples", 1)),
        tolerance=TOLERANCE,
        # no call runs under a name of flops.FLASH_PRODUCTS: the kernels of
        # a block-diffusion pass have their own (harness/blockdiff.py)
        flash_call=None, flash_layers=0, facts=facts)


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


def _rotate_half(x, positions, theta):
    """[B, R, H, D] at ``positions`` [R]: pairs (x_i, x_{i + D/2}) turned
    by ``position theta^(-2i/D)``."""
    d = x.shape[-1]
    angles = positions.astype(jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _mask_rows(start, count: int, seq: int, block: int):
    """Rows ``start .. start + count - 1`` of the [2 seq, 2 seq] boolean
    mask. A row or a column below ``seq`` is the noised stream's, position
    ``i`` of either stream lies in block ``i // block``."""
    q = start + jnp.arange(count)
    k = jnp.arange(2 * seq)
    q_noised, k_noised = (q < seq)[:, None], (k < seq)[None, :]
    q_block = ((q % seq) // block)[:, None]
    k_block = ((k % seq) // block)[None, :]
    return jnp.where(
        q_noised,
        jnp.where(k_noised, k_block == q_block, k_block < q_block),
        ~k_noised & (k_block <= q_block))


def _masked_attention(q, k, v, seq: int, block: int, bits=None):
    """[B, 2 seq, H, D] each, explicit scores under the explicit mask,
    ``REFERENCE_QUERY_BLOCK`` query rows at a time against every key. Every
    row sees a key (a noised row its own block, a clean row its own)."""
    b, rows, h, d = q.shape
    count = min(REFERENCE_QUERY_BLOCK, rows)
    if rows % count:
        raise ValueError(f"{rows} rows are not a multiple of {count}")
    k, v = _kept(k, bits), _kept(v, bits)

    @jax.checkpoint
    def some_rows(args):
        start, qb = args
        s = jnp.einsum("bqhd,bkhd->bhqk", _kept(qb, bits), k) * d ** -0.5
        s = jnp.where(_mask_rows(start, count, seq, block), s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          _kept(jax.nn.softmax(s, axis=-1), bits), v)

    blocks = q.reshape(b, rows // count, count, h, d).swapaxes(0, 1)
    out = jax.lax.map(some_rows, (jnp.arange(0, rows, count), blocks))
    return out.swapaxes(0, 1).reshape(b, rows, h, d)


def _attention(x, p, *, seq, block, heads, kv_heads, head_dim, theta, eps,
               bits):
    b, rows, _ = x.shape
    x = _kept(x, bits)
    q, k, v = ((x @ _kept(p[name]["kernel"], bits)).reshape(
        b, rows, n, head_dim) for name, n in (
            ("q_proj", heads), ("k_proj", kv_heads), ("v_proj", kv_heads)))
    positions = jnp.arange(rows) % seq  # in its sequence, either stream
    q, k = (_rotate_half(_rms_norm(a, p[name]["scale"], eps), positions,
                         theta)
            for a, name in ((q, "q_norm"), (k, "k_norm")))
    k, v = (jnp.repeat(a, heads // kv_heads, axis=2) for a in (k, v))
    return _kept(_masked_attention(q, k, v, seq, block, bits).reshape(
        b, rows, heads * head_dim), bits) @ _kept(p["o_proj"]["kernel"], bits)


def _routing(x, w_router, experts_per_token, bits=None):
    """[R, E] float32: softmax over all experts, the ``experts_per_token``
    largest kept and renormalised over themselves (the softmax of the
    chosen logits), zero elsewhere; and the choice [R, k]."""
    logits = _kept(x.reshape(-1, x.shape[-1]), bits) @ _kept(w_router, bits)
    probs = jax.nn.softmax(logits, axis=-1)
    chosen = jax.lax.top_k(jax.lax.stop_gradient(probs),
                           experts_per_token)[1]
    picked = (chosen[:, :, None] == jnp.arange(logits.shape[-1])).any(axis=1)
    kept = jnp.where(picked, probs, 0.0)
    return kept / kept.sum(axis=-1, keepdims=True), chosen


def _experts(x, p, dense, held, bits):
    """The held SwiGLU experts for every row, weighted by ``dense`` [R, E]
    (zero where the expert is not among the row's chosen)."""
    b, rows, d = x.shape
    tokens = _kept(x.reshape(b * rows, d), bits)
    first, count = held

    @jax.checkpoint
    def expert(args):
        w_gate, w_up, w_down, g = args
        w_gate, w_up, w_down = (_kept(w, bits) for w in (w_gate, w_up,
                                                          w_down))
        return g[:, None] * (_kept(
            jax.nn.silu(tokens @ w_gate) * (tokens @ w_up), bits) @ w_down)

    # one expert at a time into one sum: no [experts, R, d] stack
    out, _ = jax.lax.scan(
        lambda total, args: (total + expert(args), None),
        jnp.zeros_like(tokens),
        (p["gate_proj"], p["up_proj"], p["down_proj"],
         dense[:, first:first + count].T))
    return out.reshape(b, rows, d)


def _weighted_cross_entropy(x, w_head, batch, bits=None):
    """``(1 / (B L)) sum over masked positions of weight CE(logits, x0)``,
    ``REFERENCE_QUERY_BLOCK`` positions of float32 logits at a time."""
    d = x.shape[-1]
    rows, w_head = _kept(x.reshape(-1, d), bits), _kept(w_head, bits)
    count = min(REFERENCE_QUERY_BLOCK, rows.shape[0])
    if rows.shape[0] % count:
        raise ValueError(f"{rows.shape[0]} positions are not a multiple "
                         f"of {count}")
    weight = jnp.where(batch["masked"], batch["weight"], 0.0)

    @jax.checkpoint
    def some_rows(args):
        h, y, w = args
        logits = h @ w_head
        picked = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return (w * (jax.nn.logsumexp(logits, axis=-1) - picked)).sum()
    sums = jax.lax.map(some_rows, (
        rows.reshape(-1, count, d), batch["x0"].reshape(-1, count),
        weight.reshape(-1, count)))
    return sums.sum() / rows.shape[0]


def _layer(x, p, *, seq, block, theta, held, eps, heads, kv_heads, head_dim,
           experts_per_token, bits, router_bits):
    """One layer of the module text's equations: (the layer's output, the
    experts each row chose [R, k])."""
    x = x + jax.checkpoint(functools.partial(
        _attention, seq=seq, block=block, heads=heads, kv_heads=kv_heads,
        head_dim=head_dim, theta=theta, eps=eps, bits=bits))(
            _rms_norm(x, p["input_layernorm"]["scale"], eps),
            p["SdarAttention_0"])
    moe = p["SdarSparseMoe_0"]
    h2 = _rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    dense, chosen = _routing(h2, moe["router"], experts_per_token,
                             router_bits)
    return x + jax.checkpoint(functools.partial(
        _experts, held=held, bits=bits))(h2, moe, dense), chosen


def reference_forward(params, batch, *, lowered=False, **sizes):
    """(loss, the experts each row chose [layers, R, k]) in float32, every
    matmul at the highest precision. ``lowered`` is the control, never the
    reference: the inputs of every product rounded to the precision below
    the one ``dtype_policy`` states for them (``BELOW_BF16_BITS``, the
    router's ``BELOW_FLOAT32_BITS``). Each layer is recomputed in backward
    from its input, and the layers are one scanned body that picks its
    weights out of the blocks by the layer's number: written out layer by
    layer, the gradient of this function would be a program several times
    the size in the compile cache (PERF.md §6, PR 38)."""
    bits = BELOW_BF16_BITS if lowered else None
    router_bits = BELOW_FLOAT32_BITS if lowered else None
    seq = batch["x0"].shape[1]
    blocks = [params[f"SdarBlock_{i}"] for i in range(
        sum(1 for name in params if name.startswith("SdarBlock_")))]

    @jax.checkpoint
    def layer(x, i):
        # one layer's copy of the weights at a time, not a stack of all
        p = jax.tree_util.tree_map(
            lambda *leaves: jax.lax.select_n(i, *leaves), *blocks)
        return _layer(x, p, seq=seq, bits=bits, router_bits=router_bits,
                      **sizes)

    with jax.default_matmul_precision("highest"):
        x = params["Embed_0"]["embedding"].astype(jnp.float32)[
            jnp.concatenate([batch["xt"], batch["x0"]], axis=1)]
        x, chosen = jax.lax.scan(layer, x, jnp.arange(len(blocks)))
        x = _rms_norm(x[:, :seq], params["norm"]["scale"], sizes["eps"])
        return _weighted_cross_entropy(x, params["LmHead"]["kernel"], batch,
                                       bits), chosen


def reference_loss(params, model_state, batch, **sizes):
    return reference_forward(params, batch, **sizes)[0]


def control_job(job: Job) -> Job:
    """``job`` with the lowered reference in the program's place: what
    ``benchmark/reference_control.py`` hands the harness's own comparison,
    which has to call it not correct (``TOLERANCE`` has the readings)."""
    def loss_fn(params, batch, rng):
        return job.reference_loss(params, None, batch, lowered=True), ()
    return dataclasses.replace(job, loss_fn=loss_fn)
