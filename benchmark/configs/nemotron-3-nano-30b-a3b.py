"""Nemotron-3-Nano-30B-A3B, one chip's share of nine layers: the job the
program trains, its plain float32 reference, and its operation counts.

``build(config, traffic)`` returns a ``harness.job.Job``. The job's half is
what a user writes: the repo's flax model (``models/nemotron_h.py``: the
chunked scan of ``ops/ssd.py``, the expert share of
``parallel/ep.moe_dropless``, grouped-query attention through
``ops/flash_attention.attention``), an optax optimizer, the model's loss,
through ``dp.make_stateful_train_step`` because the routers' correction
biases are state. The reference's half is this file's own and shares no
code with either: the same equations in plain ``jax.numpy`` and float32 at
the highest matmul precision. Its scan is **the recurrence itself**, one
position a step (``lax.scan`` over time, checkpointed in blocks of time),
not the chunked algorithm; its conv is four shifted products; attention is
explicit scores in query blocks with the key heads repeated; the experts are
computed densely for every token and masked by the choice (no sort, no
grouped matmul), **over the same held experts only** and over the same
vocabulary slice; the same state rule.

``nemotron_h_forward_flops_per_token`` is the configuration's own model
FLOP count (``harness/flops.py`` knows dense decoders only); ``ssd_scan_cost``
counts the scan's products and unavoidable bytes for ``ssm_scan_roofline``
(``harness/ssm.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax

from harness.flops import TRAIN_OVER_FORWARD, attended_pairs
from harness.job import Job, Tolerance

# Readings on the chip over 12 seeds of PR 30's program (PERF.md §6, PR 30),
# relative L2 of a gradient leaf and relative error of the loss, program
# against reference; the limits below are set from PR 32's 20 seeds.
#
# The policy: the loss 1.1e-6 to 8.2e-5; the leaves off the routers' path
# 2.3-12.8% (matrices 3.3-5.6%, the 64-element A_log and dt_bias up to
# 12.8%); the leaves on it 11.7-29.6% (a held expert's up_proj 11.7-14.0%,
# down_proj 17.3-20.6%, the routers 15.7-20.3% and 23.6-29.6%). That is no
# rounding: four sigmoid routers choose 6 of 128, bf16 activations move a
# score by about 2**-9 relative, and where a token's 6th and 7th scores lie
# closer than that, program and reference send the slot to different
# experts. A share holds 8 experts that see about 384 rows each, so a
# handful of rows that moved is 1-2% of an expert's rows and, the terms
# being incoherent, the square root of that in its gradient; the
# renormalised weights make every one of the token's six weights move with
# the slot. OLMoE's 64 experts of 1024 rows read 4.2-7.4% for the same
# cause. Every leaf upstream of a router sees the flips of the routers after
# it (4.5-5% on the first mixer, the embedding and the head).
#
# One precision below, as the builder's instructions ask, each on all 12
# seeds: (1) the scan's running sums kept in bf16 (the configuration states
# float32): the largest leaf of a seed reads 41.7-110% (A_log 26-110%,
# dt_bias 21-76%, the second router 36-45%): **fails** the gradients' limit
# of 35% on every seed. (2) The blocks' inputs rounded to three significand bits
# (float8_e4m3, the nearest precision below bf16 activations): the largest
# leaf 50.4-62.7%, every matrix 10-16%: **fails** on every seed. (3) bf16
# router logits and scores: 24.1-31.5%, the policy's own readings: passes.
# What this check does NOT hold the program to is therefore the router's
# float32; its guard is the CPU test of the router's choices on equal inputs
# (tests/test_nemotron_h.py::test_bf16_router_scores_choose_other_experts).
# Nor does Cell.check_reference compare the returned state: the bias rule is
# held by tests/test_nemotron_h.py (the rule over three steps through
# dp.make_stateful_train_step, the state against the reference's).
#
# The gradients' limits, from 20 seeds on the chip on PR 32's tree, every
# variant on every seed (PERF.md §6, PR 32). The four leaves on the routers'
# path (the two gate weights, the two routed experts' matrices: near-ties
# move rows between experts) read apart from the other fifteen, so each
# class has its limit (``grad_rel_l2_under``):
#   the routers' path   policy 22.9-32.4% (the last router's gate weight
#                       every time); float8 inputs 49.8-61.0%; bf16 running
#                       sums 34.2-46.0%.                         limit 35%
#   every other leaf    policy 5.9-14.5% (A_log, dt_bias, the attention's
#                       k_proj); bf16 running sums 34.2-126% (A_log);
#                       float8 inputs 18.4-33.9%.                limit 25%
# bf16 running sums fail the second on every seed by 9 points or more,
# float8 inputs the first by 14. Until PR 32 one limit of 35% held every
# leaf: there bf16 running sums read 36.5% on one seed of the 20, 1.5 points
# over the limit and 1.1 x the largest sound reading. Neither upper reading
# is the 3 x its lower one that the driver's contract wants (2.4 x, 1.5 x):
# PERF.md §7. The loss
# resolves no precision (the variants read 1.8e-6 to 2.7e-4, as the
# policy); its limit is four times the largest sound reading and is there
# for a missing term (the shared expert, the routed scale, a layer).
TOLERANCE = Tolerance(
    loss_rtol=3.3e-4, grad_rel_l2=0.25,
    grad_rel_l2_under={"gate": 0.35, "experts": 0.35},
    reason="bf16 activations against float32 through four sigmoid "
           "top-6-of-128 routers over a share of 8 experts (384 rows "
           "each): near-ties move a few rows of an expert, which its "
           "gradient and the router's see (20 seeds on the chip: the gate "
           "weights and the routed experts' matrices 22.9-32.4%, limit "
           "35%, where float8 inputs read 49.8-61.0%; every other leaf "
           "5.9-14.5%, limit 25%, where bf16 running sums in the scan "
           "read 34.2-126%: both fail on every seed); the loss is a mean "
           "over tokens (8.2e-5 at most, limit 3.3e-4, for a missing "
           "term). NOT covered: the router's float32 (bf16 scores pass "
           "here; tests/test_nemotron_h.py guards it) and the returned "
           "state")

# what the TPU compiler made of one expert layer's ragged_dots when this
# file was written: Mosaic calls of its own (``ragged-dot-*``). Forward and
# backward are 8; where the block is recomputed its forward's come once
# more. A described fact, no limit: ``correct`` does not read it
# (harness/kernels.py says what it asks), tests/test_tpu_compile.py does
RAGGED_DOT_CALLS = {"kept": 8, "recomputed": 11}
REFERENCE_QUERY_BLOCK = 256   # rows of scores, and of logits, held at once
REFERENCE_TIME_BLOCK = 128    # positions of the recurrence between checkpoints


# -- operation counts ------------------------------------------------------------

def ssd_forward_flops_per_token(heads: int, head_dim: int, state: int,
                                groups: int, chunk: int,
                                masked_half: bool = False) -> float:
    """The chunked scan's four products for one position of one layer: in
    its chunk ``C B^T`` (groups x state a pair) and ``M (dt x)`` (heads x
    head_dim a pair), the chunk's end state and the carried state's
    contribution (heads x head_dim x state each). A position pairs with the
    ``chunk`` positions of its chunk as the model computes them, or with
    masked_half with the ``(chunk + 1) / 2`` at or before it on average:
    what no program can avoid."""
    pairs = (chunk + 1) / 2 if masked_half else chunk
    return 2.0 * (pairs * (groups * state + heads * head_dim)
                  + 2 * heads * head_dim * state)


def ssd_scan_cost(tokens: int, heads: int, head_dim: int, state: int,
                  groups: int, chunk: int, dtype_bytes: int = 2) -> tuple:
    """(FLOPs, HBM bytes) one layer's scan needs for ``tokens`` positions,
    forward and backward: three times the forward's products over the
    unmasked pairs alone; every input (x, B, C in the compute dtype, dt in
    float32) read once and y written once forward, and backward the inputs
    and dy read once and dx, dB, dC, ddt written once. No recomputation and
    nothing a fused kernel could keep on the chip is counted, so a share of
    this roofline cannot pass 100%."""
    flops = TRAIN_OVER_FORWARD * tokens * ssd_forward_flops_per_token(
        heads, head_dim, state, groups, chunk, masked_half=True)
    x = heads * head_dim * dtype_bytes
    bc = 2 * groups * state * dtype_bytes
    dt = heads * 4
    forward = x + bc + dt + x              # read x, B, C, dt; write y
    backward = (x + bc + dt + x) + (x + bc + dt)  # read those and dy; write
    return float(flops), float(tokens * (forward + backward))


def nemotron_h_forward_flops_per_token(
        pattern: str, hidden: int, mamba_heads: int, mamba_head_dim: int,
        state: int, groups: int, chunk: int, heads: int, kv_heads: int,
        head_dim: int, experts: int, experts_per_token: int, held: int,
        expert_dim: int, shared_dim: int, vocab: int, seq: int) -> dict:
    """Forward matrix work of one token by part, in FLOPs. A Mamba-2 layer:
    in- and out-projection and the chunked scan as computed (whole chunks).
    An expert layer: the router over all experts, the shared expert, and
    the held experts' two products for the ``k held / experts`` pairs a
    token sends them under a uniform router (the rows a share really sees
    are data-dependent). Attention: q, k, v, o and QK^T, PV over the causal
    pairs. The sliced head. The embedding is a gather; conv, norms and gates
    are element-wise."""
    d_in = mamba_heads * mamba_head_dim
    in_proj = 2 * d_in + 2 * groups * state + mamba_heads
    q_dim = heads * head_dim
    parts = {
        "mamba_projections": 2.0 * hidden * (in_proj + d_in),
        "mamba_scan": ssd_forward_flops_per_token(
            mamba_heads, mamba_head_dim, state, groups, chunk),
        "router": 2.0 * hidden * experts,
        "shared_expert": 2.0 * 2 * hidden * shared_dim,
        "held_experts": 2.0 * 2 * hidden * expert_dim
        * experts_per_token * held / experts,
        "attention_projections": 2.0 * hidden * (2 * q_dim + 2 * kv_heads
                                                 * head_dim),
        "attention_scores": 2.0 * 2 * attended_pairs(seq, True) * q_dim
        / seq,
        "head": 2.0 * hidden * vocab,
    }
    count = {kind: pattern.count(kind) for kind in "ME*"}
    return {
        "mamba": count["M"] * (parts["mamba_projections"]
                               + parts["mamba_scan"]),
        "experts": count["E"] * (parts["router"] + parts["shared_expert"]
                                 + parts["held_experts"]),
        "attention": count["*"] * (parts["attention_projections"]
                                   + parts["attention_scores"]),
        "head": parts["head"],
        "parts": parts,
    }


def build(config: dict, traffic: dict) -> Job:
    from horovod_tpu.models import NemotronHDecoder, nemotron_h_loss
    from horovod_tpu.ops.flash_attention import flash_min_seq

    seq = int(traffic["seq_len"])
    if seq > int(config["max_position_embeddings"]):
        raise ValueError(f"seq_len {seq} is past the published context")
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != int(config["num_layers"]):
        raise ValueError(f"pattern {pattern!r} has not num_layers = "
                         f"{config['num_layers']} layers")
    if config["mlp_hidden_act"] != "relu2" or config["mamba_hidden_act"] \
            != "silu" or not config["norm_topk_prob"] \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or config["n_shared_experts"] != 1 or config["residual_in_fp32"] \
            or config["tie_word_embeddings"] or config["attention_bias"] \
            or config["mlp_bias"] or config["mamba_proj_bias"] \
            or config["use_bias"] or not config["use_conv_bias"]:
        raise ValueError(
            "NemotronHDecoder is relu^2 experts, silu mixers, renormalised "
            "top-k weights without a group limit, one shared expert, a bf16 "
            "residual, an untied head and no bias but the conv's")
    held = (int(config["experts_held"]["first"]),
            int(config["n_routed_experts"]))
    experts = int(config["experts_held"]["of"])
    sizes = dict(
        hidden=int(config["hidden_size"]),
        mamba_heads=int(config["mamba_num_heads"]),
        mamba_head_dim=int(config["mamba_head_dim"]),
        state=int(config["ssm_state_size"]), groups=int(config["n_groups"]),
        chunk=int(config["chunk_size"]),
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config["head_dim"]), experts=experts,
        experts_per_token=int(config["num_experts_per_tok"]),
        expert_dim=int(config["moe_intermediate_size"]),
        shared_dim=int(config["moe_shared_expert_intermediate_size"]),
        vocab=int(config["vocab_size"]))
    eps = float(config["norm_eps"])
    scale = float(config["routed_scaling_factor"])
    rate = float(config["bias_update_rate"])
    recompute = config["recompute"]["kinds"]
    model = NemotronHDecoder(
        pattern=pattern, conv_kernel=int(config["conv_kernel"]),
        routed_scale=scale, bias_update_rate=rate, experts_held=held,
        dt_limits=(float(config["time_step_min"]),
                   float(config["time_step_max"]),
                   float(config["time_step_floor"])),
        eps=eps, remat=recompute, **sizes)
    opt = config["optimizer"]
    warmup = int(opt["warmup_steps"])

    def learning_rate(step):  # linear warm-up to the peak, then constant
        return opt["learning_rate"] * jnp.minimum(1.0, (step + 1) / warmup)
    optimizer = optax.adamw(learning_rate, b1=opt["b1"], b2=opt["b2"],
                            eps=opt["eps"], weight_decay=opt["weight_decay"])

    def init(key):
        variables = model.init(key, jnp.zeros((1, seq), jnp.int32))
        return variables["params"], variables["router_state"]

    def loss_fn(params, model_state, batch, rng):
        return nemotron_h_loss(model, params, model_state, batch["tokens"],
                               batch["labels"])

    def make_batch(key, n):
        tokens = jax.random.randint(key, (n, seq), 0, sizes["vocab"],
                                    jnp.int32)
        return {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}

    flash = seq >= flash_min_seq()
    per_chip = int(traffic["per_chip_batch"])
    forward = nemotron_h_forward_flops_per_token(
        pattern, held=held[1], seq=seq, **sizes)
    kinds = ("mamba", "experts", "attention", "head")
    mixers = [i for i, kind in enumerate(pattern) if kind == "M"]
    moes = [i for i, kind in enumerate(pattern) if kind == "E"]
    attentions = [i for i, kind in enumerate(pattern) if kind == "*"]

    def mixer(i, *leaf):
        return (f"NemotronHBlock_{i}", "NemotronHMamba2Mixer_0") + leaf

    def moe(i, *leaf):
        return (f"NemotronHBlock_{i}", "NemotronHMoE_0") + leaf
    check_leaves = [
        mixer(i, *leaf) for i in (mixers[0], mixers[-1])
        for leaf in (("A_log",), ("dt_bias",), ("conv1d", "kernel"),
                     ("in_proj", "kernel"), ("out_proj", "kernel"))]
    check_leaves += [
        moe(moes[0], "gate", "weight"), moe(moes[-1], "gate", "weight"),
        moe(moes[0], "experts", "up_proj"),
        moe(moes[-1], "experts", "down_proj"),
        moe(moes[0], "shared_experts", "up_proj", "kernel"),
        moe(moes[-1], "shared_experts", "down_proj", "kernel"),
        (f"NemotronHBlock_{attentions[0]}", "NemotronHAttention_0",
         "k_proj", "kernel"),
        ("Embed_0", "embedding"), ("LmHead", "kernel")]
    scan_flops, scan_bytes = ssd_scan_cost(
        per_chip * seq, sizes["mamba_heads"], sizes["mamba_head_dim"],
        sizes["state"], sizes["groups"], sizes["chunk"])
    return Job(
        unit="tokens", items_per_example=seq, stateful=True, init=init,
        loss_fn=loss_fn, optimizer=optimizer, make_batch=make_batch,
        model_flops_per_item=float(
            TRAIN_OVER_FORWARD * sum(forward[k] for k in kinds)),
        reference_loss=functools.partial(
            reference_loss, pattern=pattern, held=held, eps=eps,
            scale=scale, rate=rate, **{k: sizes[k] for k in (
                "mamba_heads", "mamba_head_dim", "state", "groups", "heads",
                "kv_heads", "head_dim", "experts_per_token")}),
        check_leaves=tuple(check_leaves),
        sample_examples=int(traffic.get("reference_examples", 1)),
        tolerance=TOLERANCE,
        flash_call=(per_chip, seq, sizes["heads"], sizes["head_dim"], True)
        if flash else None,
        flash_layers=len(attentions) if flash else 0,
        # described, not required (harness/job.py): the three flash kernels
        # of each attention layer (the forward's once more where the block
        # is recomputed) and the compiler's calls for the ragged_dots
        expected_custom_calls=(3 + ("*" in recompute)) * flash
        * len(attentions) + RAGGED_DOT_CALLS[
            "recomputed" if "E" in recompute else "kept"] * len(moes),
        facts={"layers": len(pattern), "pattern": pattern, **sizes,
               "experts_held": list(held), "seq_len": seq,
               "tied_head": False, "recompute": recompute,
               "attention": "flash" if flash else "xla",
               "forward_mflops_per_token": {
                   k: forward[k] / 1e6 for k in kinds},
               "ssm_layers": len(mixers),
               "ssd_scan_flops_per_layer_step": scan_flops,
               "ssd_scan_bytes_per_layer_step": scan_bytes})


# -- the plain reference ------------------------------------------------------

def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _relu2_mlp(x, w_up, w_down):
    return jnp.square(jnp.maximum(x @ w_up, 0.0)) @ w_down


def _recurrence(x, dt, a, b_mat, c_mat, d_skip):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t, y_t = S_t C_t + D x_t,
    one position a step. Heads as (group, head in group): x [B, T, G, R, P];
    dt [B, T, G, R]; a, d_skip [G, R]; b_mat, c_mat [B, T, G, N], read by
    every head of their group."""
    batch, t = x.shape[:2]
    block = min(REFERENCE_TIME_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence {t} is not a multiple of {block}")

    def position(state, at):
        x_t, dt_t, b_t, c_t = at
        state = jnp.exp(dt_t * a)[..., None, None] * state + \
            (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :]
        y_t = jnp.einsum("bgrpn,bgn->bgrp", state, c_t) \
            + d_skip[..., None] * x_t
        return state, y_t

    @jax.checkpoint
    def positions(state, block_of):
        return jax.lax.scan(position, state, block_of)

    def by_block(v):  # [B, T, ...] -> [T / block, block, B, ...]
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(t // block, block, *v.shape[1:])
    zeros = jnp.zeros((batch,) + x.shape[2:] + b_mat.shape[-1:],
                      jnp.float32)
    _, y = jax.lax.scan(positions, zeros,
                        tuple(map(by_block, (x, dt, b_mat, c_mat))))
    return jnp.moveaxis(y.reshape(t, *x.shape[:1], *x.shape[2:]), 0, 1)


@jax.checkpoint
def _causal_conv_silu(xbc, kernel, bias):
    """silu(b + sum_j w[j] x_{t-K+1+j}), zeros before t = 0."""
    taps, t = kernel.shape[0], xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(
        kernel[j] * padded[:, j:j + t] for j in range(taps)))


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _gated_group_norm(y, z, scale, groups, eps):
    """The gate first, then RMSNorm over each group of channels."""
    y = y * jax.nn.silu(z)
    grouped = y.reshape(*y.shape[:-1], groups, y.shape[-1] // groups)
    grouped = grouped * jax.lax.rsqrt(
        (grouped * grouped).mean(-1, keepdims=True) + eps)
    return grouped.reshape(y.shape) * scale


def _mamba2(u, p, *, mamba_heads, mamba_head_dim, state, groups, eps):
    batch, t, _ = u.shape
    h, hp, gn = mamba_heads, mamba_heads * mamba_head_dim, groups * state
    proj = u @ p["in_proj"]["kernel"]
    z, xbc, dt = proj[..., :hp], proj[..., hp:2 * hp + 2 * gn], \
        proj[..., 2 * hp + 2 * gn:]
    xbc = _causal_conv_silu(xbc, p["conv1d"]["kernel"], p["conv1d"]["bias"])
    r = h // groups
    x = xbc[..., :hp].reshape(batch, t, groups, r, mamba_head_dim)
    b_mat, c_mat = (v.reshape(batch, t, groups, state)
                    for v in (xbc[..., hp:hp + gn], xbc[..., hp + gn:]))
    y = _recurrence(
        x, jax.nn.softplus(dt + p["dt_bias"]).reshape(batch, t, groups, r),
        -jnp.exp(p["A_log"]).reshape(groups, r), b_mat, c_mat,
        p["D"].reshape(groups, r))
    return _gated_group_norm(y.reshape(batch, t, hp), z, p["norm"]["scale"],
                             groups, eps) @ p["out_proj"]["kernel"]


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


def _attention(x, p, *, heads, kv_heads, head_dim):
    b, t, _ = x.shape
    q = (x @ p["q_proj"]["kernel"]).reshape(b, t, heads, head_dim)
    k, v = (jnp.repeat((x @ p[name]["kernel"]).reshape(
        b, t, kv_heads, head_dim), heads // kv_heads, axis=2)
        for name in ("k_proj", "v_proj"))
    return _causal_attention(q, k, v).reshape(b, t, heads * head_dim) \
        @ p["o_proj"]["kernel"]


def _experts(x, p, state, *, held, experts_per_token, scale, rate):
    """The held experts for every token, weighted by the router's weight
    where the expert is among the token's chosen and by zero elsewhere, plus
    the shared expert. Returns (out, new state, chosen [T, k])."""
    b, t, d = x.shape
    tokens = x.reshape(b * t, d)
    gate_state = state["gate"]
    bias = gate_state["e_score_correction_bias"] + rate * jnp.sign(
        gate_state["load"].mean() - gate_state["load"])
    scores = jax.nn.sigmoid(tokens @ p["gate"]["weight"])
    chosen = jax.lax.top_k(jax.lax.stop_gradient(scores + bias),
                           experts_per_token)[1]
    n_experts = scores.shape[-1]
    picked = (chosen[:, :, None] == jnp.arange(n_experts)).any(axis=1)
    dense = jnp.where(picked, scores, 0.0)
    dense = dense / (dense.sum(-1, keepdims=True) + 1e-20) * scale
    first, count = held

    @jax.checkpoint
    def expert(args):
        w_up, w_down, g = args
        return g[:, None] * _relu2_mlp(tokens, w_up, w_down)

    # one expert at a time into one sum: no [experts, T, d] stack
    out, _ = jax.lax.scan(
        lambda total, args: (total + expert(args), None),
        jnp.zeros_like(tokens),
        (p["experts"]["up_proj"], p["experts"]["down_proj"],
         dense[:, first:first + count].T))
    shared = p["shared_experts"]
    out = out + _relu2_mlp(tokens, shared["up_proj"]["kernel"],
                           shared["down_proj"]["kernel"])
    new_state = {"gate": {
        "e_score_correction_bias": bias,
        "load": picked.sum(axis=0).astype(jnp.float32)}}
    return out.reshape(b, t, d), new_state, chosen


def _cross_entropy(x, w_head, labels):
    """Mean next-token cross-entropy, ``REFERENCE_QUERY_BLOCK`` positions
    of float32 logits at a time."""
    d = x.shape[-1]
    rows = x.reshape(-1, d)
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


def reference_forward(params, model_state, batch, *, pattern, held, eps,
                      scale, rate, mamba_heads, mamba_head_dim, state,
                      groups, heads, kv_heads, head_dim, experts_per_token):
    """(loss, new model state, the experts each token chose [T, k] for each
    expert layer) in float32, every matmul at the highest precision."""
    with jax.default_matmul_precision("highest"):
        x = params["Embed_0"]["embedding"].astype(jnp.float32)[
            batch["tokens"]]
        new_state, chosen = {}, []
        for i, kind in enumerate(pattern):
            name = f"NemotronHBlock_{i}"
            block = params[name]
            h = _rms_norm(x, block["norm"]["scale"], eps)
            if kind == "M":
                x = x + jax.checkpoint(functools.partial(
                    _mamba2, mamba_heads=mamba_heads,
                    mamba_head_dim=mamba_head_dim, state=state,
                    groups=groups, eps=eps))(
                        h, block["NemotronHMamba2Mixer_0"])
            elif kind == "*":
                x = x + jax.checkpoint(functools.partial(
                    _attention, heads=heads, kv_heads=kv_heads,
                    head_dim=head_dim))(h, block["NemotronHAttention_0"])
            else:
                out, layer_state, layer_chosen = jax.checkpoint(
                    functools.partial(
                        _experts, held=held,
                        experts_per_token=experts_per_token, scale=scale,
                        rate=rate))(h, block["NemotronHMoE_0"],
                                    model_state[name]["NemotronHMoE_0"])
                x = x + out
                new_state[name] = {"NemotronHMoE_0": layer_state}
                chosen.append(layer_chosen)
        x = _rms_norm(x, params["norm_f"]["scale"], eps)
        loss = _cross_entropy(x, params["LmHead"]["kernel"],
                              batch["labels"])
        return loss, new_state, chosen


def reference_loss(params, model_state, batch, **sizes):
    return reference_forward(params, model_state, batch, **sizes)[0]
