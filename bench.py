"""Headline benchmark: ResNet-50 synthetic data-parallel training throughput.

Mirrors the reference's synthetic benchmark protocol
(reference: examples/pytorch/pytorch_synthetic_benchmark.py,
docs/benchmarks.rst:67-83 — synthetic ImageNet-shaped data, timed train
steps, images/sec). Runs the full framework train step (forward, backward,
fused gradient allreduce over the mesh, SGD update) on every visible device
of the current platform; on the CI host that is one TPU chip.

Baseline: the reference's only published absolute throughput is ResNet-101
at 1656.82 images/sec on 16 Pascal P100s = 103.55 images/sec/GPU
(reference: docs/benchmarks.rst:32-43). vs_baseline reports
images/sec/chip against that per-device number.

Secondary figures, all honest (no clamps):
- scaling_sweep: weak-scaling efficiency at 1/2/4/8 devices on a virtual
  CPU mesh, normalized against the TRUE single-device baseline at the same
  per-device batch (efficiency_n = t_1 / t_n; ideal weak scaling keeps the
  per-step time flat at t_1). Values > 1.0 are never silently reported —
  when they occur an explanatory field accompanies them. The raw
  no-collective/with-collective overhead ratio at 8 devices rides along.
  A host mesh can't price ICI, but it prices everything the framework adds
  around the collectives (the north star is the reference's ~90% at scale,
  docs/benchmarks.rst:9-14).
- mfu: model FLOPs utilization against the chip's bf16 peak, computed by
  the shared calculator (horovod_tpu/profiler): XLA cost analysis of the
  compiled step, analytic fallback, provenance in resnet_config.method.
- collective_bytes_per_step_per_replica: ring-cost gradient-exchange wire
  bytes per replica for {fp32, bf16, int8} x {allreduce, sharded ZeRO-1}
  (one shared formula, parallel/zero.py collective_bytes_per_step).
- grad_exchange_sweep: measured images/sec/chip for the same mode matrix.
- resnet_config: the swept per-chip batch (the sweep picks it, nothing is
  hardcoded), layout, dtype policy and MFU accounting method.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

Modes: ``--scaling-probe`` (internal subprocess), ``--host-microbench``
(host data-plane Combine kernel bytes/s incl. the scalar-baseline speedup;
prints its own JSON line and exits — no TPU needed), ``--tuning-only``
(refresh just the ``tuning`` block: the bounded CPU-backend autotuner
session, horovod_tpu/tune/smoke.py — no TPU needed), ``--autoscale-only``
(refresh just the ``autoscale`` block: the closed-loop fleet sim,
serve/autoscale_smoke.py — no TPU needed).
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.profiler import flops as pflops
from horovod_tpu.profiler import mfu as pmfu


# Floors of 1: zero warmup would leave the timed loop's `out` unbound and
# zero reps would report 0 images/sec — both knobs are smoke-size dials,
# not off-switches.
WARMUP = max(1, int(os.environ.get("HVD_BENCH_WARMUP", 5)))
ITERS = max(1, int(os.environ.get("HVD_BENCH_ITERS", 20)))
# Best-of-REPS windows. Kept for now: with the chip attached to this host
# its one merit is dropping a window that another process on the shared CPU
# cores disturbed, and it hides the spread — the cell benchmark (ROADMAP
# Speed 1) replaces it with the median and quartiles.
REPS = max(1, int(os.environ.get("HVD_BENCH_REPS", 4)))
# CI-smoke hook: skip named sections ("bert,flash,scaling,modes") — the
# driver's TPU run never sets it, so the published JSON is always complete.
SKIP = {s for s in os.environ.get("HVD_BENCH_SKIP", "").split(",") if s}
BASELINE_PER_DEVICE = 1656.82 / 16.0  # reference docs/benchmarks.rst:32-43

# Per-chip batch candidates for the ResNet sweep (largest that fits wins on
# throughput; OOM candidates are recorded and skipped). Env-overridable for
# smoke runs: HVD_BENCH_RESNET_BATCHES="32,64".
RESNET_BATCH_CANDIDATES = tuple(
    int(b) for b in os.environ.get(
        "HVD_BENCH_RESNET_BATCHES", "128,256,512").split(",") if b)
# One read for every consumer (_bert_bench, step_attribution) — two copies
# of the default would drift.
BERT_BATCH = int(os.environ.get("HVD_BENCH_BERT_BATCH", 32))

RESNET50_PARAMS = pflops.RESNET50_PARAMS
BERT_BASE_PARAMS = pflops.BERT_BASE_PARAMS
BERT_SEQ = 128
BERT_TRAIN_FLOPS_PER_SEQ = pflops.transformer_train_flops_per_seq(
    BERT_BASE_PARAMS, BERT_SEQ)


def _scaling_probe():
    """Weak-scaling sweep on a virtual CPU mesh: per-step time of the full
    DP train step at 1/2/4/8 devices with a fixed per-device batch, plus a
    no-collective control at 8 devices. Prints one JSON line
    {"t": {"1": s, ...}, "t_nosync8": s}."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import MnistConvNet
    from horovod_tpu.parallel import dp, mesh as mesh_lib

    model = MnistConvNet(dtype=jnp.float32)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 28, 28, 1)))["params"]
    opt = optax.sgd(0.01, momentum=0.9)

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["image"],
                             train=False)
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]).mean()
        return loss, {}

    def local_step(params, opt_state, batch, rng):
        # the no-collective control: same compute, grads stay local
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch, rng)
        updates, new_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_state, loss

    rs = np.random.RandomState(0)
    per_dev = 64

    def time_step(step, mesh, batch):
        p = dp.replicate(params, mesh)
        s = dp.replicate(opt.init(params), mesh)
        for _ in range(3):
            out = step(p, s, batch, jax.random.key(1))
            p, s = out[0], out[1]
        jax.block_until_ready(p)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(10):
                out = step(p, s, batch, jax.random.key(1))
                p, s = out[0], out[1]
            jax.block_until_ready(p)
            best = min(best, (time.perf_counter() - t0) / 10)
        return best

    times = {}
    t_nosync8 = None
    for n in (1, 2, 4, 8):
        mesh = mesh_lib.data_parallel_mesh(jax.devices("cpu")[:n])
        b = per_dev * n
        batch = {
            "image": dp.shard_batch(
                jnp.asarray(rs.rand(b, 28, 28, 1), jnp.float32), mesh),
            "label": dp.shard_batch(jnp.asarray(rs.randint(0, 10, b)),
                                    mesh),
        }
        step = dp.make_train_step(loss_fn, opt, mesh, donate=False)
        times[str(n)] = time_step(step, mesh, batch)
        if n == 8:
            nosync = jax.jit(jax.shard_map(
                local_step, mesh=mesh,
                in_specs=(P(), P(), P(("data",)), P()),
                out_specs=(P(), P(), P()), check_vma=False))
            t_nosync8 = time_step(nosync, mesh, batch)
    print(json.dumps({"t": times, "t_nosync8": t_nosync8}))


def _run_scaling_probe():
    """Launch the CPU-mesh probe in a clean subprocess (the parent owns the
    TPU backend; the probe needs a forced-host CPU platform). Returns
    (sweep_efficiency dict, raw overhead ratio) — unclamped."""
    env = dict(os.environ,
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=8").strip(),
               JAX_PLATFORMS="cpu")
    out = None
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--scaling-probe"],
            env=env, capture_output=True, timeout=900)
        line = out.stdout.decode().strip().splitlines()[-1]
        data = json.loads(line)
        t1 = data["t"]["1"]
        # Weak-scaling efficiency against the TRUE single-device baseline
        # at the same per-device batch: ideal weak scaling keeps per-step
        # time flat at t_1, so efficiency_n = t_1 / t_n. No core-count
        # rescaling — on a virtual CPU mesh whose devices contend for
        # physical cores this understates a real slice, which is the honest
        # direction; the context field carries the caveat. Values > 1.0
        # (timing jitter at small n) are reported only alongside an
        # explanation, never bare.
        sweep = {n: round(t1 / t, 3) for n, t in data["t"].items()}
        context = {
            "baseline": "single-device per-step time at the same "
                        "per-device batch (t_1 / t_n)",
            "physical_cores": os.cpu_count() or 1,
            "note": "virtual CPU devices contend for host cores, so large-n"
                    " figures lower-bound a real TPU slice",
        }
        gt1 = {n: e for n, e in sweep.items() if e > 1.0}
        if gt1:
            context["efficiency_gt_1"] = {
                "values": gt1,
                "explanation": "efficiency above 1.0 means the n-device step"
                               " timed FASTER per step than the single-device"
                               " baseline — on this virtual-device probe that"
                               " is timing jitter / cache effects, not real"
                               " superlinear scaling",
            }
        overhead = round(data["t_nosync8"] / data["t"]["8"], 3)
        return sweep, context, overhead
    except Exception as e:  # probe failure must not sink the headline metric
        print(f"scaling probe failed: {e!r}", file=sys.stderr)
        if out is not None:
            print(out.stderr.decode(errors="replace")[-2000:],
                  file=sys.stderr)
        return {}, {}, -1.0


def _bert_bench(mesh, n_dev, use_flash=False):
    """BASELINE config 3: BERT pretraining step with grouped/fused gradient
    allreduce + bf16 wire compression (reference protocol:
    docs/benchmarks.rst:67-83). Returns sequences/sec/chip. BERT-Base
    geometry at seq 128 — the largest config that fits comfortably beside
    the ResNet run in one CI bench invocation. ``use_flash`` routes
    attention through the Pallas flash kernel (ops/flash_attention.py)."""
    from horovod_tpu.jax.compression import Compression
    from horovod_tpu.models import BertBase
    from horovod_tpu.parallel import dp

    per_chip = BERT_BATCH
    model = BertBase(max_len=BERT_SEQ, use_flash=use_flash)
    rs = np.random.RandomState(0)
    tokens = jnp.asarray(rs.randint(0, 30522, (8, BERT_SEQ)))
    params = model.init(jax.random.key(0), tokens)["params"]
    opt = optax.adamw(1e-4)

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["labels"]).mean()
        return loss, {}

    step = dp.make_train_step(loss_fn, opt, mesh, donate=True,
                              compression=Compression.bf16)
    b = per_chip * n_dev
    batch = {
        "tokens": dp.shard_batch(
            jnp.asarray(rs.randint(0, 30522, (b, BERT_SEQ))), mesh),
        "labels": dp.shard_batch(
            jnp.asarray(rs.randint(0, 30522, (b, BERT_SEQ))), mesh),
    }
    p = dp.replicate(params, mesh)
    s = dp.replicate(opt.init(params), mesh)
    key = jax.random.key(1)
    for _ in range(WARMUP):
        out = step(p, s, batch, key)
        p, s = out.params, out.opt_state
    float(out.loss)
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            out = step(p, s, batch, key)
            p, s = out.params, out.opt_state
        float(out.loss)
        best = min(best, time.perf_counter() - t0)
    return round(b * ITERS / best / n_dev, 2)


def _flash_longcontext_bench():
    """Pallas flash kernel vs XLA dot attention at 8k tokens, causal — the
    long-context regime the kernel exists for. Returns the speedup (x)."""
    from horovod_tpu.ops.flash_attention import flash_attention

    B, T, H, D = 1, 8192, 12, 64
    rs = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rs.randn(B, T, H, D), jnp.bfloat16)
               for _ in range(3))

    def xla_attn(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(D)
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p,
                          v.astype(jnp.float32)).astype(q.dtype)

    iters = 30

    def chain(attn):
        def run(q, k, v):
            def body(i, x):
                return attn(x, k, v) * 0.5 + x * 0.5
            return jax.lax.fori_loop(0, iters, body, q)
        return jax.jit(run)

    times = {}
    for name, attn in (("flash",
                        lambda q, k, v: flash_attention(q, k, v,
                                                        causal=True)),
                       ("xla", xla_attn)):
        f = chain(attn)
        out = f(q, k, v)
        float(jnp.sum(out.astype(jnp.float32)))
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            out = f(q, k, v)
            float(jnp.sum(out.astype(jnp.float32)))
            best = min(best, (time.perf_counter() - t0) / iters)
        times[name] = best
    return round(times["xla"] / times["flash"], 2)


def _resnet_mode_bench(loss_fn, mesh, n_dev, params, batch_stats, batch,
                       batch_size, opt, *, sharded, compression,
                       bucket_bytes=0):
    """Measured images/sec/chip for one gradient-exchange mode — short
    windows (secondary figures; the headline keeps the long windows).
    ``bucket_bytes > 0`` measures the bucketed backward-overlap path."""
    import functools

    from horovod_tpu.parallel import dp, zero

    step = dp.make_stateful_train_step(loss_fn, opt, mesh, donate=True,
                                       sharded_update=sharded,
                                       compression=compression,
                                       bucket_bytes=bucket_bytes)
    init_opt = functools.partial(zero.sharded_opt_init,
                                 bucket_bytes=bucket_bytes) \
        if sharded else None
    rate, _ = _time_resnet(
        dp, step, mesh, params, batch_stats, opt, batch, n_dev, batch_size,
        warmup=3, iters=10, reps=2, init_opt_state=init_opt)
    return round(rate, 2)


def _make_resnet_batch(dp, mesh, rs, batch_size):
    return {
        "image": dp.shard_batch(
            jnp.asarray(rs.rand(batch_size, 224, 224, 3), jnp.bfloat16),
            mesh),
        "label": dp.shard_batch(
            jnp.asarray(rs.randint(0, 1000, batch_size)), mesh),
    }


def _time_resnet(dp, step, mesh, params, batch_stats, opt, batch, n_dev,
                 batch_size, *, warmup, iters, reps, init_opt_state=None):
    """Best-of-reps images/sec/chip for one (step, batch) config, starting
    from fresh replicated state (the donating step consumed the last).
    The ONE timing protocol every ResNet figure uses — headline, batch
    sweep and mode sweep — so the methodology (completion via
    ``block_until_ready``, best-of windows) cannot diverge between them.
    ``init_opt_state`` overrides the replicated opt init (the ZeRO mode
    passes ``zero.sharded_opt_init``)."""
    params_d = dp.replicate(params, mesh)
    opt_state = init_opt_state(opt, params, mesh) if init_opt_state \
        else dp.replicate(opt.init(params), mesh)
    state_d = dp.replicate(batch_stats, mesh)
    key = jax.random.key(1)
    for _ in range(warmup):
        out = step(params_d, opt_state, state_d, batch, key)
        params_d, opt_state, state_d = (out.params, out.opt_state,
                                        out.model_state)
    # dispatch is asynchronous: wait for the device before reading the clock
    jax.block_until_ready(out)
    best_dt = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = step(params_d, opt_state, state_d, batch, key)
            params_d, opt_state, state_d = (out.params, out.opt_state,
                                            out.model_state)
        jax.block_until_ready(out)
        best_dt = min(best_dt, time.perf_counter() - t0)
    final_state = (params_d, opt_state, state_d, batch, key)
    return batch_size * iters / best_dt / n_dev, final_state


def _sweep_resnet_batch(dp, get_step, mesh, params, batch_stats, opt, rs,
                        n_dev):
    """Pick the per-chip batch by measurement, not convention: short timed
    windows per candidate (each is its own XLA program, AOT-compiled once
    via ``get_step`` and reused by the headline run), OOMs recorded and
    skipped. Returns (chosen_batch_per_chip, {candidate: imgs/s/chip})."""
    results = {}
    for b in RESNET_BATCH_CANDIDATES:
        batch_size = b * n_dev
        batch = None
        try:
            batch = _make_resnet_batch(dp, mesh, rs, batch_size)
            rate, _ = _time_resnet(dp, get_step(batch, batch_size), mesh,
                                   params, batch_stats, opt,
                                   batch, n_dev, batch_size,
                                   warmup=3, iters=8, reps=2)
            results[str(b)] = round(rate, 2)
        except Exception as e:  # OOM or compile failure: candidate loses
            print(f"resnet batch {b} failed: {e!r}", file=sys.stderr)
            results[str(b)] = -1.0
        finally:
            del batch
    viable = {int(b): r for b, r in results.items() if r > 0}
    if not viable:
        raise RuntimeError(f"no ResNet batch candidate survived: {results}")
    chosen = max(viable, key=viable.get)
    return chosen, results


def main():
    from horovod_tpu.common.compile_cache import enable_compile_cache
    from horovod_tpu.models import ResNet50
    from horovod_tpu.parallel import dp, mesh as mesh_lib

    enable_compile_cache()
    devices = jax.devices()
    n_dev = len(devices)
    mesh = mesh_lib.data_parallel_mesh(devices)

    # Explicit conv-path mixed-precision policy (models/resnet.py): bf16
    # conv/matmul compute on the MXU, fp32 master weights AND fp32 BN
    # scale/bias/running-statistics (flax force-float32s the stat
    # reductions), NHWC layout, stem zero-padded 3 -> 8 channels so the 7x7
    # conv's input contraction stops misaligning the (8,128) tiling.
    resnet_policy = dict(dtype=jnp.bfloat16, param_dtype=jnp.float32,
                         input_layout="NHWC", pad_stem_to=8)
    model = ResNet50(num_classes=1000, **resnet_policy)
    rng = jax.random.key(0)
    init_images = jnp.zeros((8, 224, 224, 3), jnp.bfloat16)
    variables = model.init(rng, init_images, train=True)
    # Host-side snapshots: device_put may alias device buffers, and the
    # donating step invalidates them — each (re)replication below must start
    # from memory donation can't reach.
    params = jax.tree_util.tree_map(np.asarray, variables["params"])
    batch_stats = jax.tree_util.tree_map(
        np.asarray, variables.get("batch_stats", {}))
    opt = optax.sgd(0.05, momentum=0.9)

    def loss_fn(params, model_state, batch, rng):
        logits, new_model_state = model.apply(
            {"params": params, "batch_stats": model_state},
            batch["image"], train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["label"]).mean()
        return loss, (new_model_state["batch_stats"], {})

    # Donated buffers: params/opt_state/batch_stats update in place, saving
    # the per-step output allocations + copies in HBM.
    step = dp.make_stateful_train_step(loss_fn, opt, mesh, donate=True)

    # AOT-compile each batch shape exactly once and reuse the executable
    # for the sweep window, the headline run AND the MFU cost analysis —
    # jit's call-path cache is not shared with lower().compile(), so going
    # through jit here would pay a full second compile per shape.
    compiled_cache = {}

    def _aot_step(batch, batch_size):
        if batch_size not in compiled_cache:
            p = dp.replicate(params, mesh)
            s = dp.replicate(opt.init(params), mesh)
            st = dp.replicate(batch_stats, mesh)
            # .lower() forwards through the timed-step wrapper to the
            # raw jitted fn, so the compiled executable must be
            # re-wrapped for the step-time stats to reach the
            # engine_metrics BENCH field (cost_analysis still forwards).
            from horovod_tpu.metrics import timed_step
            compiled_cache[batch_size] = timed_step(step.lower(
                p, s, st, batch, jax.random.key(1)).compile(),
                framework="jax")
        return compiled_cache[batch_size]

    rs = np.random.RandomState(0)
    batch_per_chip, batch_sweep = _sweep_resnet_batch(
        dp, _aot_step, mesh, params, batch_stats, opt, rs, n_dev)
    batch_size = batch_per_chip * n_dev
    batch = _make_resnet_batch(dp, mesh, rs, batch_size)
    rate, _ = _time_resnet(
        dp, _aot_step(batch, batch_size), mesh, params, batch_stats, opt,
        batch, n_dev, batch_size, warmup=WARMUP, iters=ITERS, reps=REPS)

    if "scaling" in SKIP:
        sweep, sweep_context, overhead = {}, {}, -1.0
    else:
        sweep, sweep_context, overhead = _run_scaling_probe()

    # Gradient-exchange mode sweep: the ZeRO-1 sharded pipeline and the int8
    # quantized wire vs the stock paths, same model/batch (short windows).
    from horovod_tpu.jax.compression import Compression
    # (the fp32 allreduce figure is the primary metric above — only the
    # three modes it doesn't cover get extra compiles)
    modes = {
        "bf16_allreduce": dict(sharded=False, compression=Compression.bf16),
        "sharded_fp32": dict(sharded=True, compression=None),
        "sharded_int8": dict(sharded=True, compression=Compression.int8),
    }
    grad_sweep = {}
    for mode_name, kw in modes.items():
        if "modes" in SKIP:
            grad_sweep[mode_name] = -1.0
            continue
        try:
            grad_sweep[mode_name] = _resnet_mode_bench(
                loss_fn, mesh, n_dev, params, batch_stats, batch, batch_size,
                opt, **kw)
        except Exception as e:  # secondary figure must not sink the bench
            print(f"grad mode {mode_name} failed: {e!r}", file=sys.stderr)
            grad_sweep[mode_name] = -1.0
    # Headline BERT figure: XLA dot attention wins at seq 128 (tiny score
    # tiles). The use_flash=True variant measures the length ROUTER
    # (ops/flash_attention.attention): below HOROVOD_FLASH_MIN_SEQ it takes
    # the XLA path, so flash-BERT >= plain-BERT at seq 128 by construction;
    # the Pallas kernel's own win is the long-context figure below (1.5x at
    # 2k tokens, ~3.8x at 8k, measured on v5e).
    bert_seq_per_sec = bert_flash_seq_per_sec = -1.0
    if "bert" not in SKIP:
        try:
            bert_seq_per_sec = _bert_bench(mesh, n_dev, use_flash=False)
        except Exception as e:  # secondary figure must not sink the bench
            print(f"bert bench failed: {e!r}", file=sys.stderr)
        try:
            bert_flash_seq_per_sec = _bert_bench(mesh, n_dev, use_flash=True)
        except Exception as e:
            print(f"bert flash bench failed: {e!r}", file=sys.stderr)
    flash_speedup_8k = -1.0
    if "flash" not in SKIP:
        try:
            flash_speedup_8k = _flash_longcontext_bench()
        except Exception as e:
            print(f"flash long-context bench failed: {e!r}", file=sys.stderr)

    per_chip = rate
    peak = pmfu.peak_tflops()

    # MFU accounting via the shared profiler calculator: XLA cost analysis
    # of the exact compiled step (per-device SPMD module), cross-checked
    # against the analytic model — a >2x disagreement means the backend is
    # reporting something other than per-device model FLOPs, and the
    # analytic number (auditable) wins.
    analytic_per_image = pflops.resnet50_train_flops_per_image()
    local_batch = max(batch_size // n_dev, 1)
    # Cost-analyze the SAME executable the timed loop ran — no extra
    # compile (profiler.flops.executable_flops contract).
    ca_flops = pflops.executable_flops(compiled_cache.get(batch_size))
    if ca_flops:
        est = pflops.FlopsEstimate(
            ca_flops, "xla_cost_analysis",
            "cost_analysis() of the timed AOT executable")
    else:
        est = pflops.FlopsEstimate(
            analytic_per_image * local_batch, "analytic",
            "3 x 4.09 GFLOP/image (fwd + 2x-cost bwd)")
    flops_per_image = est.flops / local_batch if est.flops > 0 else -1.0
    flops_note = ""
    if est.source == "xla_cost_analysis" and analytic_per_image > 0 and \
            not (0.5 <= flops_per_image / analytic_per_image <= 2.0):
        flops_note = (f"cost_analysis gave {flops_per_image:.3e} "
                      f"FLOP/image vs analytic {analytic_per_image:.3e}; "
                      "using analytic (per-device attribution suspect)")
        flops_per_image = analytic_per_image
        est = pflops.FlopsEstimate(analytic_per_image * local_batch,
                                   "analytic", flops_note)
    # One provenance formatter (profiler.mfu.mfu_report) for the value +
    # its accounting, so this JSON and the tests share a report shape.
    mfu_accounting = pmfu.mfu_report(
        per_chip, pflops.FlopsEstimate(flops_per_image, est.source,
                                       est.detail), peak)
    resnet_mfu = mfu_accounting["mfu"]
    bert_mfu = round(pmfu.mfu(bert_seq_per_sec, BERT_TRAIN_FLOPS_PER_SEQ,
                              peak), 4) \
        if peak > 0 and bert_seq_per_sec > 0 else -1.0

    method = (
        f"per-chip batch swept over {list(RESNET_BATCH_CANDIDATES)} "
        f"(short windows, best throughput wins; chosen={batch_per_chip}); "
        f"MFU = imgs/s/chip * FLOPs/image / bf16 peak, FLOPs/image from "
        f"{est.source}"
        + (f" ({flops_note})" if flops_note else "")
        + f"; policy: bf16 conv/matmul, fp32 params + BN stats, NHWC, "
          f"stem padded 3->8 channels")
    if 0 < resnet_mfu < 0.30:
        method += (
            "; remaining blocker: conv path is memory-bandwidth-bound "
            "between matmul-shaped stages (BN+ReLU elementwise traffic "
            "around the 1x1 convs) — see the merged profiler trace "
            "(docs/DESIGN.md profiler section) for the per-stage "
            "attribution")
    resnet_config = {
        "batch_per_chip": batch_per_chip,
        "batch_sweep_images_per_sec_per_chip": batch_sweep,
        "layout": "NHWC",
        "compute_dtype": "bfloat16",
        "param_dtype": "float32",
        "bn_stats_dtype": "float32",
        "stem_pad_channels_to": 8,
        "donate_buffers": True,
        "mfu_accounting": mfu_accounting,
        "method": method,
    }
    # One shared formula (parallel/zero.py) for the wire-byte accounting so
    # tests, docs, and this bench can't drift apart. N_REF = 8: the slice
    # size the multichip dryruns and scaling probe use.
    from horovod_tpu.parallel import zero
    N_REF = 8

    def _bytes(mode, wire):
        return zero.collective_bytes_per_step(
            int(RESNET50_PARAMS), N_REF, mode=mode, wire_bytes_per_elem=wire)

    fp32_allreduce_bytes = _bytes("allreduce", 4.0)
    coll_bytes = {
        "formula": "2*(N-1)/N * wire_payload bytes per replica per phase "
                   "pair (reduce-scatter + all-gather); int8 payloads add "
                   "one fp32 scale per 256-element block on each phase",
        "world_size": N_REF,
        "resnet50_fp32_allreduce": fp32_allreduce_bytes,
        "resnet50_bf16_allreduce": _bytes("allreduce", 2.0),
        "resnet50_int8_allreduce": _bytes("allreduce", 1.0),
        "resnet50_sharded_fp32": _bytes("sharded", 4.0),
        "resnet50_sharded_bf16": _bytes("sharded", 2.0),
        "resnet50_sharded_int8": _bytes("sharded", 1.0),
        "bert_base_bf16_allreduce": zero.collective_bytes_per_step(
            int(BERT_BASE_PARAMS), N_REF, mode="allreduce",
            wire_bytes_per_elem=2.0),
    }
    coll_bytes["reduction_vs_fp32_allreduce"] = {
        k: round(fp32_allreduce_bytes / v, 2)
        for k, v in coll_bytes.items()
        if isinstance(v, int) and k.startswith("resnet50") and v > 0
    }

    # Engine + frontend telemetry snapshot: the perf trajectory records
    # cache hit rate / fusion efficiency / step-time stats alongside img/s
    # (ISSUE 3 acceptance: engine_metrics field in BENCH json). Single-chip
    # CI runs have no engine (size 1) — the field is then frontend-only.
    from horovod_tpu.metrics import bench_snapshot
    try:
        engine_metrics = bench_snapshot()
    except Exception as e:  # telemetry must not sink the bench
        print(f"metrics snapshot failed: {e!r}", file=sys.stderr)
        engine_metrics = {"error": repr(e)}

    # Measured ResNet per-step wall time, shared by the two overhead
    # accountings below (one derivation, not two drifting copies).
    resnet_step_sec = batch_per_chip / rate if rate > 0 else None

    # Flight-recorder overhead (ISSUE 5 acceptance: the always-on black box
    # must cost <1% of step time). ns/Record measured on-vs-off through the
    # C API; a collective costs ~5 lifecycle events, and an eager-path step
    # rarely exceeds ~200 collectives, so 1000 records/step is the
    # conservative scale factor against the measured ResNet step time.
    try:
        from horovod_tpu.engine import bindings as engine_bindings
        on_ns = min(engine_bindings.bench_flight_record(200_000)
                    for _ in range(3))
        off_ns = min(engine_bindings.bench_flight_record(200_000,
                                                         enabled=False)
                     for _ in range(3))
        records_per_step = 1000
        step_sec = resnet_step_sec
        delta_ns = max(0.0, on_ns - off_ns)
        flight_overhead = {
            "ns_per_record_on": round(on_ns, 2),
            "ns_per_record_off": round(off_ns, 2),
            "assumed_records_per_step": records_per_step,
            "resnet_step_seconds": round(step_sec, 6) if step_sec else None,
            "overhead_pct_of_step": round(
                100.0 * delta_ns * 1e-9 * records_per_step / step_sec, 5)
            if step_sec else None,
            "budget_pct": 1.0,
        }
    except Exception as e:  # telemetry must not sink the bench
        print(f"flight-recorder bench failed: {e!r}", file=sys.stderr)
        flight_overhead = {"error": repr(e)}

    # Step-time attribution (ISSUE 7 acceptance: per-model compute /
    # exposed-comm / stall decomposition + critical-path rank, and the
    # attributor's measured per-step cost against its 1% budget). The
    # block is the input contract for the ROADMAP autotuner PR.
    try:
        from horovod_tpu.obs import attribution as obs_attribution
        step_secs = {}
        if resnet_step_sec:
            step_secs["resnet50"] = resnet_step_sec
        if bert_seq_per_sec > 0:
            step_secs["bert_base"] = BERT_BATCH / bert_seq_per_sec
        step_attribution = obs_attribution.bench_block(step_secs)
    except Exception as e:  # telemetry must not sink the bench
        print(f"step attribution failed: {e!r}", file=sys.stderr)
        step_attribution = {"error": repr(e)}

    # Serving plane (ISSUE 8 acceptance: `serving` block with p50/p99 +
    # throughput at >=3 offered-load points incl. one past saturation, and
    # the int8-activation vs fp32 wire-byte savings). Local serving stack
    # over this host's devices; the cross-host regime is the same code via
    # serve/worker.py + HOROVOD_SERVING_MODE.
    if "serving" in SKIP:
        serving = {"skipped": True}
    else:
        try:
            serving = _serving_bench()
        except Exception as e:  # serving bench must not sink the training
            print(f"serving bench failed: {e!r}", file=sys.stderr)
            serving = {"error": repr(e)}

    # Serving fast path (ISSUE 16 acceptance: `serving_fastpath` block —
    # goodput of the paged-KV cache + prefix reuse + speculative decode
    # vs the recompute batcher on the seeded shared-prefix trace, at the
    # deadline-fixed p99 bound, with spec greedy token-identity checked
    # live).
    if "serving_fastpath" in SKIP:
        serving_fastpath = {"skipped": True}
    else:
        try:
            serving_fastpath = _serving_fastpath_bench()
        except Exception as e:  # must not sink the training bench
            print(f"serving fastpath bench failed: {e!r}", file=sys.stderr)
            serving_fastpath = {"error": repr(e)}

    # Traffic-driven autoscaling (ISSUE 15 acceptance: `autoscale` block —
    # diurnal + flash-crowd traces through the real Autoscaler closed
    # loop, a chaos kill injected mid-resize, p99 held within the SLO
    # bound, accepted-request loss pinned at zero, and a fleet trace
    # showing scale-up AND drain-based scale-down with no flapping).
    if "autoscale" in SKIP:
        autoscale_block = {"skipped": True}
    else:
        try:
            autoscale_block = _autoscale_bench()
        except Exception as e:  # must not sink the training bench
            print(f"autoscale bench failed: {e!r}", file=sys.stderr)
            autoscale_block = {"error": repr(e)}

    # Elastic resize (ISSUE 9 acceptance: `elastic` block — recovery time
    # after a kill, resize cost in seconds + wire bytes for 8→7 and 7→8,
    # checkpoint-restore vs live-reshard comparison).
    if "elastic" in SKIP:
        elastic_block = {"skipped": True}
    else:
        try:
            elastic_block = _elastic_bench()
        except Exception as e:  # must not sink the training bench
            print(f"elastic bench failed: {e!r}", file=sys.stderr)
            elastic_block = {"error": repr(e)}

    # Control-plane availability (ISSUE 10 acceptance: `control_plane`
    # block — driver recovery time, KV replay seconds vs WAL size,
    # headless-mode duration during the kill drill).
    if "control_plane" in SKIP:
        control_plane = {"skipped": True}
    else:
        try:
            control_plane = _control_plane_bench()
        except Exception as e:  # must not sink the training bench
            print(f"control-plane bench failed: {e!r}", file=sys.stderr)
            control_plane = {"error": repr(e)}

    # Autotuner + bucketed overlap (ISSUE 11 acceptance: `tuning` block —
    # before/after exposed-comm on the CPU closed loop, converged knob
    # values, search trace length, and before/after MFU of the bucketed
    # ResNet path on this bench's accelerator).
    if "tuning" in SKIP:
        tuning = {"skipped": True}
    else:
        try:
            def _measure_resnet_bucketed(bb):
                return _resnet_mode_bench(
                    loss_fn, mesh, n_dev, params, batch_stats, batch,
                    batch_size, opt, sharded=False, compression=None,
                    bucket_bytes=bb)

            def _mfu_of_rate(rate_after):
                return round(pmfu.mfu(rate_after, flops_per_image, peak),
                             4) if peak > 0 and flops_per_image > 0 \
                    else None

            tuning = _tuning_bench(
                measure_resnet=_measure_resnet_bucketed,
                resnet_mfu_before=resnet_mfu,
                mfu_of_rate=_mfu_of_rate)
        except Exception as e:  # must not sink the training bench
            print(f"tuning bench failed: {e!r}", file=sys.stderr)
            tuning = {"error": repr(e)}

    print(json.dumps({
        "metric": "resnet50_synthetic_train_images_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_PER_DEVICE, 3),
        "scaling_sweep_weak_efficiency": sweep,
        "scaling_sweep_context": sweep_context,
        "grad_exchange_sweep_images_per_sec_per_chip": grad_sweep,
        "collective_overhead_ratio_8dev": overhead,
        "resnet50_mfu_vs_bf16_peak": resnet_mfu,
        "resnet_config": resnet_config,
        "bert_base_bf16comp_seqs_per_sec_per_chip": bert_seq_per_sec,
        "bert_base_mfu_vs_bf16_peak": bert_mfu,
        "bert_base_flash_attention_seqs_per_sec_per_chip":
            bert_flash_seq_per_sec,
        "flash_attention_8k_causal_speedup_vs_xla": flash_speedup_8k,
        "collective_bytes_per_step_per_replica": coll_bytes,
        "engine_metrics": engine_metrics,
        "flight_recorder_overhead": flight_overhead,
        "step_attribution": step_attribution,
        "serving": serving,
        "serving_fastpath": serving_fastpath,
        "autoscale": autoscale_block,
        "elastic": elastic_block,
        "control_plane": control_plane,
        "tuning": tuning,
        "device_kind": jax.devices()[0].device_kind,
    }))


def _elastic_bench():
    """The BENCH ``elastic`` block: measured cost of checkpoint-free
    resize at ResNet-50 optimizer-state scale.

    Method: a synthetic Adam-shaped state (m+v rows over RESNET50_PARAMS
    fp32 elements) is laid out on the ZeRO-1 flat-shard geometry at 8
    ranks; for 8→7 (one rank drains) and 7→8 (one joiner) the full
    old→new transfer plan executes in-process for EVERY rank (pack →
    exchange → unpack), so the reported seconds are the whole cluster's
    CPU cost of a resize on one host, and the wire bytes come from the
    same formula the runtime metrics use (zero.reshard_wire_bytes). The
    checkpoint-restore comparison prices the legacy path the same way:
    rank 0 re-broadcasting the full replicated state to every other rank.
    The recovery figure is the end-to-end wall time of a simulated kill →
    plan → transfer → resume (buddy-sourced dead shard), the quantity
    ``hvd_elastic_recovery_seconds`` tracks in production.
    """
    from horovod_tpu.parallel import zero

    n_params = int(RESNET50_PARAMS)
    rows = {"float32": 2}  # Adam: m + v
    template = [np.zeros(n_params, np.float32)]
    rng = np.random.RandomState(0)

    def shards_at(world):
        g = zero._group_leaves(template, world, zero.LANE)[0]
        full = np.zeros((2, g.padded), np.float32)
        full[:, :n_params] = rng.randn(2, n_params).astype(np.float32)
        return g, {r: {g.key: full[:, r * g.shard:(r + 1) * g.shard]}
                   for r in range(world)}

    def run_resize(old, new, sources, quantized=False):
        # pack and unpack each run exactly ONCE per rank inside the timed
        # window (calling zero.reshard here would re-pack internally and
        # double-count serialization against the reported seconds); the
        # segment plans and sinks are the same code the runtime uses
        g, shards = shards_at(old)
        plan = zero.reshard_plan(template, old, new, zero.LANE)
        t0 = time.perf_counter()
        send = {}
        for me in range(new):
            for dst in range(new):
                segs = plan.segments_for_pair(me, dst, sources)
                if segs:
                    send[(me, dst)] = zero.pack_segments(
                        plan, segs, lambda key, r: shards[r][key],
                        quantized)
        outs = []
        for me in range(new):
            stacks = {ng.key: np.zeros((rows[ng.key], ng.shard),
                                       np.float32)
                      for ng in plan.new_groups}
            for serving in range(new):
                segs = plan.segments_for_pair(serving, me, sources)
                if not segs:
                    continue

                def sink(key, off, chunk, _out=stacks):
                    if off is None:
                        return rows[key]
                    _out[key][:, off:off + chunk.shape[1]] = chunk
                    return None

                zero.unpack_segments(plan, segs, send[(serving, me)],
                                     sink, quantized)
            outs.append(stacks)
        dt = time.perf_counter() - t0
        wire = zero.reshard_wire_bytes(plan, sources, rows,
                                       quantized=quantized)
        return dt, wire, outs

    out = {}
    # 8→7: rank 7 drains; its shard is served by the handoff on rank 0
    src_8_7 = {r: r for r in range(7)}
    src_8_7[7] = 0
    # 7→8: everyone survives in place; rank 7 joins empty
    src_7_8 = {r: r for r in range(7)}
    for label, (old, new, sources) in {
            "resize_8_to_7": (8, 7, src_8_7),
            "resize_7_to_8": (7, 8, src_7_8)}.items():
        dt, wire, _ = run_resize(old, new, sources)
        _, wire_q, _ = run_resize(old, new, sources, quantized=True)
        out[label] = {
            "seconds": round(dt, 4),
            "wire_bytes": int(wire),
            "wire_bytes_int8": int(wire_q),
            "int8_reduction": round(wire / wire_q, 2) if wire_q else None,
        }

    # legacy path: roll back to the in-memory checkpoint and re-broadcast
    # the FULL replicated state from rank 0 to every other rank
    g8 = zero._group_leaves(template, 8, zero.LANE)[0]
    checkpoint_bytes = 2 * g8.padded * 4 * (8 - 1)
    live_bytes = out["resize_8_to_7"]["wire_bytes"]
    out["checkpoint_restore_bytes"] = int(checkpoint_bytes)
    out["live_reshard_bytes"] = int(live_bytes)
    out["reduction_vs_checkpoint_restore"] = \
        round(checkpoint_bytes / live_bytes, 2) if live_bytes else None

    # recovery after a hard kill: old rank 3 dies, survivors {0,1,2,4..7}
    # renumber to 0..6, and the dead shard is served by its ring buddy
    # (old rank 4, now new rank 3) — plan + transfer + resume
    survivors = [r for r in range(8) if r != 3]
    src_kill = {old: new for new, old in enumerate(survivors)}
    src_kill[3] = src_kill[4]  # buddy replica serves the dead shard
    # run_resize's internal timer brackets exactly pack->exchange->unpack;
    # timing around the call would also charge the synthetic state
    # generation (~200MB of randn) — pure benchmark fixture, not recovery
    dt_kill, wire_kill, _ = run_resize(8, 7, src_kill)
    from horovod_tpu.common.env_registry import env_float
    out["kill_recovery"] = {
        "recovery_seconds": round(dt_kill, 4),
        "wire_bytes": int(wire_kill),
        "bound_seconds": env_float(
            "HOROVOD_ELASTIC_RECOVERY_BOUND_SECONDS"),
    }
    out["method"] = (
        f"Adam-shaped state (m+v, {n_params} fp32 params) on the ZeRO-1 "
        "flat-shard layout; every rank's pack->exchange->unpack executed "
        "in-process, so seconds = whole-cluster resize CPU cost on one "
        "host; wire bytes from zero.reshard_wire_bytes (the runtime "
        "hvd_resize_bytes formula); checkpoint comparison = full-state "
        "broadcast from rank 0 to N-1 ranks")
    return out


def _control_plane_bench():
    """The BENCH ``control_plane`` block: the measured cost of losing and
    recovering the control plane (ISSUE 10).

    Method: a durable rendezvous KV is loaded with a realistic key count
    (topology records + worker state + heartbeats for a 64-rank job,
    cycled to grow the WAL), a worker-shaped heartbeat loop runs against
    it, and the server is killed and respawned the way the supervisor
    respawns a crashed driver (same port, WAL replay, epoch bump). The
    reported recovery time is kill → first post-recovery heartbeat ack —
    the same quantity ``hvd_driver_recovery_seconds`` tracks — and the
    headless duration is the gap between the last pre-kill ack and that
    first post-recovery ack, i.e. what ``hvd_driver_unreachable_seconds``
    peaks at during the drill.
    """
    import tempfile
    import threading
    from horovod_tpu.common import kv_keys
    from horovod_tpu.runner.http_kv import KVClient, KVServer

    out = {}
    with tempfile.TemporaryDirectory() as d:
        kv = KVServer(kv_dir=d).start()
        epoch_before = kv.epoch
        # 64-rank-shaped control state: topology + worker state +
        # heartbeats, re-written over several generations so the WAL
        # carries realistic churn (not just a minimal snapshot)
        for gen in range(4):
            for rank in range(64):
                kv.put_json(
                    kv_keys.rank_and_size(gen, f"host{rank // 8}",
                                          rank % 8),
                    {"rank": rank, "size": 64, "controller_addr": "h0",
                     "controller_port": 4242,
                     "controller_data_port": 4243, "epoch": 1},
                    epoch=epoch_before)
                # worker-shaped records: epoch-less by design (workers
                # never claim driver authority)
                # hvd-lint: disable=HVL008
                kv.put_json(kv_keys.worker_state(gen, f"host{rank // 8}",
                                                 rank % 8),
                            {"state": "READY", "ts": time.time()})
                # hvd-lint: disable=HVL008
                kv.put_json(kv_keys.worker_heartbeat(f"host{rank // 8}",
                                                     rank % 8),
                            {"pid": 1000 + rank, "rank": rank,
                             "ts": time.time()})
            kv.put_json(kv_keys.generation(),
                        {"generation": gen, "epoch": 1},
                        epoch=epoch_before)
        wal_bytes = kv.wal_bytes
        n_keys = len(kv.keys())
        port = kv.port

        # worker-shaped heartbeat probe: short total deadline per beat
        acks, stop = [], threading.Event()

        def beat_loop():
            client = KVClient("127.0.0.1", port)
            while not stop.is_set():
                try:
                    # hvd-lint: disable=HVL008 — worker-shaped beat
                    client.put_json(kv_keys.worker_heartbeat("bench", 0),
                                    {"pid": 1, "ts": time.time()},
                                    timeout=0.5, attempts=1, deadline=0.5)
                    acks.append(time.monotonic())
                except Exception:  # noqa: BLE001 — the outage under test
                    pass
                time.sleep(0.02)

        t = threading.Thread(target=beat_loop, daemon=True)
        t.start()
        # wait for the probe's first landed ack (a fixed sleep flakes on
        # a loaded machine and IndexErrors the whole block)
        warm_deadline = time.monotonic() + 10.0
        while not acks and time.monotonic() < warm_deadline:
            time.sleep(0.01)
        if not acks:
            raise RuntimeError("heartbeat probe never reached the KV")
        time.sleep(0.2)
        last_ack_before = acks[-1]
        t_kill = time.monotonic()
        kv.stop()  # SIGKILL-equivalent: per-record WAL flush, no snapshot
        time.sleep(0.2)  # supervisor restart backoff
        kv2 = KVServer(port=port, kv_dir=d).start()
        deadline = time.monotonic() + 10.0
        while (not acks or acks[-1] <= t_kill) and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        stop.set()
        t.join(timeout=2)
        first_ack_after = next((a for a in acks if a > t_kill), None)
        out = {
            "kv_keys": n_keys,
            "kv_wal_bytes": int(wal_bytes),
            "kv_replay_seconds": round(kv2.replay_seconds, 4),
            "driver_recovery_seconds":
                round(first_ack_after - t_kill, 4)
                if first_ack_after else None,
            "headless_seconds":
                round(first_ack_after - last_ack_before, 4)
                if first_ack_after else None,
            "epoch_before": epoch_before,
            "epoch_after": kv2.epoch,
            "recovered_keys": len(kv2.keys()),
        }
        # >=: the probe's own heartbeat key lands after the count
        assert out["recovered_keys"] >= n_keys, \
            "KV replay lost keys during the bench drill"
        kv2.stop()
    out["method"] = (
        "durable KV loaded with 64-rank topology/state/heartbeat keys "
        "over 4 generations; server killed and respawned on the same "
        "port (supervisor restart backoff 0.2s); recovery = kill -> "
        "first post-recovery heartbeat ack from a worker-shaped probe "
        "(20ms beat, 0.5s total-deadline PUTs); headless = last pre-kill "
        "ack -> first post-recovery ack; replay seconds from the "
        "hvd_kv_replay_seconds gauge's source")
    return out


def _telemetry_bench():
    """The BENCH ``telemetry`` block (ISSUE 18): the measured win of the
    tiered scrape plane at 1024 ranks / 32 hosts, and the cost of
    end-to-end request tracing at three sample rates.

    Method, scrape leg: 1024 live ``MetricsExporter`` endpoints (32
    fake-worker ranks per host, distinct counters/histograms/gauges per
    rank) behind 32 real ``HostAggregator`` instances, all announced to
    a real rendezvous KV exactly the way workers announce themselves.
    Both paths run the production ``TieredScrape.heartbeat`` — the
    direct leg with a KV view that hides ``agg_addr`` records (forcing
    the per-rank fallback, 1024 HTTP GETs), the tiered leg with the
    full KV (32 ``/agg.json`` GETs). Wall time is the best of 3 beats
    after a baseline-establishing warm beat. Counter-total fidelity is
    asserted byte-identical: every counter family summed over all 1024
    direct ``/metrics.json`` scrapes vs summed over the 32 host
    aggregates, compared as sorted JSON (the fleet is static, and the
    fake counters are integer-valued, so float addition order cannot
    leak in).

    Method, tracing leg: the local continuous-batching stack (real
    batcher + ServingLoop on the TP LM step) driven closed-loop at
    sample rates 0 / 0.01 / 1.0 — the ingress mint (``maybe_trace``)
    plus every downstream span site is on the measured path, exactly
    as in production. Reported overhead is the p50 delta vs the
    sample=0 baseline.
    """
    import statistics
    import threading
    from horovod_tpu.common import kv_keys
    from horovod_tpu.metrics import MetricsExporter, record_step
    from horovod_tpu.metrics.aggregator import (HostAggregator,
                                                TieredScrape,
                                                counter_totals,
                                                merge_snapshots)
    from horovod_tpu.metrics.registry import MetricsRegistry
    from horovod_tpu.runner.http_kv import KVServer

    try:  # 1024 listening sockets: make sure the FD ceiling clears them
        import resource
        soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
        if soft < 4096:
            resource.setrlimit(resource.RLIMIT_NOFILE,
                               (min(4096, hard), hard))
    except Exception:  # noqa: BLE001 — best effort; default is usually fine
        pass

    n_hosts, per_host = 32, 32
    n_ranks = n_hosts * per_host
    kv = KVServer(port=0).start()
    exporters, aggregators = [], []
    slots = []
    out = {"fleet": {"hosts": n_hosts, "ranks_per_host": per_host,
                     "ranks": n_ranks}}
    try:
        for h in range(n_hosts):
            host = f"host{h:02d}"
            targets = []
            for lr in range(per_host):
                rank = h * per_host + lr
                reg = MetricsRegistry()
                record_step("jax", 0.05 + 0.001 * (rank % 16),
                            registry=reg)
                # integer-valued counters: the byte-identity check must
                # not hinge on float addition order
                reg.counter("hvd_step_anomaly_total").inc(rank % 3)
                reg.counter("hvd_engine_responses_total").inc(10 + rank)
                reg.gauge("hvd_engine_queue_depth").set(lr % 4)
                e = MetricsExporter(reg, port=0,
                                    labels={"rank": str(rank)}).start()
                exporters.append(e)
                # hvd-lint: disable=HVL008 — worker-shaped announce
                kv.put_json(kv_keys.metrics_addr(host, lr),
                            {"addr": "127.0.0.1", "port": e.port,
                             "rank": rank})
                targets.append({"rank": rank, "local_rank": lr,
                                "addr": "127.0.0.1", "port": e.port})
                slots.append((host, lr))
            agg = HostAggregator(targets, host=host)
            agg.refresh()  # synchronous pass: deterministic, no thread
            aggregators.append(agg)
            # production hosting: local_rank 0's exporter serves /agg.json
            exporters[h * per_host].aggregator = agg
            # hvd-lint: disable=HVL008 — worker-shaped announce
            kv.put_json(kv_keys.agg_addr(host),
                        {"addr": "127.0.0.1",
                         "port": exporters[h * per_host].port,
                         "host": host, "local_size": per_host})

        def hide_agg(key):
            m = kv_keys.match(key)
            if m is not None and m[0] == "agg_addr":
                return None  # aggregator tier invisible: direct fallback
            return kv.get_json(key)

        def beat_wall(scrape, reps=3):
            prev_m, prev_a = {}, {}
            scrape.heartbeat(slots, prev_m, prev_a)  # establish baselines
            best, result = float("inf"), None
            for _ in range(reps):
                t0 = time.perf_counter()
                result = scrape.heartbeat(slots, prev_m, prev_a)
                best = min(best, time.perf_counter() - t0)
            return best, result

        direct_wall, direct_res = beat_wall(TieredScrape(hide_agg))
        # fleet setup takes longer than HOROVOD_AGG_STALE_SECONDS; in
        # production the background loop refreshes every second — one
        # synchronous pass stands in for it right before the tiered leg
        for agg in aggregators:
            agg.refresh()
        tiered_wall, tiered_res = beat_wall(TieredScrape(kv.get_json))
        assert len(direct_res.fallback_hosts) == n_hosts
        assert len(tiered_res.agg_hosts) == n_hosts

        # counter-total fidelity on the static fleet: all-rank direct
        # merge vs merge of the 32 host aggregates, byte-compared
        import urllib.request
        direct_snaps = []
        for e in exporters:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{e.port}/metrics.json",
                    timeout=2.0) as resp:
                snap = json.loads(resp.read())
            direct_snaps.append((int(snap["labels"]["rank"]), snap))
        totals_direct = counter_totals(merge_snapshots(direct_snaps))
        totals_tiered = counter_totals(merge_snapshots(
            [(h, aggregators[h].payload()["merged"])
             for h in range(n_hosts)]))
        bytes_direct = json.dumps(totals_direct, sort_keys=True)
        bytes_tiered = json.dumps(totals_tiered, sort_keys=True)
        assert bytes_direct == bytes_tiered, \
            "tiered counter totals diverged from the direct scrape"

        ratio = tiered_wall / direct_wall if direct_wall > 0 else None
        out["scrape"] = {
            "direct_wall_seconds": round(direct_wall, 4),
            "tiered_wall_seconds": round(tiered_wall, 4),
            "tiered_vs_direct_ratio": round(ratio, 4),
            "ratio_bound": 0.25,
            "ratio_pass": bool(ratio is not None and ratio <= 0.25),
            "http_gets_direct": n_ranks,
            "http_gets_tiered": n_hosts,
            "counter_totals_byte_identical": True,
            "counter_families": len(totals_direct),
        }
    finally:
        kv.stop()
        for agg in aggregators:
            agg.stop()
        stoppers = [threading.Thread(target=e.stop) for e in exporters]
        for t in stoppers:
            t.start()
        for t in stoppers:
            t.join(timeout=10)

    # -- tracing overhead leg ------------------------------------------------
    from horovod_tpu.metrics.registry import MetricsRegistry as _Reg
    from horovod_tpu.obs import tracing
    from horovod_tpu.serve import (ContinuousBatcher, ServingLoop,
                                   make_tp_lm_step)

    step_fn, info = make_tp_lm_step(compression="none", vocab=256,
                                    hidden=64, mlp_dim=256, layers=2)
    reg = _Reg()
    batcher = ContinuousBatcher(max_batch=8, queue_depth=32,
                                default_deadline_ms=5000.0, max_len=128,
                                registry=reg)
    loop = ServingLoop(step_fn, batcher, registry=reg).start()
    tokens = [(7 * j) % 251 for j in range(16)]

    tracer_off = tracing.Tracer(sample=0.0)

    def run_one(tracer):
        tid = tracer.maybe_trace()  # the ingress mint, on-path
        t0 = time.perf_counter()
        req = batcher.submit(list(tokens), max_new_tokens=4, trace=tid)
        req.wait(10.0)
        req.result()
        return time.perf_counter() - t0

    def run_paired(n_pairs, tracer_on):
        # Alternate baseline/sampled requests within ONE steady-state
        # stream: both classes see the identical process conditions, so
        # the median difference isolates the tracing cost rather than
        # cross-block drift (which dwarfs a ~1% signal on a shared box).
        base, on = [], []
        for i in range(n_pairs * 2):
            if i % 2:
                on.append(run_one(tracer_on))
            else:
                base.append(run_one(tracer_off))
        return base, on

    def p50_p99(lats):
        return (statistics.median(lats) * 1e3,
                sorted(lats)[int(0.99 * len(lats))] * 1e3)

    rates = {}
    try:
        tracing.configure(sample=0.0)
        for _ in range(40):  # warm compiles + steady-state batcher
            run_one(tracer_off)
        base_lats = [run_one(tracer_off) for _ in range(300)]
        p50, p99 = p50_p99(base_lats)
        rates["0.0"] = {"p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
                        "spans_recorded": 0}
        for rate in (0.01, 1.0):
            tracer = tracing.configure(sample=rate,
                                       buffer_spans=1 << 15)
            paired_base, lats = run_paired(300, tracer)
            p50, p99 = p50_p99(lats)
            base_p50, _ = p50_p99(paired_base)
            spans = tracer.spans()
            entry = {
                "p50_ms": round(p50, 3),
                "p99_ms": round(p99, 3),
                "spans_recorded": len(spans),
                "p50_overhead_pct": round(
                    100.0 * (p50 - base_p50) / base_p50, 2),
            }
            if rate == 1.0:
                entry["span_kinds"] = sorted({s["name"] for s in spans})
            rates[str(rate)] = entry
    finally:
        loop.drain(timeout=10.0)
        loop.stop()
        tracing.configure()  # back to env-configured defaults

    out["tracing"] = {
        "requests_per_rate": 300,
        "rates": rates,
        "overhead_bound_pct_at_1pct": 1.0,
        "overhead_pass": bool(
            rates["0.01"]["p50_overhead_pct"] < 1.0),
    }
    out["method"] = (
        "scrape: 1024 live exporter endpoints (32 ranks x 32 hosts, "
        "integer-valued fake counters) behind 32 real HostAggregators, "
        "announced to a real rendezvous KV; both legs run the production "
        "TieredScrape.heartbeat — direct with agg_addr records hidden "
        "(1024 /metrics.json GETs), tiered with the full KV (32 "
        "/agg.json GETs); best of 3 beats after a warm beat; counter "
        "totals byte-compared as sorted JSON over all families. "
        "tracing: closed-loop requests through the real batcher + "
        "ServingLoop with the ingress sampling mint on-path; per rate, "
        "300 sampled requests interleaved 1:1 with 300 sample=0 "
        "baseline requests in one stream (paired medians cancel "
        "cross-block drift); p50 delta vs the in-stream baseline")
    return out


def _autoscale_bench():
    """The BENCH ``autoscale`` block: the full closed loop from offered
    load to fleet size (serve/autoscale_smoke.py — real Autoscaler, real
    router, epoch-claimed KV decision records).

    Method: a flash-crowd trace (base load, a crowd ~2.4x one worker's
    capacity, recession) with a chaos kill dropped on the original worker
    WHILE the scale-up resize is in flight — the router re-routes its
    in-flight requests and the fleet re-grows; and a diurnal staircase
    with no chaos. Acceptance per trace: accepted-request loss == 0
    (429s/sheds are backpressure, not loss), every completed-load
    window's p99 inside the SLO bound, at least one scale-up AND one
    drain-based scale-down in the decision log, and no opposite-direction
    decisions inside one hysteresis window (no flapping)."""
    from horovod_tpu.serve.autoscale_smoke import run_smoke

    out = {}
    for trace, chaos in (("flash", True), ("diurnal", False)):
        r = run_smoke(trace=trace, chaos_kill=chaos, seconds_scale=3.0)
        fleet_sizes = [p["fleet"] for p in r["fleet_trace"]
                       if "fleet" in p]
        out[trace] = {
            "single_worker_capacity_qps": r[
                "single_worker_capacity_qps"],
            "p99_bound_ms": r["p99_bound_ms"],
            "windows": [{k: w[k] for k in (
                "offered_qps", "completed_ok", "rejected", "expired",
                "failed", "achieved_qps", "p50_ms", "p99_ms",
                "fleet_at_end")} for w in r["windows"]],
            "decisions": r["decisions"],
            "fleet_sizes": fleet_sizes,
            "fleet_max": r["fleet_max"],
            "chaos": r["chaos"],
            "rerouted": r["rerouted"],
            "accepted_loss": r["accepted_loss"],
            "max_p99_ms": r["max_p99_ms"],
            "acceptance": {
                "p99_within_bound": r["p99_within_bound"],
                "zero_accepted_loss": r["accepted_loss"] == 0,
                "scale_up_seen": r["scale_up_seen"],
                "scale_down_seen": r["scale_down_seen"],
                "no_flap": r["no_flap"],
            },
        }
    return out


def _serving_bench():
    """The BENCH ``serving`` block: offered-load sweep over a local
    continuous-batching stack running the tensor-parallel LM with int8
    activation collectives.

    Method: a high offered-load probe measures capacity (the achieved QPS
    when arrivals far outrun the server), then three open-loop windows at
    0.5x / 0.8x / well-past capacity (3x, floored at capacity + 25 qps —
    the probe under-reports capacity when deadline expiry dominates)
    record p50/p99 and throughput — the past-saturation point demonstrates
    graceful backpressure (bounded queue, immediate rejects, completed
    requests keep a deadline-bounded p99) rather than collapse. Wire-byte savings come from the shared TP accounting
    (parallel/tp.py), and the small-tensor cliff microbench pins the
    serving-mode express-lane win over fused-mode negotiation."""
    from horovod_tpu.metrics.registry import MetricsRegistry
    from horovod_tpu.serve import (ContinuousBatcher, ServingLoop,
                                   make_tp_lm_step)
    from horovod_tpu.serve import loadgen
    from horovod_tpu.serve.batcher import AdmissionRejected

    reg = MetricsRegistry()  # isolated: the training metrics stay clean
    step_fn, info = make_tp_lm_step(compression="int8", vocab=512,
                                    hidden=128, mlp_dim=512, layers=4)
    batcher = ContinuousBatcher(max_batch=8, queue_depth=16,
                                default_deadline_ms=1000.0, max_len=256,
                                registry=reg)
    loop = ServingLoop(step_fn, batcher, registry=reg).start()

    def make_payload(i):
        n = 8 * ((i % 3) + 1)  # 8/16/24-token prompts across buckets
        return {"tokens": [(7 * i + j) % 509 for j in range(n)],
                "max_new_tokens": 4}

    def submit(payload):
        try:
            req = batcher.submit(payload["tokens"],
                                 max_new_tokens=payload["max_new_tokens"])
        except AdmissionRejected:
            return {"status": "rejected"}
        req.wait(5.0)
        return req.result()

    try:
        loadgen.run_load(submit, 20.0, 1.0, make_payload)  # warm compiles
        probe = loadgen.run_load(submit, 400.0, 2.0, make_payload)
        capacity = max(probe["achieved_qps"], 1.0)
        # sub-/near-/past-saturation. The probe's achieved rate
        # under-reports capacity when deadline expiry dominates, so the
        # past point gets a hard floor well above anything this stack
        # sustains on a CPU host — the JSON must show the backpressure
        # knee, not a third comfortable point.
        points = [round(capacity * 0.5, 1), round(capacity * 0.8, 1),
                  round(max(capacity * 3.0, capacity + 25.0), 1)]
        sweep = loadgen.run_points(submit, make_payload, points,
                                   duration_sec=3.0)
    finally:
        loop.drain(timeout=10.0)
        loop.stop()
    past = sweep[-1]
    return {
        "model": {k: info[k] for k in ("vocab", "hidden", "mlp_dim",
                                       "layers", "tp_world",
                                       "compression")},
        "capacity_qps": capacity,
        "offered_load_sweep": sweep,
        "past_saturation_graceful": bool(
            past["rejected"] > 0 and past["completed_ok"] > 0),
        "activation_wire_bytes": info["wire"],
        "small_tensor_cliff": loadgen.small_tensor_cliff_report(iters=10),
    }


def _serving_fastpath_bench():
    """The BENCH ``serving_fastpath`` block (ISSUE 16): goodput of the
    paged-KV fast path vs today's recompute batcher on the seeded
    shared-prefix trace, at a fixed p99 bound.

    Method: both stacks run the SAME reference RNN LM weights — the
    baseline through the classic recompute StepFn (the pre-fast-path
    batcher: O(prompt+generated) work per emitted token), the fast path
    through the incremental CachedStep behind the block-paged cache
    (prefix state shared CoW across requests, draft proposals verified
    in one batched target step). The p99 bound is fixed by the shared
    request deadline: a request that cannot meet it expires and drops
    out of goodput, so the achieved ok-rate at a common offered load IS
    goodput at the bound. Speculative greedy output is checked
    token-identical to the baseline greedy path on a trace prompt before
    any load runs, and the no-silent-loss router contract + int8
    activation wire cut are covered by the `serving` block and
    tests/test_serving.py — this block changes neither path."""
    from horovod_tpu.metrics.registry import MetricsRegistry
    from horovod_tpu.serve import loadgen
    from horovod_tpu.serve.batcher import (AdmissionRejected,
                                           ContinuousBatcher)
    from horovod_tpu.serve.executor import ServingLoop, make_rnn_lm_step
    from horovod_tpu.serve.kv_cache import PagedKVCache

    hidden, vocab = 192, 256
    prefix_len, tail_len, new_tokens = 160, 16, 16
    deadline_ms = 1500.0
    trace = loadgen.shared_prefix_trace(
        seed=0, requests=512, tenants=4, prefix_len=prefix_len,
        tail_len=tail_len, max_new_tokens=new_tokens, vocab=vocab)
    step_fn, cached, draft, info = make_rnn_lm_step(hidden=hidden,
                                                    vocab=vocab)

    def build(fast):
        reg = MetricsRegistry()
        cache = PagedKVCache(block_tokens=16, pool_blocks=256,
                             registry=reg) if fast else None
        batcher = ContinuousBatcher(max_batch=8, queue_depth=32,
                                    default_deadline_ms=deadline_ms,
                                    max_len=256, registry=reg, cache=cache)
        loop = ServingLoop(step_fn, batcher, registry=reg,
                           cached_step=cached if fast else None,
                           draft_step=draft if fast else None,
                           spec_k=4).start()
        return reg, batcher, loop

    def submitter(batcher):
        def submit(payload):
            try:
                req = batcher.submit(
                    payload["tokens"],
                    max_new_tokens=payload["max_new_tokens"])
            except AdmissionRejected:
                return {"status": "rejected"}
            req.wait(deadline_ms / 1e3 + 2.0)
            return req.result()
        return submit

    def run_stack(fast, offered=None):
        reg, batcher, loop = build(fast)
        submit = submitter(batcher)
        try:
            # warm sequentially: per-tenant first requests publish the
            # shared prefixes (fast path) and prime both decode loops
            for t in range(4):
                submit(dict(trace[t]))
            probe = loadgen.run_load(submit, 200.0, 2.0,
                                     loadgen.trace_payload_fn(trace))
            window = loadgen.run_load(
                submit, offered, 3.0, loadgen.trace_payload_fn(trace)) \
                if offered is not None else None
        finally:
            loop.drain(timeout=10.0)
            loop.stop()
        out = {"capacity_qps": max(probe["achieved_qps"], 0.1),
               "probe": probe, "window": window}
        if fast:
            from horovod_tpu.metrics import snapshot_value
            snap = reg.snapshot()
            lookups = snapshot_value(snap,
                                     "hvd_serve_cache_lookups_total") or 0
            hits = snapshot_value(snap, "hvd_serve_cache_hits_total") or 0
            prop = snapshot_value(snap,
                                  "hvd_serve_spec_proposed_total") or 0
            acc = snapshot_value(snap, "hvd_serve_spec_accepted_total") or 0
            out["cache"] = {
                "hit_pct": round(100.0 * hits / lookups, 1)
                if lookups else None,
                "prefill_tokens_saved": snapshot_value(
                    snap, "hvd_serve_cache_prefill_tokens_saved_total"),
                "spec_accept_pct": round(100.0 * acc / prop, 1)
                if prop else None,
                "pool_balanced": batcher.cache.balanced(),
            }
        return out

    # spec-decode greedy identity on a trace prompt (baseline recompute
    # vs cached + speculative) — the acceptance pin, checked live
    def decode_once(fast):
        _, batcher, loop = build(fast)
        try:
            req = batcher.submit(trace[0]["tokens"],
                                 max_new_tokens=new_tokens)
            req.wait(10.0)
            return req.result()["tokens"]
        finally:
            loop.drain(timeout=10.0)
            loop.stop()

    base_toks, fast_toks = decode_once(False), decode_once(True)
    identical = base_toks == fast_toks and len(base_toks) > 0

    base = run_stack(False)
    # the matched window saturates BOTH stacks (offered above the fast
    # path's measured capacity), so each side's achieved ok-rate is its
    # goodput at the shared deadline-fixed p99 bound
    fast_probe = run_stack(True)
    offered = round(max(fast_probe["capacity_qps"] * 1.2,
                        base["capacity_qps"] * 4.0), 1)
    base_w = run_stack(False, offered=offered)["window"]
    fast_w = run_stack(True, offered=offered)["window"]
    ratio = round(fast_w["achieved_qps"] / base_w["achieved_qps"], 2) \
        if base_w["achieved_qps"] else None
    return {
        "model": dict(info, kind="rnn_reference_lm"),
        "trace": {"seed": 0, "tenants": 4, "prefix_len": prefix_len,
                  "tail_len": tail_len, "max_new_tokens": new_tokens},
        "deadline_ms_p99_bound": deadline_ms,
        "spec_greedy_token_identical": identical,
        "baseline_capacity_qps": base["capacity_qps"],
        "fastpath_capacity_qps": fast_probe["capacity_qps"],
        "fastpath_cache": fast_probe.get("cache"),
        "matched_offered_qps": offered,
        "baseline_window": base_w,
        "fastpath_window": fast_w,
        "goodput_ratio_at_p99_bound": ratio,
        "target_3x_met": bool(ratio is not None and ratio >= 3.0),
    }


def _tuning_bench(measure_resnet=None, resnet_mfu_before=None,
                  mfu_of_rate=None):
    """The BENCH ``tuning`` block (ISSUE 11): a bounded autotuner session
    on the CPU backend plus, when a resnet harness is supplied, the
    before/after MFU of the bucketed overlap path.

    The CPU record is a REAL closed loop — 2 loopback engine ranks, a
    ResNet-50-shaped gradient set submitted bucket-by-bucket, exposed-comm
    objective from the flight-ring step decomposition — measured with the
    tuner off (bucket_bytes=0, engine defaults) and then under the
    converged configuration (horovod_tpu/tune/smoke.py). ``measure_resnet
    (bucket_bytes) -> imgs/s/chip`` re-times the in-jit train step with
    the converged bucket bound so the block carries before/after MFU on
    whatever accelerator ran the bench."""
    from horovod_tpu.tune import smoke

    cpu = smoke.run_smoke(world=2, epoch_steps=5, samples=15,
                          warmup_epochs=1, scale=8)
    block = {
        "objective": "exposed-comm seconds (obs/attribution step "
                     "decomposition; wall-time fallback without an "
                     "engine)",
        "search": "coordinate sweep + neighbor refinement over "
                  "bucket_bytes / fusion threshold / cycle time / "
                  "express-lane class (horovod_tpu/tune/search.py)",
        "cpu_backend": cpu,
        "search_trace_len": cpu.get("search_trace_len"),
        "converged_config": cpu.get("converged_config"),
        "exposed_comm_drop_pct": cpu.get("exposed_comm_drop_pct"),
    }
    if measure_resnet is not None:
        # Measure exactly what the tuner converged to — bucket_bytes=0
        # ("bucketing off beat every bucket size") is a legitimate outcome
        # and must be reported as such, not silently swapped for a bound
        # the search rejected.
        cc = cpu.get("converged_config") or {}
        bb = int(cc.get("bucket_bytes", 0))
        try:
            rate_after = measure_resnet(bb)
            entry = {
                "bucket_bytes": bb,
                "images_per_sec_per_chip_after": rate_after,
                "mfu_before": resnet_mfu_before,
            }
            if mfu_of_rate is not None and rate_after and rate_after > 0:
                entry["mfu_after"] = mfu_of_rate(rate_after)
            block["resnet_bucketed_overlap"] = entry
        except Exception as e:  # secondary figure must not sink the block
            print(f"tuned resnet mode failed: {e!r}", file=sys.stderr)
            block["resnet_bucketed_overlap"] = {"error": repr(e)}
    return block


def _dataplane_bench():
    """The BENCH ``dataplane_topology`` block (ISSUE 14): a loopback
    algorithm sweep over the host data plane's routing space — star vs
    ring vs recursive-doubling vs hierarchical across 256B-64MiB at
    2/4/8 ranks, with 2-host simulated locality (block AND cyclic
    placements) and inter-host wire-byte accounting from the engine's
    ``data_{inter,intra}host_bytes`` counters.

    Acceptance figures (ISSUE 14): recursive-doubling mean latency <=
    0.6x star for <=4KiB allreduces at 8 ranks, and hierarchical
    inter-host bytes <= 0.30x the flat ring's at 8 ranks / 2 simulated
    hosts for >=1MiB payloads. The inter-host comparison is reported for
    BOTH placements: cyclic (ranks alternate hosts — the layout a
    topology-blind ring cannot avoid paying for, and the acceptance
    figure) and block (host-contiguous ranks, the friendly case, where
    the hierarchy still wins but by less). No TPU, no second process.
    """
    import threading
    import uuid

    from horovod_tpu.engine import bindings
    from horovod_tpu.engine.bindings import EngineSession

    lib = bindings.load_library()

    def run_all(sessions, fn):
        results = [None] * len(sessions)
        errors = [None] * len(sessions)

        def work(r):
            try:
                results[r] = fn(r, sessions[r])
            except Exception as e:  # noqa: BLE001
                errors[r] = e

        threads = [threading.Thread(target=work, args=(r,))
                   for r in range(len(sessions))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for e in errors:
            if e is not None:
                raise e
        return results

    def with_sessions(n, env, host_ids, fn):
        saved = {}
        for k, v in env.items():
            saved[k] = os.environ.get(k)
            os.environ[k] = v
        group = f"dpbench-{uuid.uuid4().hex[:8]}"
        sessions = [EngineSession(
            rank=r, size=n, transport="loopback", group=group,
            host_id=(host_ids[r] if host_ids else None),
            cycle_time_ms=5.0) for r in range(n)]
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            return fn(sessions)
        finally:
            for s in sessions:
                s._lib.hvdtpu_shutdown(s._session)
            for s in sessions:
                s.destroy()

    def time_allreduce(sessions, nbytes, iters, warmup=2):
        """Mean per-op wall seconds (max across ranks — a collective is
        done when its slowest rank is) over direct lockstep data-plane
        calls, plus the summed inter/intra-host wire-byte deltas."""
        elements = max(1, nbytes // 4)

        def snap(s):
            c = s.metrics()["counters"]
            return (c["data_interhost_bytes"], c["data_intrahost_bytes"])

        before = [snap(s) for s in sessions]

        def fn(r, s):
            buf = np.full(elements, float(r + 1), np.float32)
            for _ in range(warmup):
                rc = lib.hvdtpu_data_allreduce(
                    s._session, buf.ctypes.data, elements,
                    bindings.DTYPE_IDS["float32"], 0, 1.0, 1.0)
                assert rc == 0, lib.hvdtpu_last_error().decode()
            t0 = time.perf_counter()
            for _ in range(iters):
                rc = lib.hvdtpu_data_allreduce(
                    s._session, buf.ctypes.data, elements,
                    bindings.DTYPE_IDS["float32"], 0, 1.0, 1.0)
                assert rc == 0, lib.hvdtpu_last_error().decode()
            return (time.perf_counter() - t0) / iters

        per_rank = run_all(sessions, fn)
        after = [snap(s) for s in sessions]
        inter = sum(a[0] - b[0] for a, b in zip(after, before))
        intra = sum(a[1] - b[1] for a, b in zip(after, before))
        ops = warmup + iters
        return max(per_rank), inter / ops, intra / ops

    KB, MB = 1024, 1 << 20
    sizes = [256, 4 * KB, 64 * KB, 1 * MB, 16 * MB, 64 * MB]
    # env per algorithm: force the route regardless of payload size
    algo_env = {
        "star": {"HOROVOD_RING_THRESHOLD_BYTES": str(1 << 40)},
        "ring": {"HOROVOD_RING_THRESHOLD_BYTES": "1"},
        # rd is gated to the sub-lane class; raise the lane so the sweep
        # can show where the log2(p) route stops winning
        "rd": {"HOROVOD_SMALL_TENSOR_ALGO": "rd",
               "HOROVOD_LOW_LATENCY_THRESHOLD": str(1 << 40),
               "HOROVOD_RING_THRESHOLD_BYTES": str(1 << 40)},
        "hier": {"HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
                 "HOROVOD_LOW_LATENCY_THRESHOLD": "0"},
    }
    # bounded wall clock: fewer iters at bulk sizes
    iters_of = {256: 60, 4 * KB: 60, 64 * KB: 30, 1 * MB: 10,
                16 * MB: 3, 64 * MB: 2}
    # ring needs num_elements >= ranks; every swept size satisfies it.
    # hier needs a multi-host locality map -> only in host'd configs.
    sweep = {}
    for n in (2, 4, 8):
        hosts_block = [0 if r < n // 2 else 1 for r in range(n)]
        for algo in ("star", "ring", "rd", "hier"):
            host_ids = hosts_block if algo == "hier" else None
            for nbytes in sizes:
                lat, inter, intra = with_sessions(
                    n, algo_env[algo], host_ids,
                    lambda ss: time_allreduce(ss, nbytes,
                                              iters_of[nbytes]))
                sweep.setdefault(str(n), {}).setdefault(algo, {})[
                    str(nbytes)] = {
                    "mean_latency_us": round(lat * 1e6, 1),
                    "interhost_bytes_per_op": int(inter),
                    "intrahost_bytes_per_op": int(intra),
                }

    # acceptance 1: rd vs star latency for <=4KiB allreduces. The
    # structural win is critical-path shape: the star serializes 2(p-1)
    # frame handlings through the rank-0 hub while rd runs log2(p)
    # PARALLEL pairwise hops (2*log2(p) transfers per rank). Expressing
    # that in wall clock needs cores for the hops to be parallel ON —
    # a 1-core CI container scheduler-serializes all in-process ranks,
    # so both algorithms degenerate to their total context-switch count
    # and the measured 8-rank ratio saturates near 1.0. Both the
    # measured ratios (2/4/8 ranks) and the hub-serialization model are
    # reported; the 0.6x @ 8 ranks acceptance is met measured when the
    # host has cores to run hops in parallel, else carried as a
    # documented hardware gap (the BENCH_r06 precedent: the PR-11 MFU
    # figure awaited a TPU-attached container the same way).
    import math
    cores = os.cpu_count() or 1
    small = {"container_cores": cores}
    for n in (2, 4, 8):
        per_size = {}
        for nbytes in (256, 1 * KB, 4 * KB):
            star_lat, _, _ = with_sessions(
                n, algo_env["star"], None,
                lambda ss: time_allreduce(ss, nbytes, 150))
            rd_lat, _, _ = with_sessions(
                n, algo_env["rd"], None,
                lambda ss: time_allreduce(ss, nbytes, 150))
            per_size[str(nbytes)] = {
                "star_us": round(star_lat * 1e6, 1),
                "rd_us": round(rd_lat * 1e6, 1),
                "ratio": round(rd_lat / star_lat, 3),
            }
        ratios = [v["ratio"] for v in per_size.values()]
        per_size["mean_ratio"] = round(sum(ratios) / len(ratios), 3)
        # critical-path transfers: star = 2(p-1) serialized at the hub;
        # rd = 2*log2(p) per rank, hops parallel across pairs
        per_size["modeled_critical_path_ratio"] = round(
            (2 * math.log2(n)) / (2 * (n - 1)), 3)
        small[f"{n}_ranks"] = per_size
    small["target"] = ("mean rd latency <= 0.6x star for <=4KiB at 8 "
                       "ranks (needs >= 2 cores so pairwise hops can "
                       "actually parallelize)")
    small["measured_8rank_mean_ratio"] = small["8_ranks"]["mean_ratio"]
    small["pass_measured"] = small["8_ranks"]["mean_ratio"] <= 0.6
    small["pass_modeled"] = \
        small["8_ranks"]["modeled_critical_path_ratio"] <= 0.6
    if not small["pass_measured"] and cores < 2:
        small["hardware_gap"] = (
            f"container has {cores} core(s): in-process ranks are "
            "scheduler-serialized, so parallel-hop latency cannot be "
            "expressed in wall clock (measured 2-rank ratio "
            f"{small['2_ranks']['mean_ratio']} DOES meet the bound "
            "where a single pairwise hop needs no parallelism); "
            "re-measure on a >= 4-core host")

    # acceptance 2: hierarchical inter-host bytes vs the flat ring at
    # 8 ranks / 2 simulated hosts, >=1MiB payloads, both placements
    hier_block = {}
    for layout, host_ids in (("cyclic", [r % 2 for r in range(8)]),
                             ("block", [0] * 4 + [1] * 4)):
        per_size = {}
        for nbytes in (1 * MB, 16 * MB):
            _, ring_inter, _ = with_sessions(
                8, algo_env["ring"], host_ids,
                lambda ss: time_allreduce(ss, nbytes, 4))
            _, hier_inter, _ = with_sessions(
                8, algo_env["hier"], host_ids,
                lambda ss: time_allreduce(ss, nbytes, 4))
            per_size[str(nbytes)] = {
                "flat_ring_interhost_bytes_per_op": int(ring_inter),
                "hier_interhost_bytes_per_op": int(hier_inter),
                "ratio": round(hier_inter / max(ring_inter, 1), 3),
            }
        hier_block[layout] = per_size
    cyc = [v["ratio"] for v in hier_block["cyclic"].values()]
    hier_block["cyclic_max_ratio"] = round(max(cyc), 3)
    hier_block["target"] = ("hier inter-host bytes <= 0.30x flat ring at "
                            "8 ranks / 2 hosts, >=1MiB (cyclic placement "
                            "— the layout a topology-blind ring pays "
                            "for; block placement reported alongside)")
    hier_block["pass"] = hier_block["cyclic_max_ratio"] <= 0.30

    return {
        "metric": "dataplane_topology",
        "transport": "loopback (in-process ranks, 2 simulated hosts)",
        "accounting": "engine data_{inter,intra}host_bytes counters — "
                      "logical payload bytes each rank sends, classified "
                      "by the locality map",
        "sweep": sweep,
        "small_tensor_rd_vs_star_8ranks": small,
        "hier_interhost_vs_flat_ring_8ranks_2hosts": hier_block,
    }


def _host_microbench():
    """Host data-plane reduction-kernel bandwidth (``--host-microbench``).

    Times the in-process SUM Combine kernel (engine/src/data_plane.cc) on
    local buffers — the per-hop compute of the host ring allreduce, the
    thing that must beat NIC line rate for the ring to be network-bound.
    For fp16/bf16 the replaced scalar kernel is timed too, so the reported
    speedup is measured against real code (VERDICT item 4 target: >=4x on
    fp16 sum). No TPU, no transport, no second process.
    """
    from horovod_tpu.engine import bindings

    n = 1 << 22
    iters = 50
    out = {
        "metric": "host_data_plane_combine_sum_bytes_per_sec",
        "elements": n,
        "iters_per_rep": iters,
        "reps": 3,
        "note": "payload bytes reduced per second (one operand's wire "
                "bytes); *_speedup_vs_scalar is vectorized kernel vs the "
                "per-element scalar kernel it replaced",
    }
    for dt in ("float16", "bfloat16", "float32"):
        best = max(bindings.bench_combine(dt, n, iters) for _ in range(3))
        out[dt] = round(best, 1)
        if dt != "float32":
            base = max(bindings.bench_combine(dt, n, iters,
                                              scalar_baseline=True)
                       for _ in range(3))
            out[f"{dt}_scalar_baseline"] = round(base, 1)
            out[f"{dt}_speedup_vs_scalar"] = \
                round(best / base, 2) if base > 0 else -1.0
    print(json.dumps(out))


def _doctor_bench():
    """The BENCH ``doctor`` block (ISSUE 20): journal append overhead
    (ns/event and % of a measured step, budget <1%) and hvd-doctor
    analysis wall time over a synthesized 64-rank soak artifact set.

    Method, append leg: a real ``JournalWriter`` (production framing,
    flush-per-append) on a tmpdir, timed over 2000 appends of a typical
    driver event, best of 3 reps. The reference step for the % figure
    is a jitted 4-layer 1024-wide MLP grad step (batch 128) on the CPU
    backend — tens of ms, i.e. *smaller* than any real TPU training
    step, so the reported percentage is an upper bound. Steady-state
    training journals at most a handful of events per step (anomalies,
    control-plane transitions), so the budget is stated per event.

    Method, analysis leg: a synthesized 64-rank incident artifact set —
    driver journal with resize/spawn/step events, a SIGKILLed worker
    mid-run, and a serve-plane cache-exhaustion shed storm — then one
    timed ``build_timeline`` + ``diagnose`` pass (the whole hvd-doctor
    hot path minus argv parsing and printing). The verdict is asserted,
    not just timed: a run where the doctor misses the seeded dead rank
    reports ``verdict_ok: false``.
    """
    import statistics
    import tempfile
    import time as _time
    from horovod_tpu.common.journal import JournalWriter
    from horovod_tpu.obs import doctor

    out = {}

    # -- append leg: ns/event, % of a measured step -------------------
    with tempfile.TemporaryDirectory() as d:
        w = JournalWriter(d, segment_bytes=1 << 30)
        n = 2000
        for i in range(100):  # warm the file handle + allocator
            w.append("driver", "step_anomaly", rank=3, step=i, z=3.4)
        best = None
        for _rep in range(3):
            t0 = _time.perf_counter()
            for i in range(n):
                w.append("driver", "step_anomaly", rank=3, step=i, z=3.4)
            dt = _time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        w.close()
    append_ns = best / n * 1e9

    def _mlp_loss(p, x, y):
        h = x
        for wt in p:
            h = jnp.tanh(h @ wt)
        return jnp.mean((h - y) ** 2)

    grad_step = jax.jit(jax.grad(_mlp_loss))
    key = jax.random.PRNGKey(0)
    params = [jax.random.normal(key, (1024, 1024)) * 0.02
              for _ in range(4)]
    x = jax.random.normal(key, (128, 1024))
    y = jax.random.normal(key, (128, 1024))
    jax.block_until_ready(grad_step(params, x, y))  # compile
    reps = []
    for _ in range(10):
        t0 = _time.perf_counter()
        jax.block_until_ready(grad_step(params, x, y))
        reps.append(_time.perf_counter() - t0)
    step_ms = statistics.median(reps) * 1e3
    pct = append_ns / (step_ms * 1e6) * 100.0
    out["append"] = {
        "ns_per_event": round(append_ns, 1),
        "reference_step_ms": round(step_ms, 2),
        "pct_of_step_per_event": round(pct, 4),
        "budget_pct": 1.0,
        "within_budget": pct < 1.0,
    }

    # -- analysis leg: doctor wall time on a 64-rank artifact set -----
    ranks, hosts = 64, 8
    with tempfile.TemporaryDirectory() as root:
        jd = os.path.join(root, "journal")
        wd = JournalWriter(jd, host="driver0", pid=1,
                           segment_bytes=1 << 30)
        wd.append("driver", "resize", generation=1, slots=ranks,
                  hosts=hosts, first=True)
        for r in range(ranks):
            wd.append("driver", "worker_spawn", rank=r, generation=1,
                      host=f"h{r // 8}", local_rank=r % 8)
        for step in range(50):
            for r in range(0, ranks, 16):
                wd.append("driver", "step_time", rank=r, step=step,
                          step_time_sec=0.1)
        wd.append("driver", "worker_exit", generation=1,
                  reason="failure", exit_code=-9, host="h3",
                  local_rank=2)
        wd.append("driver", "resize", generation=2, slots=ranks - 1,
                  hosts=hosts)
        ws = JournalWriter(jd, host="serve0", pid=2,
                           segment_bytes=1 << 30)
        for i in range(200):
            ws.append("serve", "shed",
                      reason="kv cache blocks exhausted",
                      trace_id=f"t{i}")
        wd.close()
        ws.close()
        t0 = _time.perf_counter()
        ctx = doctor.build_timeline(root)
        verdict = doctor.diagnose(ctx)
        wall_ms = (_time.perf_counter() - t0) * 1e3
    out["analysis"] = {
        "ranks": ranks,
        "events": len(ctx["events"]),
        "wall_ms": round(wall_ms, 1),
        "top_cause": verdict["top_cause"],
        "incidents": len(verdict["incidents"]),
        # the timing only counts if the doctor actually caught the
        # seeded incident
        "verdict_ok": verdict["top_cause"] == "dead_rank",
    }
    return out


if __name__ == "__main__":
    if "--scaling-probe" in sys.argv:
        _scaling_probe()
    elif "--host-microbench" in sys.argv:
        _host_microbench()
    elif "--tuning-only" in sys.argv:
        # Refresh just the tuner block (no TPU / no ResNet compile):
        # the CPU-backend closed loop + converged config, one JSON line.
        print(json.dumps({"metric": "tuning", "tuning": _tuning_bench()}))
    elif "--dataplane-only" in sys.argv:
        # Data-plane topology sweep (star/ring/rd/hier, loopback
        # multi-host simulation, inter-host wire accounting); one JSON
        # line, no TPU needed.
        print(json.dumps(_dataplane_bench()))
    elif "--serving-fastpath-only" in sys.argv:
        # Refresh just the serving fast-path block (paged KV cache +
        # prefix reuse + speculative decode vs the recompute batcher on
        # the shared-prefix trace); one JSON line, no TPU needed.
        print(json.dumps({"metric": "serving_fastpath",
                          "serving_fastpath": _serving_fastpath_bench()}))
    elif "--autoscale-only" in sys.argv:
        # Refresh just the autoscale block (closed-loop fleet sim —
        # flash crowd w/ chaos kill + diurnal trace); one JSON line,
        # no TPU needed.
        print(json.dumps({"metric": "autoscale",
                          "autoscale": _autoscale_bench()}))
    elif "--telemetry-only" in sys.argv:
        # Refresh just the telemetry block (tiered scrape at 1024
        # ranks / 32 hosts + request-tracing overhead sweep); one JSON
        # line, no TPU needed.
        print(json.dumps({"metric": "telemetry",
                          "telemetry": _telemetry_bench()}))
    elif "--doctor-only" in sys.argv:
        # Refresh just the doctor block (journal append overhead vs a
        # measured step + hvd-doctor analysis wall time on a 64-rank
        # artifact set); one JSON line, no TPU needed.
        print(json.dumps({"metric": "doctor",
                          "doctor": _doctor_bench()}))
    else:
        main()
