"""The cells PR 40 added: ``run.py --rehearse`` for ``sdar-t8192-bd4`` and
for ``resnet50-b256-dp4`` (on four virtual devices) at the files' rehearse
sizes on the CPU, the configuration's FLOP count and reference,
``blockdiff_pairs`` against a count of the mask, how ``BENCHMARK.json`` holds
the cells, and the block-diffusion kernels' readers (``harness/blockdiff.py``)
on a hand-built trace and a hand-written compiled text with known
answers."""

import base64
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths  # puts benchmark/ on sys.path
from bench_run import check_rehearsal_result, result_line, run_cell
from harness import blockdiff, flops, hlo_text
from harness import spec as spec_lib
from harness.job import Run
from harness.trace_reduce import DeviceTrace, Span, Trace

CELL, CONFIG, TRAFFIC = "sdar-t8192-bd4", "sdar-30b-a3b", "t8192-bd4-b1"
DP4 = "resnet50-b256-dp4"
BLOCKDIFF_METRICS = ("blockdiff_time_share", "blockdiff_fwd_roofline",
                     "blockdiff_bwd_dq_roofline",
                     "blockdiff_bwd_dkv_roofline",
                     "blockdiff_blocks_skipped_share")
MS = 1e6  # nanoseconds


def job_of(rehearse=False):
    spec = spec_lib.load()
    config, builder = spec_lib.config(spec, CONFIG, rehearse)
    module = spec_lib.load_module(builder)
    return module, module.build(config, spec_lib.traffic(TRAFFIC, rehearse)), \
        config


# -- the rehearsals --------------------------------------------------------------

def test_rehearsal_reports_the_end_to_end_metrics():
    """Tiny widths, two layers, 1024 data tokens in blocks of 4 (2048 rows a
    layer; the kernels under the two block masks interpreted), experts 4 of
    16 held from 4 on. The rate counts data tokens, not rows."""
    result, earlier = result_line(run_cell(
        "--workload", CELL, "--rehearse", "--seconds", "1", "--seed",
        "2147483659", "--trace", "0"))
    check_rehearsal_result(result, 1, {"tokens_per_s_per_chip",
                                       "peak_hbm_gb", "setup_s"})
    facts = earlier[0]
    assert facts["items_per_step_per_chip"] == 1024
    assert facts["rows_per_layer"] == 2048 and facts["block_length"] == 4
    assert facts["attention"] == "flash"
    assert facts["experts"] == 16 and facts["experts_held"] == [4, 4]
    assert facts["blockdiff_call"] == [1, 1024, 8, 16, 4]
    assert facts["mask_token_id"] == facts["vocab"] - 1 == 511
    assert facts["recompute"] == "blocks_keep_attention"
    checks = next(e for e in earlier if "checks" in e)
    assert checks["programs_in_windows"] == 0
    # two steps at the head of a 2000-step warm-up need not lower a float32
    # loss, and four held experts of tiny width see few rows each, so their
    # leaves read apart from the chip's limits: every other check holds
    assert all(ok for name, ok in checks["checks"].items()
               if name not in ("warmup_loss", "reference"))
    reference = next(e for e in earlier
                     if e.get("check") == "float32 reference")
    assert reference["loss_relative_error"] <= reference["loss_rtol"]
    # 13 leaves at six layers; at two the routers of layers 0 and last - 1
    # are one leaf
    assert len(reference["gradient_relative_l2_error"]) == 12
    off_path = [e for name, e in reference[
        "gradient_relative_l2_error"].items() if "SdarSparseMoe" not in name]
    assert len(off_path) == 8 and max(off_path) <= \
        reference["gradient_tolerance"]
    held = next(e for e in earlier
                if e.get("check", "").startswith("the kernels"))
    assert held["required"] == {} and held["not_asked_for"] == {}


def test_traced_rehearsal_leaves_the_device_readers_out():
    """No device plane on the CPU: the block-diffusion readers find nothing
    to read, return None, and the line leaves their metrics out."""
    result, _ = result_line(run_cell(
        "--workload", CELL, "--rehearse", "--seconds", "1", "--trace", "1"))
    check_rehearsal_result(result, 1, {
        "init_s", "compile_s", "programs_after_warmup", "host_dispatch_ms"})


def test_four_chip_resnet_cell_on_four_virtual_devices():
    """``resnet50-b256-dp4`` is data: the accepted configuration under a
    new traffic file. Four shards' mean, an all-reduce in the text,
    parameters (and BatchNorm statistics) bit-identical on the four."""
    result, earlier = result_line(run_cell(
        "--workload", DP4, "--rehearse", "--seconds", "2", "--trace", "0",
        devices=4))
    check_rehearsal_result(result, 4, {
        "images_per_s_per_chip", "scaling_efficiency", "peak_hbm_gb",
        "setup_s"})
    assert result["correct"] is True
    shard = next(e for e in earlier if "shard_mean" in e)
    assert shard["ok"] and shard["all_reduce_in_compiled_text"]
    reference = next(e for e in earlier
                     if e.get("phase") == "one_chip_reference")
    assert len(reference["shard_losses"]) == 4
    identical = next(e for e in earlier if e.get("check", "").startswith(
        "parameters bit-identical"))
    assert identical["ok"]
    assert [p["chips"] for p in earlier if "program" in p] == [1, 4]


# -- the configuration -------------------------------------------------------------

def hand_mask(seq, block):
    """The block-diffusion mask over the 2 seq rows of one sequence, pair
    by pair (the noised stream first)."""
    seen = np.zeros((2 * seq, 2 * seq), bool)
    for i in range(2 * seq):
        for j in range(2 * seq):
            bq, bk = (i % seq) // block, (j % seq) // block
            if i < seq:
                seen[i, j] = bk == bq if j < seq else bk < bq
            else:
                seen[i, j] = j >= seq and bk <= bq
    return seen


def test_blockdiff_pairs_counts_the_mask():
    module = job_of()[0]
    for seq, block in [(16, 1), (16, 4), (32, 16), (48, 4), (64, 64)]:
        seen = hand_mask(seq, block)
        assert module.blockdiff_pairs(seq, block) == int(seen.sum()) == \
            seq * seq + seq * block, (seq, block)
        # the kernels' two calls: the clean queries, and the noised ones on
        # the clean keys; a noised block on itself is no kernel's
        assert blockdiff.call_pairs(seq, block, "le") == \
            int(seen[seq:, seq:].sum())
        assert blockdiff.call_pairs(seq, block, "lt") == \
            int(seen[:seq, seq:].sum())
        np.testing.assert_array_equal(
            np.asarray(module._mask_rows(0, 2 * seq, seq, block)), seen)
    assert module.blockdiff_pairs(8192, 4) == 8192 ** 2 + 8192 * 4
    # twice a causal model's at the same L, a quarter of the [2L, 2L] grid
    assert module.blockdiff_pairs(8192, 4) / flops.attended_pairs(
        8192, True) == pytest.approx(2.0, abs=1e-3)
    assert module.blockdiff_pairs(8192, 4) / (2 * 8192) ** 2 == \
        pytest.approx(0.25, abs=1e-3)


def test_flop_count_by_hand():
    """ISSUE 40's forward TFLOP a step at 8192 data tokens and six layers:
    scores and values 6 x 1.100, projections 3.71, experts 0.93, head 0.64,
    routers 0.05: 11.93, 35.8 trained, 4.368 GFLOP a data token; attention's
    products 55% of it. The job counts the experts at the rows that reach
    them from the cell's start (1.4995 of a data token's two: 0.70): 11.70,
    4.283 GFLOP a data token."""
    module, job, config = job_of()
    layers = config["num_layers"]
    sizes = dict(hidden=2048, heads=32, kv_heads=4, head_dim=128,
                 experts=128, experts_per_token=8, held=16, expert_dim=768,
                 vocab=18992, seq=8192, block=4)
    forward = module.sdar_forward_flops_per_token(layers, **sizes)
    routed = module.sdar_forward_flops_per_token(layers, routed_rows=1.4995,
                                                 **sizes)
    parts = forward["parts"]
    assert parts["row_projections"] == 2 * 2048 * (2 * 4096 + 2 * 512)
    assert parts["scores"] == 4 * 4096 * (8192 ** 2 + 8192 * 4) / 8192
    assert parts["row_router"] == 2 * 2048 * 128
    assert parts["row_held_experts"] == 6 * 2048 * 768 * 8 * 16 / 128
    assert parts["head"] == 2 * 2048 * 18992
    assert forward["attention"] == layers * parts["scores"]
    assert forward["projections"] == layers * 2 * parts["row_projections"]
    total = sum(forward[k] for k in module.KINDS)
    assert routed["experts"] == layers * 1.4995 * parts["row_held_experts"]
    assert {k: routed[k] for k in module.KINDS if k != "experts"} == \
        {k: forward[k] for k in module.KINDS if k != "experts"}
    assert job.facts["routed_rows_per_token"] == pytest.approx(1.4995)
    assert job.model_flops_per_item == pytest.approx(
        3 * sum(routed[k] for k in module.KINDS), rel=1e-12)
    assert job.facts["moe_train_flops_per_token_per_layer"] == \
        pytest.approx(3 * 1.4995 * parts["row_held_experts"], rel=1e-12)
    if layers == 6:
        step = 8192 / 1e12
        assert forward["attention"] * step == pytest.approx(6.60, abs=0.01)
        assert forward["projections"] * step == pytest.approx(3.71, abs=0.01)
        assert forward["experts"] * step == pytest.approx(0.93, abs=0.01)
        assert forward["head"] * step == pytest.approx(0.64, abs=0.01)
        assert forward["router"] * step == pytest.approx(0.05, abs=0.01)
        assert total * step == pytest.approx(11.93, abs=0.01)
        assert 3 * total / 1e9 == pytest.approx(4.368, abs=0.001)
        assert routed["experts"] * step == pytest.approx(0.70, abs=0.01)
        assert job.model_flops_per_item / 1e9 == pytest.approx(4.283,
                                                               abs=0.001)
        assert forward["attention"] / total == pytest.approx(0.55, abs=0.01)


def test_reference_positions_norm_and_router_by_hand():
    """The reference's own parts against arithmetic written here: rotary
    at a row's position in its sequence (row L + i is turned as row i, row 0
    not at all), the per-head norm over a head's own values, the routing
    weights the softmax's top k renormalised, zero elsewhere."""
    module = job_of()[0]
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(1, 16, 2, 8), jnp.float32)
    positions = jnp.arange(16) % 8
    turned = np.asarray(module._rotate_half(x, positions, 1e4))
    np.testing.assert_allclose(turned[:, 0], np.asarray(x)[:, 0], rtol=1e-6)
    np.testing.assert_allclose(turned[:, 8], np.asarray(x)[:, 8], rtol=1e-6)
    same = np.asarray(module._rotate_half(
        jnp.concatenate([x[:, :8], x[:, :8]], axis=1), positions, 1e4))
    np.testing.assert_allclose(same[:, :8], same[:, 8:], rtol=1e-6)
    angle = 3.0 * 1e4 ** (-2.0 / 8)  # position 3, the second pair (i = 1)
    a, b = np.asarray(x)[0, 3, 0, 1], np.asarray(x)[0, 3, 0, 5]
    assert turned[0, 3, 0, 1] == pytest.approx(
        a * np.cos(angle) - b * np.sin(angle), rel=1e-5)
    assert turned[0, 3, 0, 5] == pytest.approx(
        b * np.cos(angle) + a * np.sin(angle), rel=1e-5)
    scale = jnp.asarray(rng.rand(8) + 0.5, jnp.float32)
    normed = np.asarray(module._rms_norm(x, scale, 1e-6))
    head = np.asarray(x)[0, 5, 1]
    np.testing.assert_allclose(
        normed[0, 5, 1],
        head / np.sqrt((head ** 2).mean() + 1e-6) * np.asarray(scale),
        rtol=1e-5)
    tokens = jnp.asarray(rng.randn(5, 8), jnp.float32)
    w_router = jnp.asarray(rng.randn(8, 6), jnp.float32)
    with jax.default_matmul_precision("highest"):
        dense, chosen = module._routing(tokens, w_router, 2)
    logits = np.asarray(tokens) @ np.asarray(w_router)
    probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    for t in range(5):
        top = np.argsort(-logits[t])[:2]
        assert set(np.asarray(chosen)[t]) == set(top)
        want = np.zeros(6)
        want[top] = probs[t, top] / probs[t, top].sum()
        np.testing.assert_allclose(np.asarray(dense)[t], want, rtol=1e-5,
                                   atol=1e-7)


def test_benchmark_json_holds_both_cells():
    spec = spec_lib.load()
    cells = {c["name"]: c for c in spec["workloads"]}
    assert len(cells) == 10  # a quarter of ten, rounded down, is two
    assert [c["name"] for c in spec["workloads"]][-2:] == [DP4, CELL]
    assert sorted(n for n, c in cells.items() if c["chips"] == 4) == \
        ["gpt2s-t1024-dp4", DP4]
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": TRAFFIC, "chips": 1}
    assert cells[DP4] == {**cells[DP4], "config": "resnet50",
                          "traffic": "b256-dp4", "chips": 4}
    entry = spec["configs"][-1]
    assert entry["name"] == CONFIG and entry["reduced"] == [
        "num_layers", "num_experts", "vocab_size"]
    assert entry["source"] == \
        "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"

    def reported(cell):
        return {m["name"] for kind in ("end_to_end", "per_layer")
                for m in spec_lib.metrics(spec, kind, cell)}
    like = reported("smallthinker-t16384")
    got = reported(CELL)
    assert {m for m in like if not m.startswith(("window_", "flash_"))} \
        | set(BLOCKDIFF_METRICS) | {"flash_time_share"} == got
    assert reported(DP4) == {
        m.replace("tokens_per_s", "images_per_s") if "_per_s_" in m else
        m if m in ("peak_hbm_gb", "setup_s", "scaling_efficiency", "init_s",
                   "compile_s", "hvd_init_s", "step_trace_lower_s",
                   "step_traces", "collective_ms", "exposed_collective_ms")
        else m + ".images"
        for m in reported("gpt2s-t1024-dp4")
        if not m.startswith("flash_")}
    by_name = {m["name"]: m for m in spec["per_layer"]}
    assert [m["name"] for m in spec["per_layer"]][-5:] == \
        list(BLOCKDIFF_METRICS)
    for name in BLOCKDIFF_METRICS:
        m = by_name[name]
        assert m["layer"] == "kernels" and m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip" and m["unit"] == "%"
        assert m["better"] == ("lower" if name == "blockdiff_time_share"
                               else "higher")
        assert m["source"] == ("program_counter" if "blocks" in name
                               else "device_trace")
    traffic = spec_lib.traffic(TRAFFIC)
    assert (traffic["per_chip_batch"], traffic["seq_len"]) == (1, 8192)
    assert (traffic["block_steps"], traffic["warmup_blocks"],
            traffic["trace_blocks"], traffic["step"]) == (3, 2, 2, {})
    memory = traffic["memory_analysis"]
    # described facts of the compile, which no run reads as a limit
    assert memory["workload"] == CELL
    layers = json.load(open(os.path.join(
        bench_paths.BENCH, "configs", CONFIG + ".json")))["num_layers"]
    assert memory["kernels"] == {
        "_fwd_blockdiff_kernel": 2 * layers,
        "_bwd_dq_blockdiff_kernel": 2 * layers,
        "_bwd_dkv_blockdiff_kernel": 2 * layers,
        "_add_rows_kernel": 2 * layers}
    assert memory["kernels_missing"] == memory["kernels_not_asked_for"] == {}
    assert 4e9 < memory["argument_bytes"] + memory["temp_bytes"] < 15.0e9
    dp4 = spec_lib.traffic("b256-dp4")
    assert (dp4["per_chip_batch"], dp4["image_size"], dp4["chips"]) == \
        (256, 224, 4)
    assert (dp4["block_steps"], dp4["warmup_blocks"], dp4["trace_blocks"],
            dp4["step"], dp4["one_chip_reference"]) == \
        (5, 2, 3, {}, {"share_of_seconds": 0.25})
    assert dp4["memory_analysis"]["workload"] == DP4
    assert "all-reduce" in dp4["memory_analysis"]["collectives"]


# -- the block-diffusion kernels' readers --------------------------------------------

def kernel_call(name, function):
    """A ``tpu_custom_call`` whose Mosaic body names ``function``."""
    body = base64.b64encode(b"\x00module\x00" + function.encode()
                            + b"\x00").decode()
    return (f'  %{name} = bf16[8]{{0}} custom-call(%a), '
            f'custom_call_target="tpu_custom_call", '
            f'backend_config={{"custom_call_config": {{"body":"{body}"}}}}, '
            f'metadata={{op_name="jit(_local_step)/phase_forward_backward/'
            f'attn_blockdiff/pallas_call"}}\n')


def text(kernels):
    return ("HloModule jit__local_step, is_scheduled=true\n\n"
            "ENTRY %main (a: f32[8]) -> f32[8] {\n"
            "  %a = f32[8]{0} parameter(0)\n"
            + "".join(kernel_call(name, function)
                      for name, function in kernels)
            + '  %head.1 = f32[8]{0} add(%a, %a), metadata={op_name="jit('
            '_local_step)/phase_forward_backward/LmHead/dot_general"}\n}\n')


# one layer: the clean queries' call and the noised queries' of each role;
# and a causal forward, which these readers pass over
KERNELS = [("bf.1", "_fwd_blockdiff_kernel"), ("bf.2", "_fwd_blockdiff_kernel"),
           ("bq.1", "_bwd_dq_blockdiff_kernel"),
           ("bq.2", "_bwd_dq_blockdiff_kernel"),
           ("bk.1", "_bwd_dkv_blockdiff_kernel"),
           ("bk.2", "_bwd_dkv_blockdiff_kernel"), ("cf.1", "_fwd_kernel")]


class FakeJob:
    # batch 1, 64 positions, 2 heads of 8, blocks of 4
    facts = {"blockdiff_call": [1, 64, 2, 8, 4]}
    flash_call = None
    flash_layers = 0


def run_of(hlo, job=FakeJob):
    return Run(job=job, chips=1, block_steps=2,
               peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e12},
               hlo=hlo, program=hlo.module, init_s=0.0, compile_s=0.0,
               programs_after_warmup=0, dispatch_seconds=[],
               items_per_step_per_chip=64.0)


def two_steps():
    """Two step runs of 20 ms: the two forward calls 1 ms each and, the
    block recomputed, 1 ms each again; dq 2 x 2 ms, dk/dv 2 x 3 ms, the
    causal forward 1 ms, the head 5 ms."""
    def ops(start):
        named = (("bf.1", 0, 1), ("bf.2", 1, 2), ("cf.1", 2, 3),
                 ("head.1", 3, 8), ("bf.1", 8, 9), ("bf.2", 9, 10),
                 ("bq.1", 10, 12), ("bq.2", 12, 14), ("bk.1", 14, 17),
                 ("bk.2", 17, 20))
        return [Span(name, (start + lo) * MS, (start + hi) * MS)
                for name, lo, hi in named]
    return Trace(devices=[DeviceTrace(0, ops=ops(0) + ops(20), modules=[
        Span("jit__local_step(1)", 0, 20 * MS),
        Span("jit__local_step(1)", 20 * MS, 40 * MS)])],
        host=[Span("bench.block", 0, 40 * MS)])


def test_blockdiff_cost_is_the_roles_products_over_the_mean_calls_pairs():
    le, lt = (blockdiff.call_pairs(64, 4, edge) for edge in ("le", "lt"))
    assert (le, lt) == (64 * 68 // 2, 64 * 60 // 2) and le + lt == 64 * 64
    for kernel, role in blockdiff.BLOCKDIFF_KERNELS.items():
        got_flops, got_bytes = blockdiff.blockdiff_kernel_cost(
            kernel, 1, 64, 2, 8, 4)
        assert got_flops == flops.FLASH_PRODUCTS[role] * 2 * 8 * 2 * 2048
        causal = flops.flash_kernel_cost(role, 1, 64, 2, 8, True)
        assert got_bytes == causal[1] and got_flops < causal[0]
    assert set(blockdiff.BLOCKDIFF_KERNELS.values()) == \
        set(flops.FLASH_PRODUCTS)
    assert not set(blockdiff.BLOCKDIFF_KERNELS) & set(flops.FLASH_PRODUCTS)
    from harness import window
    assert not set(blockdiff.BLOCKDIFF_KERNELS) & set(window.WINDOW_KERNELS)


def test_the_readers_cost_a_kernel_by_the_times_it_ran():
    hlo = hlo_text.HloIndex(text(KERNELS))
    trace, run = two_steps(), run_of(hlo)
    assert {hlo.kernel_name(i) for i in hlo.kernels()} == {
        *blockdiff.BLOCKDIFF_KERNELS, "_fwd_kernel"}
    found = blockdiff.runs_and_seconds(trace, run)
    assert found == pytest.approx({
        "_fwd_blockdiff_kernel": (8, 8e-3),
        "_bwd_dq_blockdiff_kernel": (4, 8e-3),
        "_bwd_dkv_blockdiff_kernel": (4, 12e-3)})
    reader = spec_lib.layer_reader
    # 14 of a step's 20 ms
    assert reader("blockdiff_time_share")(trace, run) == pytest.approx(70.0)
    # one call's least: products x 2 x 8 x (2 heads x 2048 pairs) / 1e9;
    # the text holds each forward call once, it ran twice a step: costed
    # by the times it ran
    one = 2 * 8 * 4096 / 1e9
    assert reader("blockdiff_fwd_roofline")(trace, run) == pytest.approx(
        100 * 8 * 2 * one / 8e-3)
    assert reader("blockdiff_bwd_dq_roofline")(trace, run) == pytest.approx(
        100 * 4 * 3 * one / 8e-3)
    assert reader("blockdiff_bwd_dkv_roofline")(trace, run) == pytest.approx(
        100 * 4 * 4 * one / 12e-3)
    # the causal readers never cost a block-diffusion call, and a job that
    # names no flash shapes reads no flash roofline at all
    from harness import kernels, roofline
    assert roofline.flash_share(trace, run, tuple(flops.FLASH_PRODUCTS)) \
        is None
    assert kernels.unasked(FakeJob, hlo_text.HloIndex(text(KERNELS[:6]))) \
        == {}
    assert kernels.unasked(FakeJob, hlo) == {"_fwd_kernel": 1}


def test_blocks_skipped_share_reads_the_programs_counter(monkeypatch):
    """The share of the [2L, 2L] grid no kernel loads, from
    ``hvd_flash_block_visits``'s ``blockdiff_*`` kinds; a causal call's
    counts stay out of it. On a registry of its own."""
    from horovod_tpu.metrics import registry
    from horovod_tpu.metrics.registry import get_registry
    from horovod_tpu.ops.flash_attention import (blockdiff_attention,
                                                 blockdiff_block_plan,
                                                 flash_attention)
    monkeypatch.setattr(registry, "_default_registry",
                        registry.MetricsRegistry())
    hlo = hlo_text.HloIndex(text(KERNELS))
    trace, run = two_steps(), run_of(hlo)

    def count(kind):
        return get_registry().counter("hvd_flash_block_visits",
                                      kind=kind).value
    assert all(count(kind) == 0 for kind in blockdiff.BLOCKDIFF_KINDS)
    assert blockdiff.blocks_skipped_share(trace, run) is None
    q = jax.ShapeDtypeStruct((1, 16384, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 16384, 2, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k: blockdiff_attention(
        q, k, k, 4, interpret=True), q, kv)
    jax.eval_shape(lambda k: flash_attention(
        k, k, k, causal=True, interpret=True), kv)
    jax.eval_shape(lambda q, k: blockdiff_attention(
        q, k, k, 4, interpret=True), q, kv)
    plan = blockdiff_block_plan(8192, 512, 512, 4)
    want = 100.0 * (plan["skipped"] + plan["noised_keys"]) / 1024
    assert want == pytest.approx(73.44, abs=0.01)  # of the 75% the mask allows
    got = spec_lib.layer_reader("blockdiff_blocks_skipped_share")(trace, run)
    assert got == pytest.approx(want)
    assert blockdiff.blocks_skipped_share(Trace(), run) is None


def test_a_program_without_blockdiff_kernels_reads_nothing():
    """The parent's programs and every other configuration: no such kernel
    in the step and no ``blockdiff_call`` among the facts, so the readers
    return None and raise nothing."""
    class Plain:
        facts = {}
        flash_call = (1, 64, 2, 8, True)
        flash_layers = 1
    other = hlo_text.HloIndex(text([("cf.1", "_fwd_kernel")]))
    trace = Trace(devices=[DeviceTrace(0, ops=[Span("cf.1", 0, MS)],
                                       modules=[Span("jit__local_step(1)", 0,
                                                     MS)])],
                  host=[Span("bench.block", 0, MS)])
    for job in (Plain, FakeJob):
        run = run_of(other, job)
        for name in BLOCKDIFF_METRICS[:4]:
            assert spec_lib.layer_reader(name)(trace, run) is None, name
            assert spec_lib.layer_reader(name)(Trace(), run) is None
            assert spec_lib.layer_reader(name)(None, run) is None
    # the kernels in the step, but a job that states no call
    run = run_of(hlo_text.HloIndex(text(KERNELS)), Plain)
    for name in BLOCKDIFF_METRICS[1:4]:
        assert spec_lib.layer_reader(name)(two_steps(), run) is None


# -- the control: the reference one precision below the stated one --------------

def test_kept_bits_round_as_the_named_dtypes_do():
    module = job_of()[0]
    x = jnp.asarray(np.random.RandomState(5).randn(4096), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(module._kept(x, module.BELOW_FLOAT32_BITS)),
        np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
    inside = jnp.where(jnp.abs(x) < 2.0 ** -5, 1.0, x)  # e4m3: 2^-6 .. 448
    np.testing.assert_array_equal(
        np.asarray(module._kept(inside, module.BELOW_BF16_BITS)),
        np.asarray(inside.astype(jnp.float8_e4m3fn).astype(jnp.float32)))
    assert module._kept(x, None) is x


def test_the_control_is_not_correct_by_the_harness_own_comparison():
    """``reference_control.py``: the lowered reference in the program's
    place is not ``ok``, by the limit of the leaves off the routers' path,
    which the program itself keeps (tiny sizes: the limits are set from the
    chip's readings; four tiny held experts read apart from them here)."""
    done = run_cell("--workload", CELL, "--seeds", "5", "--rehearse",
                    script=os.path.join(bench_paths.BENCH,
                                        "reference_control.py"))
    last, earlier = result_line(done)
    assert last["control_none_ok"]
    name = "gradient_relative_l2_error"
    assert last["sound_largest"][name] < last["limits"][name] \
        < last["control_smallest"][name]
    assert last["control_smallest"][name] > 3 * last["sound_largest"][name]
    assert last["sound_largest"]["loss_relative_error"] \
        < last["limits"]["loss_relative_error"]
    readings = [e for e in earlier if "reading" in e]
    assert [e["reading"] for e in readings] == ["sound", "control"]
    assert readings[1]["ok"] is False
