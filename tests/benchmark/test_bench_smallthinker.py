"""The cell PR 38 added: ``run.py --rehearse`` for ``smallthinker-t16384``
at the files' rehearse sizes on the CPU, the configuration's FLOP count and
reference, ``window_pairs`` against a count of the mask, how
``BENCHMARK.json`` holds the cell, and the window kernels' readers
(``harness/window.py``) on a hand-built trace and a hand-written compiled
text with known answers."""

import base64
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths  # puts benchmark/ on sys.path
from bench_run import check_rehearsal_result, result_line, run_cell
from harness import flops, hlo_text, window
from harness import spec as spec_lib
from harness.job import Run
from harness.trace_reduce import DeviceTrace, Span, Trace

CELL, CONFIG, TRAFFIC = "smallthinker-t16384", "smallthinker-21b-a3b", \
    "t16384-b1"
WINDOW_METRICS = ("window_time_share", "window_fwd_roofline",
                  "window_bwd_dq_roofline", "window_bwd_dkv_roofline",
                  "window_blocks_skipped_share")
MS = 1e6  # nanoseconds


def job_of(rehearse=False):
    spec = spec_lib.load()
    config, builder = spec_lib.config(spec, CONFIG, rehearse)
    module = spec_lib.load_module(builder)
    return module, module.build(config, spec_lib.traffic(TRAFFIC, rehearse)), \
        config


# -- the rehearsals --------------------------------------------------------------

def test_rehearsal_ends_correct_and_reports_the_end_to_end_metrics():
    """Tiny widths, one period 0,1,1,1, a window of 200 under 1024 tokens
    (shorter than the sequence, no multiple of a block), experts 4 of 16
    held from 4 on; the kernels interpreted."""
    result, earlier = result_line(run_cell(
        "--workload", CELL, "--rehearse", "--seconds", "1", "--seed",
        "2147483659", "--trace", "0"))
    check_rehearsal_result(result, 1, {"tokens_per_s_per_chip",
                                       "peak_hbm_gb", "setup_s"})
    facts = earlier[0]
    assert facts["sliding_window_layout"] == facts["rope_layout"] == \
        [0, 1, 1, 1]
    assert facts["attention"] == "flash" and facts["window"] == 200
    assert facts["experts"] == 16 and facts["experts_held"] == [4, 4]
    assert facts["window_call"] == [1, 1024, 4, 16, 200]
    assert facts["recompute"] == "blocks_keep_attention"
    checks = next(e for e in earlier if "checks" in e)
    assert checks["programs_in_windows"] == 0
    # two steps at the head of a 2000-step warm-up to 1e-6 need not lower a
    # float32 loss: every other check holds
    assert all(ok for name, ok in checks["checks"].items()
               if name != "warmup_loss")
    reference = next(e for e in earlier
                     if e.get("check") == "float32 reference")
    assert reference["loss_relative_error"] <= reference["loss_rtol"]
    assert len(reference["gradient_relative_l2_error"]) == 11


def test_traced_rehearsal_leaves_the_device_readers_out():
    """No device plane on the CPU: the window's readers find nothing to
    read, return None, and the line leaves their metrics out."""
    result, _ = result_line(run_cell(
        "--workload", CELL, "--rehearse", "--seconds", "1", "--trace", "1"))
    check_rehearsal_result(result, 1, {
        "init_s", "compile_s", "programs_after_warmup", "host_dispatch_ms"})


# -- the configuration -------------------------------------------------------------

def test_window_pairs_counts_the_mask():
    for seq, w in [(16, 1), (16, 5), (16, 16), (16, 40), (64, 24),
                   (300, 128)]:
        ahead = np.arange(seq)[:, None] - np.arange(seq)[None, :]
        assert window.window_pairs(seq, w) == \
            int(((ahead >= 0) & (ahead < w)).sum()), (seq, w)
    assert window.window_pairs(16384, 4096) == 58722304
    assert flops.attended_pairs(16384, True) == 134225920
    assert window.window_pairs(16384, 4096) / 134225920 == \
        pytest.approx(0.4375, abs=1e-3)  # ISSUE 38's 43.7%


def test_flop_count_by_hand():
    """ISSUE 38's forward MFLOP a token at 16 384: scores and values 2 x
    117.4 (full) + 6 x 51.4 (window), projections 8 x 41.9, the head 97,
    the held experts 8 x 8.85, the routers 8 x 0.33: 1049, 3.15 GFLOP
    trained; attention's products 52% of it, the window layers' 29%."""
    module, job, _ = job_of()
    forward = module.smallthinker_forward_flops_per_token(
        (0, 1, 1, 1, 0, 1, 1, 1), hidden=2560, heads=28, kv_heads=4,
        head_dim=128, experts=64, experts_per_token=6, held=8,
        expert_dim=768, vocab=18992, seq=16384, window=4096)
    parts = forward["parts"]
    assert parts["attention_projections"] == 2 * 2560 * (2 * 3584 + 1024)
    assert parts["full_scores"] == 4 * 3584 * 16385 / 2
    assert parts["window_scores"] == 4 * 3584 * 58722304 / 16384
    assert parts["router"] == 2 * 2560 * 64
    assert parts["held_experts"] == 6 * 2560 * 768 * 6 * 8 / 64
    assert parts["head"] == 2 * 2560 * 18992
    assert parts["full_scores"] / 1e6 == pytest.approx(117.4, abs=0.1)
    assert parts["window_scores"] / 1e6 == pytest.approx(51.4, abs=0.1)
    assert forward["projections"] / 1e6 == pytest.approx(335.5, abs=0.1)
    assert forward["experts"] / 1e6 == pytest.approx(70.8, abs=0.1)
    assert forward["head"] / 1e6 == pytest.approx(97.2, abs=0.1)
    total = sum(forward[k] for k in module.KINDS)
    assert job.model_flops_per_item == 3 * total
    assert job.model_flops_per_item / 1e9 == pytest.approx(3.148, abs=0.001)
    attention = forward["full_attention"] + forward["window_attention"]
    assert attention / total == pytest.approx(0.52, abs=0.005)
    assert forward["window_attention"] / total == pytest.approx(0.29,
                                                                abs=0.005)
    assert job.facts["moe_train_flops_per_token_per_layer"] == \
        3 * parts["held_experts"]


def test_reference_masks_positions_and_router_by_hand():
    """The reference's own parts against arithmetic written here: the
    window mask of one row, rotary at position 0 the identity, the routing
    weights the softmax of the chosen logits and zero elsewhere."""
    module = job_of()[0]
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, 128, 2, 8), jnp.float32)
               for _ in range(3))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(module._masked_attention(q, k, v, 5))
        causal = np.asarray(module._masked_attention(q, k, v, None))
    s = np.einsum("hd,khd->hk", np.asarray(q)[0, 100],
                  np.asarray(k)[0, 96:101]) / np.sqrt(8)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hk,khd->hd", p, np.asarray(v)[0, 96:101])
    np.testing.assert_allclose(got[0, 100], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got[0, :5], causal[0, :5], rtol=1e-6)
    assert not np.allclose(got[0, 100], causal[0, 100], atol=1e-3)
    turned = np.asarray(module._rotate_half(q, 1.5e6))
    np.testing.assert_allclose(turned[0, 0], np.asarray(q)[0, 0])
    np.testing.assert_allclose(  # a rotation keeps every pair's length
        np.linalg.norm(turned, axis=-1), np.linalg.norm(q, axis=-1),
        rtol=1e-5)
    x = jnp.asarray(rng.randn(1, 32, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 8), jnp.float32)
    dense, chosen = module._routing(x, w, 3)
    logits = np.asarray(x)[0] @ np.asarray(w)
    np.testing.assert_array_equal(np.sort(np.asarray(chosen), -1),
                                  np.sort(np.argsort(-logits, -1)[:, :3], -1))
    assert ((np.asarray(dense) > 0).sum(-1) == 3).all()
    np.testing.assert_allclose(np.asarray(dense).sum(-1), 1.0, rtol=1e-6)
    top = np.sort(logits, -1)[:, -3:]
    np.testing.assert_allclose(
        np.sort(np.asarray(dense), -1)[:, -3:],
        np.exp(top) / np.exp(top).sum(-1, keepdims=True), rtol=1e-5)


def test_benchmark_json_holds_the_cell_together():
    spec = spec_lib.load()
    cell = spec_lib.workload(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    end_to_end = {m["name"] for m in spec_lib.metrics(spec, "end_to_end",
                                                      CELL)}
    assert end_to_end == {"tokens_per_s_per_chip", "peak_hbm_gb", "setup_s"}
    got = {m["name"] for m in spec_lib.metrics(spec, "per_layer", CELL)}
    like = {m["name"] for m in spec_lib.metrics(spec, "per_layer",
                                                "nemotron3n-t8192")}
    # what the Nemotron-H cell reports but for the mixer's three, the
    # window's five, and the held experts' products against the peak
    assert {m for m in like if not m.startswith("ssm_")} \
        | set(WINDOW_METRICS) | {"moe_experts_mfu"} == got
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in WINDOW_METRICS:
        m = by_name[name]
        assert m["layer"] == "kernels" and m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip" and m["unit"] == "%"
        assert m["better"] == ("lower" if name == "window_time_share"
                               else "higher")
        assert m["source"] == ("program_counter" if "blocks" in name
                               else "device_trace")
    traffic = spec_lib.traffic(TRAFFIC)
    assert (traffic["per_chip_batch"], traffic["seq_len"]) == (1, 16384)
    assert (traffic["block_steps"], traffic["warmup_blocks"],
            traffic["trace_blocks"], traffic["step"]) == (3, 2, 2, {})
    memory = traffic["memory_analysis"]
    # described facts of the compile, which no run reads as a limit
    assert memory["workload"] == CELL
    assert memory["kernels"] == {
        "_fwd_kernel": 2, "_bwd_dq_kernel": 2, "_bwd_dkv_kernel": 2,
        "_fwd_window_kernel": 6, "_bwd_dq_window_kernel": 6,
        "_bwd_dkv_window_kernel": 6}
    assert memory["kernels_missing"] == {}
    assert 4e9 < memory["argument_bytes"] + memory["temp_bytes"] < 15.0e9


# -- the window kernels' readers ---------------------------------------------------

def kernel_call(name, function):
    """A ``tpu_custom_call`` whose Mosaic body names ``function``."""
    body = base64.b64encode(b"\x00module\x00" + function.encode()
                            + b"\x00").decode()
    return (f'  %{name} = bf16[8]{{0}} custom-call(%a), '
            f'custom_call_target="tpu_custom_call", '
            f'backend_config={{"custom_call_config": {{"body":"{body}"}}}}, '
            f'metadata={{op_name="jit(_local_step)/phase_forward_backward/'
            f'attn_window/pallas_call"}}\n')


def text(kernels):
    return ("HloModule jit__local_step, is_scheduled=true\n\n"
            "ENTRY %main (a: f32[8]) -> f32[8] {\n"
            "  %a = f32[8]{0} parameter(0)\n"
            + "".join(kernel_call(name, function)
                      for name, function in kernels)
            + '  %head.1 = f32[8]{0} add(%a, %a), metadata={op_name="jit('
            '_local_step)/phase_forward_backward/LmHead/dot_general"}\n}\n')


# a step whose one window layer is recomputed: its forward kernel is in the
# text twice; and one causal layer, which the window's readers pass over
KERNELS = [("wf.1", "_fwd_window_kernel"), ("wf.2", "_fwd_window_kernel"),
           ("wq.1", "_bwd_dq_window_kernel"),
           ("wk.1", "_bwd_dkv_window_kernel"), ("cf.1", "_fwd_kernel")]


class FakeJob:
    # batch 1, 64 positions, 2 heads of 8, a window of 16: 904 pairs a head
    facts = {"window_call": [1, 64, 2, 8, 16]}
    flash_call = (1, 64, 2, 8, True)
    flash_layers = 1


def run_of(hlo, job=FakeJob):
    return Run(job=job, chips=1, block_steps=2,
               peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e12},
               hlo=hlo, program=hlo.module, init_s=0.0, compile_s=0.0,
               programs_after_warmup=0, dispatch_seconds=[],
               items_per_step_per_chip=64.0)


def two_steps():
    """Two step runs of 10 ms: the window forward twice a step (1 ms each),
    dq 2 ms, dk/dv 3 ms, the causal forward 1 ms, the head 2 ms."""
    def ops(start):
        named = (("wf.1", 0, 1), ("cf.1", 1, 2), ("head.1", 2, 4),
                 ("wf.2", 4, 5), ("wq.1", 5, 7), ("wk.1", 7, 10))
        return [Span(name, (start + lo) * MS, (start + hi) * MS)
                for name, lo, hi in named]
    return Trace(devices=[DeviceTrace(0, ops=ops(0) + ops(10), modules=[
        Span("jit__local_step(1)", 0, 10 * MS),
        Span("jit__local_step(1)", 10 * MS, 20 * MS)])],
        host=[Span("bench.block", 0, 20 * MS)])


def test_window_cost_is_the_roles_products_over_the_windows_pairs():
    pairs = 2 * window.window_pairs(64, 16)
    assert window.window_pairs(64, 16) == 16 * 17 // 2 + 48 * 16 == 904
    for kernel, role in window.WINDOW_KERNELS.items():
        got_flops, got_bytes = window.window_kernel_cost(kernel, 1, 64, 2, 8,
                                                         16)
        assert got_flops == flops.FLASH_PRODUCTS[role] * 2 * 8 * pairs
        causal = flops.flash_kernel_cost(role, 1, 64, 2, 8, True)
        assert got_bytes == causal[1] and got_flops < causal[0]
    assert set(window.WINDOW_KERNELS.values()) == set(flops.FLASH_PRODUCTS)
    assert not set(window.WINDOW_KERNELS) & set(flops.FLASH_PRODUCTS)


def test_the_readers_on_a_step_with_a_recomputed_window_layer():
    hlo = hlo_text.HloIndex(text(KERNELS))
    trace, run = two_steps(), run_of(hlo)
    assert {hlo.kernel_name(i) for i in hlo.kernels()} == {
        "_fwd_window_kernel", "_bwd_dq_window_kernel",
        "_bwd_dkv_window_kernel", "_fwd_kernel"}
    spent = window.seconds_per_step(trace, run)
    assert spent == pytest.approx({
        "_fwd_window_kernel": 2e-3, "_bwd_dq_window_kernel": 2e-3,
        "_bwd_dkv_window_kernel": 3e-3})
    reader = spec_lib.layer_reader
    # 7 of a step's 10 ms
    assert reader("window_time_share")(trace, run) == pytest.approx(70.0)
    # one call's least: products x 2 x 8 x 1808 pairs / 1e9 FLOP/s; the
    # forward is in the step twice and is costed twice
    one = 2 * 8 * 1808 / 1e9
    assert reader("window_fwd_roofline")(trace, run) == pytest.approx(
        100 * 2 * 2 * one / 2e-3)
    assert reader("window_bwd_dq_roofline")(trace, run) == pytest.approx(
        100 * 3 * one / 2e-3)
    assert reader("window_bwd_dkv_roofline")(trace, run) == pytest.approx(
        100 * 4 * one / 3e-3)
    # the causal readers see the causal forward alone
    from harness import roofline
    causal = flops.roofline_seconds(*flops.flash_kernel_cost(
        "_fwd_kernel", 1, 64, 2, 8, True), run.peaks)[0]
    assert roofline.flash_share(trace, run, ("_fwd_kernel",)) == \
        pytest.approx(100 * causal / 1e-3)


def test_blocks_skipped_share_reads_the_programs_counter(monkeypatch):
    """The share of a window call's grid never loaded, from
    ``hvd_flash_block_visits``'s ``window_*`` kinds; a causal call's counts
    stay out of it. On a registry of its own: whatever an earlier test of
    this process traced is not this run's."""
    from horovod_tpu.metrics import registry
    from horovod_tpu.metrics.registry import get_registry
    from horovod_tpu.ops.flash_attention import block_plan, flash_attention
    monkeypatch.setattr(registry, "_default_registry",
                        registry.MetricsRegistry())
    hlo = hlo_text.HloIndex(text(KERNELS))
    trace, run = two_steps(), run_of(hlo)

    def count(kind):
        return get_registry().counter("hvd_flash_block_visits",
                                      kind=kind).value
    assert all(count(kind) == 0 for kind in window.WINDOW_KINDS)
    assert window.blocks_skipped_share(trace, run) is None
    x = jax.ShapeDtypeStruct((1, 16384, 2, 128), jnp.bfloat16)
    for w in (4096, None, 4096):
        jax.eval_shape(lambda q: flash_attention(
            q, q, q, causal=True, interpret=True, window=w), x)
    plan = block_plan(16384, 16384, 512, 512, True, window=4096)
    want = 100.0 * (plan["skipped"] + plan["skipped_behind"]) / 1024
    assert want == pytest.approx(75.39, abs=0.01)
    got = spec_lib.layer_reader("window_blocks_skipped_share")(trace, run)
    assert got == pytest.approx(want)
    assert window.blocks_skipped_share(Trace(), run) is None


def test_a_program_without_window_kernels_reads_nothing():
    """The parent's programs and every other configuration: no window
    kernel in the step and no ``window_call`` among the facts, so the
    readers return None and raise nothing."""
    class Plain:
        facts = {}
        flash_call = (1, 64, 2, 8, True)
        flash_layers = 1
    other = hlo_text.HloIndex(text([("cf.1", "_fwd_kernel")]))
    trace = two_steps()
    for job in (Plain, FakeJob):
        run = run_of(other, job)
        for name in WINDOW_METRICS[:4]:
            assert spec_lib.layer_reader(name)(trace, run) is None, name
            assert spec_lib.layer_reader(name)(Trace(), run) is None
            assert spec_lib.layer_reader(name)(None, run) is None
    # window kernels in the step, but a job that states no window call
    run = run_of(hlo_text.HloIndex(text(KERNELS)), Plain)
    for name in WINDOW_METRICS[1:4]:
        assert spec_lib.layer_reader(name)(trace, run) is None


# -- the control: the reference one precision below the stated one --------------

def test_kept_bits_round_as_the_named_dtypes_do():
    """7 bits is bfloat16's rounding, 3 bits float8_e4m3's inside its normal
    range; in backward the rounding is passed straight through."""
    module = job_of()[0]
    x = jnp.asarray(np.random.RandomState(5).randn(4096), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(module._kept(x, module.BELOW_FLOAT32_BITS)),
        np.asarray(x.astype(jnp.bfloat16).astype(jnp.float32)))
    inside = jnp.where(jnp.abs(x) < 2.0 ** -5, 1.0, x)  # e4m3: 2^-6 .. 448
    np.testing.assert_array_equal(
        np.asarray(module._kept(inside, module.BELOW_BF16_BITS)),
        np.asarray(inside.astype(jnp.float8_e4m3fn).astype(jnp.float32)))
    tiny = module._kept(1e-4 * x, module.BELOW_BF16_BITS)  # float32's range
    np.testing.assert_allclose(np.asarray(tiny), 1e-4 * np.asarray(x),
                               rtol=2.0 ** -4)
    assert module._kept(x, None) is x
    np.testing.assert_array_equal(
        np.asarray(jax.grad(lambda a: (module._kept(a, 3) ** 2).sum())(x)),
        2 * np.asarray(module._kept(x, 3)))


def test_the_control_is_not_correct_by_the_harness_own_comparison():
    """``reference_control.py``: the program against the reference is ok,
    the lowered reference in the program's place is not, by the limit of
    the leaves off the routers' path and by no other (tiny sizes: the
    limits themselves are set from the chip's readings)."""
    done = run_cell("--workload", CELL, "--seeds", "2147483659",
                    "--rehearse", script=os.path.join(
                        bench_paths.BENCH, "reference_control.py"))
    last, earlier = result_line(done)
    assert last["sound_all_ok"] and last["control_none_ok"]
    name = "gradient_relative_l2_error"
    assert last["sound_largest"][name] < last["limits"][name] \
        < last["control_smallest"][name]
    assert last["control_smallest"][name] > 5 * last["sound_largest"][name]
    assert last["control_smallest"]["loss_relative_error"] \
        < last["limits"]["loss_relative_error"]
    readings = [e for e in earlier if "reading" in e]
    assert [(e["reading"], e["ok"]) for e in readings] == [
        ("sound", True), ("control", False)]
