"""The cell ``joyai-t8192``: its rehearsals on the CPU (the whole path of
``run.py`` at the files' tiny sizes), the configuration's own operation
counts and reference pieces by hand, what ``BENCHMARK.json`` says of the cell,
the latent kernels', the operator's and the module's readers on a small built
trace, and the control."""

import base64
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths  # puts benchmark/ on sys.path
from bench_run import check_rehearsal_result, result_line, run_cell
from harness import flops, hlo_text, latent
from harness import spec as spec_lib
from harness.job import Run
from harness.trace_reduce import DeviceTrace, Span, Trace

CELL, CONFIG, TRAFFIC = "joyai-t8192", "joyai-llm-flash", "t8192-b1-joyai"
KERNEL_METRICS = {"latent_fwd_roofline": "_fwd_latent_kernel",
                  "latent_bwd_dq_roofline": "_bwd_dq_latent_kernel",
                  "latent_bwd_dkv_roofline": "_bwd_dkv_latent_kernel"}
NEW_METRICS = (*KERNEL_METRICS, "latent_time_share",
               "latent_attention_roofline", "mtp_time_share")
MS = 1e6  # nanoseconds


def job_of(rehearse=False):
    spec = spec_lib.load()
    config, builder = spec_lib.config(spec, CONFIG, rehearse)
    module = spec_lib.load_module(builder)
    return module, module.build(config, spec_lib.traffic(TRAFFIC, rehearse)), \
        config


# -- the rehearsals --------------------------------------------------------------

def test_rehearsal_reports_the_end_to_end_metrics():
    """Tiny widths at which q/k (16 + 8) and v (16) still differ, the dense
    layer, two sparse layers and the module, 1024 tokens (the latent kernels
    interpreted), experts 4 of 16 held from 4 on, through the stateful
    step."""
    result, earlier = result_line(run_cell(
        "--workload", CELL, "--rehearse", "--seconds", "1", "--seed",
        "2147483659", "--trace", "0"))
    check_rehearsal_result(result, 1, {"tokens_per_s_per_chip",
                                       "peak_hbm_gb", "setup_s"})
    facts = earlier[0]
    assert facts["items_per_step_per_chip"] == 1024
    assert (facts["num_layers"], facts["first_k_dense"],
            facts["mtp_layers"]) == (3, 1, 1)
    assert facts["layers"] == facts["latent_layers"] == 4
    assert facts["attention"] == "flash"
    assert facts["latent_call"] == [1, 1024, 4, 24, 16]
    assert facts["experts"] == 16 and facts["experts_held"] == [4, 4]
    assert facts["mtp_lambda"] == 0.3
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
    errors = reference["gradient_relative_l2_error"]
    off_path = [e for name, e in errors.items() if "JoyaiMoE_0/gate" not in
                name and "JoyaiMoE_0/experts" not in name]
    assert len(off_path) == 21 and max(off_path) <= \
        reference["gradient_tolerance"]
    # the embedding and the head (each used twice) and the module's own
    for leaf in ("embed_tokens/embedding", "lm_head/kernel",
                 "JoyaiMtp_0/eh_proj/kernel",
                 "JoyaiBlock_1/JoyaiLatentAttention_0/kv_a_proj_with_mqa/"
                 "kernel"):
        assert leaf in errors, leaf
    assert set(reference["gradient_tolerance_under"]) == {"gate", "experts"}
    held = next(e for e in earlier
                if e.get("check", "").startswith("the kernels"))
    # the causal names are none of this cell's: it asks for none, holds none
    assert held["required"] == {} and held["not_asked_for"] == {}


def test_traced_rehearsal_names_the_six_readers_and_leaves_them_out():
    """The cell reports the six new metrics (``BENCHMARK.json`` names them
    for it, and each has its reader); on the CPU the trace has no device
    plane, so the readers find nothing to read, return None, and the line
    leaves their metrics out."""
    spec = spec_lib.load()
    named = {m["name"] for m in spec_lib.metrics(spec, "per_layer", CELL)}
    assert set(NEW_METRICS) <= named
    for name in NEW_METRICS:
        assert spec_lib.layer_reader(name)(None, None) is None
    result, earlier = result_line(run_cell(
        "--workload", CELL, "--rehearse", "--seconds", "1", "--trace", "1"))
    check_rehearsal_result(result, 1, {
        "init_s", "compile_s", "programs_after_warmup", "host_dispatch_ms"})
    assert not any("latent_ms" in e or "mtp_ms" in e for e in earlier)


# -- the configuration -------------------------------------------------------------

def test_flop_count_by_hand():
    """ISSUE 48's count at 8192 tokens: a token costs 1.133 GFLOP forward
    (3.40 GFLOP trained, 27.8 TFLOP a step): the six latent-attention
    operators 72% (their products 44%, their projections 28%), the two heads
    12%, the dense feed-forward 8%, the five expert layers 7%."""
    module, job, config = job_of()
    d, seq = 2048, 8192
    projections = 2 * (d * 1536 + 1536 * 32 * 192 + d * 576
                       + 512 * 32 * 256 + 32 * 128 * d)
    scores = 2 * (192 + 128) * 32 * (seq * (seq + 1) // 2) / seq
    dense = 2 * 3 * d * 7168
    router, shared = 2 * d * 256, 2 * 3 * d * 768
    held = 2 * 3 * d * 768 * 8 * 16 / 256
    merge, head = 2 * 2 * d * d, 2 * d * 16160
    total = 6 * (projections + scores) + dense \
        + 5 * (router + shared + held) + merge + 2 * head
    assert job.model_flops_per_item == pytest.approx(3 * total)
    assert total / 1e6 == pytest.approx(1132.8, abs=0.1)
    assert 3 * total * seq / 1e12 == pytest.approx(27.8, abs=0.1)
    forward = job.facts["forward_mflops_per_token"]
    assert forward["latent_attention"] * 1e6 == pytest.approx(
        6 * (projections + scores))
    assert 6 * scores / total == pytest.approx(0.44, abs=0.005)
    assert 6 * projections / total == pytest.approx(0.28, abs=0.005)
    assert forward["head"] * 1e6 == pytest.approx(2 * head)
    assert forward["experts"] * 1e6 == pytest.approx(
        5 * (router + shared + held))
    # moe_experts_mfu multiplies its per-layer count by facts["layers"]
    assert job.facts["moe_train_flops_per_token_per_layer"] \
        * job.facts["layers"] == pytest.approx(3 * 5 * held)
    sizes = {k: job.facts[k] for k in (
        "num_layers", "first_k_dense", "mtp_layers", "hidden", "heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_dim", "qk_rope_dim", "v_dim",
        "dense_dim", "experts", "experts_per_token", "expert_dim",
        "shared_experts", "vocab")}
    at_4096 = module.joyai_forward_flops_per_token(held=16, seq=4096, **sizes)
    assert 6 * at_4096["parts"]["latent_scores"] / sum(
        at_4096[k] for k in module.KINDS) == pytest.approx(0.29, abs=0.01)


@pytest.mark.parametrize("kernel,over_qk,over_v,arrays", [
    ("_fwd_latent_kernel", 1, 1, (1, 1, 2, 1)),
    ("_bwd_dq_latent_kernel", 2, 1, (2, 1, 2, 2)),
    ("_bwd_dkv_latent_kernel", 2, 2, (1, 2, 3, 2))])
def test_latent_kernel_cost_by_hand(kernel, over_qk, over_v, arrays):
    """A call at the cell's shapes: the products over the causal pairs at
    their own widths (forward ``2 (qk + v)`` a pair, dq ``2 (2 qk + v)``,
    dk/dv ``2 (2 qk + 2 v)``), every array once at its own width, the rotary
    64 of a key-like array once a sequence and not once a head."""
    batch, seq, heads, qk, v = 1, 8192, 32, 192, 128
    got_flops, got_bytes = latent.latent_kernel_cost(kernel, batch, seq,
                                                     heads, qk, v)
    pairs = heads * seq * (seq + 1) // 2
    assert got_flops == 2 * (over_qk * qk + over_v * v) * pairs
    q_like, key_like, v_like, rows = arrays
    elements = heads * seq * (q_like * qk + v_like * v + key_like * 128) \
        + key_like * seq * 64
    assert got_bytes == 2 * elements + 4 * rows * heads * seq
    # what one head width would have said of the same call (the causal
    # names' pricing at 192): more, by the values' products and bytes
    role = latent.LATENT_KERNELS[kernel]
    at_one_width = flops.flash_kernel_cost(role, batch, seq, heads, qk, True)
    assert got_flops < at_one_width[0] and got_bytes < at_one_width[1]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(got_flops, got_bytes, peaks)[1] == \
        "compute"


@pytest.mark.parametrize("forwards,kept", [(1, 1), (2, 1), (2, 2)])
def test_latent_attention_cost_by_hand(forwards, kept):
    """One operator and step: the five projections' products in every pass,
    the kernels' own costs (the forward kernel once where a recomputed block
    keeps its attention's output), and the bytes no writing can avoid."""
    module = job_of()[0]
    tokens, d = 8192, 2048
    weights = d * 1536 + 1536 * 6144 + d * 576 + 512 * 8192 + 4096 * d
    assert weights == 26347520 - 1536 - 512  # the operator less its norms
    costs = {name: latent.latent_kernel_cost(name, 1, tokens, 32, 192, 128)
             for name in latent.LATENT_KERNELS}
    got_flops, got_bytes = module.latent_attention_cost(
        tokens, d, weights, costs, forwards=forwards,
        forward_kernel_runs=kept)
    fwd, dq, dkv = (costs[name] for name in latent.LATENT_KERNELS)
    assert got_flops == (forwards + 2) * 2 * weights * tokens \
        + kept * fwd[0] + dq[0] + dkv[0]
    assert got_bytes == forwards * (2 * tokens * d * 2 + 2 * weights) \
        + 3 * tokens * d * 2 + 6 * weights + kept * fwd[1] + dq[1] + dkv[1]
    if (forwards, kept) == (2, 1):
        job = job_of()[1]
        assert job.facts["latent_attention_flops_per_layer_step"] == \
            got_flops
        assert job.facts["latent_attention_bytes_per_layer_step"] == \
            got_bytes


@pytest.mark.parametrize("ahead", [1, 2])
def test_reference_cross_entropy_by_hand(ahead):
    """``logits_i`` against ``t_{i+ahead}`` over the ``T - ahead`` positions
    that have one; the roll's wrapped labels weigh nothing."""
    module = job_of()[0]
    rng = np.random.RandomState(ahead)
    b, t, d, vocab = 2, 16, 8, 32
    x, w = rng.randn(b, t, d), rng.randn(d, vocab)
    tokens = rng.randint(0, vocab, (b, t))
    with jax.default_matmul_precision("highest"):
        got = module._cross_entropy(jnp.asarray(x, jnp.float32),
                                    jnp.asarray(w, jnp.float32),
                                    jnp.asarray(tokens), ahead)
    logits = x @ w
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    want = -np.mean([logp[n, i, tokens[n, i + ahead]]
                     for n in range(b) for i in range(t - ahead)])
    assert float(got) == pytest.approx(want, rel=1e-5)


def test_benchmark_json_holds_the_cell():
    spec = spec_lib.load()
    cells = {c["name"]: c for c in spec["workloads"]}
    assert len(cells) >= 12 and CELL in cells
    assert sorted(n for n, c in cells.items() if c["chips"] == 4) == \
        ["gpt2s-t1024-dp4", "resnet50-b256-dp4"]
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": TRAFFIC, "chips": 1}
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert entry["source"] == "https://huggingface.co/jdopensource/" \
        "JoyAI-LLM-Flash/blob/main/config.json"
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert len(spec["configs"]) >= 8

    def reported(cell):
        return {m["name"] for kind in ("end_to_end", "per_layer")
                for m in spec_lib.metrics(spec, kind, cell)}
    like = reported("sdar-t8192-bd4")  # another cell that states no flash_call
    assert {m for m in like if not m.startswith("blockdiff_")
            and m != "flash_time_share"} | set(NEW_METRICS) == reported(CELL)
    assert not any(m.startswith("flash_") for m in reported(CELL))
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["better"] == ("lower" if name.endswith("_time_share")
                               else "higher")
        assert (m["layer"], m["source"]) == {
            "latent_attention_roofline": ("latent attention",
                                          "program_span"),
            "mtp_time_share": ("model step", "program_span")}.get(
                name, ("kernels", "device_trace"))
    window = by_name["window_time_share"]
    assert {k: v for k, v in by_name["latent_time_share"].items()
            if k not in ("name", "workloads")} == \
        {k: v for k, v in window.items() if k not in ("name", "workloads")}
    traffic = spec_lib.traffic(TRAFFIC)
    assert (traffic["per_chip_batch"], traffic["seq_len"]) == (1, 8192)
    # as ISSUE 48 names the cell: the other cells' 10 steps of warm-up, though
    # the bias rule needs 29-40 to bring the step-0 loads to one tile a walk
    # (PERF.md §6, PR 48: the window's median reads the balanced blocks)
    assert (traffic["block_steps"], traffic["warmup_blocks"],
            traffic["trace_blocks"], traffic["reference_examples"],
            traffic["step"]) == (5, 2, 2, 1, {})
    memory = traffic["memory_analysis"]
    # described facts of the compile, which no run reads as a limit
    assert memory["workload"] == CELL
    # every block keeps its attention's output: the forward kernel once a
    # layer; the walk's way back twice a sparse layer
    assert memory["kernels"] == {
        "_fwd_latent_kernel": 6, "_bwd_dq_latent_kernel": 6,
        "_bwd_dkv_latent_kernel": 6, "_add_rows_kernel": 10}
    assert memory["kernels_missing"] == memory["kernels_not_asked_for"] == {}
    assert 4e9 < memory["argument_bytes"] + memory["temp_bytes"] < 15.0e9
    # the other 8192-token traffic file is another cell's, as it was
    assert spec_lib.traffic("t8192-b1")["memory_analysis"]["workload"] == \
        "nemotron3n-t8192"


# -- the readers ---------------------------------------------------------------------

def instruction(name, scopes, opcode="fusion", phase=True, backward=False):
    model = "transpose(jvp(JoyaiFlashDecoder))" if backward else \
        "jvp(JoyaiFlashDecoder)"
    op_name = "jit(_local_step)/" + (
        f"phase_forward_backward/{model}/" if phase else "")
    if scopes:
        op_name += "/".join(scopes) + "/mul"
    elif phase:
        op_name += "JoyaiBlock_0/mlp/w1/dot_general"
    metadata = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return f"  %{name} = f32[8]{{0}} {opcode}(%a){metadata}\n"


def text(instructions):
    return ("HloModule jit__local_step, is_scheduled=true\n\n"
            "ENTRY %main (a: f32[8]) -> f32[8] {\n"
            "  %a = f32[8]{0} parameter(0)\n" + "".join(instructions)
            + "}\n")


def kernel_call(name, function, scopes=("attn_latent",)):
    """A ``tpu_custom_call`` whose Mosaic body names ``function``."""
    body = base64.b64encode(b"\x00module\x00" + function.encode()
                            + b"\x00").decode()
    return (f'  %{name} = bf16[8]{{0}} custom-call(%a), '
            f'custom_call_target="tpu_custom_call", '
            f'backend_config={{"custom_call_config": {{"body":"{body}"}}}}, '
            f'metadata={{op_name="jit(_local_step)/phase_forward_backward/'
            f'jvp(JoyaiFlashDecoder)/{"/".join(scopes)}/pallas_call"}}\n')


STEP = [instruction("q.1", ["JoyaiBlock_1", "mla_q_proj"]),
        instruction("kv.1", ["JoyaiBlock_1", "mla_kv_proj"]),
        instruction("rope.1", ["JoyaiBlock_1", "mla_rope"]),
        "  %copy.1 = f32[8]{0} copy(%a)\n",  # no scope: inherits the rope's
        kernel_call("fwd.1", "_fwd_latent_kernel"),
        instruction("out.1", ["JoyaiBlock_1", "mla_out_proj"]),
        instruction("ff.1", None),
        "  %copy.2 = f32[8]{0} copy(%a)\n",  # inherits the lack of one
        instruction("merge.1", ["JoyaiMtp_0", "mtp_merge"]),
        # the module's block: its own scope AND the operator's
        instruction("mq.1", ["JoyaiMtp_0", "mtp_block", "JoyaiBlock_0",
                             "mla_q_proj"]),
        kernel_call("fwd.2", "_fwd_latent_kernel",
                    ("JoyaiMtp_0", "mtp_block", "attn_latent")),
        instruction("head.1", ["mtp_head"]),
        kernel_call("dkv.1", "_bwd_dkv_latent_kernel"),
        kernel_call("dq.1", "_bwd_dq_latent_kernel")]


class FakeJob:
    # two operators whose step needs 3e6 FLOPs and 1e3 bytes each; a call
    # over 1000 positions, 2 heads, q/k of 24 and v of 16
    facts = {"latent_layers": 2,
             "latent_attention_flops_per_layer_step": 3e6,
             "latent_attention_bytes_per_layer_step": 1e3,
             "latent_call": [1, 1000, 2, 24, 16]}
    flash_call = None
    flash_layers = 0


def run_of(hlo, job=FakeJob):
    return Run(job=job, chips=1, block_steps=2,
               peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e12},
               hlo=hlo, program=hlo.module, init_s=0.0, compile_s=0.0,
               programs_after_warmup=0, dispatch_seconds=[],
               items_per_step_per_chip=64.0)


def two_steps():
    """Two step runs of 30 ms, 29 ms busy."""
    def ops(start):
        named = (("q.1", 0, 2), ("kv.1", 2, 3), ("rope.1", 3, 4),
                 ("copy.1", 4, 5), ("fwd.1", 5, 8), ("out.1", 8, 9),
                 ("ff.1", 9, 13), ("copy.2", 13, 14), ("merge.1", 14, 15),
                 ("mq.1", 15, 17), ("fwd.2", 17, 19), ("head.1", 19, 21),
                 ("dkv.1", 21, 25), ("dq.1", 25, 29))
        return [Span(name, (start + lo) * MS, (start + hi) * MS)
                for name, lo, hi in named]
    return Trace(devices=[DeviceTrace(0, ops=ops(0) + ops(30), modules=[
        Span("jit__local_step(1)", 0, 30 * MS),
        Span("jit__local_step(1)", 30 * MS, 60 * MS)])],
        host=[Span("bench.block", 0, 60 * MS)])


def test_the_readers_count_an_operation_under_its_scope_or_the_one_before():
    hlo = hlo_text.HloIndex(text(STEP))
    trace, run = two_steps(), run_of(hlo)
    found = latent.reduce(trace, hlo, hlo.module, latent.OPERATOR)
    assert found["seconds"] == pytest.approx({
        "mla_q_proj": 4e-3, "mla_kv_proj": 1e-3, "mla_rope": 2e-3,
        "attn_latent": 13e-3, "mla_out_proj": 1e-3})
    assert found["inherited"] == pytest.approx({"mla_rope": 1e-3})
    assert found["total"] == pytest.approx(29e-3)
    module = latent.reduce(trace, hlo, hlo.module, latent.MODULE)
    assert module["seconds"] == pytest.approx({
        "mtp_merge": 1e-3, "mtp_block": 4e-3, "mtp_head": 2e-3})
    assert module["inherited"] == {}
    reader = spec_lib.layer_reader
    assert reader("mtp_time_share")(trace, run) == pytest.approx(100 * 7 / 29)
    # the least the peaks allow two operators is 2 x 3 ms of products (the
    # bytes' 1 ns), over the 21 ms under the five scopes
    assert reader("latent_attention_roofline")(trace, run) == pytest.approx(
        100 * 6 / 21)
    assert reader("latent_time_share")(trace, run) == pytest.approx(
        100 * 13 / 29)


def test_the_kernels_readers_cost_a_kernel_by_the_times_it_ran():
    """The forward kernel ran twice a step (two operators) and each backward
    one once: a kernel's least time is its call's x its spans in the traced
    stretch, whatever the compiled text holds."""
    hlo = hlo_text.HloIndex(text(STEP))
    trace, run = two_steps(), run_of(hlo)
    found = latent.runs_and_seconds(trace, run)
    assert found == pytest.approx({
        "_fwd_latent_kernel": (4, 10e-3), "_bwd_dkv_latent_kernel": (2, 8e-3),
        "_bwd_dq_latent_kernel": (2, 8e-3)})
    reader = spec_lib.layer_reader
    for metric, kernel in KERNEL_METRICS.items():
        ran, spent = found[kernel]
        least = flops.roofline_seconds(
            *latent.latent_kernel_cost(kernel, 1, 1000, 2, 24, 16),
            run.peaks)[0]
        assert reader(metric)(trace, run) == pytest.approx(
            100 * ran * least / spent)
    # compute-bound at these peaks: the products over the causal pairs
    pairs = 2 * 1000 * 1001 // 2
    assert reader("latent_fwd_roofline")(trace, run) == pytest.approx(
        100 * 4 * 2 * (24 + 16) * pairs / 1e9 / 10e-3)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_reads_nothing(name):
    """The parent's programs and every other configuration: no ``mla_*`` or
    ``mtp_*`` scope and no latent kernel in the step's text, so nothing to
    read."""
    plain = hlo_text.HloIndex(text([
        instruction("ff.1", None), instruction("ff.2", None),
        kernel_call("fwd.1", "_fwd_kernel", ("attn_full",))]))
    trace = Trace(devices=[DeviceTrace(0, ops=[
        Span("ff.1", 0, 5 * MS), Span("ff.2", 5 * MS, 9 * MS),
        Span("fwd.1", 9 * MS, 10 * MS)], modules=[
        Span("jit__local_step(1)", 0, 10 * MS)])],
        host=[Span("bench.block", 0, 10 * MS)])
    reader = spec_lib.layer_reader(name)
    assert reader(trace, run_of(plain)) is None
    assert reader(None, run_of(plain)) is None
    # and a job that states no counts reads no roofline, scopes or not

    class NoCounts(FakeJob):
        facts = {}
    scoped = hlo_text.HloIndex(text(STEP))
    got = reader(two_steps(), run_of(scoped, NoCounts))
    assert (got is None) == name.endswith("_roofline")


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


def test_the_control_reads_apart_through_the_harness_own_comparison():
    """``reference_control.py`` runs both readings through ``run.py``'s own
    comparison: the lowered reference in the program's place reads several
    times the program's distance on the leaves off the routers' path. The
    limits are set from the chip's readings at the published widths (where
    the control fails each of them); at the rehearsal's tiny sizes four held
    experts see few rows each and their leaves read apart from the chip's
    limits, so only the leaves off the routers' path are held to theirs."""
    done = run_cell("--workload", CELL, "--seeds", "5", "--rehearse",
                    script=os.path.join(bench_paths.BENCH,
                                        "reference_control.py"))
    last, earlier = result_line(done)
    name = "gradient_relative_l2_error"
    assert set(last["limits"]) == {"loss_relative_error", name,
                                   name + ".gate", name + ".experts"}
    assert last["sound_largest"][name] < last["limits"][name]
    assert last["control_smallest"][name] > 4 * last["sound_largest"][name]
    for under in (".gate", ".experts"):
        assert last["control_smallest"][name + under] > \
            1.5 * last["sound_largest"][name + under]
    # a mean over two thousand tokens resolves no precision: no upper reading
    assert last["sound_largest"]["loss_relative_error"] \
        < last["limits"]["loss_relative_error"]
    readings = [e for e in earlier if "reading" in e]
    assert [e["reading"] for e in readings] == ["sound", "control"]
    config = json.load(open(os.path.join(
        bench_paths.BENCH, "configs", CONFIG + ".json")))
    assert "float32" in config["dtype_policy"]["router"] and \
        "float32" in config["dtype_policy"]["logits_and_loss"]


def test_with_the_choices_forced_alike_the_routers_leaves_read_as_the_rest():
    """``reference_forced.py``: the reference's chosen experts sit in the
    state both sides route from (the stack's routers and the module's), and
    the experts' and the routers' leaves, which read eight times the others
    in the sound reading above at this size too, come down to the others'
    distance: that part was the choices, not rounding."""
    done = run_cell("--workload", CELL, "--seeds", "5", "--rehearse",
                    script=os.path.join(bench_paths.BENCH,
                                        "reference_forced.py"))
    last, earlier = result_line(done)
    name = "gradient_relative_l2_error"
    assert set(last["forced_largest"]) == set(last["limits"]) == {
        "loss_relative_error", name, name + ".gate", name + ".experts"}
    off_the_path = last["forced_largest"][name]
    assert 0 < off_the_path < 0.03
    for under in (".gate", ".experts"):
        assert 0 < last["forced_largest"][name + under] < 1.5 * off_the_path
    assert [e["reading"] for e in earlier if "reading" in e] == ["forced"]


def test_a_bf16_router_alone_reads_inside_every_limit():
    """``reference_router.py``: the float32 reference with the routers'
    logits alone in bf16, in the program's place, reads as a sound program
    does (its near-ties fall otherwise, nothing else differs) and passes the
    comparison: ``correct`` does not hold the router's float32, which is why
    ``tests/test_joyai_flash.py`` holds the routing equation by hand."""
    done = run_cell("--workload", CELL, "--seeds", "5", "--rehearse",
                    script=os.path.join(bench_paths.BENCH,
                                        "reference_router.py"))
    last, earlier = result_line(done)
    name = "gradient_relative_l2_error"
    assert last["router_none_ok"] is False
    for key, limit in last["limits"].items():
        assert 0 < last["router_largest"][key] < limit
    # the choices moved: the routers' and the experts' leaves read apart
    # from the rest, as they do for the program
    for under in (".gate", ".experts"):
        assert last["router_smallest"][name + under] > \
            3 * last["router_largest"][name]
    assert [e["reading"] for e in earlier if "reading" in e] == ["router"]

