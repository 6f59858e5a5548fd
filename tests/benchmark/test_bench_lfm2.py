"""The cell ``lfm2-t16384``: its rehearsals on the CPU (the whole path of
``run.py`` at the files' tiny sizes), the configuration's own operation
counts and reference pieces by hand, what ``BENCHMARK.json`` says of the cell,
the short-conv operator's readers on a small built trace, and the control."""

import base64
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths  # puts benchmark/ on sys.path
from bench_run import check_rehearsal_result, result_line, run_cell
from harness import flops, hlo_text, shortconv
from harness import spec as spec_lib
from harness.job import Run
from harness.trace_reduce import DeviceTrace, Span, Trace

CELL, CONFIG, TRAFFIC = "lfm2-t16384", "lfm2-8b-a1b", "t16384-b1-lfm2"
SHORTCONV_METRICS = ("shortconv_time_share", "shortconv_mix_ms",
                     "shortconv_roofline", "shortconv_mix_fwd_roofline",
                     "shortconv_mix_bwd_roofline")
MIX_KERNELS = ("_mix_fwd_kernel", "_mix_bwd_kernel")
MS = 1e6  # nanoseconds


def job_of(rehearse=False):
    spec = spec_lib.load()
    config, builder = spec_lib.config(spec, CONFIG, rehearse)
    module = spec_lib.load_module(builder)
    return module, module.build(config, spec_lib.traffic(TRAFFIC, rehearse)), \
        config


# -- the rehearsals --------------------------------------------------------------

def test_rehearsal_reports_the_end_to_end_metrics():
    """Tiny widths, three layers (a dense conv layer, a sparse attention
    layer, a sparse conv layer), 1024 tokens (the kernels interpreted),
    experts 4 of 16 held from 4 on, through the stateful step."""
    result, earlier = result_line(run_cell(
        "--workload", CELL, "--rehearse", "--seconds", "1", "--seed",
        "2147483659", "--trace", "0"))
    check_rehearsal_result(result, 1, {"tokens_per_s_per_chip",
                                       "peak_hbm_gb", "setup_s"})
    facts = earlier[0]
    assert facts["items_per_step_per_chip"] == 1024
    assert facts["layer_types"] == ["conv", "full_attention", "conv"]
    assert facts["num_dense_layers"] == 1 and facts["layers"] == 3
    assert facts["attention"] == "flash" and facts["tied_head"] is True
    assert facts["experts"] == 16 and facts["experts_held"] == [4, 4]
    assert facts["shortconv_layers"] == 2 and facts["conv_taps"] == 3
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
    off_path = [e for name, e in reference[
        "gradient_relative_l2_error"].items()
        if "Lfm2SparseMoe" not in name]
    assert len(off_path) == 15 and max(off_path) <= \
        reference["gradient_tolerance"]
    assert set(reference["gradient_tolerance_under"]) == {"gate", "experts"}
    held = next(e for e in earlier
                if e.get("check", "").startswith("the kernels"))
    assert set(held["required"]) == {"_fwd_kernel", "_bwd_dq_kernel",
                                     "_bwd_dkv_kernel"}
    assert held["not_asked_for"] == {}


def test_traced_rehearsal_names_the_three_readers_and_leaves_them_out():
    """The cell reports the three new metrics (``BENCHMARK.json`` names them
    for it, and each has its reader); on the CPU the trace has no device
    plane, so the readers find nothing to read, return None, and the line
    leaves their metrics out."""
    spec = spec_lib.load()
    named = {m["name"] for m in spec_lib.metrics(spec, "per_layer", CELL)}
    assert set(SHORTCONV_METRICS) <= named
    for name in SHORTCONV_METRICS:
        assert spec_lib.layer_reader(name)(None, None) is None
    result, earlier = result_line(run_cell(
        "--workload", CELL, "--rehearse", "--seconds", "1", "--trace", "1"))
    check_rehearsal_result(result, 1, {
        "init_s", "compile_s", "programs_after_warmup", "host_dispatch_ms"})
    assert not any("shortconv_ms" in e for e in earlier)


# -- the configuration -------------------------------------------------------------

def test_flop_count_by_hand():
    """ISSUE 44's count at 16 384 tokens: a token costs 466 MFLOP forward
    (1.40 GFLOP trained): the four short-conv operators' projections 29%,
    the dense feed-forward 19%, the held experts 19%, attention with its
    projections 19% (its products 14%), the head 14%."""
    module, job, config = job_of()
    d, seq = 2048, 16384
    conv = 2 * d * 4 * d
    attention_projections = 2 * d * (2 * 2048 + 2 * 512)
    scores = 2 * 2 * (seq * (seq + 1) // 2) * 2048 / seq
    dense = 2 * 3 * d * 7168
    held = 2 * 3 * d * 1792 * 4 * 8 / 32
    router = 2 * d * 32
    head = 2 * d * 16384
    total = 4 * conv + attention_projections + scores + dense \
        + 4 * (held + router) + head
    assert job.model_flops_per_item == pytest.approx(3 * total)
    assert total / 1e6 == pytest.approx(466.1, abs=0.1)
    forward = job.facts["forward_mflops_per_token"]
    assert forward["shortconv"] * 1e6 == pytest.approx(4 * conv)
    assert forward["attention"] * 1e6 == pytest.approx(
        attention_projections + scores)
    assert scores / total == pytest.approx(0.14, abs=0.005)
    assert forward["experts"] * 1e6 == pytest.approx(4 * (held + router))
    # moe_experts_mfu multiplies its per-layer count by facts["layers"]
    assert job.facts["moe_train_flops_per_token_per_layer"] \
        * job.facts["layers"] == pytest.approx(3 * 4 * held)
    at_4096 = module.lfm2_forward_flops_per_token(
        config["layer_types"], 1, hidden=d, heads=32, kv_heads=8,
        head_dim=64, dense_dim=7168, experts=32, experts_per_token=4, held=8,
        expert_dim=1792, vocab=16384, seq=4096)
    assert at_4096["parts"]["attention_scores"] / sum(
        at_4096[k] for k in module.KINDS) == pytest.approx(0.04, abs=0.005)


@pytest.mark.parametrize("forwards", [1, 2])
def test_shortconv_cost_by_hand(forwards):
    """One operator and step: the two projections' products in every pass,
    and the bytes no writing can avoid; nothing of ``[B | C | u]``."""
    module = job_of()[0]
    tokens, d = 16384, 2048
    got_flops, got_bytes = module.shortconv_cost(tokens, d, 3,
                                                 forwards=forwards)
    assert got_flops == (forwards + 2) * 2 * tokens * (d * 3 * d + d * d)
    weights = 4 * d * d + 3 * d
    assert got_bytes == forwards * (2 * tokens * d * 2 + 2 * weights) \
        + 3 * tokens * d * 2 + 2 * weights + 4 * weights
    # compute-bound on the v5e, by an order of magnitude
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = flops.roofline_seconds(got_flops, got_bytes, peaks)
    assert bound == "compute" and least > 8 * got_bytes / 819e9
    if forwards == 2:
        job = job_of()[1]
        assert job.facts["shortconv_flops_per_layer_step"] == got_flops
        assert job.facts["shortconv_bytes_per_layer_step"] == got_bytes


@pytest.mark.parametrize("seq,d,taps", [(5, 2, 3), (16, 3, 3), (9, 4, 2)])
def test_reference_short_convolution_by_hand(seq, d, taps):
    """The reference's operator against loops over positions and taps: a
    position reads itself and the ``taps - 1`` before it, zeros before the
    sequence, no bias, no activation; the gates before and after."""
    module = job_of()[0]
    rng = np.random.RandomState(seq)
    x = rng.randn(1, seq, d)
    p = {"in_proj": {"kernel": rng.randn(d, 3 * d)},
         "conv": rng.randn(taps, d),
         "out_proj": {"kernel": rng.randn(d, d)}}
    with jax.default_matmul_precision("highest"):
        got = module._short_conv(
            jnp.asarray(x, jnp.float32),
            jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), p),
            bits=None)
    bcu = x[0] @ p["in_proj"]["kernel"]
    g = bcu[:, :d] * bcu[:, 2 * d:]
    conv = np.zeros_like(g)
    for t in range(seq):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                conv[t] += p["conv"][j] * g[t - (taps - 1) + j]
    want = (bcu[:, d:2 * d] * conv) @ p["out_proj"]["kernel"]
    np.testing.assert_allclose(np.asarray(got)[0], want, rtol=2e-4,
                               atol=2e-5)


def test_benchmark_json_holds_the_cell():
    spec = spec_lib.load()
    cells = {c["name"]: c for c in spec["workloads"]}
    assert len(cells) >= 11 and CELL in cells
    assert sorted(n for n, c in cells.items() if c["chips"] == 4) == \
        ["gpt2s-t1024-dp4", "resnet50-b256-dp4"]
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": TRAFFIC, "chips": 1}
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_layers", "layer_types",
                                "num_dense_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == \
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"

    def reported(cell):
        return {m["name"] for kind in ("end_to_end", "per_layer")
                for m in spec_lib.metrics(spec, kind, cell)}
    like = reported("nemotron3n-t8192")
    assert {m for m in like if not m.startswith("ssm_")} \
        | set(SHORTCONV_METRICS) | {"moe_experts_mfu"} == reported(CELL)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in SHORTCONV_METRICS:
        m = by_name[name]
        assert m["layer"] == "short-conv operator" and \
            m["workloads"] == [CELL]
        assert m["moves"] == "tokens_per_s_per_chip"
        assert m["source"] == ("device_trace" if "_mix_" in name
                               and name.endswith("_roofline")
                               else "program_span")
        assert (m["unit"], m["better"]) == {
            "shortconv_time_share": ("%", "lower"),
            "shortconv_mix_ms": ("ms", "lower")}.get(name, ("%", "higher"))
    traffic = spec_lib.traffic(TRAFFIC)
    assert (traffic["per_chip_batch"], traffic["seq_len"]) == (1, 16384)
    assert (traffic["block_steps"], traffic["warmup_blocks"],
            traffic["trace_blocks"], traffic["reference_examples"],
            traffic["step"]) == (5, 2, 2, 1, {})
    memory = traffic["memory_analysis"]
    # described facts of the compile, which no run reads as a limit
    assert memory["workload"] == CELL
    # the middle's forward twice a conv layer (the blocks are recomputed)
    assert memory["kernels"] == {
        "_fwd_kernel": 1, "_bwd_dq_kernel": 1, "_bwd_dkv_kernel": 1,
        "_add_rows_kernel": 8, "_mix_fwd_kernel": 8, "_mix_bwd_kernel": 4}
    assert memory["kernels_missing"] == memory["kernels_not_asked_for"] == {}
    assert 4e9 < memory["argument_bytes"] + memory["temp_bytes"] < 15.0e9
    # the other 16 384-token traffic file is another cell's, as it was
    assert spec_lib.traffic("t16384-b1")["memory_analysis"]["workload"] == \
        "smallthinker-t16384"


# -- the short-conv operator's readers ---------------------------------------------

def instruction(name, scope, opcode="fusion", phase=True, backward=False):
    model = "transpose(jvp(Lfm2MoeDecoder))" if backward else \
        "jvp(Lfm2MoeDecoder)"
    op_name = "jit(_local_step)/" + (
        f"phase_forward_backward/{model}/Lfm2Block_0/" if phase else "")
    if scope:
        op_name += f"Lfm2ShortConv_0/{scope}/mul"
    elif phase:
        op_name += "Lfm2Mlp_0/w1/dot_general"
    metadata = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return f"  %{name} = f32[8]{{0}} {opcode}(%a){metadata}\n"


def text(instructions):
    return ("HloModule jit__local_step, is_scheduled=true\n\n"
            "ENTRY %main (a: f32[8]) -> f32[8] {\n"
            "  %a = f32[8]{0} parameter(0)\n" + "".join(instructions)
            + "}\n")


STEP = [instruction("in.1", "shortconv_in_proj"),
        instruction("mix.1", "shortconv_mix"),
        "  %copy.1 = f32[8]{0} copy(%a)\n",  # no scope: inherits the mix's
        instruction("out.1", "shortconv_out_proj"),
        instruction("ff.1", None),
        "  %copy.2 = f32[8]{0} copy(%a)\n",  # inherits the lack of one
        instruction("mix.2", "shortconv_mix", backward=True),
        instruction("in.2", "shortconv_in_proj", backward=True)]


def kernel_call(name, function):
    """A ``tpu_custom_call`` whose Mosaic body names ``function``."""
    body = base64.b64encode(b"\x00module\x00" + function.encode()
                            + b"\x00").decode()
    return (f'  %{name} = bf16[8]{{0}} custom-call(%a), '
            f'custom_call_target="tpu_custom_call", '
            f'backend_config={{"custom_call_config": {{"body":"{body}"}}}}, '
            f'metadata={{op_name="jit(_local_step)/phase_forward_backward/'
            f'jvp(Lfm2MoeDecoder)/Lfm2Block_0/Lfm2ShortConv_0/shortconv_mix/'
            f'pallas_call"}}\n')


class FakeJob:
    # one conv layer whose step needs 4e6 FLOPs and 1e3 bytes; a call of
    # the middle over 1000 tokens of 500 channels, 3 taps
    facts = {"shortconv_layers": 1, "shortconv_flops_per_layer_step": 4e6,
             "shortconv_bytes_per_layer_step": 1e3,
             "shortconv_mix_call": [1000, 500, 3]}
    flash_call = None
    flash_layers = 0


def run_of(hlo, job=FakeJob):
    return Run(job=job, chips=1, block_steps=2,
               peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e12},
               hlo=hlo, program=hlo.module, init_s=0.0, compile_s=0.0,
               programs_after_warmup=0, dispatch_seconds=[],
               items_per_step_per_chip=64.0)


def two_steps():
    """Two step runs of 20 ms: in-projection 3 ms, the middle 1 ms and a
    copy of 1 ms behind it, out-projection 2 ms, the feed-forward 5 ms and a
    copy of 1 ms behind it, the middle's backward 2 ms, the in-projection's
    4 ms; 1 ms idle."""
    def ops(start):
        named = (("in.1", 0, 3), ("mix.1", 3, 4), ("copy.1", 4, 5),
                 ("out.1", 5, 7), ("ff.1", 7, 12), ("copy.2", 12, 13),
                 ("mix.2", 13, 15), ("in.2", 15, 19))
        return [Span(name, (start + lo) * MS, (start + hi) * MS)
                for name, lo, hi in named]
    return Trace(devices=[DeviceTrace(0, ops=ops(0) + ops(20), modules=[
        Span("jit__local_step(1)", 0, 20 * MS),
        Span("jit__local_step(1)", 20 * MS, 40 * MS)])],
        host=[Span("bench.block", 0, 40 * MS)])


def test_the_readers_count_an_operation_under_its_scope_or_the_one_before():
    hlo = hlo_text.HloIndex(text(STEP))
    trace, run = two_steps(), run_of(hlo)
    found = shortconv.reduce(trace, hlo, hlo.module)
    assert found["seconds"] == pytest.approx({
        "shortconv_in_proj": 7e-3, "shortconv_mix": 4e-3,
        "shortconv_out_proj": 2e-3})
    assert found["inherited"] == pytest.approx({"shortconv_mix": 1e-3})
    assert found["total"] == pytest.approx(19e-3)
    reader = spec_lib.layer_reader
    assert reader("shortconv_time_share")(trace, run) == pytest.approx(
        100 * 13 / 19)
    assert reader("shortconv_mix_ms")(trace, run) == pytest.approx(4.0)
    # the least the peaks allow is the products' 4 ms (the bytes' 1 ns),
    # over the 13 ms under the three scopes
    assert reader("shortconv_roofline")(trace, run) == pytest.approx(
        100 * 4 / 13)


def test_the_kernels_readers_cost_a_kernel_by_the_calls_the_step_holds():
    """The forward kernel twice in the text (the pass and its recomputation)
    and the backward once; a call's least time is its bytes at the chip's
    bandwidth: every [tokens, channels] array once."""
    hlo = hlo_text.HloIndex(text([
        kernel_call("mf.1", "_mix_fwd_kernel"), instruction("ff.1", None),
        kernel_call("mf.2", "_mix_fwd_kernel"),
        kernel_call("mb.1", "_mix_bwd_kernel")]))
    assert {hlo.kernel_name(i) for i in hlo.kernels()} == set(MIX_KERNELS)

    def ops(start):
        named = (("mf.1", 0, 1), ("ff.1", 1, 6), ("mf.2", 6, 7),
                 ("mb.1", 7, 10))
        return [Span(name, (start + lo) * MS, (start + hi) * MS)
                for name, lo, hi in named]
    trace = Trace(devices=[DeviceTrace(0, ops=ops(0) + ops(10), modules=[
        Span("jit__local_step(1)", 0, 10 * MS),
        Span("jit__local_step(1)", 10 * MS, 20 * MS)])],
        host=[Span("bench.block", 0, 20 * MS)])
    run = run_of(hlo)
    run.peaks["hbm_bytes_per_s"] = 1e9
    run.peaks["bf16_flops_per_s"] = 1e15
    elements = 1000 * 500
    assert shortconv.mix_kernel_cost("_mix_fwd_kernel", 1000, 500, 3) == \
        (8.0 * elements, 4.0 * elements * 2)
    assert shortconv.mix_kernel_cost("_mix_bwd_kernel", 1000, 500, 3) == \
        ((8 + 1 + 6 + 3 + 6) * elements, 7.0 * elements * 2)
    reader = spec_lib.layer_reader
    # two calls of 4 ms least in 2 ms a step; one of 7 ms least in 3 ms
    assert reader("shortconv_mix_fwd_roofline")(trace, run) == \
        pytest.approx(100 * 2 * 4e-3 / 2e-3)
    assert reader("shortconv_mix_bwd_roofline")(trace, run) == \
        pytest.approx(100 * 7e-3 / 3e-3)
    # the kernels' time counts under the scope their op_name carries
    assert reader("shortconv_mix_ms")(trace, run) == pytest.approx(5.0)


@pytest.mark.parametrize("name", SHORTCONV_METRICS)
def test_a_program_without_the_scopes_reads_nothing(name):
    """The parent's programs and every other configuration: no
    ``shortconv_*`` scope in the step's text, so nothing to read."""
    plain = hlo_text.HloIndex(text([instruction("ff.1", None),
                                    instruction("ff.2", None)]))
    trace = Trace(devices=[DeviceTrace(0, ops=[
        Span("ff.1", 0, 5 * MS), Span("ff.2", 5 * MS, 9 * MS)], modules=[
        Span("jit__local_step(1)", 0, 10 * MS)])], host=[])
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
    the control fails each of them); at the rehearsal's tiny sizes every
    reading is smaller and the control's need not pass a limit."""
    done = run_cell("--workload", CELL, "--seeds", "5", "--rehearse",
                    script=os.path.join(bench_paths.BENCH,
                                        "reference_control.py"))
    last, earlier = result_line(done)
    assert last["sound_all_ok"]
    name = "gradient_relative_l2_error"
    assert set(last["limits"]) == {"loss_relative_error", name,
                                   name + ".gate", name + ".experts"}
    assert last["sound_largest"][name] < last["limits"][name]
    assert last["control_smallest"][name] > 5 * last["sound_largest"][name]
    assert last["control_smallest"]["loss_relative_error"] > \
        10 * last["sound_largest"]["loss_relative_error"]
    assert last["sound_largest"]["loss_relative_error"] \
        < last["limits"]["loss_relative_error"]
    readings = [e for e in earlier if "reading" in e]
    assert [e["reading"] for e in readings] == ["sound", "control"]
    config = json.load(open(os.path.join(
        bench_paths.BENCH, "configs", CONFIG + ".json")))
    assert "float32" in config["dtype_policy"]["router"] and \
        "float32" in config["dtype_policy"]["short_convolution"]


def test_with_the_choices_forced_alike_the_routers_leaves_read_as_the_rest():
    """``reference_forced.py``: the reference's chosen experts sit in the
    state both sides route from, and the experts' and the routers' leaves,
    which read ten times the others in the sound reading above at this size
    too, come down to the others' distance: that part was the choices."""
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
