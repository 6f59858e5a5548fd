"""The cell ``trinity-t16384``: its rehearsals on the CPU (the whole path of
``run.py`` at the files' tiny sizes), the configuration's own operation
counts and reference pieces by hand, what ``BENCHMARK.json`` says of the cell,
the gate's and the post-norms' readers on a small built trace, and the
control. Nothing here counts cells or reads an entry off the end of a list: a
cell appended later breaks none of it."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths  # puts benchmark/ on sys.path
from bench_run import check_rehearsal_result, result_line, run_cell
from harness import hlo_text, outgate
from harness import spec as spec_lib
from harness.job import Run
from harness.trace_reduce import DeviceTrace, Span, Trace

CELL, CONFIG, TRAFFIC = "trinity-t16384", "trinity-mini", "t16384-b1-trinity"
LIKE = "smallthinker-t16384"  # the other cell with window kernels
NEW_METRICS = ("outgate_time_share", "outgate_mul_ms")
MS = 1e6  # nanoseconds


def job_of(rehearse=False):
    spec = spec_lib.load()
    config, builder = spec_lib.config(spec, CONFIG, rehearse)
    module = spec_lib.load_module(builder)
    return module, module.build(config, spec_lib.traffic(TRAFFIC, rehearse)), \
        config


# -- the rehearsals --------------------------------------------------------------

def test_rehearsal_reports_the_end_to_end_metrics():
    """Tiny widths, a dense sliding layer, a sparse full and a sparse sliding
    one, 1024 tokens (the causal and the window kernels interpreted), a
    window of 200, experts 4 of 16 held from 4 on, through the stateful
    step."""
    result, earlier = result_line(run_cell(
        "--workload", CELL, "--rehearse", "--seconds", "1", "--seed",
        "2147483659", "--trace", "0"))
    check_rehearsal_result(result, 1, {"tokens_per_s_per_chip",
                                       "peak_hbm_gb", "setup_s"})
    facts = earlier[0]
    assert facts["items_per_step_per_chip"] == 1024
    assert facts["layer_types"] == ["sliding_attention", "full_attention",
                                    "sliding_attention"]
    assert (facts["layers"], facts["num_dense_layers"],
            facts["full_layers"], facts["window_layers"]) == (3, 1, 1, 2)
    assert facts["attention"] == "flash"
    assert facts["window_call"] == [1, 1024, 4, 16, 200]
    assert facts["experts"] == 16 and facts["experts_held"] == [4, 4]
    assert facts["recompute"] == "blocks_keep_attention"
    assert facts["post_norm_start"] == 0.01  # the cell's start
    assert facts["outgate_mul_bytes_per_layer_pass"] == 3 * 1024 * 64 * 2
    checks = next(e for e in earlier if "checks" in e)
    assert checks["programs_in_windows"] == 0
    # two steps at the head of a 2000-step warm-up need not lower a float32
    # loss, and at tiny widths the leaves read apart from the chip's limits:
    # every other check holds
    assert all(ok for name, ok in checks["checks"].items()
               if name not in ("warmup_loss", "reference"))
    reference = next(e for e in earlier
                     if e.get("check") == "float32 reference")
    assert reference["loss_relative_error"] <= reference["loss_rtol"]
    errors = reference["gradient_relative_l2_error"]
    off_path = [e for name, e in errors.items()
                if "TrinityMoE_0/router" not in name
                and "TrinityMoE_0/experts" not in name]
    assert len(off_path) >= 20 and max(off_path) <= 0.15
    for leaf in ("embed_tokens/embedding", "lm_head/kernel",
                 "TrinityBlock_1/TrinityAttention_0/gate_proj/kernel",
                 "TrinityBlock_1/TrinityAttention_0/q_norm/scale",
                 "TrinityBlock_0/post_attention_layernorm/scale",
                 "TrinityBlock_1/post_mlp_layernorm/scale",
                 "TrinityBlock_1/TrinityMoE_0/router/weight",
                 "TrinityBlock_2/TrinityMoE_0/experts/down"):
        assert leaf in errors, leaf
    assert set(reference["gradient_tolerance_under"]) == {"router",
                                                          "experts"}
    held = next(e for e in earlier
                if e.get("check", "").startswith("the kernels"))
    # the full layer's calls run under the causal names, once each
    assert held["required"] == {"_fwd_kernel": 1, "_bwd_dq_kernel": 1,
                                "_bwd_dkv_kernel": 1}


def test_traced_rehearsal_names_the_two_readers_and_leaves_them_out():
    """The cell reports the two new metrics (``BENCHMARK.json`` names them
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
    assert not any("outgate_ms" in e or "postnorm_ms" in e for e in earlier)


# -- the configuration -------------------------------------------------------------

def test_flop_count_by_hand():
    """ISSUE 52's count at 16 384 tokens: a token costs 2.44 GFLOP trained:
    attention's products 32% (the full layer 16.5, the four sliding layers
    15.5), the five gate projections 10%, the head 12.6%, the held experts
    6%."""
    module, job, config = job_of()
    d, seq, window = 2048, 16384, 2048
    projections = 2 * d * (2 * 4096 + 2 * 512)
    gate = 2 * d * 4096
    full = 2 * 2 * 4096 * (seq * (seq + 1) // 2) / seq
    pairs = window * (window + 1) // 2 + (seq - window) * window
    sliding = 2 * 2 * 4096 * pairs / seq
    dense = 2 * 3 * d * 6144
    router, shared = 2 * d * 128, 2 * 3 * d * 1024
    held = 2 * 3 * d * 1024 * 8 * 16 / 128
    head = 2 * d * 25024
    total = 5 * (projections + gate) + full + 4 * sliding + dense \
        + 4 * (router + shared + held) + head
    assert job.model_flops_per_item == pytest.approx(3 * total)
    assert 3 * total / 1e9 == pytest.approx(2.44, abs=0.005)
    assert pairs / (seq * (seq + 1) // 2) == pytest.approx(0.234, abs=0.001)
    forward = job.facts["forward_mflops_per_token"]
    assert forward["full_attention"] * 1e6 == pytest.approx(full)
    assert forward["window_attention"] * 1e6 == pytest.approx(4 * sliding)
    assert forward["gate_projections"] * 1e6 == pytest.approx(5 * gate)
    assert (full + 4 * sliding) / total == pytest.approx(0.32, abs=0.005)
    assert 5 * gate / total == pytest.approx(0.10, abs=0.005)
    assert head / total == pytest.approx(0.126, abs=0.001)
    assert 4 * held / total == pytest.approx(0.06, abs=0.005)
    # moe_experts_mfu multiplies its per-layer count by facts["layers"]
    assert job.facts["moe_train_flops_per_token_per_layer"] \
        * job.facts["layers"] == pytest.approx(3 * 4 * held)
    # what the gate's product must move a pass: o and g read, o * s written
    assert job.facts["outgate_mul_bytes_per_layer_pass"] == \
        3 * seq * 4096 * 2


def test_reference_layer_by_hand():
    """One dense full layer of the reference against the equations written
    out in numpy: four norms, per-head q/k norm, no positions, a causal
    softmax with query head j on key head j // 2, the gate on its output,
    SwiGLU."""
    module = job_of()[0]
    rng = np.random.RandomState(0)
    t, d, heads, kv, hd, f = 8, 16, 4, 2, 4, 24

    def w(*shape):
        return rng.randn(*shape) / np.sqrt(shape[0])
    p = {"TrinityAttention_0": {
        "q_proj": {"kernel": w(d, heads * hd)},
        "k_proj": {"kernel": w(d, kv * hd)},
        "v_proj": {"kernel": w(d, kv * hd)},
        "gate_proj": {"kernel": w(d, heads * hd)},
        "o_proj": {"kernel": w(heads * hd, d)},
        "q_norm": {"scale": 1 + 0.1 * rng.randn(hd)},
        "k_norm": {"scale": 1 + 0.1 * rng.randn(hd)}},
        "mlp": {"gate_proj": {"kernel": w(d, f)},
                "up_proj": {"kernel": w(d, f)},
                "down_proj": {"kernel": w(f, d)}},
        **{name: {"scale": 1 + 0.1 * rng.randn(d)} for name in (
            "input_layernorm", "post_attention_layernorm",
            "pre_mlp_layernorm", "post_mlp_layernorm")}}
    x = rng.randn(1, t, d)
    eps = 1e-5

    def norm(y, scale):
        return y / np.sqrt((y * y).mean(-1, keepdims=True) + eps) * scale
    a = p["TrinityAttention_0"]
    h = norm(x[0], p["input_layernorm"]["scale"])
    q = norm((h @ a["q_proj"]["kernel"]).reshape(t, heads, hd),
             a["q_norm"]["scale"])
    k = norm((h @ a["k_proj"]["kernel"]).reshape(t, kv, hd),
             a["k_norm"]["scale"])
    v = (h @ a["v_proj"]["kernel"]).reshape(t, kv, hd)
    o = np.zeros((t, heads, hd))
    for j in range(heads):
        s = q[:, j] @ k[:, j // 2].T / np.sqrt(hd)
        s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
        prob = np.exp(s - s.max(-1, keepdims=True))
        o[:, j] = prob / prob.sum(-1, keepdims=True) @ v[:, j // 2]
    g = h @ a["gate_proj"]["kernel"]
    branch = (o.reshape(t, -1) / (1 + np.exp(-g))) @ a["o_proj"]["kernel"]
    mid = x[0] + norm(branch, p["post_attention_layernorm"]["scale"])
    u = norm(mid, p["pre_mlp_layernorm"]["scale"])
    gate = u @ p["mlp"]["gate_proj"]["kernel"]
    ff = (gate / (1 + np.exp(-gate)) * (u @ p["mlp"]["up_proj"]["kernel"])) \
        @ p["mlp"]["down_proj"]["kernel"]
    want = mid + norm(ff, p["post_mlp_layernorm"]["scale"])
    as_f32 = jax.tree_util.tree_map(lambda y: jnp.asarray(y, jnp.float32), p)
    with jax.default_matmul_precision("highest"):
        got, state, chosen = module._layer(
            jnp.asarray(x, jnp.float32), as_f32, {}, sliding=False,
            sparse=False, window=4, theta=1e4, held=(0, 0), eps=eps,
            scale=1.0, rate=0.0, heads=heads, kv_heads=kv, head_dim=hd,
            experts_per_token=1, bits=None, router_bits=None)
        slid = module._layer(
            jnp.asarray(x, jnp.float32), as_f32, {}, sliding=True,
            sparse=False, window=4, theta=1e4, held=(0, 0), eps=eps,
            scale=1.0, rate=0.0, heads=heads, kv_heads=kv, head_dim=hd,
            experts_per_token=1, bits=None, router_bits=None)[0]
    assert state is None and chosen is None
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)
    # a sliding layer is another layer: rotary, and a window of 4 of the 8
    assert float(jnp.abs(slid - got).max()) > 1e-2
    np.testing.assert_allclose(slid[0, 0], got[0, 0], rtol=2e-4, atol=2e-5)


def test_reference_window_mask_by_hand():
    """``0 <= i - j < window`` on a sliding layer, ``0 <= i - j`` on a full
    one: uniform scores, so a row's output is the mean of the values it
    sees."""
    module = job_of()[0]
    t, window = 128, 24
    q = jnp.zeros((1, t, 1, 4))
    v = jnp.broadcast_to(jnp.arange(t, dtype=jnp.float32)[None, :, None,
                                                          None], (1, t, 1, 4))
    for sliding in (True, False):
        got = np.asarray(module._masked_attention(q, q, v, window,
                                                  sliding))[0, :, 0, 0]
        first = [max(0, i - window + 1) if sliding else 0 for i in range(t)]
        np.testing.assert_allclose(
            got, [(lo + i) / 2 for i, lo in enumerate(first)], rtol=1e-5)


def test_benchmark_json_holds_the_cell():
    spec = spec_lib.load()
    cells = {c["name"]: c for c in spec["workloads"]}
    assert CELL in cells and LIKE in cells
    assert cells[CELL] == {**cells[CELL], "config": CONFIG,
                           "traffic": TRAFFIC, "chips": 1}
    assert len(cells[CELL]["why"]) <= 200
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_layers", "layer_types",
                                "num_dense_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == "https://huggingface.co/arcee-ai/" \
        "Trinity-Mini/blob/main/config.json"
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"

    def reported(cell):
        return {m["name"] for kind in ("end_to_end", "per_layer")
                for m in spec_lib.metrics(spec, kind, cell)}
    # the other cell with window kernels, and the two of this PR: the
    # branches' norms are a printed line and no metric (harness/outgate.py)
    assert "postnorm_ms" not in reported(CELL)
    assert reported(LIKE) | set(NEW_METRICS) == reported(CELL)
    assert not set(NEW_METRICS) & reported(LIKE)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL]
        assert m["unit"] == ("%" if name.endswith("_share") else "ms")
        assert (m["better"], m["moves"], m["layer"], m["source"]) == (
            "lower", "tokens_per_s_per_chip", "model step", "program_span")
    for name in ("window_time_share", "window_fwd_roofline",
                 "window_bwd_dq_roofline", "window_bwd_dkv_roofline",
                 "window_blocks_skipped_share", "flash_fwd_roofline",
                 "moe_experts_mfu", "mfu_device", "attn_proj_ms",
                 "attn_outside_kernels_ms", "head_loss_ms"):
        assert {CELL, LIKE} <= set(by_name[name]["workloads"]), name
    traffic = spec_lib.traffic(TRAFFIC)
    assert (traffic["per_chip_batch"], traffic["seq_len"]) == (1, 16384)
    assert (traffic["block_steps"], traffic["warmup_blocks"],
            traffic["trace_blocks"], traffic["reference_examples"],
            traffic["step"]) == (3, 2, 2, 1, {})
    memory = traffic["memory_analysis"]
    # described facts of the compile, which no run reads as a limit
    assert memory["workload"] == CELL
    # every block keeps its attention's output: a forward kernel once a
    # layer; the walk's way back three times a sparse layer (forward, the
    # recomputation that the post-norm keeps alive, backward)
    kernels = memory["kernels"]
    assert {k: kernels[k] for k in kernels if "fwd" in k or "bwd" in k} == {
        "_fwd_kernel": 1, "_bwd_dq_kernel": 1, "_bwd_dkv_kernel": 1,
        "_fwd_window_kernel": 4, "_bwd_dq_window_kernel": 4,
        "_bwd_dkv_window_kernel": 4}
    assert kernels["_add_rows_kernel"] == 12 and kernels["_gmm_kernel"] == 48
    assert memory["kernels_missing"] == memory["kernels_not_asked_for"] == {}
    assert 4e9 < memory["argument_bytes"] + memory["temp_bytes"] < 15.0e9
    # the other 16 384-token traffic files are other cells', as they were
    assert spec_lib.traffic("t16384-b1")["memory_analysis"]["workload"] == \
        LIKE


# -- the readers ---------------------------------------------------------------------

def instruction(name, scopes, opcode="fusion", phase=True, way="forward"):
    model = {"forward": "jvp(TrinityDecoder)",
             "backward": "transpose(jvp(TrinityDecoder))/checkpoint",
             "recomputed": "transpose(jvp(TrinityDecoder))/checkpoint/"
                           "rematted_computation"}[way]
    op_name = "jit(_local_step)/" + (
        f"phase_forward_backward/{model}/" if phase else "")
    if scopes:
        op_name += "/".join(scopes) + "/mul"
    elif phase:
        op_name += "TrinityBlock_0/mlp/gate_proj/dot_general"
    metadata = f', metadata={{op_name="{op_name}"}}' if op_name else ""
    return f"  %{name} = f32[8]{{0}} {opcode}(%a){metadata}\n"


def text(instructions):
    return ("HloModule jit__local_step, is_scheduled=true\n\n"
            "ENTRY %main (a: f32[8]) -> f32[8] {\n"
            "  %a = f32[8]{0} parameter(0)\n" + "".join(instructions)
            + "}\n")


ATTENTION = ["TrinityBlock_1", "TrinityAttention_0"]
STEP = [instruction("gp.1", ATTENTION + ["outgate_proj"]),
        instruction("qkv.1", ATTENTION + ["attn_qkv_proj"]),
        instruction("gm.1", ATTENTION + ["outgate_mul"]),
        "  %copy.1 = f32[8]{0} copy(%a)\n",  # no scope: inherits the mul's
        instruction("pa.1", ["TrinityBlock_1", "postnorm_attn"]),
        instruction("ff.1", None),
        "  %copy.2 = f32[8]{0} copy(%a)\n",  # inherits the lack of one
        instruction("pf.1", ["TrinityBlock_1", "postnorm_ff"]),
        instruction("gp.2", ATTENTION + ["outgate_proj"], way="recomputed"),
        instruction("gm.2", ATTENTION + ["outgate_mul"], way="recomputed"),
        instruction("pf.2", ["TrinityBlock_1", "postnorm_ff"],
                    way="backward"),
        instruction("gm.3", ATTENTION + ["outgate_mul"], way="backward"),
        instruction("gp.3", ATTENTION + ["outgate_proj"], way="backward")]


class FakeJob:
    facts = {"outgate_mul_bytes_per_layer_pass": 3 * 1024 * 64 * 2}
    flash_call = None
    flash_layers = 0


def run_of(hlo, job=FakeJob):
    return Run(job=job, chips=1, block_steps=2,
               peaks={"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e12},
               hlo=hlo, program=hlo.module, init_s=0.0, compile_s=0.0,
               programs_after_warmup=0, dispatch_seconds=[],
               items_per_step_per_chip=64.0)


def two_steps():
    """Two step runs of 30 ms, 28 ms busy."""
    def ops(start):
        named = (("gp.1", 0, 2), ("qkv.1", 2, 5), ("gm.1", 5, 6),
                 ("copy.1", 6, 7), ("pa.1", 7, 9), ("ff.1", 9, 13),
                 ("copy.2", 13, 14), ("pf.1", 14, 15), ("gp.2", 15, 17),
                 ("gm.2", 17, 18), ("pf.2", 18, 21), ("gm.3", 21, 24),
                 ("gp.3", 24, 28))
        return [Span(name, (start + lo) * MS, (start + hi) * MS)
                for name, lo, hi in named]
    return Trace(devices=[DeviceTrace(0, ops=ops(0) + ops(30), modules=[
        Span("jit__local_step(1)", 0, 30 * MS),
        Span("jit__local_step(1)", 30 * MS, 60 * MS)])],
        host=[Span("bench.block", 0, 60 * MS)])


def test_the_readers_count_an_operation_under_its_scope_by_direction(capsys):
    hlo = hlo_text.HloIndex(text(STEP))
    trace, run = two_steps(), run_of(hlo)
    found = outgate.reduce(trace, hlo, hlo.module)
    assert {scope: pytest.approx(ways)
            for scope, ways in found["parts"].items()} == {
        "outgate_proj": {"forward": 2e-3, "recomputed": 2e-3,
                         "backward": 4e-3},
        "outgate_mul": {"forward": 2e-3, "recomputed": 1e-3,
                        "backward": 3e-3},
        "postnorm_attn": {"forward": 2e-3},
        "postnorm_ff": {"forward": 1e-3, "backward": 3e-3}}
    assert found["inherited"] == pytest.approx({"outgate_mul": 1e-3})
    assert found["total"] == pytest.approx(28e-3)
    assert outgate.seconds(found, "postnorm") == pytest.approx(6e-3)
    reader = spec_lib.layer_reader
    assert reader("outgate_time_share")(trace, run) == pytest.approx(
        100 * 14 / 28)
    assert reader("outgate_mul_ms")(trace, run) == pytest.approx(6.0)
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    said = next(line for line in lines if "outgate_ms" in line)
    assert said["outgate_ms"]["outgate_mul"] == pytest.approx(
        {"forward": 2.0, "recomputed": 1.0, "backward": 3.0})
    # the bytes the product must move, beside the time: a fact, no share
    assert said["outgate_mul_bytes_per_layer_pass"] == 3 * 1024 * 64 * 2
    assert said["outgate_mul_least_ms_per_layer_pass"] == pytest.approx(
        1e3 * 3 * 1024 * 64 * 2 / 1e12)
    assert said["outgate_total_ms"] == pytest.approx(14.0)
    assert said["inherited_ms"] == pytest.approx({"outgate_mul": 1.0})
    # the branches' norms: a line by direction, and no metric
    norms = next(line for line in lines if "postnorm_ms" in line)
    assert norms["postnorm_ms"] == {
        "postnorm_attn": pytest.approx({"forward": 2.0}),
        "postnorm_ff": pytest.approx({"forward": 1.0, "backward": 3.0})}
    assert norms["postnorm_total_ms"] == pytest.approx(6.0)
    assert norms["inherited_ms"] == {} and len(lines) == 2
    with pytest.raises(spec_lib.SpecError):
        reader("postnorm_ms")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_program_without_the_scopes_reads_nothing(name):
    """The parent's programs and every other configuration: no ``outgate_*``
    or ``postnorm_*`` scope in the step's text, so nothing to read; and no
    device plane, nothing either."""
    plain = hlo_text.HloIndex(text([
        instruction("ff.1", None), instruction("ff.2", None),
        instruction("qkv.1", ATTENTION + ["attn_qkv_proj"])]))
    trace = Trace(devices=[DeviceTrace(0, ops=[
        Span("ff.1", 0, 5 * MS), Span("ff.2", 5 * MS, 9 * MS),
        Span("qkv.1", 9 * MS, 10 * MS)], modules=[
        Span("jit__local_step(1)", 0, 10 * MS)])],
        host=[Span("bench.block", 0, 10 * MS)])
    reader = spec_lib.layer_reader(name)
    assert reader(trace, run_of(plain)) is None
    assert reader(None, run_of(plain)) is None
    scoped = hlo_text.HloIndex(text(STEP))
    assert reader(Trace(devices=[], host=[]), run_of(scoped)) is None
    assert reader(two_steps(), run_of(scoped)) > 0


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
    comparison: the lowered reference in the program's place reads half as
    far again as the program, at the least, on the leaves off the routers'
    path (3.5 times as far on the chip). The
    limits are set from the chip's readings at the published widths (where
    the control fails them); at the rehearsal's tiny sizes the readings say
    nothing about the limits."""
    done = run_cell("--workload", CELL, "--seeds", "5", "--rehearse",
                    script=os.path.join(bench_paths.BENCH,
                                        "reference_control.py"))
    last, earlier = result_line(done)
    name = "gradient_relative_l2_error"
    assert set(last["limits"]) == {"loss_relative_error", name,
                                   name + ".router", name + ".experts"}
    assert last["control_smallest"][name] > 1.4 * last["sound_largest"][name]
    assert last["control_none_ok"] is True
    assert last["sound_largest"]["loss_relative_error"] \
        < last["limits"]["loss_relative_error"]
    readings = [e for e in earlier if "reading" in e]
    assert [e["reading"] for e in readings] == ["sound", "control"]
    config = json.load(open(os.path.join(
        bench_paths.BENCH, "configs", CONFIG + ".json")))
    assert "float32" in config["dtype_policy"]["router"] and \
        "float32" in config["dtype_policy"]["logits_and_loss"]



def test_a_router_on_another_stream_reads_apart_through_the_same_comparison():
    """``reference_router.py`` hands the comparison the float32 reference
    with every router on the layer's normed input (before attention) and
    nothing else changed: no rounding anywhere, so what reads is the fault
    alone, and it is never ``ok``. It is the routers' upper reading, which
    the precision below does not give (the control's routers read as a sound
    program's: ``TOLERANCE``)."""
    done = run_cell("--workload", CELL, "--seeds", "5", "--rehearse",
                    script=os.path.join(bench_paths.BENCH,
                                        "reference_router.py"))
    last, earlier = result_line(done)
    name = "gradient_relative_l2_error"
    assert last["router_none_ok"] is True
    assert last["router_smallest"][name + ".router"] \
        > last["limits"][name + ".router"]
    # float32 against float32: the loss of a mean over tokens hardly moves
    assert last["router_largest"]["loss_relative_error"] < 1e-4
    assert [e["reading"] for e in earlier if "reading" in e] == ["router"]
    module, job, _ = job_of(rehearse=True)
    other = module.router_control_job(job)
    assert other.reference_loss is job.reference_loss \
        and other.tolerance is job.tolerance \
        and other.loss_fn is not job.loss_fn
