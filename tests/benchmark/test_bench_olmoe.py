"""The cells PR 26 added: ``run.py --rehearse`` for ``olmoe-t4096`` and
``gpt2s-t2048`` at the files' rehearse sizes on the CPU, and the expert
layer's readers (``harness/moe.py``) on a hand-built trace and a hand-written
compiled text with known answers."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths  # noqa: F401  (puts benchmark/ on sys.path)
from bench_run import check_rehearsal_result, result_line, run_cell
from harness import hlo_text, moe
from harness import spec as spec_lib
from harness.job import Run
from harness.trace_reduce import DeviceTrace, Span, Trace

MS = 1e6  # nanoseconds
FB = "jit(_local_step)/phase_forward_backward"
MLP = "/jvp(OlmoeDecoder)/OlmoeBlock_0/OlmoeSparseMoe_0/"
BACK = "/transpose(phase_forward_backward)" + MLP


# -- the rehearsals --------------------------------------------------------------

@pytest.mark.parametrize("workload,attention", [
    ("olmoe-t4096", "flash"), ("gpt2s-t2048", "flash")])
def test_rehearsal_reports_the_end_to_end_metrics(workload, attention):
    result, earlier = result_line(run_cell(
        "--workload", workload, "--rehearse", "--seconds", "1", "--seed",
        "2147483649", "--trace", "0"))
    check_rehearsal_result(result, 1, {"tokens_per_s_per_chip",
                                       "peak_hbm_gb", "setup_s"})
    assert earlier[0]["attention"] == attention
    checks = next(e for e in earlier if "checks" in e)
    assert checks["programs_in_windows"] == 0
    assert checks["checks"]["warmup_loss"] and checks["checks"]["finite"]
    reference = next(e for e in earlier
                     if e.get("check") == "float32 reference")
    assert reference["loss_relative_error"] <= reference["loss_rtol"]


def test_traced_rehearsal_of_the_expert_cell():
    """No device plane on the CPU: the expert layer's readers find nothing
    to read, return None, and the line leaves their metrics out."""
    result, earlier = result_line(run_cell(
        "--workload", "olmoe-t4096", "--rehearse", "--seconds", "1",
        "--trace", "1"))
    check_rehearsal_result(result, 1, {
        "init_s", "compile_s", "programs_after_warmup", "host_dispatch_ms"})
    facts = earlier[0]
    assert facts["experts"] == 8 and facts["experts_per_token"] == 2
    assert facts["layers"] == 2 and facts["tied_head"] is False
    assert not any("moe_ms" in e or "expert_load" in e for e in earlier)


def test_the_new_cells_report_what_the_8k_cell_reports():
    spec = spec_lib.load()
    like = {m["name"] for kind in ("end_to_end", "per_layer")
            for m in spec_lib.metrics(spec, kind, "gpt2s-t8192")}
    for cell in ("olmoe-t4096", "gpt2s-t2048"):
        got = {m["name"] for kind in ("end_to_end", "per_layer")
               for m in spec_lib.metrics(spec, kind, cell)}
        assert like <= got, like - got
    experts = {m["name"] for m in spec["per_layer"]
               if m["layer"] == "experts"}
    assert experts == {"moe_time_share", "moe_dispatch_ms",
                       "moe_experts_mfu"}
    # the layer is read in cells of configurations with routed experts, and
    # in no other: which those are is the configuration file's own count of
    # experts, so a later routed cell or a later metric of the layer is an
    # appended entry and no edit here. ``moe_experts_mfu`` wants a fact that
    # gives the experts' FLOPs (a share's rows are data: nemotron's job
    # states none).
    files = {c["name"]: c["file"] for c in spec["configs"]}

    def routed(cell):
        sizes = json.load(open(spec_lib.ROOT / files[cell["config"]]))
        return "num_experts" in sizes or "n_routed_experts" in sizes
    routed_cells = {c["name"] for c in spec["workloads"] if routed(c)}
    assert {"olmoe-t4096", "nemotron3n-t8192"} <= routed_cells
    assert not {"gpt2s-t8192", "resnet50-b256"} & routed_cells
    for m in spec["per_layer"]:
        if m["layer"] == "experts":
            assert set(m["workloads"]) <= routed_cells
            assert "olmoe-t4096" in m["workloads"]
            if m["name"] != "moe_experts_mfu":
                assert "nemotron3n-t8192" in m["workloads"]
            assert m["moves"] == "tokens_per_s_per_chip"
            assert m["source"] == "program_span"
    cells = {c["name"]: c for c in spec["workloads"]}
    assert cells["olmoe-t4096"]["chips"] == cells["gpt2s-t2048"]["chips"] == 1
    t4096, t2048 = spec_lib.traffic("t4096"), spec_lib.traffic("t2048")
    assert (t4096["per_chip_batch"], t4096["seq_len"]) == (2, 4096)
    assert (t2048["per_chip_batch"], t2048["seq_len"]) == (8, 2048)
    for traffic in (t4096, t2048):
        assert (traffic["block_steps"], traffic["warmup_blocks"],
                traffic["trace_blocks"], traffic["step"]) == (5, 2, 3, {})
        assert traffic["memory_analysis"]["argument_bytes"] + \
            traffic["memory_analysis"]["temp_bytes"] < 15.0e9


# -- the expert layer's readers ---------------------------------------------------

INSTRUCTIONS = [
    # name, op_name, scope, says so itself
    ("attn.1", FB + "/jvp(OlmoeDecoder)/OlmoeBlock_0/OlmoeAttention_0/"
     "pallas_call",
     None, True),
    ("topk.1", FB + MLP + "moe_router/top_k", "moe_router", True),
    ("sort.1", FB + MLP + "moe_dispatch/jit(argsort)/sort", "moe_dispatch",
     True),
    ("rdot.1", "ragged-dot-none", "moe_experts", True),
    ("meta.1", "ragged-dot-metadata", "moe_experts", True),
    ("silu.1", FB + MLP + "moe_experts/mul", "moe_experts", True),
    ("sum.1", FB + MLP + "moe_combine/dot_general", "moe_combine", True),
    ("head.1", FB + "/jvp(OlmoeDecoder)/LmHead/dot_general", None, True),
    ("gath.1", FB + BACK + "moe_combine/jit(_take)/gather", "moe_combine",
     True),
    ("rsum.1", FB + BACK + "moe_dispatch/reduce_sum", "moe_dispatch", True),
    ("adam.1", "jit(_local_step)/phase_optimizer_update/add", None, True),
    ("copy.1", None, None, False),
]


def text(instructions):
    def line(name, op_name):
        meta = f', metadata={{op_name="{op_name}"}}' if op_name else ""
        return f"  %{name} = f32[8]{{0}} add(%a, %a){meta}\n"
    return ("HloModule jit__local_step, is_scheduled=true\n\n"
            "ENTRY %main (a: f32[8]) -> f32[8] {\n"
            "  %a = f32[8]{0} parameter(0)\n" +
            "".join(line(name, op_name) for name, op_name, *_ in
                    instructions) + "}\n")


@pytest.fixture(scope="module")
def hlo():
    return hlo_text.HloIndex(text(INSTRUCTIONS))


class FakeJob:
    facts = {"moe_train_flops_per_token_per_layer": 6.0e6, "layers": 1}


def run_of(hlo, job=FakeJob):
    return Run(job=job, chips=1, block_steps=2,
               peaks={"bf16_flops_per_s": 1e12}, hlo=hlo, program=hlo.module,
               init_s=0.0, compile_s=0.0, programs_after_warmup=0,
               dispatch_seconds=[], items_per_step_per_chip=1000.0)


@pytest.mark.parametrize("name,op_name,scope,own", INSTRUCTIONS,
                         ids=[i[0] for i in INSTRUCTIONS])
def test_scope_of_an_instruction(hlo, name, op_name, scope, own):
    assert moe.scope_of(hlo.get(name)) == (scope, own)


def one_step():
    """One step run. The compiler's unnamed copy inherits whatever came
    before it: nothing at the start, ``moe_experts`` after the ragged dot,
    nothing again after the head."""
    def ops(*named):
        return [Span(name, lo * MS, hi * MS) for name, lo, hi in named]
    return Trace(devices=[DeviceTrace(0, ops=ops(
        ("copy.1", 0, 1),      # before any scope: outside the layer
        ("attn.1", 1, 11),     # outside 10
        ("topk.1", 11, 12),    # router 1
        ("sort.1", 12, 15),    # dispatch 3
        ("meta.1", 15, 16),    # experts 1, by name
        ("rdot.1", 16, 36),    # experts 20, by name
        ("copy.1", 36, 38),    # inherits experts 2
        ("silu.1", 38, 40),    # experts 2
        ("sum.1", 40, 44),     # combine 4
        ("head.1", 44, 64),    # outside 20
        ("copy.1", 64, 65),    # inherits outside
        ("gath.1", 65, 70),    # combine (backward) 5
        ("rsum.1", 70, 72),    # dispatch (backward) 2
        ("adam.1", 72, 100),   # outside 28
    ), modules=[Span("jit__local_step(1)", 0, 100 * MS)])],
        host=[Span("bench.block", 0, 100 * MS)])


def test_parts_rules_and_the_three_readers(hlo, capsys):
    trace, run = one_step(), run_of(hlo)
    found = moe.reduce(trace, hlo, hlo.module)
    ms = {k: round(1e3 * v, 6) for k, v in found["seconds"].items()}
    assert ms == {"moe_router": 1.0, "moe_dispatch": 5.0,
                  "moe_experts": 25.0, "moe_combine": 9.0}
    assert found["inherited"] == pytest.approx({"moe_experts": 0.002})
    assert found["by_name"] == pytest.approx(0.021)
    assert found["total"] == pytest.approx(0.100)
    reader = spec_lib.layer_reader
    assert reader("moe_time_share")(trace, run) == pytest.approx(40.0)
    assert reader("moe_dispatch_ms")(trace, run) == pytest.approx(15.0)
    # 6e6 FLOPs a token x 1000 tokens over 25 ms over 1e12 FLOP/s
    assert reader("moe_experts_mfu")(trace, run) == pytest.approx(24.0)
    said = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert len(said) == 1  # made once for the three readers
    assert said[0]["moe_total_ms"] == pytest.approx(40.0)
    assert said[0]["ragged_dot_by_name_ms"] == pytest.approx(21.0)
    assert said[0]["busy_in_steps_ms"] == pytest.approx(100.0)


def test_a_program_without_the_layer_reads_nothing():
    """The parent's programs and the dense cells: no ``moe_*`` scope."""
    dense = hlo_text.HloIndex(text(
        [i for i in INSTRUCTIONS if i[0] in ("attn.1", "head.1", "adam.1")]))
    trace, run = one_step(), run_of(dense)
    for name in ("moe_time_share", "moe_dispatch_ms", "moe_experts_mfu"):
        assert spec_lib.layer_reader(name)(trace, run) is None
        assert spec_lib.layer_reader(name)(Trace(), run) is None
        assert spec_lib.layer_reader(name)(None, run) is None


def test_the_reference_shows_nothing_dropped_under_the_worst_router():
    """The program's counts sum to k x tokens by construction, so they prove
    nothing; the dense reference does. With the routers' weights at zero
    every logit ties and ``top_k`` gives every token experts 0 and 1: the
    worst imbalance. The rehearsal's job and its reference then agree on
    the counts, on the loss, and on the gradients of the experts' weights
    within the job's tolerance (a layer that dropped what a capacity of
    1.25 x the mean drops would miss three quarters of expert 0's rows)."""
    spec = spec_lib.load()
    traffic = spec_lib.traffic("t4096", rehearse=True)
    config, builder = spec_lib.config(spec, "olmoe-1b-7b", rehearse=True)
    job = spec_lib.load_module(builder).build(config, traffic)
    key_params, key_batch = jax.random.split(jax.random.key(2147483650))
    params, state = jax.jit(job.init)(key_params)
    moes = [params[f"OlmoeBlock_{i}"]["OlmoeSparseMoe_0"] for i in range(2)]
    for moe_params in moes:
        moe_params["router"] = jnp.zeros_like(moe_params["router"])
    batch = job.make_batch(key_batch, 1)
    tokens = batch["tokens"].size

    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: job.loss_fn(p, batch, jax.random.key(1)), has_aux=True))(
            params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: job.reference_loss(p, state, batch)))(params)
    counts = np.asarray(aux["expert_tokens"])
    assert counts.tolist() == [[tokens, tokens, 0, 0, 0, 0, 0, 0]] * 2
    assert abs(float(loss) - float(want)) <= \
        job.tolerance.loss_rtol * abs(float(want))
    for i in range(2):
        for leaf in ("gate_proj", "up_proj", "down_proj"):
            got = np.asarray(
                grads[f"OlmoeBlock_{i}"]["OlmoeSparseMoe_0"][leaf][:2])
            ref = np.asarray(
                want_grads[f"OlmoeBlock_{i}"]["OlmoeSparseMoe_0"][leaf][:2])
            assert np.linalg.norm(ref) > 0
            assert np.linalg.norm(got - ref) <= \
                job.tolerance.grad_rel_l2 * np.linalg.norm(ref), (i, leaf)
