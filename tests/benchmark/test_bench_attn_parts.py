"""The readers of an attention operator's parts and of the head
(``harness/attn_parts.py``) on small built traces: the three groups' sums,
the latent operator's ``mla_*`` names beside the shared ones, a
configuration's own loss through ``phases.LOSS_BLOCK``, inheritance ending at
a step run's edge, the split by direction, the earlier line's shape, and
what a program from before the names, a missing device plane and a rehearsal
report."""

import base64
import json

import pytest

import bench_paths  # noqa: F401  puts benchmark/ on sys.path
from bench_run import check_rehearsal_result, result_line, run_cell
from harness import attn_parts, hlo_text
from harness import spec as spec_lib
from harness.job import Run
from harness.trace_reduce import DeviceTrace, Span, Trace
from horovod_tpu.profiler import annotate

METRICS = ("attn_outside_kernels_ms", "attn_proj_ms", "head_loss_ms")
TOKEN_CELLS = ["gpt2s-t512", "gpt2s-t8192", "gpt2s-t1024-dp4", "olmoe-t4096",
               "gpt2s-t2048", "nemotron3n-t8192", "smallthinker-t16384",
               "sdar-t8192-bd4", "lfm2-t16384", "joyai-t8192"]
MS = 1e6  # nanoseconds
MODEL = "SdarMoeDecoder"
ATTENTION = "SdarBlock_0/SdarAttention_0"


def instruction(name, scopes, opcode="fusion", way="forward", model=MODEL):
    """An instruction under the step's phase: ``scopes`` below the model
    (None: outside any attention module), ``way`` forward, backward or
    recomputed; ``model=None`` leaves it under the phase outside the model,
    the ``loss`` block."""
    if model is None:
        below = "transpose(jvp(reduce_sum))" if way == "backward" else ""
    else:
        below = {"forward": f"jvp({model})",
                 "backward": f"transpose(jvp({model}))",
                 "recomputed": f"transpose(jvp({model}))/jvp({model})/"
                               "checkpoint/rematted_computation"}[way]
    path = "/".join(scopes) if scopes else "SdarBlock_0/mlp/w1"
    op_name = "/".join(part for part in (
        "jit(_local_step)/phase_forward_backward", below, path, "mul")
        if part)
    return (f"  %{name} = f32[8]{{0}} {opcode}(%a), "
            f'metadata={{op_name="{op_name}"}}\n')


def unscoped(name, opcode="copy"):
    return f"  %{name} = f32[8]{{0}} {opcode}(%a)\n"


def kernel_call(name, function, scopes=(ATTENTION, "attn_blockdiff")):
    """A ``tpu_custom_call`` whose Mosaic body names ``function``."""
    body = base64.b64encode(b"\x00module\x00" + function.encode()
                            + b"\x00").decode()
    return (f'  %{name} = bf16[8]{{0}} custom-call(%a), '
            f'custom_call_target="tpu_custom_call", '
            f'backend_config={{"custom_call_config": {{"body":"{body}"}}}}, '
            f'metadata={{op_name="jit(_local_step)/phase_forward_backward/'
            f'jvp({MODEL})/{"/".join(scopes)}/pallas_call"}}\n')


def text(instructions):
    return ("HloModule jit__local_step, is_scheduled=true\n\n"
            "ENTRY %main (a: f32[8]) -> f32[8] {\n"
            "  %a = f32[8]{0} parameter(0)\n" + "".join(instructions)
            + "}\n")


def run_of(hlo):
    return Run(job=None, chips=1, block_steps=2, peaks={}, hlo=hlo,
               program=hlo.module, init_s=0.0, compile_s=0.0,
               programs_after_warmup=0, dispatch_seconds=[],
               items_per_step_per_chip=64.0)


def close_to(got, want):
    """``got == want`` with every number within rounding: ``pytest.approx``
    takes no table of tables."""
    if isinstance(want, dict):
        return set(got) == set(want) and all(
            close_to(got[k], want[k]) for k in want)
    return got == pytest.approx(want)


def steps_of(named, step_ms, steps=2):
    """``steps`` step runs of ``step_ms`` each, the same operations
    ``(name, from ms, to ms)`` in every one."""
    ops = [Span(name, (at * step_ms + lo) * MS, (at * step_ms + hi) * MS)
           for at in range(steps) for name, lo, hi in named]
    return Trace(devices=[DeviceTrace(0, ops=ops, modules=[
        Span("jit__local_step(1)", at * step_ms * MS,
             (at + 1) * step_ms * MS) for at in range(steps)])],
        host=[Span("bench.block", 0, steps * step_ms * MS)])


# one block-diffusion attention operator, its feed-forward, the head and a
# diffusion loss: every shared part once, with what the compiler leaves
# unnamed beside them
STEP = [
    instruction("qkv.1", [ATTENTION, "attn_qkv_proj", "q_proj"]),
    instruction("norm.1", [ATTENTION, "attn_qk_norm", "q_norm"]),
    instruction("rope.1", [ATTENTION, "attn_blockdiff", "attn_rope"]),
    unscoped("copy.1"),                       # inherits the rope's
    instruction("io.1", [ATTENTION, "attn_blockdiff", "attn_kernel_io"],
                opcode="transpose"),
    kernel_call("fwd.1", "_fwd_blockdiff_kernel"),
    unscoped("copy.2"),                       # after a kernel: no part
    instruction("self.1", [ATTENTION, "attn_blockdiff", "attn_self_block"]),
    instruction("merge.1", [ATTENTION, "attn_blockdiff", "attn_merge"]),
    instruction("out.1", [ATTENTION, "attn_out_proj", "o_proj"]),
    instruction("scores.1", [ATTENTION, "attn_blockdiff"]),  # names no part
    instruction("ff.1", None),
    instruction("logits.1", ["head_logits", "LmHead"]),
    instruction("loss.1", ["diffusion_loss"], model=None),
    instruction("aux.1", ["add"], model=None),  # the loss block, unnamed
    instruction("rope.2", [ATTENTION, "attn_blockdiff", "attn_rope"],
                way="recomputed"),
    instruction("io.2", [ATTENTION, "attn_blockdiff", "attn_kernel_io"],
                way="backward", opcode="convert"),
    kernel_call("dq.1", "_bwd_dq_blockdiff_kernel"),
    instruction("qkv.2", [ATTENTION, "attn_qkv_proj", "q_proj"],
                way="backward"),
    unscoped("tail.1", "fusion"),             # the step's last: inherits
]
NAMED = (("qkv.1", 0, 4), ("norm.1", 4, 6), ("rope.1", 6, 8),
         ("copy.1", 8, 9), ("io.1", 9, 10), ("fwd.1", 10, 15),
         ("copy.2", 15, 16), ("self.1", 16, 18), ("merge.1", 18, 19),
         ("out.1", 19, 21), ("scores.1", 21, 22), ("ff.1", 22, 30),
         ("logits.1", 30, 33), ("loss.1", 33, 35), ("aux.1", 35, 36),
         ("rope.2", 36, 38), ("io.2", 38, 40), ("dq.1", 40, 46),
         ("qkv.2", 46, 49), ("tail.1", 49, 50))


def test_the_groups_add_up_and_the_line_splits_them(capsys):
    hlo = hlo_text.HloIndex(text(STEP))
    trace, run = steps_of(NAMED, 50), run_of(hlo)
    reader = spec_lib.layer_reader
    # norm 2, rope 2 + 2 + the copy's 1, io 1 + 2, self 2, merge 1
    assert reader("attn_outside_kernels_ms")(trace, run) == \
        pytest.approx(13.0)
    # qkv 4 + 3 + the tail's 1, out 2
    assert reader("attn_proj_ms")(trace, run) == pytest.approx(10.0)
    # logits 3, diffusion_loss 2, the rest of the loss block 1: the step
    # writes no head_loss of its own
    assert reader("head_loss_ms")(trace, run) == pytest.approx(6.0)
    lines = [json.loads(line) for line in capsys.readouterr().out.split("\n")
             if line]
    assert len(lines) == 1  # made once a trace, whichever reader asks first
    line = lines[0]
    assert list(line) == [
        "attn_parts_ms", "recomputed_forward_marked", "inherited_ms",
        "copies_ms", "attention_kernels_ms", "groups_ms", "head_loss_from",
        "attention_modules_ms", "attention_modules_unnamed_ms",
        "busy_in_steps_ms", "reduction_s"]
    assert close_to(line["attn_parts_ms"], {
        "attn_kernel_io": {"forward": 1.0, "backward": 2.0},
        "attn_merge": {"forward": 1.0},
        "attn_out_proj": {"forward": 2.0},
        "attn_qk_norm": {"forward": 2.0},
        "attn_qkv_proj": {"forward": 4.0, "backward": 4.0},
        "attn_rope": {"forward": 3.0, "recomputed": 2.0},
        "attn_self_block": {"forward": 2.0},
        "diffusion_loss": {"forward": 2.0},
        "head_logits": {"forward": 3.0},
        "head_loss": {"forward": 1.0}})
    assert line["recomputed_forward_marked"] is True
    assert close_to(line["inherited_ms"],
                    {"attn_qkv_proj": 1.0, "attn_rope": 1.0})
    # the copy the rope was assigned, and the operand's way to a kernel and
    # the cast on the way back, which say what they are themselves
    assert close_to(line["copies_ms"],
                    {"attn_kernel_io": 3.0, "attn_rope": 1.0})
    assert close_to(line["attention_kernels_ms"], {
        "_bwd_dq_blockdiff_kernel": 6.0, "_fwd_blockdiff_kernel": 5.0})
    assert close_to(line["groups_ms"], {"head": 6.0, "outside": 13.0,
                                        "projections": 10.0})
    assert line["head_loss_from"] == "loss block"
    # every operation that names the module: 21 of parts, 11 of kernels and
    # the scores' 1, which no part names
    assert line["attention_modules_ms"] == pytest.approx(33.0)
    assert line["attention_modules_unnamed_ms"] == pytest.approx(1.0)
    assert line["busy_in_steps_ms"] == pytest.approx(50.0)
    assert 0 <= line["reduction_s"] < 5
    found = attn_parts.reduced(trace, run)
    for part, ways in found["detail"]["parts"].items():
        assert sum(ways.values()) == pytest.approx(found["seconds"][part])


def test_inheritance_ends_at_a_step_runs_edge():
    """A step's first operations name nothing: they inherit nothing from the
    step before, whose last operation was a projection's."""
    step = [unscoped("copy.1"),
            instruction("qkv.1", [ATTENTION, "attn_qkv_proj", "q_proj"]),
            unscoped("copy.2")]
    hlo = hlo_text.HloIndex(text(step))
    trace = steps_of((("copy.1", 0, 3), ("qkv.1", 3, 5), ("copy.2", 5, 6)),
                     6, steps=3)
    found = attn_parts.reduce(trace, hlo, hlo.module)
    assert found["seconds"] == pytest.approx({"attn_qkv_proj": 3e-3})
    assert found["inherited"] == pytest.approx({"attn_qkv_proj": 1e-3})
    assert close_to(found["detail"]["parts"],
                    {"attn_qkv_proj": {"forward": 3e-3}})
    assert found["total"] == pytest.approx(6e-3)


def test_the_latent_operators_names_count_beside_the_shared_ones():
    """``models/joyai_flash.py`` keeps ``mla_*`` for its operator and
    ``mtp_head`` for the module's head and loss; what ``flash_attention``
    does around the latent kernels is ``attn_kernel_io`` like everyone's. The
    module's block names its own scope AND the operator's: the part counts.
    The model writes ``head_loss`` itself, so the loss block stays what it
    is."""
    block = "JoyaiBlock_1/JoyaiLatentAttention_0"
    step = [
        instruction("q.1", [block, "mla_q_proj"], model="JoyaiFlashDecoder"),
        instruction("kv.1", [block, "mla_kv_proj"],
                    model="JoyaiFlashDecoder"),
        instruction("rope.1", [block, "mla_rope"], model="JoyaiFlashDecoder"),
        instruction("io.1", [block, "attn_latent", "attn_kernel_io"],
                    model="JoyaiFlashDecoder", opcode="copy"),
        kernel_call("fwd.1", "_fwd_latent_kernel", (block, "attn_latent")),
        instruction("out.1", [block, "mla_out_proj"],
                    model="JoyaiFlashDecoder"),
        instruction("mq.1", ["JoyaiMtp_0", "mtp_block", "JoyaiBlock_0",
                             "JoyaiLatentAttention_0", "mla_q_proj"],
                    model="JoyaiFlashDecoder"),
        instruction("logits.1", ["head_logits", "lm_head"],
                    model="JoyaiFlashDecoder"),
        instruction("mtp.1", ["mtp_head", "lm_head"],
                    model="JoyaiFlashDecoder"),
        instruction("ce.1", ["jvp(head_loss)"], model=None),
        instruction("sum.1", ["add"], model=None)]
    hlo = hlo_text.HloIndex(text(step))
    trace = steps_of((("q.1", 0, 2), ("kv.1", 2, 3), ("rope.1", 3, 6),
                      ("io.1", 6, 7), ("fwd.1", 7, 10), ("out.1", 10, 11),
                      ("mq.1", 11, 13), ("logits.1", 13, 15),
                      ("mtp.1", 15, 16), ("ce.1", 16, 17),
                      ("sum.1", 17, 18)), 18)
    run = run_of(hlo)
    reader = spec_lib.layer_reader
    assert reader("attn_proj_ms")(trace, run) == pytest.approx(2 + 1 + 1 + 2)
    assert reader("attn_outside_kernels_ms")(trace, run) == \
        pytest.approx(3 + 1)
    assert reader("head_loss_ms")(trace, run) == pytest.approx(2 + 1 + 1)
    found = attn_parts.reduced(trace, run)
    assert found["loss_from"] == "head_loss"
    assert found["detail"]["kernels"] == pytest.approx(
        {"_fwd_latent_kernel": 3e-3})
    assert found["detail"]["copies"] == pytest.approx(
        {"attn_kernel_io": 1e-3})


def test_a_configurations_own_loss_counts_through_the_loss_block():
    """The GPT cells' loss is written in ``benchmark/configs/gpt2-small.py``,
    under no name: what lies under ``phase_forward_backward`` outside the
    model is the ``loss`` block of ``harness/phases.py``, forward and
    backward, and what the compiler leaves unnamed after it inherits it."""
    attention = "EncoderBlock_0/FlashSelfAttention_0"
    step = [
        instruction("qkv.1", [attention, "attn_qkv_proj", "query"],
                    model="GptDecoder"),
        instruction("logits.1", ["head_logits", "Embed_0.attend"],
                    model="GptDecoder"),
        instruction("ce.1", ["reduce_max"], model=None),
        unscoped("copy.1"),
        instruction("ce.2", ["sub"], model=None, way="backward"),
        instruction("logits.2", ["head_logits", "Embed_0.attend"],
                    model="GptDecoder", way="backward")]
    hlo = hlo_text.HloIndex(text(step))
    trace = steps_of((("qkv.1", 0, 2), ("logits.1", 2, 5), ("ce.1", 5, 7),
                      ("copy.1", 7, 8), ("ce.2", 8, 10),
                      ("logits.2", 10, 14)), 14)
    run = run_of(hlo)
    assert spec_lib.layer_reader("head_loss_ms")(trace, run) == \
        pytest.approx(3 + 2 + 1 + 2 + 4)
    found = attn_parts.reduced(trace, run)
    assert found["loss_from"] == "loss block"
    assert found["detail"]["parts"]["head_loss"] == pytest.approx(
        {"forward": 3e-3, "backward": 2e-3})
    assert found["inherited"] == pytest.approx({"head_loss": 1e-3})
    assert found["detail"]["parts"]["head_logits"] == pytest.approx(
        {"forward": 3e-3, "backward": 4e-3})
    # no block is recomputed: the text has no mark, and the line says so
    assert not any(attn_parts.REMAT_MARK in i.op_name
                   for i in hlo.instructions.values())


@pytest.mark.parametrize("name", METRICS)
def test_a_reader_finds_nothing_without_a_plane_or_without_the_names(
        name, capsys):
    """The parent's programs hold none of the shared names, though one of
    them holds ``mla_*``, ``mtp_head`` and another ``diffusion_loss``: every
    reader returns None there and prints nothing, as it does without a
    device plane."""
    read = spec_lib.layer_reader(name)
    assert read(None, None) is None
    hlo = hlo_text.HloIndex(text(STEP))
    assert read(Trace(devices=[], host=[]), run_of(hlo)) is None
    block = "JoyaiBlock_1/JoyaiLatentAttention_0"
    before = [instruction("q.1", [block, "mla_q_proj"]),
              instruction("mtp.1", ["mtp_head", "lm_head"]),
              instruction("loss.1", ["diffusion_loss"], model=None),
              instruction("ff.1", None)]
    hlo = hlo_text.HloIndex(text(before))
    trace = steps_of((("q.1", 0, 2), ("mtp.1", 2, 3), ("loss.1", 3, 4),
                      ("ff.1", 4, 6)), 6)
    assert read(trace, run_of(hlo)) is None
    assert capsys.readouterr().out == ""


def test_the_tables_hold_the_programs_names():
    """Every name of the two shared families is in one group, the pattern
    that tells a program of this vocabulary from an earlier one names them
    all and nothing else, and the other families' names the groups take are
    names those families have."""
    grouped = [name for names in attn_parts.GROUPS.values() for name in names]
    assert len(grouped) == len(set(grouped))
    shared = annotate.ATTN_PART_SCOPES + annotate.HEAD_SCOPES
    assert set(shared) <= set(grouped)
    for name in grouped:
        assert attn_parts.PARTS.search(f"x/{name}/mul").group(1) == name
        assert bool(attn_parts.SHARED.search(f"x/{name}/mul")) == \
            (name in shared)
    others = set(grouped) - set(shared)
    assert others == {"mla_q_proj", "mla_kv_proj", "mla_rope",
                      "mla_out_proj", "mtp_head", "diffusion_loss"}
    assert others <= set(annotate.MLA_SCOPES + annotate.MTP_SCOPES
                         + annotate.DIFFUSION_SCOPES)
    # a kind is no part: a kernel's call names its kind and stays unnamed
    assert not any(attn_parts.PARTS.search(kind)
                   for kind in annotate.ATTN_SCOPES)


def test_benchmark_json_names_the_three_for_the_token_cells():
    spec = spec_lib.load()
    assert [m["name"] for m in spec["per_layer"][-3:]] == list(METRICS)
    like = next(m for m in spec["per_layer"] if m["name"] == "forward_ms")
    for m in spec["per_layer"][-3:]:
        assert m == {**like, "name": m["name"], "workloads": TOKEN_CELLS}
        assert m["better"] == "lower" and m["unit"] == "ms"


def test_traced_rehearsal_keeps_its_metric_names():
    """On the CPU the trace has no device plane: the three readers find
    nothing to read, print no line and leave the rehearsal's result line the
    set of names it was."""
    result, earlier = result_line(run_cell(
        "--workload", "gpt2s-t512", "--rehearse", "--seconds", "1",
        "--trace", "1"))
    check_rehearsal_result(result, 1, {
        "init_s", "compile_s", "programs_after_warmup", "host_dispatch_ms"})
    assert not any("attn_parts_ms" in e for e in earlier)
