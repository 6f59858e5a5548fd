"""The four-chip cell rehearsed on four virtual devices, and a fifth cell
made of new files only: a configuration file, a traffic file and one
appended ``workloads`` entry run without an edit to any file that is
there."""

import json
import os
import shutil

import bench_paths
from bench_run import check_rehearsal_result, result_line, run_cell


def test_four_chip_cell_on_four_virtual_devices():
    result, earlier = result_line(run_cell(
        "--workload", "gpt2s-t1024-dp4", "--rehearse", "--seconds", "2",
        "--trace", "0", devices=4))
    check_rehearsal_result(result, 4, {
        "tokens_per_s_per_chip", "scaling_efficiency", "peak_hbm_gb",
        "setup_s"})
    assert result["correct"] is True
    shard = next(e for e in earlier if "shard_mean" in e)
    assert shard["ok"] and shard["all_reduce_in_compiled_text"]
    assert shard["relative_error"] <= shard["rtol"]
    reference = next(e for e in earlier
                     if e.get("phase") == "one_chip_reference")
    assert len(reference["shard_losses"]) == 4
    identical = next(e for e in earlier if e.get("check", "").startswith(
        "parameters bit-identical"))
    assert identical["ok"]
    programs = [e for e in earlier if "program" in e]
    assert [p["chips"] for p in programs] == [1, 4]


def test_a_fifth_cell_is_new_files_and_one_appended_entry(tmp_path):
    """A copy of the benchmark's files, to which only files are added and
    one entry appended: GPT-2 small under ZeRO-1 with bf16 on the wire,
    which is pure data (``step`` keyword arguments)."""
    root = tmp_path / "checkout"
    shutil.copytree(bench_paths.BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    spec = json.load(open(os.path.join(bench_paths.REPO, "BENCHMARK.json")))

    configs = root / "benchmark" / "configs"
    config = json.loads((configs / "gpt2-small.json").read_text())
    config["name"] = "gpt2-small-again"
    (configs / "gpt2-small-again.json").write_text(json.dumps(config))
    shutil.copy(configs / "gpt2-small.py", configs / "gpt2-small-again.py")
    traffic = json.loads(
        (root / "benchmark" / "traffic" / "t1024-dp4.json").read_text())
    traffic.update(name="t1024-zero1-bf16wire",
                   step={"sharded_update": True, "compression": "bf16"})
    del traffic["one_chip_reference"]
    (root / "benchmark" / "traffic" / "t1024-zero1-bf16wire.json") \
        .write_text(json.dumps(traffic))
    spec["configs"].append({
        "name": "gpt2-small-again", "source": config["source"],
        "file": "benchmark/configs/gpt2-small-again.json",
        "reduced": config["reduced"], "why": "a fifth cell's configuration"})
    spec["workloads"].append({
        "name": "fifth", "config": "gpt2-small-again",
        "traffic": "t1024-zero1-bf16wire", "chips": 4, "why": "ZeRO-1"})
    for metric in spec["end_to_end"]:
        if metric["name"] == "tokens_per_s_per_chip":
            metric["workloads"].append("fifth")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    result, earlier = result_line(run_cell(
        "--workload", "fifth", "--rehearse", "--seconds", "1", devices=4,
        script=str(root / "benchmark" / "run.py"), cwd=str(root)))
    check_rehearsal_result(result, 4, {"tokens_per_s_per_chip",
                                       "peak_hbm_gb", "setup_s"})
    program = next(e for e in earlier if "program" in e)
    assert program["chips"] == 4
    # ZeRO-1 asks for a reduce-scatter and an all-gather (the CPU compiler
    # keeps them; a 2x2 v5e makes an all-reduce of the first, PR 21)
    assert "all-gather" in program["collectives"]
    for path, content in before.items():  # nothing that was there changed
        assert path.read_bytes() == content, path
