"""The serving cells end to end at toy widths on the CPU, and the proof
that the harness is driven by data: a configuration of another block
type with its plain reference, traffic mixes, a per-layer metric and two
cells, added as files in a temporary directory, run without a change to
the harness."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark_rehearse_train import REPO, rehearse  # noqa: E402

TOY_DIR = os.path.join(REPO, "tests", "benchmark", "toy")
ADDED_DIR = os.path.join(REPO, "tests", "benchmark", "added")


def _harness_digest():
    """One hash over every file under benchmark/ (caches apart)."""
    h = hashlib.sha256()
    for folder, dirs, files in sorted(os.walk(os.path.join(REPO,
                                                           "benchmark"))):
        dirs[:] = sorted(d for d in dirs if d != "__pycache__")
        for name in sorted(files):
            with open(os.path.join(folder, name), "rb") as f:
                h.update(name.encode() + f.read())
    return h.hexdigest()


def test_open_loop_serving_cell_end_to_end():
    line = rehearse("toy_instruct", trace=0, seconds=4)
    assert set(line["metrics"]) == {"request_ms_p50", "request_ms_p95",
                                    "setup_s"}
    assert line["attempted"] >= 200          # a p95 needs them
    m = line["metrics"]
    assert 0 < m["request_ms_p50"]["value"] <= m["request_ms_p95"]["value"]


def test_open_loop_serving_cell_traced_reads_the_engines_journal():
    line = rehearse("toy_instruct", trace=1, seconds=4)
    assert {"engine_ttft_ms_p50.instruct", "engine_tpot_ms_p50",
            "gen_lateness_ms_max"} <= set(line["metrics"])
    assert "device_idle_pct.instruct" not in line["metrics"]
    # the generator kept its schedule on this host
    assert line["metrics"]["gen_lateness_ms_max"]["value"] < 100


def _added_spec(tmp_path):
    """The toy benchmark plus what a later PR would add, all of it new
    files (tests/benchmark/added) and new entries: a configuration of
    another block type with the reference it names, two traffic mixes, a
    per-layer metric with its reader, and two cells."""
    root = tmp_path / "added"
    shutil.copytree(TOY_DIR, root)
    shutil.copytree(ADDED_DIR, root, dirs_exist_ok=True)
    with open(root / "spec.json") as f:
        spec = json.load(f)
    spec["configs"].append({"name": "toy-falcon", "source": "none",
                            "file": "toy-falcon.json", "reduced": [],
                            "why": "added"})
    spec["workloads"] += [
        {"name": "added_serve", "config": "toy-falcon",
         "traffic": "added_serve", "chips": 1, "why": "added"},
        {"name": "added_train", "config": "toy-falcon",
         "traffic": "added_train", "chips": 1, "why": "added"}]
    for m in spec["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("added_serve")
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("added_train")
    spec["per_layer"] += [
        {"name": "requests_done_count", "unit": "requests",
         "better": "higher", "source": "host_clock", "layer": "generator",
         "moves": "serve_tokens_per_s", "workloads": ["added_serve"]},
        {"name": "engine_ttft_ms_p50.added", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "engine",
         "moves": "serve_tokens_per_s", "workloads": ["added_serve"]}]
    with open(root / "spec.json", "w") as f:
        json.dump(spec, f)
    return str(root / "spec.json")


def _child_log(cell):
    with open(os.path.join(REPO, "runs", "benchmark", cell,
                           "child.log")) as f:
        return f.read()


def test_a_cell_of_another_block_type_added_as_files_is_served(tmp_path):
    """No file of the harness knows the Falcon block: the server runs it
    from the flags its reference file gives, and `correct` holds the
    replies to that file's forward pass."""
    before = _harness_digest()
    line = rehearse("added_serve", trace=1, seconds=5,
                    spec=_added_spec(tmp_path))
    assert set(line["metrics"]) == {"requests_done_count",
                                    "engine_ttft_ms_p50.added"}
    assert line["metrics"]["requests_done_count"] == {
        "value": float(line["attempted"]), "unit": "requests"}
    assert "paged KV" in _child_log("added_serve")
    with open(os.path.join(REPO, "runs", "benchmark", "added_serve",
                           "plan.json")) as f:
        plan = json.load(f)
    assert plan["reference"].endswith(
        os.path.join("added", "reference", "toyfalcon.py"))
    assert not plan["reference"].startswith(REPO)
    assert _harness_digest() == before


def test_a_cell_of_another_block_type_added_as_files_is_trained(tmp_path):
    line = rehearse("added_train", trace=0, seconds=3,
                    spec=_added_spec(tmp_path))
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    with open(os.path.join(REPO, "runs", "benchmark", "added_train",
                           "result.json")) as f:
        child = json.load(f)
    # the trainer built the Falcon block (tied head 512*64; a layer: two
    # LayerNorms 2*128, q and o 2*4096, k and v 2*2048, MLP 2*16384; the
    # last LayerNorm 128), and its first loss is the added reference's on
    # the same batch
    assert "params: 123,520" in _child_log("added_train")
    assert abs(child["steps"][0]["loss"]
               - child["reference_first_loss"]) < 0.02
    # a reference that says nothing of FLOPs: the run notes no MFU
    assert "train_flops_per_token" not in child


def test_a_configuration_that_names_no_reference_is_refused(tmp_path):
    spec_path = _added_spec(tmp_path)
    root = os.path.dirname(spec_path)
    with open(os.path.join(root, "toy-falcon.json")) as f:
        config = json.load(f)
    for name, said in (("nowhere", "no reference/nowhere.py"),
                       (None, "names no \"reference\"")):
        config["reference"] = name
        with open(os.path.join(root, "toy-falcon.json"), "w") as f:
            json.dump(config, f)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
             "--spec", spec_path, "--workload", "added_train", "--seed", "1",
             "--seconds", "1", "--trace", "0", "--rehearse"],
            cwd=REPO, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 2 and proc.stdout == ""
        assert said in proc.stderr
