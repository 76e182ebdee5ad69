"""The training cells end to end at toy widths on the CPU: the same
command, drivers and children as on the chip, kernels interpreted, the
four-chip cell on four virtual devices. A rehearsal finds wrong paths,
flags and control flow; it never gives a time (its line says
"rehearsal" and names the CPU)."""

import json
import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOY = os.path.join(REPO, "tests", "benchmark", "toy", "spec.json")


def rehearse(workload, trace, seconds, spec=TOY, seed=3):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--spec", spec, "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line.get("problems")
    assert line["failed"] == 0 and line["attempted"] > 0
    # each number `correct` compared, beside its limit: the line's last
    # key and the last lines of standard error
    assert list(line)[-1] == "compared" and line["compared"]
    said = proc.stderr.strip().splitlines()[-len(line["compared"]):]
    for text, (name, got) in zip(said, line["compared"].items()):
        limit = got.get("at_most", got.get("at_least"))
        assert set(got) - {"value"} in ({"at_most"}, {"at_least"})
        assert text.split() == ["benchmark:", "compared", name, "value",
                                str(got["value"]), *(set(got) - {"value"}),
                                str(limit)]
        assert (got["value"] <= limit if "at_most" in got
                else got["value"] >= limit)
    return line


def test_one_chip_training_cell_end_to_end():
    line = rehearse("toy_train", trace=0, seconds=3)
    assert line["device"]["count"] == 1
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert line["metrics"]["train_tokens_per_s"]["unit"] == "tokens/s"
    assert line["metrics"]["setup_s"]["value"] > 1
    # the steps counted are whole: 256 tokens each
    result = os.path.join(REPO, "runs", "benchmark", "toy_train",
                          "result.json")
    with open(result) as f:
        child = json.load(f)
    first = child["steps"][0]
    assert first["iteration"] == 1 and first["ntokens"] == 256
    # the first step's loss is the plain reference's on the same batch
    assert abs(first["loss"] - child["reference_first_loss"]) < 0.02


def test_four_chip_training_cell_on_four_virtual_devices_traced():
    line = rehearse("toy_train4", trace=1, seconds=3)
    assert line["device"]["count"] == 4
    # program spans are read; device metrics have no device to read
    assert {"train_step_ms_p50", "train_data_wait_pct"} <= set(
        line["metrics"])
    assert not {"device_idle_pct.train",
                "collective_exposed_pct"} & set(line["metrics"])
    # what the compiler says the step needs is read on any backend, from
    # the `step_program` record the trainer journals after a traced run
    assert line["metrics"]["step_hbm_gb"]["value"] > 0
    assert "busy_s" not in line["device"]
    log = os.path.join(REPO, "runs", "benchmark", "toy_train4", "child.log")
    with open(log) as f:
        text = f.read()
    assert "'data': 2" in text and "'tensor': 2" in text
    assert "profiler: trace written" in text
