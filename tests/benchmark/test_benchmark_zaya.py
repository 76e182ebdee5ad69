"""The ZAYA1 share's configuration, cell and two readers (PR 71): the
contract on the real file and on the tree a later cell-adding PR leaves,
the cell rehearsed at toy widths on the CPU through the unchanged
harness, and the two readers where there is nothing for them to read."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import spec  # noqa: E402
from benchmark.harness.trace import named  # noqa: E402
from test_benchmark_contract import (  # noqa: E402
    _load, _published_file, cell_contract, configuration_contract,
    share_contract,
)
from test_benchmark_named import FIXTURES, _fake_run  # noqa: E402
from test_benchmark_rehearse_train import rehearse  # noqa: E402

CONFIG = "zaya1-8b-s8-d5"
CELL = "train_zaya1_share8_seq8k"
OWN = ["cca_mix_ms_per_step", "moe_bias_abs_max"]
# what a one-chip training share reports on any backend, with its own
# counter (the device readers have no device plane on a CPU)
ON_A_CPU = {"train_step_ms_p50", "train_data_wait_pct",
            "train_host_ms_per_step", "step_hbm_gb", "step_temp_hbm_gb",
            "moe_load_max_over_mean", "moe_held_rows_share",
            "moe_bias_abs_max"}


def test_the_configuration_keeps_the_contract_and_every_published_width(
        held_spec):
    configuration_contract(held_spec, CONFIG)
    cell_contract(held_spec, CELL)
    cell = spec.Cell(held_spec, CELL)
    config = cell.config
    published = _load(_published_file(held_spec, CONFIG))["config"]
    share_contract(CONFIG, config, published, config["reduced"])
    assert list(config["reduced"]) == [
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"]
    for width in ("hidden_size", "head_dim", "num_attention_heads",
                  "num_key_value_heads", "moe_intermediate_size",
                  "router_hidden_size", "cca_time0", "cca_time1",
                  "partial_rotary_factor", "rope_parameters",
                  "num_experts_per_tok", "rms_norm_eps",
                  "tie_word_embeddings"):
        assert config[width] == published[width], width
    assert config["whole"] == {"num_experts": 16, "vocab_size": 262272}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 32896)
    assert config["vocab_size"] * 8 == 263168 >= published["vocab_size"]
    assert config["deployment"].startswith("8 chips share each layer")
    for stated in ("residual scaling", "value projections", "convolutions",
                   "q-k mean", "qk norm and temperature", "convolution init",
                   "router input", "router depth carry", "router mlp",
                   "router scores", "skip output", "balancing",
                   "initializer_range", "head_dim", "expert_share"):
        assert {"value", "why"} <= set(config["assumed"][stated]), stated


def test_the_cell_is_one_chip_under_its_mix_and_lists_what_it_reads(
        held_spec):
    cell = spec.Cell(held_spec, CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name) == (
        1, CONFIG, "zaya1_seq8k")
    mix = cell.traffic
    assert (mix["seq_length"], mix["micro_batch_size"],
            mix["global_batch_size"]) == (8192, 2, 2)
    rate = float(mix["flags"][mix["flags"].index("--moe_bias_update_rate")
                              + 1])
    assert rate == cell.config["assumed"]["balancing"]["value"]["u"]
    assert 1e-4 <= rate <= 1e-2 and 20 <= mix["warmup_steps"] <= 128
    assert [m["name"] for m in cell.end_to_end()] == [
        "train_tokens_per_s", "setup_s"]
    asked = [m["name"] for m in cell.per_layer()]
    assert asked[-2:] == OWN or set(OWN) <= set(asked)
    assert {"moe_held_experts_roofline_pct", "flash_fwd_roofline_pct",
            "flash_bwd_roofline_pct", "attention_matmul_roofline_pct",
            "moe_held_rows_share", "moe_router_ms_per_step"} <= set(asked)
    # never the rooflines whose cost files count another step: all 16
    # experts, or a band a kind
    assert not {"moe_experts_roofline_pct", "flash_fwd_by_kind_roofline_pct",
                "flash_bwd_by_kind_roofline_pct",
                "mlp_matmul_roofline_pct"} & set(asked)
    entries = {m["name"]: m for m in _load(held_spec)["per_layer"]}
    for name in OWN:
        assert entries[name]["workloads"][0] == CELL
        assert entries[name]["moves"] == "train_tokens_per_s"
    # the held experts' cost file reads this file's own keys
    needed = cell.kernel_cost("moe_held_experts")((8192.0, 2048, 1), 2,
                                                  cell.config)
    per_row = 3 * 2048 * 2048
    assert needed[0] == 3 * 2.0 * 8192 * per_row
    flash = cell.kernel_cost("flash_fwd")((2, 8, 8192, 128), 2, cell.config)
    assert flash is not None and flash[0] > 0


# --- the cell at toy widths through the unchanged harness ---------------------

def toy_spec(held_spec, root):
    """The spec's entries beside a toy of the cell's two files: the
    configuration at toy widths (its entry's `file` points at it) and a
    mix of the same name with short sequences, found before the real one
    (the harness's own readers and reference are found where they are)."""
    s = _load(held_spec)
    config = _load(os.path.join(
        REPO, "benchmark", "configs", CONFIG + ".json"))
    config.update(hidden_size=64, head_dim=16, num_attention_heads=4,
                  num_key_value_heads=2, moe_intermediate_size=32,
                  router_hidden_size=16, vocab_size=256, num_hidden_layers=2,
                  layer_types=["hybrid"] * 2)
    os.makedirs(root / "tests" / "benchmark" / "traffic")
    with open(root / "tests" / "benchmark" / "zaya-toy.json", "w") as f:
        json.dump(config, f)
    for entry in s["configs"]:
        if entry["name"] == CONFIG:
            entry["file"] = "tests/benchmark/zaya-toy.json"
    mix = _load(os.path.join(REPO, "benchmark", "traffic",
                             "zaya1_seq8k.json"))
    mix.update(seq_length=128, warmup_steps=3, max_steps_per_s=60,
               trace_steps=3, first_loss_tolerance=0.05,
               loss_must_fall_by=0.0,
               corpus={"tokens": 60000, "cycle": 64, "doc_tokens_median": 100,
                       "doc_tokens_sigma": 1.0, "doc_tokens_min": 8,
                       "doc_tokens_max": 1024})
    mix["flags"] = [("64" if flag == "512" else "3e-3" if flag == "1e-4"
                     else flag) for flag in mix["flags"]]
    with open(root / "tests" / "benchmark" / "traffic" / "zaya1_seq8k.json",
              "w") as f:
        json.dump(mix, f)
    path = root / "BENCHMARK.json"
    with open(path, "w") as f:
        json.dump(s, f)
    return str(path)


def test_the_cell_runs_rehearsed_and_reports_exactly_its_listed_metrics(
        held_spec, tmp_path):
    toy = toy_spec(held_spec, tmp_path)
    untraced, traced = (rehearse(CELL, trace, 2, spec=toy, seed=2500000071)
                        for trace in (0, 1))
    cell = spec.Cell(toy, CELL)
    assert cell.config["hidden_size"] == 64
    assert set(untraced["metrics"]) == {
        m["name"] for m in cell.end_to_end()} == {"train_tokens_per_s",
                                                  "setup_s"}
    listed = {m["name"] for m in cell.per_layer()}
    assert set(traced["metrics"]) == ON_A_CPU <= listed
    assert 0.0 < traced["metrics"]["moe_held_rows_share"]["value"] < 1.0
    assert 0.0 < traced["metrics"]["moe_bias_abs_max"]["value"] <= 0.2
    run_dir = os.path.join(REPO, "runs", "benchmark", CELL)
    result = _load(os.path.join(run_dir, "result.json"))
    assert result["steps"][0]["ntokens"] == 2 * 128
    assert abs(result["steps"][0]["loss"]
               - result["reference_first_loss"]) < 0.02
    assert result["first_batch_ids"][1] < 256
    # the trainer journalled the field, and only this model does
    steps = [r for r in named.journal(os.path.join(
        run_dir, "tele", "events.jsonl")) if r.get("kind") == "step"]
    assert steps and all("moe_bias_abs_max" in r for r in steps)
    assert steps[0]["moe_bias_abs_max"] == pytest.approx(2e-3)


# --- the two readers where there is nothing to read ----------------------------

@pytest.mark.parametrize("metric", OWN)
def test_the_new_readers_read_nothing_from_no_trace_and_no_step(
        metric, held_spec, monkeypatch):
    cell = spec.Cell(held_spec, CELL)
    read = cell.reader(metric)
    monkeypatch.setattr(named, "run_files", lambda run: pytest.fail(
        f"{metric} looked for the run's files"))
    assert read(_fake_run(cell)) is None


def test_cca_mix_says_nothing_of_a_program_without_the_scope(
        held_spec, tmp_path, monkeypatch):
    """A recorded run of the dense Mistral step (a parent commit's, a dense
    toy's): named regions and no `cca_mix` anywhere."""
    cell = spec.Cell(held_spec, CELL)
    fixture = os.path.join(FIXTURES, "named_seq4k_tpu_v5e.xplane.pb")
    monkeypatch.setattr(named, "run_files", lambda run: (
        fixture, str(tmp_path / "none.jsonl")))
    run = _fake_run(cell, trace={"devices": 1},
                    steps=[{"t": 1.0, "step_ms": 170.0, "iteration": 4}])
    assert named.region_ms(run, "attention") > 0
    assert named.scope_ms(run, "cca_mix") == 0.0
    assert cell.reader("cca_mix_ms_per_step")(run) is None


def test_bias_abs_max_says_nothing_of_a_parent_shaped_journal(
        held_spec, tmp_path, monkeypatch):
    cell = spec.Cell(held_spec, CELL)
    read = cell.reader("moe_bias_abs_max")
    journal = tmp_path / "events.jsonl"
    monkeypatch.setattr(named, "run_files", lambda run: (
        str(tmp_path), str(journal)))
    run = _fake_run(cell, steps=[{"iteration": i} for i in (4, 5, 6)])
    # a parent commit's records, or a dense toy's: no such field
    journal.write_text("".join(json.dumps(
        {"kind": "step", "iteration": i, "loss": 1.0,
         "moe_load_max_over_mean": 1.2}) + "\n" for i in range(1, 8)))
    assert read(run) is None
    # this PR's: the median over the window's steps alone
    journal.write_text("".join(json.dumps(
        {"kind": "step", "iteration": i, "moe_bias_abs_max": i / 1000})
        + "\n" for i in range(1, 8)))
    assert read(run) == pytest.approx(0.005)
