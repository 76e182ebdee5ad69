"""A toy of the Nemotron-H block type (tests/test_nemotron_h.py's: one
block a layer, Mamba-2, LatentMoE as one chip's share, attention) through
the unchanged harness on the CPU, as a served open-loop cell made of
files in a temporary directory: the server builds it from the flags
benchmark/reference/nemotron_h.py gives, `correct` holds the window's
replies to that file's forward pass, and the traced run reads the
engine's two counters of routed rows off the journal."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark_contract import own_served_entries  # noqa: E402
from test_benchmark_rehearse_train import REPO, rehearse  # noqa: E402

sys.path.insert(0, os.path.join(REPO, "tests"))

CELL, MIX = "toy_nemotron_agent", "toy_agent"
READERS = ("moe_experts_decode_ms_per_step", "moe_routing_decode_ms_per_step",
           "moe_latent_shared_decode_ms_per_step", "moe_prefill_ms_per_chunk",
           "decode_step_ms_p50", "ssm_decode_ms_per_step")


@pytest.fixture(scope="module")
def spec_path(tmp_path_factory):
    from test_nemotron_h import TOY

    root = tmp_path_factory.mktemp("toy_nemotron")
    os.makedirs(root / "traffic")
    config = dict(
        TOY, reference="nemotron_h",
        source="none: a toy for the CPU rehearsal, never a cell",
        program={"flags": ["--bf16"], "serve": {
            "seq_length": 64,
            "flags": ["--serve_kv_paging", "--serve_page_size", "4",
                      "--serve_prefill_chunk", "16", "--serve_num_slots", "4",
                      "--serve_max_seq_len", "64",
                      "--serve_drain_timeout", "5"]}})
    with open(root / "toy-nemotron.json", "w") as f:
        json.dump(config, f)
    mix = {"driver": "serve_open", "rate_rps": 6.0,
           # about a third of the prompts cross a 16-token chunk: the
           # state is carried between chunks and the last chunk padded
           "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                             "min": 3, "max": 36},
           "new_tokens": {"dist": "uniform", "min": 4, "max": 20},
           "lead_s": 1, "trail_s": 20, "trace_after_s": 1, "trace_s": 1,
           "check": {"requests": 4, "logit_gap_tolerance": 0.1,
                     "why": "a toy's limit, set from no chip reading"}}
    with open(root / "traffic" / (MIX + ".json"), "w") as f:
        json.dump(mix, f)
    entry = lambda name, unit, source, layer, moves: {  # noqa: E731
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": layer, "moves": moves, "workloads": [CELL]}
    spec = {
        "command": ["python3", "benchmark/run.py"], "paths": ["."],
        "run_seconds": 10,
        "configs": [{"name": "toy-nemotron", "source": config["source"],
                     "file": "toy-nemotron.json", "reduced": [],
                     "why": "a toy"}],
        "workloads": [{"name": CELL, "config": "toy-nemotron",
                       "traffic": MIX, "chips": 1, "why": "CPU rehearsal"}],
        "end_to_end": [
            {"name": "request_ms_p50", "unit": "ms", "better": "lower",
             "bound": 0.07, "source": "host_clock", "workloads": [CELL]},
            {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
             "source": "host_clock"}],
        "per_layer": own_served_entries(CELL, MIX) + [
            entry(name, "ms", "device_trace", "mlp", "request_ms_p50")
            for name in READERS] + [
            entry("serve_moe_held_rows_share", "share", "program_counter",
                  "mlp", "request_ms_p50"),
            entry("engine_rows_per_tick", "rows", "program_counter",
                  "engine", "request_ms_p50")]}
    with open(root / "spec.json", "w") as f:
        json.dump(spec, f)
    return str(root / "spec.json")


def test_a_toy_of_the_block_type_is_served_and_held_to_its_reference(
        spec_path):
    line = rehearse(CELL, trace=0, seconds=4, spec=spec_path)
    # (a p95 wants 200 requests; this toy's few seconds offer two dozen)
    assert set(line["metrics"]) == {"request_ms_p50", "setup_s"}
    assert line["attempted"] >= 15
    assert line["compared"]["logit_gap"]["value"] <= 0.1
    rows = line["extras"]["check"]["sequences"]
    assert len(rows) == 4 and all(r["served_tokens"] >= 4 for r in rows)


def test_the_traced_toy_reads_the_routed_rows_and_no_device_scope(spec_path):
    """On the CPU no device plane exists: the readers of the new scopes
    give nothing and the line leaves them out (as a parent commit's would);
    the engine's two counters are in the journal, and 4 held of 16 experts
    take a share of the rows that is neither none nor all."""
    line = rehearse(CELL, trace=1, seconds=4, spec=spec_path)
    got = line["metrics"]
    assert not set(READERS) & set(got)
    assert {"serve_moe_held_rows_share", "engine_rows_per_tick"} <= set(got)
    assert 0.05 < got["serve_moe_held_rows_share"]["value"] < 0.6
