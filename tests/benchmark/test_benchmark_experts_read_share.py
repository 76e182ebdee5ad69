"""`serve_moe_experts_read_share` (ISSUE 62): the engine's cumulative
pair `moe_experts` ([the held experts a decoding row reached, the held
experts there were a tick]) between the window's first and last
`serve_ticks` record. Over a journal with known answers; None on a journal
that lacks the pair (a parent commit's, a model that holds every expert);
through the unchanged harness on the toy served cell of
test_benchmark_nemotron_h.py with the entry appended; and the entry as
BENCHMARK.json holds it."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from test_benchmark_engine_spans import (  # noqa: E402
    BENCHMARK, T0, read, request, run_over, snapshot,
)
from test_benchmark_nemotron_h import CELL, spec_path  # noqa: E402,F401
from test_benchmark_rehearse_train import REPO, rehearse  # noqa: E402

NAME = "serve_moe_experts_read_share"
AGENT = "serve_nemotron3super_share4_agent"


def journal(pairs):
    """A retirement and a snapshot every 0.4 s; `pairs(i)` is what the
    i-th snapshot carries beside the loop's counters."""
    records = []
    for i in range(100):
        ts = T0 + i * 0.4
        records.append(request(i, ts, 0.001))
        records.append(snapshot(ts, 1_000 + 50 * i, 20_000 + 450 * i,
                                **pairs(i)))
    return records


@pytest.mark.parametrize("read_a_tick, want", [(640, 1.0), (460, 0.71875),
                                               (5, 0.0078125)])
def test_the_share_is_what_the_ticks_between_two_snapshots_read(
        tmp_path, monkeypatch, read_a_tick, want):
    """128 held experts x 5 layers offered a tick, 50 ticks a snapshot:
    the share is the pair's growth over the window, whatever it stood at
    before."""
    run = run_over(tmp_path, monkeypatch, journal(lambda i: {
        "moe_rows": [7_000 + 900 * i, 30_000 + 3_520 * i],
        "moe_experts": [123_456 + 50 * read_a_tick * i,
                        640_000 + 50 * 640 * i]}))
    assert read(run, NAME) == pytest.approx(want)
    assert 0.0 < read(run, NAME) <= 1.0
    # the routing's own share stays what the rows say, beside it
    assert read(run, "serve_moe_held_rows_share") == pytest.approx(
        900 / 3_520)


@pytest.mark.parametrize("why, pairs", [
    ("a parent commit: the rows' pair alone",
     lambda i: {"moe_rows": [7_000 + 900 * i, 30_000 + 3_520 * i]}),
    ("a model that holds every expert or none: neither pair",
     lambda i: {}),
    ("no decode tick inside the window: the pair stands still",
     lambda i: {"moe_experts": [500, 640]}),
])
def test_nothing_is_read_where_the_pair_is_missing_or_still(
        tmp_path, monkeypatch, why, pairs):
    run = run_over(tmp_path, monkeypatch, journal(pairs))
    assert read(run, NAME) is None, why


def test_one_snapshot_is_no_interval(tmp_path, monkeypatch):
    records = [request(0, T0, 0.001),
               snapshot(T0, 1_000, 20_000, moe_experts=[300, 640])]
    assert read(run_over(tmp_path, monkeypatch, records), NAME) is None


def test_the_entry_as_the_benchmark_holds_it():
    """One appended entry, the last of `per_layer`, for the one cell whose
    engine counts the pair; its reader's file is beside the others."""
    with open(BENCHMARK) as f:
        entries = json.load(f)["per_layer"]
    assert entries[-1] == {
        "name": NAME, "unit": "share", "better": "lower",
        "source": "program_counter", "layer": "mlp",
        "moves": "request_ms_p50", "workloads": [AGENT]}
    assert [m["name"] for m in entries].count(NAME) == 1
    assert os.path.exists(os.path.join(
        REPO, "benchmark", "layer_metrics", NAME + ".py"))


OWN_CELL = "toy_nemotron_read_share"


@pytest.fixture(scope="module")
def spec_with_the_entry(spec_path):  # noqa: F811
    """The toy served cell's spec with this PR's entry appended, beside
    the files it names; the cell under a name of its own, so that its
    run directory is not the other file's (the workers run both at
    once)."""
    with open(spec_path) as f:
        spec = json.loads(f.read().replace(CELL, OWN_CELL))
    spec["per_layer"].append({
        "name": NAME, "unit": "share", "better": "lower",
        "source": "program_counter", "layer": "mlp",
        "moves": "request_ms_p50", "workloads": [OWN_CELL]})
    path = os.path.join(os.path.dirname(spec_path), "spec_read_share.json")
    with open(path, "w") as f:
        json.dump(spec, f)
    return path


def test_the_traced_toy_reads_the_share_off_its_journal(spec_with_the_entry):
    """Four slots, 4 held of 16 experts, 3 a token: a tick's decoding
    rows reach some of the held experts and seldom all; the routing's
    share stays what the rows that count say."""
    line = rehearse(OWN_CELL, trace=1, seconds=4, spec=spec_with_the_entry)
    got = line["metrics"]
    assert got[NAME]["unit"] == "share"
    assert 0.0 < got[NAME]["value"] <= 1.0
    assert 0.05 < got["serve_moe_held_rows_share"]["value"] < 0.6
