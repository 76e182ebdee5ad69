"""The five readers of the engine's own spans and records (ISSUE 54):
`engine_host_ms_per_tick` over the host plane's `serve-tick` line,
`engine_rows_per_tick`, `engine_queue_ms_p95`, `server_overhead_ms_p50`
and `engine_stall_ms_total` over the journal. Each over a small fixture
with known answers; nothing on a journal or a trace of the parent commit
(no `id`, no `phase_s`, no tick marked); and rehearsals through
`benchmark/run.py --rehearse` of two toy cells, the second added behind
the first, whose traced lines hold all five. The five entries are held
in a spec by name and by prefix (ISSUE 60), as PR 23's twelve and PR 34's
nine are: a later served cell appends its name to them and its own
entries behind them."""

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import common, serve_journal, spec  # noqa: E402
from benchmark.harness.trace import named, serve_ticks, xplane  # noqa: E402
from test_benchmark_contract import (  # noqa: E402
    SERVED_CELL, added_tree, own_served_entries, reported_by_every,
)
from test_benchmark_rehearse_train import rehearse  # noqa: E402

BENCHMARK = os.path.join(REPO, "BENCHMARK.json")
TOY_DIR = os.path.join(REPO, "tests", "benchmark", "toy")
ADDED_DIR = os.path.join(REPO, "tests", "benchmark", "added")
CELLS = ["serve_mistral7b_instruct", "serve_jamba2_3b_reasoning"]
FIVE = {"engine_host_ms_per_tick": ("ms", "program_span", "request_ms_p50"),
        "engine_rows_per_tick": ("rows", "program_counter", "request_ms_p50"),
        "engine_queue_ms_p95": ("ms", "program_span", "request_ms_p95"),
        "server_overhead_ms_p50": ("ms", "program_span", "request_ms_p50"),
        "engine_stall_ms_total": ("ms", "program_span", "request_ms_p95")}


def five_entries_contract(spec_path):
    """PR 54's five, found by name in a spec, in their order among
    themselves, each as it was accepted and with the cells it listed in
    front, in their order. Where in `per_layer` they stand, what stands
    behind them and which cells' names stand behind those two is a later
    PR's to write: it appends."""
    with open(spec_path) as f:
        entries = json.load(f)["per_layer"]
    ours = [m for m in entries if m["name"] in FIVE]
    assert [m["name"] for m in ours] == list(FIVE)
    for m in ours:
        unit, source, moves = FIVE[m["name"]]
        assert dict(m, workloads=None) == {
            "name": m["name"], "unit": unit, "better": "lower",
            "source": source, "layer": "engine", "moves": moves,
            "workloads": None}
        assert m["workloads"][:len(CELLS)] == CELLS
        assert len(set(m["workloads"])) == len(m["workloads"])


@pytest.mark.parametrize("tree", ["BENCHMARK.json", "rehearsed"])
def test_benchmark_json_holds_the_five_entries_in_their_order(tree,
                                                              tmp_path):
    """In the real file, and as a PR that adds a served open-loop cell
    with two entries of its own leaves it (test_benchmark_contract.py)."""
    if tree == "BENCHMARK.json":
        return five_entries_contract(BENCHMARK)
    rehearsed = added_tree(tmp_path)
    five_entries_contract(rehearsed)
    # its cell stands behind the two in each of the five, and entries
    # stand behind the five
    with open(rehearsed) as f:
        entries = json.load(f)["per_layer"]
    assert all(m["workloads"][-1] == SERVED_CELL
               for m in entries if m["name"] in FIVE)
    assert entries[-1]["name"] not in FIVE


def _cells_of(name, edit):
    def edited(s):
        [m] = [m for m in s["per_layer"] if m["name"] == name]
        m["workloads"] = edit(m["workloads"])
    return edited


def _an_entry_behind(s):
    s["workloads"].append(dict(s["workloads"][-1], name="a_later_cell",
                               traffic="a_later_mix"))
    s["per_layer"] += own_served_entries("a_later_cell", "a_later_mix")


@pytest.mark.parametrize("edit, taken", [
    (_cells_of("engine_queue_ms_p95", lambda c: c + ["a_later_cell"]), True),
    (_an_entry_behind, True),
    (_cells_of("engine_queue_ms_p95", lambda c: ["a_later_cell"] + c), False),
    (_cells_of("engine_queue_ms_p95", lambda c: c[::-1]), False),
    (_cells_of("engine_host_ms_per_tick", lambda c: c[1:]), False),
    (_cells_of("engine_stall_ms_total", lambda c: c[:1]), False)],
    ids=["appended", "entry_behind_the_five", "put_in_front", "reordered",
         "first_taken_out", "second_taken_out"])
def test_a_later_cell_is_appended_to_the_five_and_none_is_moved_or_taken(
        edit, taken, tmp_path):
    with open(BENCHMARK) as f:
        s = json.load(f)
    edit(s)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(s))
    if taken:
        return five_entries_contract(path)
    with pytest.raises(AssertionError):
        five_entries_contract(path)


def fake_run(**fields):
    cell = spec.Cell(BENCHMARK, CELLS[0])
    base = dict(cell=cell, seconds=50.0,
                device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
                memory_peak_bytes=12_000_000_000, setup_s=42.0,
                end_to_end={}, attempted=3, failed=0, problems=[])
    base.update(fields)
    return common.Run(**base)


def read(run, name):
    return run.cell.reader(name)(run)


# --- the host plane ---------------------------------------------------------

US = 1_000_000   # picoseconds


def ev(name, start_us, dur_us, **stats):
    return xplane.Event(name, start_us * US, dur_us * US, stats)


def tick(number, start_us, dur_us, reads, others=()):
    """A `serve-tick` with `tick-read` spans (offset, duration) and other
    program spans (name, offset, duration) inside it."""
    out = [ev("serve-tick", start_us, dur_us, step_num=number)]
    out += [ev("tick-read", start_us + at, d) for at, d in reads]
    out += [ev(name, start_us + at, d) for name, at, d in others]
    return out


LOOP_LINE = (
    tick(7, 0, 9_000, [(6_000, 2_000)],
         [("tick-pre", 5, 20), ("tick-decode", 3_000, 400)])
    # two reads: one inside a drain inside tick-pages, one at the end
    + tick(8, 9_050, 12_000, [(1_000, 3_000), (9_000, 2_500)],
           [("tick-pages", 900, 3_400), ("tick-drain", 950, 3_300),
            ("page-evict", 4_400, 50)])
    + tick(9, 21_100, 8_000, [(5_000, 2_600)])
    # the runtime's own events on the loop thread, and a span between ticks
    + [ev("PjitFunction(decode_step)", 3_010, 380),
       ev("tick-drain", 29_200, 100), ev("tick-read", 29_210, 80)])
OTHER_LINE = [ev("np.asarray(jax.Array)", 100, 50)]


def test_host_ticks_reads_the_loop_threads_line():
    plane = xplane.Plane("/host:CPU", [xplane.Line("handler", OTHER_LINE),
                                       xplane.Line("python", LOOP_LINE)], {})
    got = serve_ticks.host_ticks(plane)
    assert [t["step_num"] for t in got] == [7, 8, 9]
    assert [t["tick_ps"] for t in got] == [9_000 * US, 12_000 * US,
                                           8_000 * US]
    assert [t["read_ps"] for t in got] == [2_000 * US, 5_500 * US,
                                           2_600 * US]
    # the outermost spans: tick 8's drain and first read lie in its
    # tick-pages; its evict and its last read under no other span
    assert [t["top_ps"] for t in got] == [2_420 * US, 5_950 * US,
                                          2_600 * US]
    assert got[1]["spans"] == {"tick-read": 5_500 * US,
                               "tick-pages": 3_400 * US,
                               "tick-drain": 3_300 * US,
                               "page-evict": 50 * US}
    # no tick marked (a parent commit, a training run): nothing
    assert serve_ticks.host_ticks(
        xplane.Plane("/host:CPU", [xplane.Line("handler", OTHER_LINE)],
                     {})) == []


def test_engine_host_ms_per_tick_is_the_median_tick_less_its_reads(
        monkeypatch):
    plane = xplane.Plane("/host:CPU", [xplane.Line("python", LOOP_LINE)], {})
    monkeypatch.setattr(named, "run_files", lambda run: ("/nowhere", ""))
    monkeypatch.setattr(serve_ticks, "_read",
                        lambda path, stamp: serve_ticks.host_ticks(plane))
    run = fake_run(engine_requests=[request(0, T0, 0.001)])
    # 7.0, 6.5 and 5.4 ms of host work in the three ticks
    assert read(run, "engine_host_ms_per_tick") == pytest.approx(6.5)
    # the parent marks no tick; an untraced run is not looked at
    monkeypatch.setattr(serve_ticks, "_read", lambda path, stamp: [])
    assert read(run, "engine_host_ms_per_tick") is None
    monkeypatch.setattr(serve_ticks, "_read", None)
    assert read(fake_run(), "engine_host_ms_per_tick") is None


# --- the journal ------------------------------------------------------------

T0 = 1_790_000_000.0


def request(i, ts, queue_s, **more):
    return dict({"ts": ts, "kind": "serve_request", "id": f"c{i}",
                 "status": "ok", "prompt_len": 64, "new_tokens": 256,
                 "chunks": 1, "prefix_tokens": 0, "preemptions": 0,
                 "wall_s": 2.0, "queue_s": queue_s, "ttft_s": queue_s + 0.04,
                 "prefill_s": 0.04, "tpot_s": 0.0077}, **more)


def snapshot(ts, ticks, rows, **more):
    return dict({"ts": ts, "kind": "serve_ticks", "ticks": ticks,
                 "ahead": ticks - 1, "drains": {}, "dropped_after_eod": 0,
                 "rows": rows, "evicted": 0,
                 "phase_s": {"read": 0.7 * ticks * 0.007}}, **more)


def journal_fixture():
    """120 retirements in a window of 50 s; before it and after it,
    records the readers must leave out."""
    records = [request(900, T0 - 5.0, 9.0), snapshot(T0 - 5.0, 100, 5_000),
               {"ts": T0 - 4.0, "kind": "serve_slow_tick", "tick": 90,
                "wall_s": 7.0, "phase_s": {"evict": 6.9}}]
    for i in range(120):
        ts = T0 + i * 0.4
        # queue_s 1 .. 120 ms in a scrambled order
        records.append(request(i, ts, ((i * 37) % 120 + 1) / 1e3))
        records.append(snapshot(ts, 1_000 + 50 * i, 20_000 + 450 * i))
        # the handler's reply, a little after; every fourth request came
        # with a second prompt, whose reply is one record
        if i % 4 == 0:
            records[-2]["id"] = f"c{i}/0"
            records.append(request(i, ts + 0.001, 0.0, id=f"c{i}/1"))
            records.append(snapshot(ts + 0.001, 1_000 + 50 * i,
                                    20_000 + 450 * i))
        records.append({"ts": ts + 0.003, "kind": "serve_reply",
                        "id": f"c{i}", "status": "200", "prompts": 1,
                        "handler_s": 2.0 + (i + 1) / 1e3, "engine_s": 2.0})
    records += [
        {"ts": T0 + 20.0, "kind": "serve_slow_tick", "tick": 4_000,
         "wall_s": 0.4, "phase_s": {"pre": 0.39}},
        {"ts": T0 + 30.0, "kind": "serve_slow_tick", "tick": 5_000,
         "wall_s": 1.1, "phase_s": {"read": 1.09}},
        {"ts": T0 + 60.0, "kind": "serve_reply", "id": "late", "status":
         "200", "prompts": 1, "handler_s": 9.0, "engine_s": 1.0},
        {"ts": T0 + 61.0, "kind": "serve_slow_tick", "tick": 9_000,
         "wall_s": 3.0, "phase_s": {"read": 3.0}}]
    return sorted(records, key=lambda r: r["ts"])


def run_over(tmp_path, monkeypatch, records, **fields):
    journal = tmp_path / "events.jsonl"
    journal.write_text("".join(json.dumps(r) + "\n" for r in records))
    monkeypatch.setattr(named, "run_files",
                        lambda run: (str(tmp_path), str(journal)))
    window = [r for r in records if r["kind"] == "serve_request"
              and T0 <= r["ts"] <= T0 + 50.0]
    return fake_run(engine_requests=window, **fields)


def test_the_four_journal_readers_over_known_records(tmp_path, monkeypatch):
    run = run_over(tmp_path, monkeypatch, journal_fixture())
    assert len(run.engine_requests) == 150
    # 50 ticks and 450 rows between two snapshots
    assert read(run, "engine_rows_per_tick") == pytest.approx(9.0)
    # 150 waits: thirty of 0 ms (the second prompts), then 1 .. 120 ms:
    # the 143rd of them in order is 113 ms
    assert read(run, "engine_queue_ms_p95") == pytest.approx(113.0)
    # 120 replies 1 .. 120 ms over the engine's time: the median is 60.5;
    # the reply of a request outside the window is left out by its id
    assert read(run, "server_overhead_ms_p50") == pytest.approx(60.5)
    assert len(serve_journal.replies(run)) == 120
    # the two slow ticks inside the window, 0.4 s and 1.1 s
    assert read(run, "engine_stall_ms_total") == pytest.approx(1_500.0)
    assert [r["tick"] for r in serve_journal.of_kind(
        run, "serve_slow_tick")] == [4_000, 5_000]


def test_no_slow_tick_reads_zero_where_the_phases_are_kept(tmp_path,
                                                           monkeypatch):
    calm = [r for r in journal_fixture() if r["kind"] != "serve_slow_tick"]
    run = run_over(tmp_path, monkeypatch, calm)
    assert read(run, "engine_stall_ms_total") == 0.0


def test_the_parents_journal_reads_nothing(tmp_path, monkeypatch):
    """What the parent commit writes: `serve_request` with its two ends,
    `serve_ticks` with the lookahead's counters, and nothing else."""
    parents = []
    for i in range(120):
        ts = T0 + i * 0.4
        parents.append({"ts": ts, "kind": "serve_request", "status": "ok",
                        "prompt_len": 64, "new_tokens": 256, "wall_s": 2.0,
                        "ttft_s": 0.04, "tpot_s": 0.0077})
        parents.append({"ts": ts, "kind": "serve_ticks",
                        "ticks": 1_000 + 50 * i, "ahead": 999 + 50 * i,
                        "drains": {}, "dropped_after_eod": 0})
    run = run_over(tmp_path, monkeypatch, parents)
    monkeypatch.setattr(serve_ticks, "_read", lambda path, stamp: [])
    for name in FIVE:
        assert read(run, name) is None, name
    # the readers that were there still read it
    assert read(run, "engine_tpot_ms_p50") == pytest.approx(7.7)


def test_a_run_that_kept_no_records_is_not_looked_up(monkeypatch):
    monkeypatch.setattr(named, "run_files", None)   # would raise if called
    for name in FIVE:
        assert read(fake_run(), name) is None, name


# --- the whole path, rehearsed ----------------------------------------------

CELL = "toy_instruct_spans"
# a second served open-loop cell, of another block type, ADDED behind the
# first by files and entries as spec.py's item 5 has it
ADDED_OPEN, ADDED_OPEN_MIX = "toy_falcon_added_open", "added_open"
ADDED_OPEN_METRICS = own_served_entries(ADDED_OPEN, ADDED_OPEN_MIX)


@pytest.fixture(scope="module")
def toy_spec(tmp_path_factory):
    """The real BENCHMARK.json's own entries over two toy cells: the
    first stands where `serve_mistral7b_instruct` stands (the toy
    configuration under `toy_instruct`'s mix, 60 req/s); the second, on
    `toy-falcon` with the reference it names (tests/benchmark/added) under
    a mix of its own name, is what a later PR adds: its name BEHIND the
    first's on every metric that every accepted served cell reports, its
    two entries behind every other. Returns the spec's path."""
    root = tmp_path_factory.mktemp("toy_spans")
    with open(BENCHMARK) as f:
        bench = json.load(f)
    shared = reported_by_every(bench, CELLS)
    bench["paths"] = ["."]
    bench["configs"] = [
        {"name": name, "source": "none", "file": name + ".json",
         "reduced": [], "why": "CPU rehearsal"}
        for name in ("toy-d2", "toy-falcon")]
    bench["workloads"] = [
        {"name": CELL, "config": "toy-d2", "traffic": CELL, "chips": 1,
         "why": "CPU rehearsal of " + CELLS[0]},
        {"name": ADDED_OPEN, "config": "toy-falcon",
         "traffic": ADDED_OPEN_MIX, "chips": 1,
         "why": "CPU rehearsal of a served cell added behind another"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if CELLS[0] in m["workloads"] else []
    for m in shared:
        m["workloads"].append(ADDED_OPEN)
    bench["per_layer"] += ADDED_OPEN_METRICS
    os.makedirs(root / "traffic")
    os.makedirs(root / "reference")
    shutil.copy(os.path.join(TOY_DIR, "toy-d2.json"), root)
    shutil.copy(os.path.join(ADDED_DIR, "toy-falcon.json"), root)
    shutil.copy(os.path.join(ADDED_DIR, "reference", "toyfalcon.py"),
                root / "reference")
    for mix in (CELL, ADDED_OPEN_MIX):
        shutil.copy(os.path.join(TOY_DIR, "traffic", "toy_instruct.json"),
                    root / "traffic" / (mix + ".json"))
    with open(root / "spec.json", "w") as f:
        json.dump(bench, f)
    return str(root / "spec.json")


@pytest.fixture(scope="module")
def rehearsed(toy_spec):
    """The first cell run traced through benchmark/run.py on the CPU: the
    server's own entry point, its engine's loop, its journal."""
    line = rehearse(CELL, 1, 4, spec=toy_spec, seed=2147480054)
    journal = named.journal(os.path.join(REPO, "runs", "benchmark", CELL,
                                         "tele", "events.jsonl"))
    return line, journal


def test_the_rehearsed_line_holds_all_five(rehearsed):
    line, _ = rehearsed
    assert line["correct"] is True, line.get("problems")
    metrics = line["metrics"]
    assert set(FIVE) <= set(metrics), sorted(metrics)
    for name, (unit, _, _) in FIVE.items():
        assert metrics[name]["unit"] == unit
    # the toy's four slots under sixty requests a second
    assert 0 < metrics["engine_host_ms_per_tick"]["value"]
    assert 0 < metrics["engine_rows_per_tick"]["value"] <= 4
    assert metrics["engine_queue_ms_p95"]["value"] >= 0
    assert metrics["engine_stall_ms_total"]["value"] >= 0
    # the readers that time the engine from its two ends still read
    assert {"engine_ttft_ms_p50.instruct",
            "engine_tpot_ms_p50"} <= set(metrics)


def test_the_rehearsed_journal_keeps_its_promises(rehearsed):
    """What ISSUE 54 asks of a traced run, on the toy's own journal: the
    two sums of every `serve_request`, a `serve_reply` for each by id, and
    the mean decoding batch by Little's law."""
    _, journal = rehearsed
    served = [r for r in journal if r["kind"] == "serve_request"
              and r["status"] == "ok"]
    replies = {r["id"] for r in journal if r["kind"] == "serve_reply"}
    assert len(served) > 200
    for r in served:
        assert r["queue_s"] + r["prefill_s"] == pytest.approx(r["ttft_s"],
                                                              abs=2e-6)
        rest = (r["new_tokens"] - 1) * r.get("tpot_s", 0.0)
        assert r["ttft_s"] + rest == pytest.approx(r["wall_s"], abs=1e-4)
        # the server's own warm-up request came over no socket
        assert r["id"] in replies or r["id"] is None
    assert sum(r["id"] is None for r in served) <= 1
    snaps = [r for r in journal if r["kind"] == "serve_ticks"]
    first, last = snaps[len(snaps) // 4], snaps[-1]
    span = last["ts"] - first["ts"]
    inside = [r for r in served if first["ts"] < r["ts"] <= last["ts"]]
    # rows a tick = (requests a second x decode time a request) x seconds
    # a tick: the rows the window's requests decoded over its ticks
    decoded = sum(r["new_tokens"] - 1 for r in inside)
    rows = last["rows"] - first["rows"]
    assert rows == pytest.approx(decoded, rel=0.1), (rows, decoded, span)
    # the phases kept are the loop thread's time: all of it but its parks
    busy = sum(last["phase_s"].values()) - sum(first["phase_s"].values())
    assert 0 < busy <= span * 1.001


# --- a served cell added behind another, through the unchanged harness ------

@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_a_served_cell_added_behind_another_runs(trace, toy_spec):
    """What the PR after this one does, run: the second cell through
    benchmark/run.py on the CPU (`rehearse` holds the line to `correct`
    true and no failed request). Untraced it reports the two request
    metrics and the set-up; traced the five, the two every served cell
    shares and, of its own two, the one a CPU run can read: no device
    plane, so `device_idle_pct.added_open` says nothing, none raises."""
    line = rehearse(ADDED_OPEN, trace, 4, spec=toy_spec,
                    seed=2147480060 + trace)
    assert line["attempted"] >= 200          # a p95 needs them
    cell = spec.Cell(toy_spec, ADDED_OPEN)
    if not trace:
        assert set(line["metrics"]) == {
            m["name"] for m in cell.end_to_end()} == {
                "request_ms_p50", "request_ms_p95", "setup_s"}
        return
    asked = [m["name"] for m in cell.per_layer()]
    own = [m["name"] for m in ADDED_OPEN_METRICS]
    assert set(asked) == set(FIVE) | {"engine_tpot_ms_p50",
                                      "gen_lateness_ms_max", *own}
    assert asked[-2:] == own
    assert all(m["workloads"] == [CELL, ADDED_OPEN]
               for m in cell.per_layer()[:-2])
    assert set(line["metrics"]) == set(asked) - {own[1]}
    with open(os.path.join(REPO, "runs", "benchmark", ADDED_OPEN,
                           "plan.json")) as f:
        assert json.load(f)["reference"] == os.path.join(
            os.path.dirname(toy_spec), "reference", "toyfalcon.py")
