"""The benchmark's files held to the contract, by data: every check here
is a function of a spec's path and derives what it expects from the
files that spec names (a configuration's own keys, its published values
under published/, its reference's functions), so a PR that adds a
configuration of another architecture, or a cell on it, adds files and
entries and edits no test. The rehearsal at the end is that PR: the real
BENCHMARK.json plus the `toy-falcon` configuration of tests/benchmark/
added/ and one training cell on it, in a temporary tree, under the same
checks and through the harness on the CPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402

BENCHMARK = os.path.join(REPO, "BENCHMARK.json")
# the serving cells kept ready for the `benchmark` PR that admits them
CANDIDATES = os.path.join(REPO, "benchmark", "candidates.json")
TOY = os.path.join(REPO, "tests", "benchmark", "toy", "spec.json")
ADDED_DIR = os.path.join(REPO, "tests", "benchmark", "added")
PUBLISHED = os.path.join(REPO, "tests", "benchmark", "published")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what a configuration file holds besides the source's own keys
OURS = {"source", "reference", "reduced", "assumed", "deployment", "program"}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _names(spec_path, group):
    return [x["name"] for x in _load(spec_path)[group]]


# --- the checks, each a function of a spec's path ---------------------------

def file_contract(spec_path):
    """The contract's keys, names and limits (the driver refuses a file
    outside them before a single run)."""
    s = _load(spec_path)
    cells = [w["name"] for w in s["workloads"]]
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(spec_path) < 64 * 1024
    assert 1 <= s["run_seconds"] <= 51
    metrics = s["end_to_end"] + s["per_layer"]
    names = ([m["name"] for m in metrics] + cells
             + [c["name"] for c in s["configs"]]
             + [w["traffic"] for w in s["workloads"]])
    assert all(NAME.match(n) for n in names), names
    for group in (metrics, s["workloads"], s["configs"]):
        assert len({x["name"] for x in group}) == len(group)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= set(cells)
    for m in s["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in s["end_to_end"])
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    four = sum(w["chips"] == 4 for w in s["workloads"])
    assert four <= max(len(cells) // 4, 1)
    pairs = [(w["config"], w["traffic"]) for w in s["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in s["workloads"]}
    assert used == {c["name"] for c in s["configs"]}
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert any(c["file"].startswith(p + "/") for p in s["paths"])
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
    files = [c["file"] for c in s["configs"]]
    assert len(set(files)) == len(files)
    command = " ".join(s["command"])
    assert ".." not in command and not any(
        word.startswith("/") for word in s["command"])
    # the full check fits its budget with all 24 cells the contract allows
    runs = 2 + 14 * 24
    assert runs * (s["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def _published_file(spec_path, name):
    """published/<name>.json under a directory of the spec's `paths`,
    else under this tree's tests/benchmark (a spec kept elsewhere)."""
    root = os.path.dirname(os.path.abspath(spec_path))
    for base in _load(spec_path)["paths"]:
        path = os.path.join(root, base, "published", name + ".json")
        if os.path.exists(path):
            return path
    return os.path.join(PUBLISHED, name + ".json")


def configuration_contract(spec_path, name):
    """A configuration keeps what its source published: outside `reduced`
    every key it takes from the source has the source's own value, which
    published/<name>.json holds with the source's URL."""
    entry = {c["name"]: c for c in _load(spec_path)["configs"]}[name]
    config = _load(os.path.join(os.path.dirname(spec_path), entry["file"]))
    path = _published_file(spec_path, name)
    assert os.path.exists(path), (
        f"configuration {name!r} has no published values: add "
        f"tests/benchmark/published/{name}.json with the source's URL "
        "under \"source\" and, under \"config\", the source's own value of "
        "every key the configuration file takes from it")
    published = _load(path)
    assert published["source"] == entry["source"] == config["source"]
    reduced = config.get("reduced", {})
    assert list(reduced) == entry["reduced"]
    assert not set(reduced) & set(config.get("assumed", {}))
    theirs = [k for k in config if k not in OURS]
    assert theirs, "the configuration takes no key from its source"
    for key in theirs:
        assert key in published["config"], (
            f"{name}: key {key!r} is neither one of the harness's {sorted(OURS)} "
            f"nor in {path}: say what the source gives for it, or list "
            "it under \"assumed\" in place of a key")
        if key in reduced:
            assert config[key] != published["config"][key], (
                f"{name}: {key!r} is listed as reduced and is not")
        else:
            assert config[key] == published["config"][key], (
                f"{name}: {key!r} is {config[key]!r}, the source has "
                f"{published['config'][key]!r}, and `reduced` does not "
                "list it")


def cell_contract(spec_path, name):
    """A cell finds its files and reports what the contract asks; what
    is expected of its architecture comes from the cell's own files."""
    cell = spec.Cell(spec_path, name)
    driver = cell.traffic["driver"]
    assert driver.split("_")[0] in ("train", "serve")
    e2e = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.per_layer()
    assert layer
    for m in layer:
        assert callable(cell.reader(m["name"]))
    # the architecture is a file under `paths`, found by the name the
    # configuration gives it, that holds what the drivers call
    path = cell.reference_path()
    bases = [os.path.normpath(os.path.join(cell.root, p)) + os.sep
             for p in cell.spec["paths"]]
    assert any(path.startswith(b) for b in bases), path
    reference = spec.load_module(path)
    wanted = ["program_flags", "from_program_params", "lm_loss"]
    if driver.startswith("serve"):
        wanted.append("next_token_logprobs")
    for function in wanted:
        assert callable(getattr(reference, function, None)), function
    if driver == "train":
        flags = reference.program_flags(cell.config,
                                        cell.traffic["seq_length"])
        # the model that runs is the one the file holds
        assert flags[flags.index("--num_layers") + 1] == str(
            cell.config["num_hidden_layers"])
        tied = cell.config.get("tie_word_embeddings", True)
        assert ("--no_tie_embed_logits" in flags) == (not tied)
    else:
        flags = cell.config["program"]["serve"]["flags"]
        if "--serve_max_seq_len" in flags:
            # the longest request of the mix fits the engine's limit
            longest = (cell.traffic["prompt_tokens"]["max"]
                       + cell.traffic["new_tokens"]["max"])
            assert longest <= int(
                flags[flags.index("--serve_max_seq_len") + 1])


# --- BENCHMARK.json and the candidates under them ---------------------------

def test_benchmark_json_has_the_contracts_keys_and_names():
    file_contract(BENCHMARK)


@pytest.mark.parametrize("spec_path, name", [
    (path, name) for path in (BENCHMARK, CANDIDATES)
    for name in _names(path, "configs")],
    ids=lambda v: os.path.basename(v))
def test_configs_keep_the_published_widths(spec_path, name):
    configuration_contract(spec_path, name)


@pytest.mark.parametrize("cell_name", _names(BENCHMARK, "workloads"))
def test_every_cell_finds_its_files_and_reports_what_the_contract_asks(
        cell_name):
    cell_contract(BENCHMARK, cell_name)


@pytest.mark.parametrize("cell_name", _names(CANDIDATES, "workloads"))
def test_serving_cells_kept_ready_find_their_files(cell_name):
    cell_contract(CANDIDATES, cell_name)
    cell = spec.Cell(CANDIDATES, cell_name)
    assert cell.traffic["driver"] in ("serve_open", "serve_closed")
    assert "--serve_kv_paging" in cell.config["program"]["serve"]["flags"]


def test_every_reader_file_is_named_by_some_metric():
    stems = {m["name"].split(".")[0]
             for path in (BENCHMARK, CANDIDATES, TOY)
             for m in _load(path)["per_layer"]}
    on_disk = {f[:-3] for f in os.listdir(os.path.join(
        REPO, "benchmark", "layer_metrics")) if f.endswith(".py")}
    assert stems == on_disk


# --- the rehearsal: a PR that adds a configuration and a cell ---------------

ADDED_CONFIG = "toy-falcon"
ADDED_CELL = "train_toyfalcon_rehearsed"


def added_tree(root, published=True):
    """A copy of what the benchmark is made of with what such a PR
    brings: files (tests/benchmark/added: the configuration, the
    reference it names, its published values, a traffic mix) and entries
    (a configuration, a cell, the cell's name on each metric it
    reports). Nothing that exists is edited. Returns the spec's path."""
    os.makedirs(root / "tests")
    os.symlink(os.path.join(REPO, "benchmark"), root / "benchmark")
    shutil.copytree(os.path.join(REPO, "tests", "benchmark"),
                    root / "tests" / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(ADDED_DIR, root / "tests" / "benchmark",
                    dirs_exist_ok=True)
    if not published:
        os.remove(root / "tests" / "benchmark" / "published"
                  / (ADDED_CONFIG + ".json"))
    s = _load(BENCHMARK)
    file = "tests/benchmark/" + ADDED_CONFIG + ".json"
    s["configs"].append({
        "name": ADDED_CONFIG, "source": _load(root / file)["source"],
        "file": file, "reduced": [], "why": "another block type, added"})
    s["workloads"].append({
        "name": ADDED_CELL, "config": ADDED_CONFIG, "traffic": "added_train",
        "chips": 1, "why": "rehearsal of a cell added by files and entries"})
    # it reports what the one-chip training cells report
    alike = {w["name"] for w in _load(BENCHMARK)["workloads"]
             if w["chips"] == 1 and spec.Cell(
                 BENCHMARK, w["name"]).traffic["driver"] == "train"}
    for m in s["end_to_end"] + s["per_layer"]:
        if alike & set(m.get("workloads", [])):
            m["workloads"].append(ADDED_CELL)
    path = root / "BENCHMARK.json"
    with open(path, "w") as f:
        json.dump(s, f)
    return str(path)


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    return added_tree(tmp_path_factory.mktemp("added_pr"))


def test_rehearsed_pr_keeps_the_files_contract(added):
    file_contract(added)
    assert ADDED_CELL in _names(added, "workloads")


@pytest.mark.parametrize("name", _names(BENCHMARK, "configs")
                         + [ADDED_CONFIG])
def test_rehearsed_pr_keeps_every_configuration_to_its_source(added, name):
    configuration_contract(added, name)


@pytest.mark.parametrize("cell_name", _names(BENCHMARK, "workloads")
                         + [ADDED_CELL])
def test_rehearsed_pr_has_every_cell_find_its_files(added, cell_name):
    cell_contract(added, cell_name)


def test_a_configuration_without_published_values_is_told_which_file_to_add(
        tmp_path):
    path = added_tree(tmp_path, published=False)
    with pytest.raises(AssertionError,
                       match="tests/benchmark/published/toy-falcon.json"):
        configuration_contract(path, ADDED_CONFIG)
    # and one that drifts from its source is told the key
    config = tmp_path / "tests" / "benchmark" / "toy-falcon.json"
    drifted = _load(config)
    drifted["hidden_size"] = 96
    with open(config, "w") as f:
        json.dump(drifted, f)
    shutil.copytree(os.path.join(ADDED_DIR, "published"),
                    tmp_path / "tests" / "benchmark" / "published",
                    dirs_exist_ok=True)
    with pytest.raises(AssertionError, match="'hidden_size' is 96"):
        configuration_contract(path, ADDED_CONFIG)


def test_rehearsed_cell_runs_traced_through_the_unchanged_harness(added):
    """The added cell through run.py on the CPU, traced: every per-layer
    metric the training cells report is asked of a block type no reader
    has seen; those with something to read report it, the device readers
    say nothing, none raises."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--spec", added, "--workload", ADDED_CELL, "--seed", "2500000001",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    asked = {m["name"] for m in spec.Cell(added, ADDED_CELL).per_layer()}
    assert {"mlp_ms_per_step", "flash_fwd_roofline_pct"} <= asked
    assert set(line["metrics"]) == {
        "train_step_ms_p50", "train_data_wait_pct", "train_host_ms_per_step",
        "step_hbm_gb", "step_temp_hbm_gb"}
    assert (line["metrics"]["step_hbm_gb"]["value"]
            > line["metrics"]["step_temp_hbm_gb"]["value"] > 0)
    with open(os.path.join(REPO, "runs", "benchmark", ADDED_CELL,
                           "plan.json")) as f:
        plan = json.load(f)
    assert plan["reference"] == os.path.join(
        os.path.dirname(added), "tests", "benchmark", "reference",
        "toyfalcon.py")
