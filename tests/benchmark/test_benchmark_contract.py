"""The benchmark's files held to the contract, by data: every check here
is a function of a spec's path and derives what it expects from the
files that spec names (a configuration's own keys, its published values
under published/, its reference's functions), so a PR that adds a
configuration of another architecture, or a cell on it, adds files and
entries and edits no test. The rehearsal at the end is that PR: the real
BENCHMARK.json plus the two configurations of tests/benchmark/added/
(`toy-falcon`, another block type, cut in nothing; `toy-moe-share8`, one
chip's share of a deployment: depth, experts held and vocabulary cut to
the floors), one training cell on each and one served open-loop cell on
the first (ISSUE 60), in a temporary tree, under the same checks and
through the harness on the CPU; every fact stated of every
configuration, cell or metric of BENCHMARK.json is stated of that tree
too, so an entry a later PR may write never meets a test for the first
time in that PR."""

import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import spec  # noqa: E402
from test_benchmark_rehearse_train import rehearse  # noqa: E402

BENCHMARK = os.path.join(REPO, "BENCHMARK.json")
# a cell kept ready for the `benchmark` PR that can admit it
CANDIDATES = os.path.join(REPO, "benchmark", "candidates.json")
TOY = os.path.join(REPO, "tests", "benchmark", "toy", "spec.json")
ADDED_DIR = os.path.join(REPO, "tests", "benchmark", "added")
PUBLISHED = os.path.join(REPO, "tests", "benchmark", "published")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# what a configuration file holds besides the source's own keys
OURS = {"source", "reference", "reduced", "assumed", "deployment", "program",
        "whole"}
# `reduced` lists cuts of depth and of the chip's share of a layer (the
# `model-configs` guide, section 4), never a width: no key with a width's
# ending, but for the vocabulary held, and not the experts a token
WIDTH_ENDINGS = ("_dim", "_rank", "_size")
NO_WIDTH = {"vocab_size"}
WIDTHS_BY_NAME = {"num_experts_per_tok"}
# the keys under which sources count a layer's routed experts and the
# dense layers in front of the expert layers (a count, or the leading run
# of "dense" in a list with one entry a layer); the floors of a share
EXPERT_COUNTS = ("num_experts", "n_routed_experts", "num_local_experts")
LEADING_DENSE_COUNTS = ("first_k_dense_replace", "num_dense_layers")
LEADING_DENSE_KINDS = "mlp_layer_types"
LEAST_EXPERTS, LEAST_VOCABULARY_SHARE, LEAST_LAYERS = 8, 8, 4


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
        for key in c["reduced"]:
            assert key in NO_WIDTH or not (
                key.endswith(WIDTH_ENDINGS) or key in WIDTHS_BY_NAME), (
                f"configuration {c['name']!r} lists {key!r} in `reduced`: "
                "a width is never cut; the vocabulary held is no width "
                "(`reduced` takes the depth, the experts held and "
                "`vocab_size`)")
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


def leading_dense(config):
    """The dense layers in front of the expert layers, as the source
    counts them: a count under one of LEADING_DENSE_COUNTS, else the
    leading run of "dense" in a list of the layers' FFN kinds, else none."""
    for key in LEADING_DENSE_COUNTS:
        if key in config:
            return config[key]
    kinds = config.get(LEADING_DENSE_KINDS)
    if not isinstance(kinds, list):
        return 0
    return next((i for i, kind in enumerate(kinds) if kind != "dense"),
                len(kinds))


def per_layer_lists_contract(name, config, published, reduced):
    """A list of the source with one entry a layer (as long as the
    source's depth: the kinds of layer, of FFN, of indexer) is cut with
    the depth: it stands in `reduced`, is as long as the depth held, and
    is a contiguous run of the published list, so the kinds of layer keep
    their published order and, over whole periods, their ratio."""
    depth, whole = config["num_hidden_layers"], published["num_hidden_layers"]
    for key, theirs in published.items():
        if not (isinstance(theirs, list) and len(theirs) == whole
                and key in config):
            continue
        ours = config[key]
        if key not in reduced:      # the source's own list, checked before
            assert depth == whole, (
                f"{name}: {key!r} is left whole, {whole} entries, at a "
                f"depth of {depth}: a list with one entry a layer is cut "
                f"with the depth: list it in `reduced` and keep {depth} "
                "entries in a row of the published list")
            continue
        assert isinstance(ours, list) and len(ours) == depth, (
            f"{name}: {key!r} has {len(ours)} entries at a depth of "
            f"{depth}: a list with one entry a layer is as long as the "
            "configuration's depth")
        assert any(theirs[i:i + depth] == ours
                   for i in range(whole - depth + 1)), (
            f"{name}: {key!r} is no contiguous run of the published list: "
            "the kinds of layer keep their published order (entries i to "
            f"i + {depth - 1} of the source's {whole}, for one i)")


def whole_contract(name, config, published, cut):
    """A share's file states the published counts beside the ones held,
    under "whole", for exactly the keys of `reduced` that cut a share
    (`cut`): there a configuration's `program_flags` reads the router's
    width and the whole vocabulary. A file that cuts no share has none."""
    stated = config.get("whole")
    if not cut:
        assert stated is None, (
            f"{name}: \"whole\" {stated!r} and no share cut: it states the "
            "published counts of the keys of `reduced` that cut a share "
            f"({list(EXPERT_COUNTS)}, 'vocab_size'), and none is listed")
        return
    wanted = {key: published[key] for key in cut}
    assert stated is not None, (
        f"{name}: {cut} cut to one chip's share and no \"whole\": the file "
        "states the published counts beside the ones held, \"whole\": "
        f"{json.dumps(wanted)}")
    assert set(stated) == set(cut), (
        f"{name}: \"whole\" names {sorted(stated)}: it names exactly the "
        f"keys of `reduced` that cut a share, {sorted(cut)}")
    for key in cut:
        assert stated[key] == published[key], (
            f"{name}: \"whole\" gives {key!r} as {stated[key]!r}, the "
            f"source has {published[key]!r}")


def share_contract(name, config, published, reduced):
    """One chip's share of a deployment keeps the guide's floors, read
    off the configuration's own published values: an eighth of the
    vocabulary, 8 routed experts a layer and a whole number of such
    shares, four layers behind the leading dense ones, the published
    counts under "whole" and the deployment said in words. A
    configuration that cuts depth alone cuts no share, and none of this
    binds it."""
    cut = [k for k in reduced if k in NO_WIDTH or k in EXPERT_COUNTS]
    if "vocab_size" in reduced:
        whole = published["vocab_size"]
        least = -(-whole // LEAST_VOCABULARY_SHARE)
        assert least <= config["vocab_size"] < whole, (
            f"{name}: a vocabulary of {config['vocab_size']} held of "
            f"{whole}: a slice is at least an eighth, {least}")
    for key in set(reduced) & set(EXPERT_COUNTS):
        held, whole = config[key], published[key]
        assert LEAST_EXPERTS <= held < whole and whole % held == 0, (
            f"{name}: {held} experts held of {whole} ({key!r}): a chip "
            f"holds at least {LEAST_EXPERTS}, and a whole number of such "
            "shares makes the layer")
    whole_contract(name, config, published, cut)
    if not cut:
        return
    deployment = config.get("deployment", "")
    assert re.search(r"\b\d+ chips\b", deployment), (
        f"{name}: {cut} cut to one chip's share and no \"deployment\" that "
        "says how many chips share a layer (\"8 chips share each layer: "
        "...\")")
    layers = config["num_hidden_layers"] - leading_dense(config)
    assert layers >= LEAST_LAYERS, (
        f"{name}: {layers} layers behind the leading dense ones: where a "
        f"share is cut, at least {LEAST_LAYERS} stay")


def configuration_contract(spec_path, name):
    """A configuration keeps what its source published: outside `reduced`
    every key it takes from the source has the source's own value, which
    published/<name>.json holds with the source's URL; what `reduced`
    cuts keeps to per_layer_lists_contract() and the floors of
    share_contract()."""
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
    per_layer_lists_contract(name, config, published["config"], reduced)
    share_contract(name, config, published["config"], reduced)


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
        # a served cell's weights are the benchmark's, made by the
        # reference and handed to the program; `correct` reads its logits
        wanted += ["make_weights", "to_program_params", "logits"]
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


def depth_alone_contract(spec_path):
    """The configurations of a spec whose `reduced` is the depth alone
    pass as they are and are bound by no floor of the share, at any
    depth: each is held to its source, and would be at one layer. (One
    that cuts more is held to share_contract() by configuration_contract()
    like every other.) Returns {name: depth} of those it looked at."""
    depths = {}
    for entry in _load(spec_path)["configs"]:
        if entry["reduced"] != ["num_hidden_layers"]:
            continue
        name = entry["name"]
        configuration_contract(spec_path, name)
        config = _load(os.path.join(os.path.dirname(spec_path),
                                    entry["file"]))
        published = _load(_published_file(spec_path, name))["config"]
        share_contract(name, dict(config, num_hidden_layers=1), published,
                       config["reduced"])
        depths[name] = config["num_hidden_layers"]
    return depths


def reader_files_contract(spec_path, *others):
    """Every reader under a layer_metrics/ of the spec's `paths` is named
    by some metric of the spec or of `others` (specs that keep a reader
    under test while no cell of this one reports it), and every metric
    names one. Readers only: a file of kernel_costs/ is no reader (a
    reader names the kernels whose costs it wants, and a cost file no
    kernel carries the name of yet, as flash_bwd.py, is read by none)."""
    stems = {m["name"].split(".")[0] for path in (spec_path, *others)
             for m in _load(path)["per_layer"]}
    on_disk = set()
    for base in _load(spec_path)["paths"]:
        folder = os.path.join(os.path.dirname(spec_path), base,
                              "layer_metrics")
        if os.path.isdir(folder):
            on_disk |= {f[:-3] for f in os.listdir(folder)
                        if f.endswith(".py")}
    assert stems == on_disk


def serving_cells_contract(spec_path, cell_name):
    """A served cell offers a load fixed in its mix and searches for
    none: an open loop a rate that is a number (four fifths of the knee,
    whose sweep the mix names), a closed loop its callers; the engine is
    the paged one; the check that decides `correct` samples the window's
    own requests against a limit the mix gives with its reason."""
    cell = spec.Cell(spec_path, cell_name)
    mix = cell.traffic
    assert mix["driver"] in ("serve_open", "serve_closed")
    if mix["driver"] == "serve_open":
        assert isinstance(mix["rate_rps"], (int, float)) and mix["rate_rps"] > 0
        knee = mix["knee"]
        assert abs(mix["rate_rps"] - 0.8 * knee["sustained_rps"]) < 1e-9
        assert os.path.exists(os.path.join(REPO, knee["sweep"]))
        # its own two entries carry the mix's name (spec.py, item 5) and
        # list no cell of another mix
        mixes = {w["name"]: w["traffic"] for w in cell.spec["workloads"]}
        own = {m["name"]: m["workloads"] for m in cell.per_layer()}
        for reader in ("engine_ttft_ms_p50", "device_idle_pct"):
            listed = own[reader + "." + cell.traffic_name]
            assert {mixes[c] for c in listed} == {cell.traffic_name}
    else:
        assert mix["clients"] >= 1 and "rate_rps" not in mix
    assert "--serve_kv_paging" in cell.config["program"]["serve"]["flags"]
    check = mix["check"]
    assert set(check) == {"requests", "logit_gap_tolerance", "why"}
    assert check["requests"] >= 4 and 0 < check["logit_gap_tolerance"] < 1
    e2e = {m["name"] for m in cell.end_to_end()} - {"setup_s"}
    assert e2e == ({"request_ms_p50", "request_ms_p95"}
                   if mix["driver"] == "serve_open"
                   else {"serve_tokens_per_s"})


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


SERVED = [w["name"] for w in _load(BENCHMARK)["workloads"]
          if spec.Cell(BENCHMARK, w["name"]).traffic["driver"] != "train"]


@pytest.mark.parametrize("cell_name", _names(CANDIDATES, "workloads"))
def test_serving_cells_kept_ready_find_their_files(cell_name):
    cell_contract(CANDIDATES, cell_name)
    assert cell_name not in _names(BENCHMARK, "workloads")
    ours = {m["name"] for group in ("end_to_end", "per_layer")
            for m in _load(BENCHMARK)[group]}
    kept = {m["name"] for group in ("end_to_end", "per_layer")
            for m in _load(CANDIDATES)[group]}
    assert kept & ours == {"setup_s"}     # nothing the benchmark has


def test_the_benchmark_serves_as_well_as_trains():
    drivers = {spec.Cell(BENCHMARK, n).traffic["driver"]
               for n in _names(BENCHMARK, "workloads")}
    assert {"train", "serve_open"} <= drivers


def test_every_reader_file_is_named_by_some_metric():
    reader_files_contract(BENCHMARK, CANDIDATES, TOY)


# --- the rehearsal: a PR that adds a configuration and a cell ---------------

ADDED_CONFIG = "toy-falcon"
ADDED_CELL = "train_toyfalcon_rehearsed"
# one chip's share of a deployment: a toy of the MoE block the benchmark
# has, its depth, experts held and vocabulary each cut to the floor
SHARE_CONFIG = "toy-moe-share8"
SHARE_CELL = "train_toymoe_share8_rehearsed"
# what a configuration PR brings besides: a per-layer metric of its own
# that lists its cell alone, its reader under added/layer_metrics/
SHARE_METRIC = {
    "name": "moe_load_worst_step", "unit": "x", "better": "lower",
    "source": "program_counter", "layer": "mlp",
    "moves": "train_tokens_per_s", "workloads": [SHARE_CELL]}


def own_served_entries(cell, mix):
    """The two entries a served open-loop cell brings for its mix, by
    readers the benchmark has: each lists that cell alone."""
    return [
        {"name": "engine_ttft_ms_p50." + mix, "unit": "ms",
         "better": "lower", "source": "program_span", "layer": "engine",
         "moves": "request_ms_p95", "workloads": [cell]},
        {"name": "device_idle_pct." + mix, "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "device",
         "moves": "request_ms_p50", "workloads": [cell]}]


# a served open-loop cell on the first toy (its file has `program.serve`,
# its reference `make_weights`, `to_program_params` and `logits`)
SERVED_CELL = "serve_toyfalcon_rehearsed"
SERVED_MIX = "added_serve_open"
SERVED_METRICS = own_served_entries(SERVED_CELL, SERVED_MIX)
# (configuration, cell, traffic mix, the configuration's why, its metrics)
ADDED = [
    (ADDED_CONFIG, ADDED_CELL, "added_train", "another block type, added",
     []),
    (SHARE_CONFIG, SHARE_CELL, "added_share_train",
     "one chip's share of 8 that hold each layer, added", [SHARE_METRIC]),
    (ADDED_CONFIG, SERVED_CELL, SERVED_MIX,
     "another block type, added", SERVED_METRICS),
]


def reported_by_every(s, cells):
    """The metrics of a spec, end to end and per layer, whose `workloads`
    hold every one of `cells`."""
    return [m for m in s["end_to_end"] + s["per_layer"]
            if set(cells) <= set(m.get("workloads", ()))]


def appended_to(s, driver):
    """The metrics of BENCHMARK.json (`s`) to whose `workloads` an added
    one-chip cell under `driver` appends its name (spec.py, item 5): a
    training cell to every metric SOME one-chip training cell lists (a
    reader says nothing where its scope or kernel does not occur); a
    served open-loop cell to every metric ALL the served open-loop cells
    list (the two request metrics, `engine_tpot_ms_p50`,
    `gen_lateness_ms_max`, PR 54's five), the entries of one mix or of
    one architecture staying that cell's own."""
    alike = [w["name"] for w in s["workloads"] if w["chips"] == 1
             and spec.Cell(BENCHMARK, w["name"]).traffic["driver"] == driver]
    if driver == "train":
        return [m for m in s["end_to_end"] + s["per_layer"]
                if set(alike) & set(m.get("workloads", ()))]
    return reported_by_every(s, alike)


def added_tree(root, published=True):
    """A copy of what the benchmark is made of with what such PRs bring:
    files (tests/benchmark/added: each configuration, the reference it
    names unless the benchmark has it, its published values, a traffic
    mix, a served open-loop mix's sweep, a reader) and entries (a
    configuration with the `reduced` its file gives, a cell, the cell's
    name BEHIND those on each metric it reports, the per-layer metrics
    of its own after those that are there). Nothing that exists is
    edited. Returns the spec's path."""
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
    # what a cell reports is read off the accepted cells of its driver
    reports = {driver: appended_to(s, driver)
               for driver in ("train", "serve_open")}
    for config, cell, traffic, why, own in ADDED:
        file = "tests/benchmark/" + config + ".json"
        held = _load(root / file)
        if config not in [c["name"] for c in s["configs"]]:
            s["configs"].append({
                "name": config, "source": held["source"], "file": file,
                "reduced": list(held.get("reduced", {})), "why": why})
        s["workloads"].append({
            "name": cell, "config": config, "traffic": traffic, "chips": 1,
            "why": "rehearsal of a cell added by files and entries"})
        mix = _load(root / "tests" / "benchmark" / "traffic"
                    / (traffic + ".json"))
        for m in reports[mix["driver"]]:
            m["workloads"].append(cell)
        s["per_layer"] += [dict(m, workloads=list(m["workloads"]))
                           for m in own]
    path = root / "BENCHMARK.json"
    with open(path, "w") as f:
        json.dump(s, f)
    return str(path)


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    return added_tree(tmp_path_factory.mktemp("added_pr"))


@pytest.mark.parametrize("spec_path, cell_name", [
    (path, name) for path in (BENCHMARK, CANDIDATES)
    for name in _names(path, "workloads") if spec.Cell(
        path, name).traffic["driver"] != "train"]
    + [("rehearsed", SERVED_CELL)],
    ids=lambda v: os.path.basename(v))
def test_serving_cells_fix_their_load_and_their_deployment(spec_path,
                                                           cell_name, added):
    """Of the real files' served cells, and of the one the rehearsed PR
    adds."""
    serving_cells_contract(added if spec_path == "rehearsed" else spec_path,
                           cell_name)


def test_rehearsed_pr_keeps_the_files_contract(added):
    file_contract(added)
    assert _names(added, "workloads") == _names(BENCHMARK, "workloads") + [
        ADDED_CELL, SHARE_CELL, SERVED_CELL]
    reduced = {c["name"]: c["reduced"] for c in _load(added)["configs"]}
    assert reduced[ADDED_CONFIG] == []
    assert reduced[SHARE_CONFIG] == ["num_hidden_layers", "num_experts",
                                     "vocab_size"]
    # the cells' own metrics stand behind all of BENCHMARK.json's own,
    # which keep their places, and each lists its cell alone
    theirs = _names(BENCHMARK, "per_layer")
    entries = _load(added)["per_layer"]
    assert [m["name"] for m in entries[:len(theirs)]] == theirs
    assert entries[len(theirs):] == [SHARE_METRIC] + SERVED_METRICS
    # the served cell's name went behind the accepted cells' wherever
    # every served open-loop cell reports, end to end and per layer
    accepted = [n for n in _names(BENCHMARK, "workloads")
                if spec.Cell(BENCHMARK, n).traffic["driver"] == "serve_open"]
    grown = reported_by_every(_load(added), accepted)
    for m in grown:
        assert m["workloads"] == accepted + [SERVED_CELL]
    # today's nine, and whatever a later PR has every served cell report
    assert {"request_ms_p50", "request_ms_p95", "engine_tpot_ms_p50",
            "gen_lateness_ms_max", "engine_host_ms_per_tick",
            "engine_rows_per_tick", "engine_queue_ms_p95",
            "server_overhead_ms_p50", "engine_stall_ms_total"} <= {
                m["name"] for m in grown}
    assert reported_by_every(_load(added), [SERVED_CELL]) == (
        grown + SERVED_METRICS)


@pytest.mark.parametrize("name", _names(BENCHMARK, "configs")
                         + [ADDED_CONFIG, SHARE_CONFIG])
def test_rehearsed_pr_keeps_every_configuration_to_its_source(added, name):
    configuration_contract(added, name)


@pytest.mark.parametrize("cell_name", _names(BENCHMARK, "workloads")
                         + [ADDED_CELL, SHARE_CELL, SERVED_CELL])
def test_rehearsed_pr_has_every_cell_find_its_files(added, cell_name):
    cell_contract(added, cell_name)


def test_rehearsed_pr_has_every_reader_file_named_by_some_metric(
        added, tmp_path):
    """The rehearsed tree keeps two readers of its own beside the
    benchmark's (tests/benchmark/added/layer_metrics): the share's, and
    the one the serving rehearsal's spec names."""
    from test_benchmark_rehearse_serve import _added_spec

    own = set(os.listdir(os.path.join(ADDED_DIR, "layer_metrics")))
    assert own == {SHARE_METRIC["name"] + ".py", "requests_done_count.py"}
    assert own <= set(os.listdir(os.path.join(
        os.path.dirname(added), "tests", "benchmark", "layer_metrics")))
    reader_files_contract(added, CANDIDATES, _added_spec(tmp_path))


# --- what `reduced` may cut, and how far ------------------------------------

def _cut(key, published, held, depth=None):
    """The share-cut toy with `key` listed as reduced too: the source
    has `published`, the file holds `held` (and `depth` layers)."""
    def edit(entry, config, source):
        source[key], config[key] = published, held
        config["reduced"][key] = "cut"
        entry["reduced"].append(key)
        if depth is not None:
            config["num_hidden_layers"] = depth
    return edit


def _width(key):
    """... with `key`, a width, listed (its value halved, as such a cut
    would)."""
    def edit(entry, config, source):
        value = source.get(key, 32)
        _cut(key, value, value // 2)(entry, config, source)
    return edit


def _set(config=(), published=()):
    """... with these values, "whole" kept in step with a published count
    it states."""
    def edit(entry, held, source):
        held.update(config)
        source.update(published)
        whole = held.get("whole", {})
        whole.update({k: v for k, v in dict(published).items() if k in whole})
    return edit


def _without(key):
    def edit(entry, config, source):
        del config[key]
    return edit


def _uncut(keys, depth):
    """... at the source's own depth, `depth`, and with `keys` of its
    share held whole again, all of them unlisted."""
    def edit(entry, config, source):
        source["num_hidden_layers"] = depth
        for key in ("num_hidden_layers", *keys):
            config[key] = source[key]
            del config["reduced"][key]
            entry["reduced"].remove(key)
            config["whole"].pop(key, None)
    return edit


def _then(*edits):
    def edit(entry, config, source):
        for one in edits:
            one(entry, config, source)
    return edit


def _depth_alone(depth):
    """... as a cut of depth alone: 16 layers -> `depth`, every expert
    and the whole vocabulary held, no "whole"."""
    return _then(_uncut(["num_experts", "vocab_size"], depth),
                 _without("whole"), _cut("num_hidden_layers", 16, depth))


NEVER_A_WIDTH = "a width is never cut; the vocabulary held is no width"
BEHIND_3 = "3 layers behind the leading dense ones"
# lists with one entry a layer of the toy's 16: the kinds of layer in
# periods of four, and two dense FFNs in front of the expert FFNs
KINDS = ["conv", "conv", "full_attention", "conv"] * 4
FFNS = ["dense"] * 2 + ["sparse"] * 14
SHARE_CASES = {
    # the toy as it stands: every floor met exactly
    "as_added": (None, None),
    "hidden_size": (_width("hidden_size"), NEVER_A_WIDTH),
    "moe_intermediate_size": (_width("moe_intermediate_size"),
                              NEVER_A_WIDTH),
    "head_dim": (_width("head_dim"), NEVER_A_WIDTH),
    "kv_lora_rank": (_width("kv_lora_rank"), NEVER_A_WIDTH),
    "num_experts_per_tok": (_width("num_experts_per_tok"), NEVER_A_WIDTH),
    # an eighth, rounded up: 512 of 4096 and of 4095, not of 4097
    "vocabulary_an_eighth_rounded_up": (
        _set(published={"vocab_size": 4095}), None),
    "vocabulary_under_an_eighth": (
        _set(published={"vocab_size": 4097}), "at least an eighth, 513"),
    "vocabulary_not_cut_at_all": (
        _set({"vocab_size": 4096}), "'vocab_size' is listed as reduced"),
    "16_experts_held_of_64": (_set({"num_experts": 16}), None),
    "4_experts_held": (_set({"num_experts": 4}),
                       "4 experts held of 64"),
    "12_experts_held_of_128": (
        _set({"num_experts": 12}, {"num_experts": 128}),
        "12 experts held of 128"),
    "share_without_deployment": (_without("deployment"),
                                 "no \"deployment\""),
    "deployment_without_a_count": (
        _set({"deployment": "an expert-parallel job"}),
        "how many chips share a layer"),
    "share_at_depth_3": (_set({"num_hidden_layers": 3}), BEHIND_3),
    "share_at_depth_5_behind_2_dense": (
        _set({"num_hidden_layers": 5, "first_k_dense_replace": 2},
             {"first_k_dense_replace": 2}), BEHIND_3),
    "share_at_depth_6_behind_2_dense": (
        _set({"num_hidden_layers": 6, "first_k_dense_replace": 2},
             {"first_k_dense_replace": 2}), None),
    # only the vocabulary is sliced, at depth 3: the share's floors bind
    # whichever part of the share is cut
    "vocabulary_alone_at_depth_3": (_uncut(["num_experts"], 3), BEHIND_3),
    # the leading dense layers under the other names sources give them
    "share_at_depth_5_behind_num_dense_layers_1": (
        _set({"num_hidden_layers": 5, "num_dense_layers": 1},
             {"num_dense_layers": 1}), None),
    "share_at_depth_4_behind_num_dense_layers_1": (
        _set({"num_dense_layers": 1}, {"num_dense_layers": 1}), BEHIND_3),
    # a count of layers is depth, no width: `reduced` takes it
    "num_dense_layers_cut_with_the_depth": (
        _cut("num_dense_layers", 2, 1, depth=5), None),
    "two_dense_ffns_then_four_sparse": (
        _cut("mlp_layer_types", FFNS, FFNS[:6], depth=6), None),
    "two_dense_ffns_then_three_sparse": (
        _cut("mlp_layer_types", FFNS, FFNS[:5], depth=5), BEHIND_3),
    # a list with one entry a layer is cut with the depth, to a
    # contiguous run of the published list
    "layer_types_entries_1_to_5": (
        _cut("layer_types", KINDS, KINDS[1:6], depth=5), None),
    "layer_types_reordered": (
        _cut("layer_types", KINDS, sorted(KINDS[1:6]), depth=5),
        "'layer_types' is no contiguous run of the published list"),
    "layer_types_of_the_wrong_length": (
        _cut("layer_types", KINDS, KINDS[1:6]),
        "'layer_types' has 5 entries at a depth of 4"),
    "layer_types_left_whole_at_a_cut_depth": (
        _set({"layer_types": KINDS}, {"layer_types": KINDS}),
        "'layer_types' is left whole, 16 entries, at a depth of 4"),
    # the published counts of the share, and only where a share is cut
    "whole_missing": (_without("whole"), "no \"whole\": the file states"),
    "whole_not_the_sources": (
        _set({"whole": {"num_experts": 32, "vocab_size": 4096}}),
        "\"whole\" gives 'num_experts' as 32, the source has 64"),
    "whole_with_a_key_too_many": (
        _set({"whole": {"num_experts": 64, "vocab_size": 4096,
                        "num_hidden_layers": 16}}),
        "names exactly the keys of `reduced` that cut a share"),
    "depth_alone_at_depth_3": (_depth_alone(3), None),
    "depth_alone_with_whole": (
        _then(_depth_alone(3), _set({"whole": {}})),
        "\"whole\" {} and no share cut"),
}


def _edited_share_tree(root, edit):
    """The rehearsed tree under `root` with the share-cut toy's entry,
    file and published values edited; returns the spec's path."""
    path = added_tree(root)
    s = _load(path)
    entry = {c["name"]: c for c in s["configs"]}[SHARE_CONFIG]
    files = [root / entry["file"],
             root / "tests" / "benchmark" / "published"
             / (SHARE_CONFIG + ".json")]
    config, published = (_load(f) for f in files)
    if edit is not None:
        edit(entry, config, published["config"])
    for file, value in zip(files + [path], (config, published, s)):
        with open(file, "w") as f:
            json.dump(value, f)
    return path


@pytest.mark.parametrize("case", SHARE_CASES)
def test_a_share_keeps_the_floors_and_a_width_is_never_cut(case, tmp_path):
    """Each rule on `reduced` with a configuration it refuses and one it
    takes, on the rehearsed tree with the share-cut toy edited; what is
    expected comes from the toy's own published/<name>.json."""
    edit, refusal = SHARE_CASES[case]
    path = _edited_share_tree(tmp_path, edit)
    if refusal is None:
        file_contract(path)
        configuration_contract(path, SHARE_CONFIG)
        return
    with pytest.raises(AssertionError, match=refusal):
        file_contract(path)
        configuration_contract(path, SHARE_CONFIG)


@pytest.mark.parametrize("tree", ["BENCHMARK.json", "candidates.json",
                                  "rehearsed"])
def test_a_cut_of_depth_alone_is_bound_by_no_floor_of_the_share(tree, added):
    """One layer, two layers, eight: the configurations that cut depth
    alone pass as they are, in the real file, the candidates and the
    rehearsed tree (whose other two cut nothing, and a share)."""
    depths = depth_alone_contract({"BENCHMARK.json": BENCHMARK,
                                   "candidates.json": CANDIDATES,
                                   "rehearsed": added}[tree])
    assert depths, "no configuration here cuts depth alone"
    assert not {ADDED_CONFIG, SHARE_CONFIG} & set(depths)


@pytest.mark.parametrize("broken, refusal", [
    (None, None), (_set({"hidden_size": 96}), "'hidden_size' is 96")],
    ids=["kept", "a_width_drifts"])
def test_a_cut_of_depth_alone_is_still_held_to_its_source(
        broken, refusal, tmp_path):
    """The toy as a cut of depth alone, under the share's floor of four:
    taken as it is, refused where it breaks configuration_contract."""
    path = _edited_share_tree(tmp_path, _then(
        _depth_alone(3), *([] if broken is None else [broken])))
    if refusal is None:
        assert depth_alone_contract(path)[SHARE_CONFIG] == 3 < LEAST_LAYERS
        return
    with pytest.raises(AssertionError, match=refusal):
        depth_alone_contract(path)


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
    line = rehearse(ADDED_CELL, 1, 3, spec=added, seed=2500000001)
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


# --- the share-cut configuration through the unchanged harness --------------

@pytest.fixture(scope="module")
def share_rehearsed(added):
    """The share-cut toy's cell through run.py on the CPU, untraced and
    then traced (each run empties the cell's directory, so what is read
    from it below is the traced run's): (untraced line, traced line, the
    child's result, the ids of the corpus on disk).

    To the program as it stands a toy with 8 experts is only a smaller
    model: 8 experts behind a router 8 wide, a vocabulary of 512. The
    layer that holds 8 experts of a router 64 wide, and computes its own
    experts' part of the result, is the configuration PR's, with its own
    test that the shares add up to the uncut reference. What is rehearsed
    here is that the harness carries such a file (`reduced` depth,
    experts held and vocabulary, a `deployment`, a mix that reserves ids)
    from BENCHMARK.json to a result line unedited."""
    from megatron_tpu.data.indexed_dataset import make_dataset

    untraced, traced = (rehearse(SHARE_CELL, trace, 2, spec=added,
                                 seed=2500000003) for trace in (0, 1))
    run_dir = os.path.join(REPO, "runs", "benchmark", SHARE_CELL)
    corpus = make_dataset(os.path.join(run_dir, "corpus"))
    ids = set()
    for i in range(len(corpus)):
        ids.update(corpus[i].tolist())
    return untraced, traced, _load(os.path.join(run_dir, "result.json")), ids


def test_rehearsed_share_is_correct_and_reports_every_metric(
        added, share_rehearsed):
    untraced, traced, result, _ = share_rehearsed  # correct, none failed
    cell = spec.Cell(added, SHARE_CELL)
    assert set(untraced["metrics"]) == {
        m["name"] for m in cell.end_to_end()} == {"train_tokens_per_s",
                                                  "setup_s"}
    # asked of it: every per-layer metric a one-chip training cell
    # reports, the expert block's among them, and the one of its own
    asked = {m["name"] for m in cell.per_layer()}
    own = SHARE_METRIC["name"]
    accepted = {
        m["name"] for name in _names(BENCHMARK, "workloads")
        if spec.Cell(BENCHMARK, name).chips == 1
        and name not in SERVED
        for m in spec.Cell(BENCHMARK, name).per_layer()}
    assert asked == accepted | {own} and own not in accepted
    assert {"moe_experts_roofline_pct", "flash_bwd_roofline_pct",
            "grad_accumulate_ms_per_step"} <= asked
    # no other cell is asked for it, the other rehearsed one neither
    assert [name for name in _names(added, "workloads") if own in {
        m["name"] for m in spec.Cell(added, name).per_layer()}] == [
            SHARE_CELL]
    # in the line: what the program's spans, counters and journal give
    # on any backend (the field of the `step` records that a model with
    # experts journals, read by the accepted reader and by the cell's
    # own); the device readers have no device plane to read on a CPU,
    # say nothing, and none raises
    assert set(traced["metrics"]) == {
        "train_step_ms_p50", "train_data_wait_pct", "train_host_ms_per_step",
        "step_hbm_gb", "step_temp_hbm_gb", "moe_load_max_over_mean", own}
    assert traced["metrics"][own]["unit"] == SHARE_METRIC["unit"]
    assert 1.0 <= traced["metrics"]["moe_load_max_over_mean"]["value"] <= (
        traced["metrics"][own]["value"]) <= 8
    # the reference is the existing MoE block's, given the sliced sizes
    assert result["steps"][0]["ntokens"] == 2 * 128
    assert abs(result["steps"][0]["loss"]
               - result["reference_first_loss"]) < 0.02


def test_rehearsed_share_draws_every_id_from_the_slice(
        added, share_rehearsed):
    _, _, result, corpus_ids = share_rehearsed
    cell = spec.Cell(added, SHARE_CELL)
    vocabulary = cell.config["vocab_size"]
    assert vocabulary == 512 == _load(_published_file(
        added, SHARE_CONFIG))["config"]["vocab_size"] // 8
    # the corpus: ids of the cycle and the end-of-document id, all under
    # the sliced vocabulary; the ids the mix reserves never occur
    eod, reserved = vocabulary - 1, cell.traffic["corpus"]["reserved_ids"]
    assert eod in corpus_ids and max(corpus_ids) == eod
    assert min(corpus_ids) >= 0
    assert reserved == 2 and not corpus_ids & {eod - 1, eod - 2}
    assert len(corpus_ids) == cell.traffic["corpus"]["cycle"] + 1
    # the first global batch, tokens and labels, as the trainer drew it
    low, high = result["first_batch_ids"]
    assert 0 <= low <= high < vocabulary
