"""Find a cell's files by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; per-layer metrics name a
reader; a reader that wants a kernel's roofline names the kernel. All are
files that the harness finds by name under the directories of `paths`, so
a later PR adds a cell, a configuration, a metric or a kernel as new files
plus new entries and edits nothing that exists:

    configuration  `file` of its entry in `configs`
    architecture   <path>/reference/<name>.py, where <name> is the
                   configuration's "reference": the plain reference of one
                   block type, its map from the program's weights (a
                   served cell: its own seeded weights and their map to
                   the program's names) and its translation into the
                   program's flags (the harness itself knows no
                   architecture)
    traffic mix    <path>/traffic/<traffic>.json for a <path> in `paths`
    reader         <path>/layer_metrics/<reader>.py, where <reader> is the
                   metric's name up to its first "." (so `device_idle_pct.
                   train` and `device_idle_pct.serve` share one reader and
                   move different end-to-end metrics)
    kernel's cost  <path>/kernel_costs/<kernel>.py, where <kernel> is the
                   `name=` of the program's `pallas_call`: what one call
                   needs to do, for its share of the roofline

What a PR that adds a configuration brings, all of it new:

    1. <path>/configs/<name>.json: the source's config.json with the cut
       keys changed, plus the harness's own keys: "source", "reference",
       "reduced" {key: why}, "assumed" {what the source does not state:
       the value taken}, "deployment" (what the cut stands for), "whole"
       (only where a share is cut, see below), "program" {"flags": [...]};
    2. <path>/reference/<reference>.py, unless an existing one holds the
       block type: `program_flags(config, seq_length)`,
       `from_program_params(params)`, `lm_loss(weights, tokens, labels,
       mask, config)`, for a served cell `make_weights(config, seed)`,
       `to_program_params(weights)` and `logits(weights, tokens, config)`
       (on a cell of BENCHMARK.json also `logits(..., lowp=)` and `fp8`,
       the control of tools/serve_control.py), and optionally
       `train_flops_per_token`;
    3. tests/benchmark/published/<name>.json: {"source": the URL,
       "config": the source's own value of every key the configuration
       file takes from it}, which the contract test holds the
       configuration to outside `reduced`;
    4. <path>/traffic/<mix>.json for each new mix ("driver": "train",
       "serve_open" or "serve_closed", and that driver's parameters; a
       served mix's "check": how many of the window's requests the
       reference scores and the limit of `logit_gap`, with the readings
       it was set from);
    5. in BENCHMARK.json: the `configs` entry, a `workloads` entry a
       cell, the cell's name APPENDED to the `workloads` list of every
       metric it reports (any metric's: the tests that hold PR 23's
       twelve, PR 34's nine and PR 54's five hold the cells they listed
       in front, in their order, and take a name behind them), and its
       own per-layer entries after those that are there, each with its
       reader <path>/layer_metrics/<reader>.py. A served open-loop cell
       appends its name to the metrics every served open-loop cell
       lists (`request_ms_p50`, `request_ms_p95`, `engine_tpot_ms_p50`,
       `gen_lateness_ms_max` and PR 54's five: `engine_host_ms_per_tick`,
       `engine_rows_per_tick`, `engine_queue_ms_p95`,
       `server_overhead_ms_p50`, `engine_stall_ms_total`), and
       `engine_ttft_ms_p50.<mix>` and `device_idle_pct.<mix>` are entries
       of its own (each says which request metric it moves in that mix;
       `<mix>` is the cell's `traffic` name, and the contract test's
       `serving_cells_contract` refuses a cell of another mix on them),
       as are the readers of what only its architecture has.

What `reduced` may cut (the `model-configs` guide, section 4; the
contract test holds a configuration to it from its own published/
<name>.json): the depth, and one chip's share of a layer in a stated
deployment: the routed experts held (whichever of `num_experts`,
`n_routed_experts`, `num_local_experts` the source has) and `vocab_size`,
the slice of the vocabulary. Never a width: no other key ending in
`_dim`, `_rank` or `_size`, and not the experts a token.

With the depth go the source's other counts of layers, which are depth
and no width. The leading dense layers are read from the first the file
has of `first_k_dense_replace`, `num_dense_layers`, else the leading run
of "dense" in `mlp_layer_types`, else none; a count of them may be cut
and listed (2 -> 1). A list with one entry a layer (a value of the source
that is a list as long as its `num_hidden_layers`: `layer_types`,
`mlp_layer_types`, `indexer_types`) is cut with the depth: it is listed
in `reduced`, is as long as the depth held, and is a contiguous run of
the published list (entries i to i + depth - 1), so the kinds of layer
keep their published order and, over whole periods, their ratio; left
whole at a cut depth, of another length or reordered it is refused.

The floors of a share: at least 8 experts held and a number that divides
the published count; at least an eighth of the published vocabulary,
rounded up; and, where either is cut, at least four layers behind the
leading dense ones, a "deployment" that says over how many chips a layer
is divided ("8 chips share each layer: ...") and "whole": {key: the
source's value} for exactly the keys of `reduced` that cut a share (the
experts' count, `vocab_size`), each equal to published/<name>.json's. A
cut of depth alone is bound by none of them and states no "whole".
"whole" is where the configuration's `program_flags(config, seq)` reads
the router's published width and the whole vocabulary's size, beside the
counts held under the source's own keys: the file is all it is given.
(The rehearsed toy's reference, `olmoe`, does not read it: to the program
as it stands that toy is a model with 8 experts behind a router 8 wide.)
A sliced vocabulary is a smaller vocabulary: the corpus draws every id
under the file's `vocab_size` (its last id ends a document), and a mix
whose "corpus" gives "reserved_ids": n keeps the n ids under that one out
of the corpus, for the configuration's own tokens (a mask token). The
expert layer that is told which experts of a wider router it holds
belongs to the PR that adds the configuration, with its test that the
shares add up to the uncut reference.

A reader of a scope the new block adds is a file of its own,
`def read(run): return named.scope_ms(run, "router")` (harness/trace/
named.py: any `jax.named_scope`, any `pallas_call(name=)`), with its
`per_layer` entry; a kernel's roofline share wants the kernel's cost file
beside it. What the rehearsal demands of a new reader: tests/benchmark/
test_benchmark_contract.py appends its two toys' cells to every metric a
one-chip training cell lists, and holds their CPU lines to exact sets
(`test_rehearsed_cell_runs_traced_through_the_unchanged_harness`,
`test_rehearsed_share_is_correct_and_reports_every_metric`). So a reader
gives None wherever its scope, kernel or journal field does not occur (a
dense toy, a CPU, a parent commit), and a counter the trainer journals is
journalled only by a model that has the mechanism (as
`moe_load_max_over_mean` is: a step record of a model without experts
lacks the field).

A cell lists only the rooflines whose cost files count what ITS kernels
do: `flash_*_roofline_pct` count one causal band over S positions, so a
step under another mask (block-causal, block-diagonal, a doubled
sequence) would read too high there. kernel_costs/moe_experts.py reads
`intermediate_size` as ONE expert's width and `num_experts` as the
experts whose weights move, and its reader counts micro-batch x sequence
x k rows. So a source that gives the expert's width under
`moe_intermediate_size`, or a share (whose rows are about held / whole of
that, by the routing), brings a cost file and a reader of its own and
leaves `moe_experts_roofline_pct` out of its cell's lists: it would read
several times too high, and the driver refuses a share of a roofline
over 105 %. Such a kernel comes under a `pallas_call(name=)` of its own,
with kernel_costs/<that name>.py and readers that name it.
tests/benchmark/test_benchmark_contract.py rehearses exactly such PRs on
a copy of the tree (`added_tree()`: another block type; one chip's share
with a metric of its own; and, since PR 60, a served open-loop cell,
`serve_toyfalcon_rehearsed` under the mix `added_serve_open`, its name
behind the accepted served cells' and its two entries behind every
other), and states every fact it states of every configuration, cell or
metric of BENCHMARK.json of that copy too: the served cells' load,
deployment and plan among them. A served cell added behind another RUNS,
untraced and traced, in tests/benchmark/test_benchmark_engine_spans.py.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

HARNESS_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(HARNESS_ROOT)


class SpecError(ValueError):
    pass


def load_module(path: str):
    """A reader or a reference, loaded from its file: it need not be
    importable by name, so a later PR's directory works like this one."""
    stem = os.path.splitext(os.path.basename(path))[0]
    parent = os.path.basename(os.path.dirname(path))
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{parent}_{stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of `workloads`, with its files loaded."""

    def __init__(self, spec_path: str, name: str):
        self.spec_path = os.path.abspath(spec_path)
        self.root = os.path.dirname(self.spec_path)
        with open(self.spec_path) as f:
            self.spec = json.load(f)
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r} in {spec_path} "
                            f"(has {sorted(cells)})")
        self.workload = cells[name]
        self.name = name
        self.chips = int(self.workload["chips"])
        configs = {c["name"]: c for c in self.spec["configs"]}
        entry = configs.get(self.workload["config"])
        if entry is None:
            raise SpecError(f"workload {name!r} names config "
                            f"{self.workload['config']!r}, not in `configs`")
        self.config_name = entry["name"]
        with open(os.path.join(self.root, entry["file"])) as f:
            self.config: Dict[str, Any] = json.load(f)
        self.traffic_name = self.workload["traffic"]
        self.traffic: Dict[str, Any] = self._load_traffic()

    def _search(self, *parts: str) -> Optional[str]:
        for base in self.spec["paths"]:
            path = os.path.normpath(os.path.join(self.root, base, *parts))
            if os.path.exists(path):
                return path
        # readers that ship with the harness serve a spec kept elsewhere
        path = os.path.join(HARNESS_ROOT, *parts)
        return path if os.path.exists(path) else None

    def _load_traffic(self) -> Dict[str, Any]:
        path = self._search("traffic", self.traffic_name + ".json")
        if path is None:
            raise SpecError(f"no traffic file {self.traffic_name}.json "
                            f"under {self.spec['paths']}")
        with open(path) as f:
            return json.load(f)

    def _reported_here(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def end_to_end(self) -> List[dict]:
        return [m for m in self.spec["end_to_end"]
                if self._reported_here(m)]

    def per_layer(self) -> List[dict]:
        """Per-layer metrics of this cell: those listed for it whose
        `moves` is an end-to-end metric the cell reports."""
        here = {m["name"] for m in self.end_to_end()}
        return [m for m in self.spec["per_layer"]
                if self._reported_here(m) and m["moves"] in here]

    def reader(self, metric_name: str) -> Callable[[Any], Optional[float]]:
        stem = metric_name.split(".", 1)[0]
        path = self._search("layer_metrics", stem + ".py")
        if path is None:
            raise SpecError(f"no reader layer_metrics/{stem}.py for "
                            f"per-layer metric {metric_name!r}")
        return load_module(path).read

    def kernel_cost(self, kernel: str) -> Optional[Callable]:
        """`needed(dims, itemsize, config)` of kernel_costs/<kernel>.py,
        or None where no directory of `paths` holds that file."""
        path = self._search("kernel_costs", kernel + ".py")
        return None if path is None else load_module(path).needed

    def reference_path(self) -> str:
        """The file that holds this configuration's architecture. The
        children that hold the chip load it (it imports JAX; this
        process does not)."""
        name = self.config.get("reference")
        if not name:
            raise SpecError(
                f"configuration {self.config_name!r} names no "
                "\"reference\": the file under reference/ that holds its "
                "architecture")
        path = self._search("reference", name + ".py")
        if path is None:
            raise SpecError(f"no reference/{name}.py under "
                            f"{self.spec['paths']} for configuration "
                            f"{self.config_name!r}")
        return path
