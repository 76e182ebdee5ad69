"""The readers that find the program's own names in a traced run
(benchmark/harness/trace/names.py, named.py, kernel_cost.py, the files of
benchmark/kernel_costs/ and the twelve readers of benchmark/layer_metrics/
that use them): on made-up events, on the named TPU recordings of
benchmark/fixtures/ (cut from PR 23's first traced chip run of each cell
by benchmark/tools/cut_named_trace.py), where every number is held EQUAL
to what PR 24's tree read, and end to end in a CPU rehearsal of a toy cell
with a spec of its own."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import common, peaks, spec  # noqa: E402
from benchmark.harness.trace import (  # noqa: E402
    kernel_cost, named, reduce, xplane,
)
from test_benchmark_contract import added_tree  # noqa: E402

BENCHMARK = os.path.join(REPO, "BENCHMARK.json")
FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
NAMED_SPEC = os.path.join(REPO, "tests", "benchmark", "named", "spec.json")
NEW = ["attention_ms_per_step", "mlp_ms_per_step", "head_loss_ms_per_step",
       "optimizer_ms_per_step", "other_ms_per_step", "recompute_ms_per_step",
       "flash_fwd_ms_per_step", "flash_bwd_ms_per_step",
       "flash_fwd_roofline_pct", "flash_bwd_roofline_pct",
       "train_host_ms_per_step", "step_temp_hbm_gb"]
V5E = peaks.peaks_for("TPU v5 lite")


# --- names ------------------------------------------------------------------

@pytest.mark.parametrize("tf_op, region, kernel", [
    ("jit(train_step)/while/body/closed_call/transpose(jvp(attention))/"
     "flash_bwd_dq/pallas_call:", "attention", "flash_bwd_dq"),
    ("jit(train_step)/while/body/closed_call/jvp()/while/body/closed_call/"
     "attention/shard_map/flash_fwd/flash_fwd/pallas_call:", "attention",
     "flash_fwd"),
    # any `pallas_call(name=)`, under any scope: no list of kernels
    ("jit(train_step)/jvp(mlp)/experts/grouped_matmul/pallas_call:", "mlp",
     "grouped_matmul"),
    ("jit(train_step)/jvp(head_loss)/bsh,hv->bsv/dot_general:",
     "head_loss", None),
    ("jit(train_step)/optimizer/convert_element_type:", "optimizer", None),
    # under two scopes the inner one names the work
    ("jit(train_step)/optimizer/jvp(mlp)/mul:", "mlp", None),
    ("jit(train_step)/mlp/optimizer/mul:", "optimizer", None),
    # a name inside another word is no scope
    ("jit(train_step)/mlp_out/embedding/attention_mask/add:", "other", None),
    ("jit(train_step)/while/body/closed_call/transpose(jvp())/while:",
     "other", None),
    ("", "other", None),
])
def test_an_operation_goes_to_its_innermost_scope(tf_op, region, kernel):
    parts = named.tokens(tf_op)
    assert named.region_of(parts) == region
    assert named.kernel_of(parts) == kernel


def test_wrappers_of_differentiation_are_stripped_from_every_part():
    assert named.tokens("jit(f)/transpose(jvp(attention))/a,b->c/mul:") == [
        "f", "attention", "a,b->c", "mul"]
    assert named.tokens("vmap(transpose(jvp(embed)))") == ["embed"]


# --- the partition, on made-up events ---------------------------------------

KERNEL_TEXT = ('%flash_fwd.1 = (bf16[1,32,4096,128]{3,2,1,0}, '
               'f32[1,32,4096,128]{3,2,1,0}) custom-call(bf16[1] %a), '
               'custom_call_target="tpu_custom_call"')


BWD_TEXT = {
    "flash_bwd_dq": '%flash_bwd_dq.1 = bf16[1,32,4096,128]{3,2,1,0} '
                    'custom-call(bf16[1] %a), '
                    'custom_call_target="tpu_custom_call"',
    "flash_bwd_dkv": '%flash_bwd_dkv.1 = (bf16[1,32,4096,128]{3,2,1,0}, '
                     'bf16[1,32,4096,128]{3,2,1,0}) custom-call(bf16[1] %a),'
                     ' custom_call_target="tpu_custom_call"'}
FUSED_BWD_TEXT = ('%flash_bwd.1 = (bf16[1,32,4096,128]{3,2,1,0}, '
                  'bf16[1,32,4096,128]{3,2,1,0}, bf16[1,32,4096,128]'
                  '{3,2,1,0:T(8,128)(2,1)}) custom-call(bf16[1] %a), '
                  'custom_call_target="tpu_custom_call"')


def _made_up_plane():
    """Four runs of one program, 1000 ps each with a gap between: the
    first and the last are cut by the trace's edges and left out. Each
    whole run holds a `while` (unnamed) around an attention fusion and a
    kernel, an MLP fusion that is recomputed, an optimizer op, and 50 ps
    of nothing."""
    tf = {"%while.1 = while()": "jit(step)/while:",
          "%fusion.1 = bf16[8] fusion()":
              "jit(step)/while/body/jvp(attention)/mul:",
          KERNEL_TEXT: "jit(step)/while/body/jvp(attention)/flash_fwd/"
                       "pallas_call:",
          "%fusion.2 = bf16[8] fusion()":
              "jit(step)/transpose(jvp())/checkpoint/rematted_computation/"
              "mlp/dot_general:",
          "%fusion.3 = f32[8] fusion()": "jit(step)/optimizer/add:",
          "%copy.1 = bf16[8] copy()": ""}
    ops, modules = [], []
    ev = lambda name, at, dur: xplane.Event(name, at, dur, {})  # noqa: E731
    for run in range(4):
        t0 = 10_000 + run * 1100
        modules.append(ev("jit_step(7)", t0, 1000))
        ops += [ev("%while.1 = while()", t0, 500),
                ev("%fusion.1 = bf16[8] fusion()", t0 + 100, 100),
                ev(KERNEL_TEXT, t0 + 250, 200),
                ev("%fusion.2 = bf16[8] fusion()", t0 + 500, 250),
                ev("%fusion.3 = f32[8] fusion()", t0 + 750, 150),
                ev("%copy.1 = bf16[8] copy()", t0 + 950, 50)]
    # another, shorter program between the runs is no run of the step
    modules.append(ev("jit_convert(9)", 10_000 + 1000, 50))
    plane = xplane.Plane("/device:TPU:0", [
        xplane.Line(reduce.MODULE_LINE, modules),
        xplane.Line(reduce.OP_LINE, ops)], {})
    return plane, tf


def test_the_partition_closes_exactly_and_cut_runs_are_left_out():
    plane, tf = _made_up_plane()
    got = named.reduce_device(plane, tf)
    assert got["runs"] == 2 and got["named"] is True
    # per whole run: while 500 - 100 - 200 of children = 200 unnamed, the
    # copy 50 unnamed; attention 100 + the kernel's 200; mlp 250; 150
    assert got["regions"] == {"optimizer": 300, "head_loss": 0,
                              "attention": 600, "mlp": 500, "embed": 0,
                              "other": 500}
    busy_inside = 2 * (500 + 250 + 150 + 50)
    assert sum(got["regions"].values()) == busy_inside
    # every part of a name stack is a scope, whoever put it there: the
    # program (`attention`), JAX (`rematted_computation`, `while`)
    assert got["scopes"]["rematted_computation"] == 500
    assert got["scopes"]["attention"] == got["scopes"]["body"] == 600
    assert got["scopes"]["flash_fwd"] == 400
    assert got["scopes"]["while"] == 400 + 600   # the loop and its body
    assert got["scopes"]["step"] == busy_inside - 100   # all but the copy
    assert got["kernels"] == {"flash_fwd": {"ps": 400,
                                            "calls": {KERNEL_TEXT: 2}}}
    # the same events without their names are a program without scopes
    bare = named.reduce_device(plane, {})
    assert bare["named"] is False
    assert bare["regions"]["other"] == busy_inside


def test_an_operation_across_a_runs_edge_counts_only_its_part_inside():
    runs = [(100, 200), (300, 400)]
    starts = [100, 300]
    assert named._inside([(120, 180)], runs, starts) == 60
    assert named._inside([(50, 150)], runs, starts) == 50
    assert named._inside([(150, 350)], runs, starts) == 100
    assert named._inside([(210, 290)], runs, starts) == 0
    assert named._inside([(110, 120), (390, 450)], runs, starts) == 20
    assert named._inside([], runs, starts) == 0


# --- what the kernels need --------------------------------------------------

def _needed(kernel, dims, itemsize, window):
    """One call's needed work by benchmark/kernel_costs/<kernel>.py, as a
    cell of BENCHMARK.json finds it; the window rides in the cell's whole
    configuration."""
    cell = spec.Cell(BENCHMARK, "train_mistral7b_seq4k")
    return cell.kernel_cost(kernel)(dims, itemsize,
                                    dict(cell.config, sliding_window=window))


def test_kernel_costs_equal_hand_numbers():
    dims = (1, 32, 4096, 128)
    flops, nbytes = _needed("flash_fwd", dims, 2, window=4096)
    # 4*B*H*D * S(S+1)/2 = 16384 * 8,390,656
    assert flops == pytest.approx(137.47e9, rel=1e-3)
    assert flops == 4 * 32 * 128 * 4096 * 4097 // 2
    assert nbytes == 4 * 32 * 4096 * 128 * 2
    assert _needed("flash_fwd", dims, 2, None) == (flops, nbytes)
    # a window of S/4: each query past the first 1024 sees 1024 keys
    clipped, _ = _needed("flash_fwd", dims, 2, window=1024)
    pairs = 1024 * 1025 // 2 + 3072 * 1024
    assert kernel_cost.causal_pairs(4096, 1024) == pairs
    assert clipped == 4 * 32 * 128 * pairs
    assert flops - clipped == 4 * 32 * 128 * (3072 * 3073 // 2)
    # the backward needs five matmuls of the seven its kernels run, and
    # moves eight tensors
    dq, dq_bytes = _needed("flash_bwd_dq", dims, 2, 4096)
    dkv, dkv_bytes = _needed("flash_bwd_dkv", dims, 2, 4096)
    assert dq + dkv == pytest.approx(2.5 * flops)
    assert dq_bytes + dkv_bytes == 2 * nbytes
    # one fused call is booked exactly what the pair is: five, eight
    assert _needed("flash_bwd", dims, 2, 4096) == (dq + dkv,
                                                   dq_bytes + dkv_bytes)
    assert _needed("flash_bwd", dims, 2, 1024) == tuple(
        a + b for a, b in zip(_needed("flash_bwd_dq", dims, 2, 1024),
                              _needed("flash_bwd_dkv", dims, 2, 1024)))
    # a result of another rank is not a shape these files know
    assert _needed("flash_fwd", (32, 4096, 128), 2, 4096) is None
    # a kernel nobody wrote a cost for has none: no roofline, no default
    assert spec.Cell(BENCHMARK, "train_mistral7b_seq4k").kernel_cost(
        "paged_flash_decode") is None


def test_shapes_are_read_from_the_events_own_text():
    assert kernel_cost.result_shape(KERNEL_TEXT) == (
        "bf16", (1, 32, 4096, 128))
    assert kernel_cost.result_shape(
        "%flash_bwd_dq.1 = bf16[8,16,4096,128]{3,2,1,0:T(8,128)(2,1)} "
        "custom-call(s32[1]{0} %c)") == ("bf16", (8, 16, 4096, 128))
    # of several results the first: a fused backward's (dq, dk, dv)
    assert kernel_cost.result_shape(FUSED_BWD_TEXT) == (
        "bf16", (1, 32, 4096, 128))
    assert kernel_cost.result_shape("no hlo text") is None


def _costs(config_window=4096):
    cell = spec.Cell(BENCHMARK, "train_mistral7b_seq4k")
    return cell.kernel_cost, dict(cell.config, sliding_window=config_window)


def test_roofline_share_names_the_bound_that_applies():
    calls = {"flash_fwd": {"s": 0.020, "calls": {KERNEL_TEXT: 4.0}}}
    needed_of, config = _costs()
    roof = kernel_cost.roofline(calls, ("flash_fwd",), needed_of, config, V5E)
    # 4 calls of 137.47 GFLOP at 197 TFLOP/s are 2.79 ms of the 20
    assert roof["bound"] == "compute"
    assert roof["needed_ms"] == pytest.approx(2.791, rel=1e-3)
    assert roof["pct"] == pytest.approx(13.96, rel=1e-3)
    # at a hundredth of the FLOP/s peak... of the bandwidth, memory binds
    slow_memory = dict(V5E, hbm_bytes_per_s=V5E["hbm_bytes_per_s"] / 100)
    assert kernel_cost.roofline(calls, ("flash_fwd",), needed_of, config,
                                slow_memory)["bound"] == "memory"
    assert kernel_cost.roofline({}, ("flash_fwd",), needed_of, config,
                                V5E) is None
    unreadable = {"flash_fwd": {"s": 0.02, "calls": {"%x = token[]": 1.0}}}
    assert kernel_cost.roofline(unreadable, ("flash_fwd",), needed_of,
                                config, V5E) is None
    # a kernel that ran and has no cost file: no share, not a guess
    assert kernel_cost.roofline(calls, ("flash_fwd",), lambda name: None,
                                config, V5E) is None


def test_a_kernel_of_any_name_gets_its_roofline_from_a_file_of_its_own(
        tmp_path, monkeypatch):
    """What a PR that adds a kernel brings: kernel_costs/<kernel>.py under
    a directory of `paths` and a reader of three lines. Here the kernel is
    a grouped matmul under `mlp/experts`, the spec a copy of the named toy
    spec with this directory first in its `paths`."""
    (tmp_path / "kernel_costs").mkdir()
    (tmp_path / "kernel_costs" / "grouped_matmul.py").write_text(
        "def needed(dims, itemsize, config):\n"
        "    tokens, width = dims\n"
        "    k = config['num_experts_per_tok']\n"
        "    return 2.0 * tokens * width * config['hidden_size'] * k, "
        "float(tokens * width * itemsize)\n")
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "layer_metrics" / "experts_roofline_pct.py").write_text(
        "from benchmark.harness.trace import named\n\n\n"
        "def read(run):\n"
        "    return named.roofline_pct(run, 'experts', 'grouped_matmul')\n")
    with open(NAMED_SPEC) as f:
        s = json.load(f)
    root = os.path.dirname(NAMED_SPEC)
    s["paths"] = [str(tmp_path)] + [os.path.join(root, p) for p in s["paths"]]
    s["configs"][0]["file"] = os.path.join(root, s["configs"][0]["file"])
    s["per_layer"].append({
        "name": "experts_roofline_pct", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "train_tokens_per_s", "workloads": ["named_train"]})
    (tmp_path / "spec.json").write_text(json.dumps(s))
    cell = spec.Cell(str(tmp_path / "spec.json"), "named_train")
    cell.config["num_experts_per_tok"] = 8
    text = ('%grouped_matmul.3 = bf16[32768,1024]{1,0} custom-call(bf16[8] '
            '%x), custom_call_target="tpu_custom_call"')
    monkeypatch.setattr(named, "per_run", lambda path: {
        "kernels": {"grouped_matmul": {"s": 0.004, "calls": {text: 2.0}}}})
    run = _fake_run(cell, trace={"devices": 1})
    got = cell.reader("experts_roofline_pct")(run)
    flop = 2 * 2.0 * 32768 * 1024 * cell.config["hidden_size"] * 8
    assert got == pytest.approx(100 * flop / 197e12 / 0.004)
    assert run.extras["roofline"]["experts"]["needed_flop"] == flop
    assert named.kernel_ms(run, "grouped_matmul") == 4.0
    assert named.kernel_ms(run, "flash_fwd") is None


@pytest.mark.parametrize("ran", [
    ("flash_bwd",), ("flash_bwd_dq", "flash_bwd_dkv"),
    ("flash_bwd_dq", "flash_bwd_dkv", "flash_bwd")],
    ids=lambda ran: "+".join(ran))
def test_the_backward_readers_take_the_fused_kernels_name(ran, monkeypatch):
    """What refused PR 35: a program whose backward is ONE kernel,
    `flash_bwd`, gave the two backward readers nothing to read. A record
    holding it alone reads a time and a share, booked what the split pair
    is booked over the same result; one holding all three sums them."""
    cell = spec.Cell(BENCHMARK, "train_mistral7b_seq4k")
    texts = dict(BWD_TEXT, flash_bwd=FUSED_BWD_TEXT)
    seconds = {"flash_bwd_dq": 0.0035, "flash_bwd_dkv": 0.0041,
               "flash_bwd": 0.0060}
    # two layers, and beside them a kernel the readers do not name
    kernels = {k: {"s": seconds[k], "calls": {texts[k]: 2.0}} for k in ran}
    kernels["flash_fwd"] = {"s": 0.0036, "calls": {KERNEL_TEXT: 2.0}}
    monkeypatch.setattr(named, "per_run", lambda path: {"kernels": kernels})
    run = _fake_run(cell, trace={"devices": 1})
    ms = cell.reader("flash_bwd_ms_per_step")(run)
    assert ms == pytest.approx(1e3 * sum(seconds[k] for k in ran))
    pct = cell.reader("flash_bwd_roofline_pct")(run)
    roof = run.extras["roofline"]["flash_bwd"]
    assert roof["pct"] == pct and roof["bound"] == "compute"
    assert roof["measured_ms"] == pytest.approx(ms)
    # the pair's booking over [1, 32, 4096, 128], a layer: 5 matmuls, 8
    # tensors; all three ran = the backward ran twice over
    pair = [_needed(k, (1, 32, 4096, 128), 2, 4096)
            for k in ("flash_bwd_dq", "flash_bwd_dkv")]
    times = 2.0 * (2 if len(ran) == 3 else 1)
    assert roof["needed_flop"] == times * sum(w[0] for w in pair)
    assert roof["needed_bytes"] == times * sum(w[1] for w in pair)
    assert pct == pytest.approx(
        100 * roof["needed_flop"] / V5E["bf16_flops_per_s"] / (ms / 1e3))
    # the forward's readers see their own kernel and no other
    assert cell.reader("flash_fwd_ms_per_step")(run) == pytest.approx(3.6)


def test_a_program_without_a_backward_kernel_gives_the_readers_nothing(
        monkeypatch):
    cell = spec.Cell(BENCHMARK, "train_mistral7b_seq4k")
    monkeypatch.setattr(named, "per_run", lambda path: {"kernels": {
        "flash_fwd": {"s": 0.0036, "calls": {KERNEL_TEXT: 2.0}}}})
    run = _fake_run(cell, trace={"devices": 1})
    assert cell.reader("flash_bwd_ms_per_step")(run) is None
    assert cell.reader("flash_bwd_roofline_pct")(run) is None
    assert "flash_bwd" not in run.extras.get("roofline", {})


# --- the recordings ---------------------------------------------------------

def _busy_inside_whole_runs(path, which=lambda ev: True):
    """Per device, the union of the intervals of the operations `which`
    picks inside the whole runs, by the benchmark's own interval
    arithmetic and no name at all."""
    out = {}
    for index, plane in reduce.device_planes(path).items():
        runs = reduce.merge(
            (m.start_ps, m.end_ps) for m in reduce.whole_runs(
                reduce._line(plane, reduce.MODULE_LINE)))
        busy = reduce.merge((ev.start_ps, ev.end_ps)
                            for ev in reduce._line(plane, reduce.OP_LINE)
                            if which(ev))
        out[index] = reduce.total(busy) - reduce.total(
            reduce.subtract(busy, runs))
    return out


@pytest.mark.parametrize("name, devices, other_under", [
    ("named_seq4k_tpu_v5e.xplane.pb", 1, 0.05),
    # 5.4 % on four chips (PERF.md section 5): the layer scan's stacking
    # of saved activations and the ZeRO gathers GSPMD adds without a name
    ("named_tp2dp2_tpu_v5e.xplane.pb", 2, 0.06),
])
def test_readers_on_a_named_recording(name, devices, other_under):
    path = os.path.join(FIXTURES, name)
    got = named.per_run(path)
    assert got["devices"] == devices and got["runs"] == 2
    # the partition closes: to the picosecond on every device
    raw = named.read(path)["devices"]
    busy = _busy_inside_whole_runs(path)
    assert len(raw) == devices
    for index, ps in busy.items():
        d = raw[f"/device:TPU:{index}"]
        assert sum(d["regions"].values()) == ps > 0
    whole = sum(got["regions"].values())
    assert whole == pytest.approx(
        sum(busy.values()) / devices / 2 * reduce.PS)
    assert all(got["regions"][r] > 0 for r in named.REGIONS)
    other = got["regions"]["other"] + got["regions"]["embed"]
    assert 0 < other < other_under * whole
    assert 0 < got["scopes"]["rematted_computation"] < whole
    # a scope at any depth: `attention` is never inside another region,
    # so its scope is its region; the kernels' scopes hold the kernels
    assert got["scopes"]["attention"] == got["regions"]["attention"]
    assert got["scopes"]["flash_fwd"] >= got["kernels"]["flash_fwd"]["s"]
    # (GSPMD's own collectives carry no name stack at all: 1.6 % of the
    # four-chip step)
    assert 0.98 * whole < got["scopes"]["train_step"] <= whole
    # kernels found by name: all of the custom calls' time, none beside
    assert set(got["kernels"]) == {"flash_fwd", "flash_bwd_dq",
                                   "flash_bwd_dkv"}
    by_name = sum(k["s"] for k in got["kernels"].values())
    custom_calls = _busy_inside_whole_runs(path, reduce.is_kernel)
    assert by_name == pytest.approx(
        sum(custom_calls.values()) / devices / 2 * reduce.PS, rel=1e-9)
    # two layers: the forward and its recomputation, one backward pair
    assert sum(got["kernels"]["flash_fwd"]["calls"].values()) == 4.0
    assert sum(got["kernels"]["flash_bwd_dq"]["calls"].values()) == 2.0
    fwd = kernel_cost.roofline(got["kernels"], ("flash_fwd",), *_costs(),
                               V5E)
    bwd = kernel_cost.roofline(got["kernels"],
                               ("flash_bwd_dq", "flash_bwd_dkv"), *_costs(),
                               V5E)
    assert fwd["bound"] == bwd["bound"] == "compute"
    assert 10 < fwd["pct"] < 15 < bwd["pct"] < 18
    # the loop's passes, on the same clock: nearly all of a pass is the
    # wait for the device
    passes = named.read(path)["passes"]
    assert len(passes) >= 2
    assert [p["step_num"] for p in passes] == list(range(
        passes[0]["step_num"], passes[0]["step_num"] + len(passes)))
    for p in passes:
        host_ms = (p["pass_ps"] - p["fetch_ps"] - p["data_ps"]) * 1e-9
        assert 0 < host_ms < 10 and p["fetch_ps"] > 0.9 * p["pass_ps"]


def test_a_recording_without_the_names_gives_nothing_and_raises_nothing():
    # PR 22's recording of the same cell: the parent's program
    old = os.path.join(FIXTURES, "train_tp2dp2_tpu_v5e.xplane.pb")
    assert named.read(old)["devices"] and named.per_run(old) is None
    assert named.read(old)["passes"] == []
    assert named.per_run(os.path.join(FIXTURES, "no-such-dir")) is None


# --- the journal ------------------------------------------------------------

def test_step_program_and_dispatch_ms_are_read_from_a_journal(tmp_path):
    path = tmp_path / "events.jsonl"
    records = [
        {"kind": "run_start"},
        {"kind": "step", "iteration": 1, "step_ms": 170.1,
         "dispatch_ms": 1.25, "data_wait_ms": 0.1},
        {"kind": "profile_begin", "iteration": 2},
        {"kind": "step_program", "num_microbatches": 1,
         "argument_bytes": 9_777_259_520, "temp_bytes": 3_566_515_200,
         "output_bytes": 9_777_213_440, "alias_bytes": 9_777_210_368}]
    path.write_text("".join(json.dumps(r) + "\n" for r in records) + "\n")
    got = named.journal(str(path))
    assert [r["kind"] for r in got] == [r["kind"] for r in records]
    assert got[1]["dispatch_ms"] == 1.25
    [program] = [r for r in got if r["kind"] == "step_program"]
    assert program["temp_bytes"] / 1e9 == pytest.approx(3.5665152)
    assert named.journal(str(tmp_path / "none.jsonl")) == []


# --- the entries and their readers ------------------------------------------

def _fake_run(cell, **fields):
    base = dict(cell=cell, seconds=10.0,
                device={"platform": "tpu", "kind": "TPU v5 lite",
                        "count": cell.chips},
                memory_peak_bytes=12_000_000_000, setup_s=42.0,
                end_to_end={}, attempted=3, failed=0, problems=[],
                peaks=V5E)
    base.update(fields)
    return common.Run(**base)


def twelve_entries_contract(spec_path):
    """PR 23's twelve, found by name in a spec (later PRs append entries
    after them and cells' names to their `workloads`)."""
    with open(spec_path) as f:
        entries = json.load(f)["per_layer"]
    ours = [m for m in entries if m["name"] in NEW]
    assert [m["name"] for m in ours] == NEW
    for m in ours:
        assert m["moves"] == "train_tokens_per_s"
        assert m["workloads"][:2] == ["train_mistral7b_seq4k",
                                      "train_mistral7b_tp2dp2"]
        assert m["unit"] in ("ms", "%", "GB")
        assert m["better"] == ("higher" if m["unit"] == "%" else "lower")
    # one number is measured once: the custom calls' time is the two named
    # sums (flash_fwd_ms_per_step + flash_bwd_ms_per_step)
    assert "kernel_ms_per_step" not in [m["name"] for m in entries]


@pytest.mark.parametrize("tree", ["BENCHMARK.json", "rehearsed"])
def test_benchmark_json_holds_the_twelve_entries_in_their_order(
        tree, tmp_path):
    """In the real file, and as a PR that adds two configurations and
    their cells leaves it (test_benchmark_contract.py)."""
    twelve_entries_contract(BENCHMARK if tree == "BENCHMARK.json"
                            else added_tree(tmp_path))


@pytest.mark.parametrize("metric", NEW)
def test_every_new_entry_finds_its_reader_and_reads_nothing_from_no_trace(
        metric, monkeypatch):
    cell = spec.Cell(BENCHMARK, "train_mistral7b_seq4k")
    assert metric in [m["name"] for m in cell.per_layer()]
    read = cell.reader(metric)
    # a run that was not traced and journalled no step: None, and before
    # the disk is touched (a made-up run must not read an earlier real
    # run's files)
    monkeypatch.setattr(named, "run_files", lambda run: pytest.fail(
        f"{metric} looked for the run's files"))
    assert read(_fake_run(cell)) is None


@pytest.mark.parametrize("cell_name, fixture", [
    ("train_mistral7b_seq4k", "named_seq4k_tpu_v5e.xplane.pb"),
    ("train_mistral7b_tp2dp2", "named_tp2dp2_tpu_v5e.xplane.pb"),
])
def test_the_readers_report_a_recorded_run(cell_name, fixture, tmp_path,
                                           monkeypatch):
    cell = spec.Cell(BENCHMARK, cell_name)
    journal = tmp_path / "events.jsonl"
    journal.write_text(json.dumps(
        {"kind": "step_program", "temp_bytes": 3_566_515_200}) + "\n")
    monkeypatch.setattr(named, "run_files", lambda run: (
        os.path.join(FIXTURES, fixture), str(journal)))
    run = _fake_run(cell, trace={"devices": cell.chips},
                    steps=[{"t": 1.0, "step_ms": 170.0}])
    got = {m: cell.reader(m)(run) for m in NEW}
    assert all(v is not None and v > 0 for v in got.values()), got
    regions = sum(got[m] for m in NEW[:5])
    assert got["other_ms_per_step"] < 0.06 * regions
    per_run = named.per_run(os.path.join(FIXTURES, fixture))
    assert regions == pytest.approx(1e3 * sum(per_run["regions"].values()))
    # the two named sums are all of the custom calls' time (what the
    # retired kernel_ms_per_step read, by opcode and no name)
    path = os.path.join(FIXTURES, fixture)
    custom_calls = _busy_inside_whole_runs(path, reduce.is_kernel)
    assert got["flash_fwd_ms_per_step"] + got["flash_bwd_ms_per_step"] == (
        pytest.approx(1e3 * sum(custom_calls.values()) / len(custom_calls)
                      / per_run["runs"] * reduce.PS))
    assert got["step_temp_hbm_gb"] == pytest.approx(3.5665152)
    assert 1.0 < got["train_host_ms_per_step"] < 6.0
    # the bound that applies rides on the line's extras
    assert run.extras["roofline"]["flash_fwd"]["bound"] == "compute"
    assert run.extras["roofline"]["flash_bwd"]["pct"] == (
        got["flash_bwd_roofline_pct"])
    # a rehearsal has no peaks: no roofline, and nothing raised
    run.peaks = None
    assert cell.reader("flash_fwd_roofline_pct")(run) is None


# --- equal, not close: the numbers of PR 24's tree on the recordings --------

RECORDINGS = [("train_mistral7b_seq4k", "named_seq4k_tpu_v5e.xplane.pb"),
              ("train_mistral7b_tp2dp2", "named_tp2dp2_tpu_v5e.xplane.pb")]
with open(os.path.join(REPO, "tests", "benchmark", "named",
                       "recorded_at_pr24.json")) as _f:
    AT_PR24 = json.load(_f)


@pytest.fixture(scope="module")
def read_now(tmp_path_factory):
    """{fixture: (the twelve metrics, extras.roofline)} as this tree's
    readers give them for a run whose files are the recording and a
    journal with the record's `temp_bytes`."""
    from unittest import mock

    journal = tmp_path_factory.mktemp("journal") / "events.jsonl"
    journal.write_text(json.dumps(
        {"kind": "step_program", "temp_bytes": 3_566_515_200}) + "\n")
    out = {}
    for cell_name, fixture in RECORDINGS:
        cell = spec.Cell(BENCHMARK, cell_name)
        run = _fake_run(cell, trace={"devices": cell.chips},
                        steps=[{"t": 1.0, "step_ms": 170.0}])
        with mock.patch.object(named, "run_files", lambda run: (
                os.path.join(FIXTURES, fixture), str(journal))):
            out[fixture] = ({m: cell.reader(m)(run) for m in NEW},
                            run.extras["roofline"])
    return out


@pytest.mark.parametrize("fixture", [f for _, f in RECORDINGS])
@pytest.mark.parametrize("metric", NEW)
def test_a_named_metric_equals_what_pr24_read_on_the_recording(
        metric, fixture, read_now):
    assert read_now[fixture][0][metric] == AT_PR24[fixture]["metrics"][metric]


@pytest.mark.parametrize("fixture", [f for _, f in RECORDINGS])
@pytest.mark.parametrize("label", ["flash_fwd", "flash_bwd"])
def test_a_roofline_record_equals_what_pr24_read_on_the_recording(
        label, fixture, read_now):
    assert read_now[fixture][1][label] == AT_PR24[fixture]["roofline"][label]


# --- end to end, on the CPU -------------------------------------------------

def test_rehearsal_finds_the_loops_spans_and_the_step_program():
    """`--rehearse --trace 1` on a toy cell: no device plane on a CPU, so
    the device readers have nothing to read and say nothing; the two
    program readers find `train-pass`, the timers' spans and the
    `step_program` record that the trainer wrote."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--spec", NAMED_SPEC, "--workload", "named_train", "--seed", "3",
         "--seconds", "3", "--trace", "1", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert set(line["metrics"]) == {"train_host_ms_per_step",
                                    "step_temp_hbm_gb"}
    assert 0 < line["metrics"]["train_host_ms_per_step"]["value"] < 1000
    run_dir = os.path.join(REPO, "runs", "benchmark", "named_train")
    records = named.journal(os.path.join(run_dir, "tele", "events.jsonl"))
    [program] = [r for r in records if r["kind"] == "step_program"]
    assert program["num_microbatches"] == 1
    assert line["metrics"]["step_temp_hbm_gb"]["value"] == (
        program["temp_bytes"] / 1e9)
    for key in ("argument_bytes", "output_bytes", "alias_bytes"):
        assert program[key] > 0
    # the record is written after the loop has returned: no step follows
    kinds = [r["kind"] for r in records]
    assert "step" not in kinds[kinds.index("step_program"):]
    steps = [r for r in records if r["kind"] == "step"]
    assert all(0 < r["dispatch_ms"] <= r["step_ms"] for r in steps)
    # the trace holds the passes the window held whole (3 steps traced: 2)
    passes = named.read(os.path.join(run_dir, "trace"))["passes"]
    assert len(passes) == 2 and all(p["fetch_ps"] > 0 for p in passes)
