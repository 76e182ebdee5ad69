"""A traced run as region x class of work (benchmark/harness/trace/
classes.py), the two cost files of the dense matmuls and the nine readers
of benchmark/layer_metrics/ that read the table: on made-up events, and on
the two named TPU recordings of benchmark/fixtures/ (PR 24's tree: regions
and `hlo_category`, none of the scopes one level down, which is what a
parent commit's trace looks like to these readers)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark.harness import common, peaks, spec  # noqa: E402
from benchmark.harness.trace import classes, named, reduce, xplane  # noqa: E402
from test_benchmark_contract import (  # noqa: E402
    ADDED_CELL, SHARE_CELL, added_tree,
)

BENCHMARK = os.path.join(REPO, "BENCHMARK.json")
FIXTURES = os.path.join(REPO, "benchmark", "fixtures")
ONE, FOUR = "named_seq4k_tpu_v5e.xplane.pb", "named_tp2dp2_tpu_v5e.xplane.pb"
TODAY = "classes_tp2dp2_tpu_v5e.xplane.pb"     # this PR's tree, four chips
TODAY_ONE = "classes_seq4k_tpu_v5e.xplane.pb"  # and one
ALL = ["train_mistral7b_seq4k", "train_mistral7b_tp2dp2",
       "train_olmoe1b7b_seq4k"]
NEW = {"attention_matmul_ms_per_step": ALL,
       "attention_glue_ms_per_step": ALL,
       "mlp_matmul_ms_per_step": ALL[:2],
       "attention_matmul_roofline_pct": ALL[:2],
       "mlp_matmul_roofline_pct": ALL[:2],
       "collective_ms_per_step": ALL[1:2],
       "collective_fused_ms_per_step": ALL[1:2],
       "layer_scan_ms_per_step": ALL,
       "unnamed_ms_per_step": ALL}
V5E = peaks.peaks_for("TPU v5 lite")

KERNEL_TEXT = ('%flash_fwd.1 = (bf16[1,32,4096,128]{3,2,1,0}, '
               'f32[1,32,4096,128]{3,2,1,0}) custom-call(bf16[1] %a), '
               'custom_call_target="tpu_custom_call"')


# --- the classes ------------------------------------------------------------

@pytest.mark.parametrize("name, category, want, known", [
    (KERNEL_TEXT, "custom-call", "kernel", True),
    ("%custom-call.5 = bf16[2,8] custom-call(), custom_call_target="
     '"AllocateBuffer"', "custom-call", "rest", True),
    ("%fusion.472 = (bf16[2,8,2048,28672]{3,2,1,0}) fusion(bf16[8] %x)",
     "convolution fusion", "matmul", True),
    ("%convolution.3 = bf16[8,128] convolution(bf16[8] %x)", "convolution",
     "matmul", True),
    ("%all-gather.212 = bf16[8,8,512,4096]{3,2,1,0} all-gather(bf16[4] %x)",
     "all-gather", "collective", True),
    ("%all-reduce-start.3 = bf16[8] all-reduce-start(bf16[8] %x)",
     "all-reduce-start", "collective", True),
    ("%all-gather-done.3 = bf16[8] all-gather-done(bf16[8] %x)",
     "all-gather-done", "collective", True),
    ("%collective-permute-start = (bf16[24,2048]) collective-permute-start"
     "(bf16[24] %x)", "collective-permute-start", "collective", True),
    # a collective the program wrote itself (`lax.psum_scatter` inside a
    # `shard_map`) is named after its primitive: the category says it
    ("%reduce_scatter.13 = bf16[8,256,4096] reduce-scatter(bf16[8,512,4096]"
     " %x)", "reduce-scatter", "collective", True),
    ("%psum.3 = f32[] all-reduce(f32[] %x)", "all-reduce", "collective",
     True),
    # the chip compiler fused a reduce-scatter into the operation beside it:
    # by its name a fusion, by the profiler's category a collective
    ("%fusion.396 = bf16[8,2048,4096]{2,1,0} fusion(bf16[8,4096,4096] %x)",
     "all-reduce-scatter fusion", "collective_fused", True),
    ("%fusion.9 = bf16[8] fusion(bf16[4] %x)", "all-gather fusion",
     "collective_fused", True),
    ("%async-collective-start = (bf16[1,4096,14336]) fusion(bf16[1] %x)",
     "custom fusion", "collective_fused", True),
    ("%async-collective-done.4 = bf16[8,4096,4096] fusion(bf16[8] %x)",
     "custom fusion", "collective_fused", True),
    ("%fusion.389 = f32[8,2048]{1,0} fusion(bf16[8,2048,4096] %x)",
     "loop fusion", "elementwise", True),
    ("%fusion = bf16[16384,4096] fusion(bf16[32000,4096] %x)",
     "custom fusion", "elementwise", True),
    ("%convert.5 = f32[4096] convert(bf16[4096] %x)",
     "non-fusion elementwise", "elementwise", True),
    ("%reduce.49 = f32[] reduce(f32[8,4096] %x)", "reduce", "elementwise",
     True),
    ("%copy.182 = f32[4096,128]{0,1} copy(f32[4096,128]{1,0} %x)",
     "data formatting", "data_movement", True),
    ("%broadcast.3 = bf16[8,4,4,4096,128] broadcast(bf16[8] %x)",
     "broadcast", "data_movement", True),
    ("%copy-done.3 = bf16[2,7168,4096] copy-done((bf16[2]) %x)",
     "copy-done", "data_movement", True),
    ("%slice-done.34 = f32[4096,32] async-done((f32[4096,128]) %x)",
     "async-done", "data_movement", True),
    ("%dynamic-update-slice.73 = bf16[2,2048] dynamic-update-slice(bf16[2] "
     "%x)", "dynamic-update-slice", "data_movement", True),
    ("%while.143 = (s32[], bf16[8,2048,4096]) while((s32[]) %x)", "while",
     "rest", True),
    ("%sort = (s32[16384], s32[16384]) sort(s32[16384] %x)", "sort", "rest",
     True),
    # a category nobody has seen yet: its time is kept, under `rest`, and
    # the category is listed by name
    ("%fft.1 = c64[8] fft(c64[8] %x)", "a category of tomorrow", "rest",
     False),
    ("%fusion.1 = bf16[8] fusion(bf16[8] %x)", None, "rest", False),
])
def test_an_operation_gets_its_class_from_its_name_and_category(
        name, category, want, known):
    assert classes.class_of(name, category) == (want, known)
    assert want in classes.CLASSES


@pytest.mark.parametrize("name, category, kind", [
    ("%all-gather-start.3 = bf16[8] all-gather-start(bf16[8] %x)",
     "all-gather-start", "all-gather"),
    ("%fusion.396 = bf16[8] fusion(bf16[8] %x)", "all-reduce-scatter fusion",
     "all-reduce-scatter"),
    ("%async-collective-start = (bf16[8]) fusion(bf16[8] %x)",
     "custom fusion", "async-collective"),
    ("%reduce_scatter.13 = bf16[8] reduce-scatter(bf16[16] %x)",
     "reduce-scatter", "reduce-scatter"),
    ("%ppermute.2 = bf16[8] collective-permute-done(bf16[8] %x)",
     "collective-permute-done", "collective-permute"),
])
def test_a_collective_is_named_by_what_it_is(name, category, kind):
    assert classes.collective_kind(name, category) == kind


def test_result_bytes_are_those_of_the_largest_result():
    assert classes.result_bytes(
        "%all-gather-start.3 = (bf16[4,512]{1,0}, bf16[8,512]{1,0:T(8,128)}) "
        "all-gather-start(bf16[4,512] %x)") == 8 * 512 * 2
    assert classes.result_bytes("%r = f32[]{:T(128)} all-reduce(f32[] %x)") \
        == 4
    assert classes.result_bytes("no hlo text") == 0


# --- the table, on made-up events -------------------------------------------

def _made_up_plane():
    """Four runs of one program, 1000 ps each (the first and the last are
    cut and left out). A whole run holds a `while` (no name stack) around
    an attention matmul, a kernel and a fused reduce-scatter; an MLP
    matmul and a plain all-reduce under `mlp`; the layer scan's own
    stacking under `layer_stack` and no region; an unnamed copy; an
    operation of an unknown category under `optimizer`."""
    scan = "jit(step)/micro_batches/while/body/jvp(layer_stack)/while/body/"
    md = {
        "%while.1 = while()": {"hlo_category": "while"},
        "%fusion.1 = bf16[8,64] fusion()": {
            "tf_op": scan + "attention/attn_qkv/dot_general:",
            "hlo_category": "convolution fusion", "flops": 1000.0},
        KERNEL_TEXT: {
            "tf_op": scan + "attention/attn_core/flash_fwd/pallas_call:",
            "hlo_category": "custom-call"},
        "%fusion.2 = bf16[8,32] fusion()": {
            "tf_op": scan + "attention/attn_out/dot_general:",
            "hlo_category": "all-reduce-scatter fusion"},
        "%fusion.3 = bf16[8,128] fusion()": {
            "tf_op": scan + "mlp/mlp_in/dot_general:",
            "hlo_category": "convolution fusion", "flops": 4000.0},
        "%all-reduce.7 = bf16[8,128] all-reduce()": {
            "tf_op": scan + "mlp/mlp_out/dot_general:",
            "hlo_category": "all-reduce"},
        "%fusion.4 = bf16[2,8] fusion()": {
            "tf_op": scan + "dynamic_update_slice:",
            "hlo_category": "loop fusion"},
        "%copy.1 = bf16[8] copy()": {"hlo_category": "data formatting"},
        "%fft.1 = c64[8] fft()": {
            "tf_op": "jit(step)/optimizer/fft:",
            "hlo_category": "a category of tomorrow"},
    }
    ops, modules = [], []
    ev = lambda name, at, dur: xplane.Event(name, at, dur, {})  # noqa: E731
    for run in range(4):
        t0 = 10_000 + run * 1100
        modules.append(ev("jit_step(7)", t0, 1000))
        ops += [ev("%while.1 = while()", t0, 500),
                ev("%fusion.1 = bf16[8,64] fusion()", t0 + 50, 100),
                ev(KERNEL_TEXT, t0 + 150, 200),
                ev("%fusion.2 = bf16[8,32] fusion()", t0 + 350, 60),
                ev("%fusion.3 = bf16[8,128] fusion()", t0 + 500, 250),
                ev("%all-reduce.7 = bf16[8,128] all-reduce()", t0 + 750, 40),
                ev("%fusion.4 = bf16[2,8] fusion()", t0 + 800, 70),
                ev("%copy.1 = bf16[8] copy()", t0 + 880, 50),
                ev("%fft.1 = c64[8] fft()", t0 + 940, 30)]
    plane = xplane.Plane("/device:TPU:0", [
        xplane.Line(reduce.MODULE_LINE, modules),
        xplane.Line(reduce.OP_LINE, ops)], {})
    return plane, md


def test_the_table_closes_to_the_regions_and_drops_nothing():
    plane, md = _made_up_plane()
    got = classes.reduce_device(plane, md)
    base = named.reduce_device(plane, {k: v["tf_op"] for k, v in md.items()
                                       if "tf_op" in v})
    assert got["runs"] == base["runs"] == 2 and got["named"] is True
    # Σ classes of a region == the region, to the picosecond
    for region, ps in base["regions"].items():
        assert sum(got["table"][(region, c)]
                   for c in classes.CLASSES) == ps, region
    assert sum(got["table"].values()) == 2 * (500 + 250 + 40 + 70 + 50 + 30)
    table = {k: v for k, v in got["table"].items() if v}
    assert table == {
        ("attention", "matmul"): 200, ("attention", "kernel"): 400,
        ("attention", "collective_fused"): 120, ("mlp", "matmul"): 500,
        ("mlp", "collective"): 80, ("optimizer", "rest"): 60,
        # the loop's own 140 of its 500, the scan's stacking, the copy
        ("other", "rest"): 280, ("other", "elementwise"): 140,
        ("other", "data_movement"): 100}
    # an unknown category is under `rest` and listed by name
    assert got["unknown"] == {"a category of tomorrow": 60}
    assert got["categories"][("rest", "a category of tomorrow")] == 60
    # no name stack at all: the loop's own time and the copy
    assert {k: v for k, v in got["unnamed"].items() if v} == {
        "rest": 280, "data_movement": 100}
    # the scopes one level down, by class; the primitive is no scope
    assert got["scopes"][("attn_qkv", "matmul")] == 200
    assert got["scopes"][("attn_core", "kernel")] == 400
    assert got["scopes"][("attn_out", "collective_fused")] == 120
    assert got["scopes"][("mlp_out", "collective")] == 80
    assert ("dot_general", "matmul") not in got["scopes"]
    assert ("pallas_call", "kernel") not in got["scopes"]
    assert got["scopes"][("flash_fwd", "kernel")] == 400
    # what `layer_stack` holds beside the layers: the stacking, alone
    assert got["outside"]["layer_stack"] == 140
    assert got["outside"]["micro_batches"] == 140
    assert sum(v for (s, _c), v in got["scopes"].items()
               if s == "layer_stack") == 200 + 400 + 120 + 500 + 80 + 140
    # the compiler's count rides beside, by (region, class)
    assert got["flops"] == {("attention", "matmul"): 2000.0,
                            ("mlp", "matmul"): 8000.0}
    # a pair's `-done` half is time, not a call
    assert got["collectives"][("mlp", "all-reduce", False)] == {
        "ps": 80, "calls": 2, "bytes": 2 * 8 * 128 * 2}
    assert got["collectives"][("attention", "all-reduce-scatter", True)][
        "calls"] == 2
    # the same events without their names are a program without scopes
    bare = classes.reduce_device(plane, {})
    assert bare["named"] is False


# --- what the dense matmuls need --------------------------------------------

def _needed(kernel, dims, **config):
    cell = spec.Cell(BENCHMARK, "train_mistral7b_seq4k")
    return cell.kernel_cost(kernel)(dims, 2, dict(cell.config, **config))


def test_matmul_costs_equal_hand_numbers():
    # one 4096-token sequence through two layers of Mistral-7B's widths:
    # PERF.md's hand counts, 2.06 TFLOP of projections and 8.66 of FFN
    flops, nbytes = _needed("attention_matmul", (4096, 1))
    per_token = 4096 * (32 + 2 * 8) * 128 + 32 * 128 * 4096
    assert per_token == 41_943_040
    assert flops == 3 * 2 * 4096 * per_token * 2
    assert flops == pytest.approx(2.06e12, rel=2e-3)
    assert nbytes == 3 * 2 * 2 * (
        4096 * (4096 + 6144) + 4096 * 6144 + 4096 * (4096 + 4096)
        + 4096 * 4096)
    ffn, ffn_bytes = _needed("mlp_matmul", (4096, 1))
    assert ffn == 3 * 2 * 4096 * (4096 * 28672 + 14336 * 4096) * 2
    assert ffn == pytest.approx(8.66e12, rel=1e-3)
    # compute-bound by far: bytes over the HBM peak are a tenth of FLOP
    # over the MXU's
    assert ffn_bytes / V5E["hbm_bytes_per_s"] < 0.2 * ffn / V5E[
        "bf16_flops_per_s"]
    # TP 2 halves a device's share; eight sequences a replica are eight
    # times one
    assert _needed("mlp_matmul", (8 * 4096, 2))[0] == 4 * ffn
    assert _needed("attention_matmul", (8 * 4096, 2))[0] == 4 * flops
    # an explicit head size wins over hidden / heads
    assert _needed("attention_matmul", (4096, 1), head_dim=64)[0] == \
        flops / 2
    # another rank of dims is not a shape these files know; a model with
    # experts has no dense FFN
    assert _needed("attention_matmul", (4096,)) is None
    assert _needed("mlp_matmul", (4096, 1, 1)) is None
    assert _needed("mlp_matmul", (4096, 1), num_experts=64) is None


@pytest.mark.parametrize("cell_name, want", [
    ("train_mistral7b_seq4k", (1, 1)), ("train_mistral7b_tp2dp2", (2, 2)),
    ("train_olmoe1b7b_seq4k", (1, 1))])
def test_a_cells_parallel_sizes_come_from_its_mix(cell_name, want):
    assert classes.parallel_of(spec.Cell(BENCHMARK, cell_name)) == want


# --- the recordings ---------------------------------------------------------

@pytest.mark.parametrize("fixture, devices", [(ONE, 1), (FOUR, 2)])
def test_the_table_closes_on_a_recording(fixture, devices):
    path = os.path.join(FIXTURES, fixture)
    raw = classes.read(path)["devices"]
    base = named.read(path)["devices"]
    assert len(raw) == devices
    for name, d in raw.items():
        for region, ps in base[name]["regions"].items():
            assert sum(d["table"][(region, c)]
                       for c in classes.CLASSES) == ps > 0, (name, region)
        # every category these two traces hold is one the table knows
        assert d["unknown"] == {}
    got, regions = classes.per_run(path), named.per_run(path)["regions"]
    assert got["devices"] == devices and got["runs"] == 2
    for region, s in regions.items():
        assert sum(got["regions"][region].values()) == pytest.approx(
            s, rel=1e-12)
    # the kernels' class is the kernels' time
    kernels = sum(k["s"] for k in named.per_run(path)["kernels"].values())
    assert got["regions"]["attention"]["kernel"] == pytest.approx(
        kernels, rel=1e-12)
    assert got["unclassified"] == {}


def test_what_tp_adds_on_the_four_chip_recording():
    """PR 24's tree on four chips beside four times its one-chip times
    (ISSUE 34's table, here by class): the reduce-scatter behind the
    output projection is a fusion, and is classed `collective_fused`."""
    one = classes.per_run(os.path.join(FIXTURES, ONE))["regions"]
    four = classes.per_run(os.path.join(FIXTURES, FOUR))
    ms = lambda t, r, c: 1e3 * t[r].get(c, 0.0)  # noqa: E731
    assert ms(one, "attention", "collective_fused") == 0.0
    assert ms(four["regions"], "attention", "collective_fused") == \
        pytest.approx(15.52, abs=0.02)
    assert ms(four["regions"], "head_loss", "collective_fused") == \
        pytest.approx(13.49, abs=0.02)
    fused = [c for c in four["collectives"]
             if c["region"] == "attention" and c["fused"]
             and c["kind"] == "all-reduce-scatter"]
    assert len(fused) == 1 and fused[0]["calls"] == 12.0
    assert fused[0]["s"] == pytest.approx(15.52e-3, abs=2e-5)
    # the projections themselves: 49.5 ms against 4 x 11.6
    assert ms(four["regions"], "attention", "matmul") == pytest.approx(
        49.46, abs=0.02)
    assert 4 * ms(one, "attention", "matmul") == pytest.approx(46.56,
                                                               abs=0.05)
    glue = lambda t: sum(ms(t, "attention", c)  # noqa: E731
                         for c in classes.GLUE)
    assert glue(four["regions"]) == pytest.approx(35.46, abs=0.05)
    assert 4 * glue(one) == pytest.approx(19.2, abs=0.1)
    assert ms(four["regions"], "mlp", "matmul") == pytest.approx(220.54,
                                                                 abs=0.02)
    # one chip communicates with nobody; on four every region but the
    # optimizer's sums waits for somebody
    assert all(not row.get("collective") and not row.get("collective_fused")
               for row in one.values())
    assert 1e3 * sum(four["unnamed"].values()) == pytest.approx(12.05,
                                                                abs=0.05)
    assert 1e3 * four["unnamed"]["collective"] == pytest.approx(7.54,
                                                                abs=0.02)


def test_the_scopes_one_level_down_on_a_recording_of_this_tree():
    """The four-chip cell traced on the tree of PR 34 (two devices, two
    whole runs, cut by tools/cut_named_trace.py): the parts of the
    regions arrive, and say where what TP x SP adds stands."""
    got = classes.per_run(os.path.join(FIXTURES, TODAY))
    assert got["devices"] == 2 and got["runs"] == 2
    assert got["unclassified"] == {}
    ms = lambda s, c: 1e3 * got["scopes"].get(s, {}).get(c, 0.0)  # noqa: E731
    # every operation of `attention` is in one of its five parts
    parts = ("attn_norm", "attn_qkv", "attn_rope", "attn_core", "attn_out")
    assert sum(sum(got["scopes"][p].values()) for p in parts) == (
        pytest.approx(sum(got["regions"]["attention"].values()), rel=1e-9))
    # the fused reduce-scatters stand behind the output projection and,
    # in the backward pass, behind Q, K and V; the gather in front of them
    # is plain; the kernels and all that is around them wait for nobody
    assert ms("attn_out", "collective_fused") == pytest.approx(7.65, abs=0.05)
    assert ms("attn_qkv", "collective_fused") == pytest.approx(7.86, abs=0.05)
    assert ms("attn_qkv", "collective") == pytest.approx(5.78, abs=0.05)
    for part in ("attn_core", "attn_rope"):
        assert not ms(part, "collective") and not ms(part, "collective_fused")
    assert ms("attn_core", "kernel") == pytest.approx(
        1e3 * got["regions"]["attention"]["kernel"])
    # a collective the program wrote itself is one by its category: the
    # head's eight reduce-scatters, `%reduce_scatter.13`
    kinds = {(c["region"], c["kind"], c["fused"]): c
             for c in got["collectives"]}
    scatters = kinds[("head_loss", "reduce-scatter", False)]
    assert scatters["calls"] == 8.0
    assert scatters["s"] == pytest.approx(3.11e-3, abs=5e-5)
    # the layers' scan beside the layers; the micro-batch loop adds
    # next to nothing to it
    assert 1e3 * got["outside"]["layer_stack"] == pytest.approx(15.94,
                                                                abs=0.05)
    assert got["outside"]["micro_batches"] < 1.02 * got["outside"][
        "layer_stack"]
    assert 1e3 * sum(got["unnamed"].values()) == pytest.approx(11.6, abs=0.1)


def test_the_one_chip_cell_on_a_recording_of_this_tree():
    """The same tree on one chip (two whole runs): beside the table above
    this is what "four times the one-chip time" is read from."""
    got = classes.per_run(os.path.join(FIXTURES, TODAY_ONE))
    assert (got["devices"], got["runs"]) == (1, 2)
    assert got["collectives"] == [] and got["unclassified"] == {}
    ms = lambda s, c: 1e3 * got["scopes"].get(s, {}).get(c, 0.0)  # noqa: E731
    # the projections are the only matmuls of `attention`, the activation
    # is fused into the FFN's products, and each part holds one class
    assert ms("attn_qkv", "matmul") + ms("attn_out", "matmul") == (
        pytest.approx(1e3 * got["regions"]["attention"]["matmul"]))
    assert ms("mlp_in", "matmul") + ms("mlp_out", "matmul") == (
        pytest.approx(1e3 * got["regions"]["mlp"]["matmul"]))
    assert "mlp_act" not in got["scopes"]
    assert ms("attn_rope", "elementwise") == pytest.approx(2.18, abs=0.02)
    assert ms("attn_norm", "elementwise") == pytest.approx(0.23, abs=0.01)
    # the scan's own work: a quarter of the four-chip cell's 15.94
    assert 1e3 * got["outside"]["layer_stack"] == pytest.approx(3.80,
                                                                abs=0.02)
    assert 1e3 * sum(got["unnamed"].values()) == pytest.approx(0.23,
                                                               abs=0.01)


def test_a_recording_without_the_names_gives_nothing_and_raises_nothing():
    old = os.path.join(FIXTURES, "train_tp2dp2_tpu_v5e.xplane.pb")
    assert classes.read(old)["devices"] and classes.per_run(old) is None
    assert classes.per_run(os.path.join(FIXTURES, "no-such-dir")) is None


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


def nine_entries_contract(spec_path):
    """PR 34's nine, found by name in a spec, in their order, each with
    the cells it listed in front (later PRs append entries after them
    and cells' names to their `workloads`)."""
    with open(spec_path) as f:
        entries = json.load(f)["per_layer"]
    ours = [m for m in entries if m["name"] in NEW]
    assert [m["name"] for m in ours] == list(NEW)
    # appended after what was there (later PRs append after them)
    assert entries.index(ours[0]) > [m["name"] for m in entries].index(
        "moe_load_max_over_mean")
    for m in ours:
        assert m["workloads"][:len(NEW[m["name"]])] == NEW[m["name"]]
        assert m["source"] == "device_trace"
        assert m["moves"] == "train_tokens_per_s"
        assert (m["unit"], m["better"]) in (("ms", "lower"), ("%", "higher"))
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # what they stand beside stays: only a benchmark PR retires a metric
    names = [m["name"] for m in entries]
    assert "collective_exposed_pct" in names and "other_ms_per_step" in names


@pytest.mark.parametrize("tree", ["BENCHMARK.json", "rehearsed"])
def test_benchmark_json_holds_the_nine_entries_in_their_order(
        tree, tmp_path):
    """In the real file, and as a PR that adds two configurations and
    their cells leaves it (test_benchmark_contract.py)."""
    if tree == "BENCHMARK.json":
        return nine_entries_contract(BENCHMARK)
    rehearsed = added_tree(tmp_path)
    nine_entries_contract(rehearsed)
    # its cells stand behind, wherever a one-chip cell reads
    with open(rehearsed) as f:
        grown = [m["name"] for m in json.load(f)["per_layer"]
                 if m["name"] in NEW
                 and m["workloads"][-2:] == [ADDED_CELL, SHARE_CELL]]
    assert grown == [m for m in NEW if ALL[0] in NEW[m]]


@pytest.mark.parametrize("edit, taken", [
    (lambda cells: cells + ["a_later_cell"], True),
    (lambda cells: ["a_later_cell"] + cells, False),
    (lambda cells: cells[1::-1] + cells[2:], False),
    (lambda cells: cells[1:], False),
    (lambda cells: cells[:2] + cells[3:], False)],
    ids=["appended", "put_in_front", "reordered", "first_taken_out",
         "third_taken_out"])
def test_a_later_cell_is_appended_to_the_nine_and_none_is_moved_or_taken(
        edit, taken, tmp_path):
    with open(BENCHMARK) as f:
        s = json.load(f)
    [m] = [m for m in s["per_layer"] if m["name"] == "layer_scan_ms_per_step"]
    m["workloads"] = edit(m["workloads"])
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(s))
    if taken:
        nine_entries_contract(path)
        return
    with pytest.raises(AssertionError):
        nine_entries_contract(path)


@pytest.mark.parametrize("metric", list(NEW))
def test_every_new_entry_finds_its_reader_and_reads_nothing_from_no_trace(
        metric, monkeypatch):
    cell = spec.Cell(BENCHMARK, NEW[metric][0])
    assert metric in [m["name"] for m in cell.per_layer()]
    read = cell.reader(metric)
    monkeypatch.setattr(named, "run_files", lambda run: pytest.fail(
        f"{metric} looked for the run's files"))
    run = _fake_run(cell)
    assert read(run) is None and run.extras == {}
    # a cell the entry does not list does not report it
    for other in set(ALL) - set(NEW[metric]):
        assert metric not in [m["name"] for m in spec.Cell(
            BENCHMARK, other).per_layer()]


@pytest.mark.parametrize("cell_name, fixture", [
    ("train_mistral7b_seq4k", ONE), ("train_mistral7b_tp2dp2", FOUR)])
def test_the_readers_report_a_recorded_run(cell_name, fixture, monkeypatch):
    cell = spec.Cell(BENCHMARK, cell_name)
    monkeypatch.setattr(named, "run_files", lambda run: (
        os.path.join(FIXTURES, fixture), "no journal"))
    run = _fake_run(cell, trace={"devices": cell.chips})
    got = {m: cell.reader(m)(run) for m in NEW if cell_name in NEW[m]}
    # PR 24's tree names no `layer_stack`: nothing to read, nothing raised
    assert got.pop("layer_scan_ms_per_step") is None
    assert all(v is not None and v > 0 for v in got.values()), got
    attention = cell.reader("attention_ms_per_step")(run)
    kernels = (cell.reader("flash_fwd_ms_per_step")(run)
               + cell.reader("flash_bwd_ms_per_step")(run))
    fused = classes.region_class_ms(run, "attention", "collective_fused")
    plain = classes.region_class_ms(run, "attention", "collective")
    assert (got["attention_matmul_ms_per_step"]
            + got["attention_glue_ms_per_step"] + kernels + fused + plain
            ) == pytest.approx(attention, rel=1e-9)
    # both shares under 100, compute-bound, their record on the line
    for label in ("attention_matmul", "mlp_matmul"):
        roof = run.extras["roofline"][label]
        assert roof["pct"] == got[label + "_roofline_pct"] < 100
        assert roof["bound"] == "compute"
        assert roof["measured_ms"] == got[label + "_ms_per_step"]
        assert roof["compiler_flop"] is None   # the recordings drop `flops`
    if cell.chips == 1:
        assert got["mlp_matmul_roofline_pct"] == pytest.approx(90.65,
                                                               abs=0.05)
        assert got["attention_matmul_roofline_pct"] == pytest.approx(
            89.9, abs=0.1)
        assert got["unnamed_ms_per_step"] < 0.5
        assert "collective_ms_per_step" not in got
    else:
        assert got["mlp_matmul_roofline_pct"] == pytest.approx(79.7, abs=0.1)
        assert got["attention_matmul_roofline_pct"] == pytest.approx(
            84.6, abs=0.1)
        assert got["collective_fused_ms_per_step"] == pytest.approx(
            15.52 + 13.49 + 3.31, abs=0.05)
        assert got["collective_ms_per_step"] == pytest.approx(
            got["collective_fused_ms_per_step"] + 85.48, abs=0.1)
        assert got["unnamed_ms_per_step"] == pytest.approx(12.05, abs=0.05)
    # the whole table rides on the line, and lists nothing unclassified
    table = run.extras["step_classes"]
    assert table["classes"] == list(classes.CLASSES)
    assert table["unclassified"] == {}
    assert sum(table["regions"]["attention"].values()) == pytest.approx(
        attention, rel=1e-9)
    assert table["step_ms"] == pytest.approx(sum(
        cell.reader(m)(run) for m in (
            "attention_ms_per_step", "mlp_ms_per_step",
            "head_loss_ms_per_step", "optimizer_ms_per_step",
            "other_ms_per_step")), rel=1e-9)
    assert "rematted_computation" in table["scopes"]
    links = run.extras["collectives"]
    assert links["interconnect_gb_per_s"] == 200.0
    assert bool(links["by_region_kind"]) == (cell.chips > 1)
    json.dumps(run.extras)                 # the line can carry it
    # a rehearsal has no peaks: no roofline, and nothing raised
    run.peaks = None
    assert cell.reader("mlp_matmul_roofline_pct")(run) is None


def test_the_readers_report_a_run_of_this_tree(monkeypatch):
    """The nine on the recording of PR 34's own tree, as the chip run it
    was cut from reported them (chiprun_out, PERF.md section 6)."""
    cell = spec.Cell(BENCHMARK, "train_mistral7b_tp2dp2")
    monkeypatch.setattr(named, "run_files", lambda run: (
        os.path.join(FIXTURES, TODAY), "no journal"))
    run = _fake_run(cell, trace={"devices": cell.chips})
    got = {m: cell.reader(m)(run) for m in NEW}
    want = {"attention_matmul_ms_per_step": 49.46,
            "attention_glue_ms_per_step": 35.66,
            "mlp_matmul_ms_per_step": 220.54,
            "attention_matmul_roofline_pct": 84.63,
            "mlp_matmul_roofline_pct": 79.72,
            "collective_ms_per_step": 59.7,
            "collective_fused_ms_per_step": 20.54,
            "layer_scan_ms_per_step": 15.94,
            "unnamed_ms_per_step": 11.62}
    for metric, value in want.items():
        assert got[metric] == pytest.approx(value, abs=0.06), metric
    assert run.extras["step_classes"]["outside_regions"]["layer_stack"] == (
        got["layer_scan_ms_per_step"])


def test_the_four_chip_readers_keep_quiet_on_one_device(monkeypatch):
    cell = spec.Cell(BENCHMARK, "train_mistral7b_tp2dp2")
    monkeypatch.setattr(named, "run_files", lambda run: (
        os.path.join(FIXTURES, ONE), "no journal"))
    run = _fake_run(cell, trace={"devices": 1})
    assert cell.reader("collective_ms_per_step")(run) is None
    assert cell.reader("collective_fused_ms_per_step")(run) is None
