"""Runtime trace analysis: xplane decoding, classification, the
comm/compute/exposed split, and the measured-vs-expected contract check
(megatron_tpu/telemetry/tracing/, tools/trace_report.py).

Two evidence tiers:

  * a checked-in ~7KB fixture (tests/fixtures/tiny_cpu.xplane.pb,
    captured once on XLA:CPU: a jitted dot+tanh+psum on a 2-device fake
    mesh, 2 profiled executions) drives byte-stable golden assertions
    on the decoder and walker;
  * live captures — the REAL train step at the train_tp2_sp contract
    geometry, and the ulysses_cp2 contract target — prove the whole
    pipeline end-to-end on CPU, including measured==expected collective
    counts against the golden comm manifests (the runtime enforcement
    of PR 5's static promise).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(REPO, "tests", "fixtures", "tiny_cpu.xplane.pb")


# ---------------------------------------------------------------------------
# wire decoder
# ---------------------------------------------------------------------------


def test_proto_wire_primitives():
    from megatron_tpu.telemetry.tracing import proto

    # varint round-trip incl. multi-byte and int64 two's complement
    assert proto.read_varint(b"\x05", 0) == (5, 1)
    assert proto.read_varint(b"\xac\x02", 0) == (300, 2)
    assert proto.to_signed((1 << 64) - 1) == -1
    assert proto.to_signed(7) == 7
    # field iteration: varint field 1, length-delimited field 2
    buf = b"\x08\x96\x01" + b"\x12\x03abc"
    fs = list(proto.fields(buf))
    assert fs == [(1, proto.WIRE_VARINT, 150), (2, proto.WIRE_LEN, b"abc")]


def test_proto_malformed_raises():
    from megatron_tpu.telemetry.tracing import proto

    with pytest.raises(proto.ProtoError):
        list(proto.fields(b"\x08"))            # truncated varint payload
    with pytest.raises(proto.ProtoError):
        list(proto.fields(b"\x12\x05ab"))      # truncated length-delimited
    with pytest.raises(proto.ProtoError):
        list(proto.fields(b"\x0b"))            # wire type 3 (group)


# ---------------------------------------------------------------------------
# fixture goldens: decoder + walker + classification
# ---------------------------------------------------------------------------


def _fixture_events():
    from megatron_tpu.telemetry.tracing import classify_xspace, load_xspace

    return classify_xspace(load_xspace(FIXTURE))


def test_fixture_decodes_known_planes_and_ops():
    from megatron_tpu.telemetry.tracing import load_xspace

    space = load_xspace(FIXTURE)
    names = [p.name for p in space.planes]
    assert "/host:CPU" in names
    cpu = space.plane("/host:CPU")
    # the interned metadata tables resolved: op names exist as events
    all_names = {e.name for ln in cpu.lines for e in ln.events}
    assert "dot.1" in all_names
    assert "all-reduce" in all_names
    # stat interning: the op events carry hlo_module via ref_value
    op = next(e for ln in cpu.lines for e in ln.events if e.name == "dot.1")
    assert op.stats["hlo_module"] == "jit_fixture_step"
    assert isinstance(op.stats["program_id"], int)
    assert op.duration_ps > 0


def test_fixture_classification_golden():
    from megatron_tpu.telemetry.tracing.events import (
        KIND_COLLECTIVE, KIND_COMPUTE, KIND_HOST,
    )

    events = _fixture_events()
    colls = [e for e in events if e.kind == KIND_COLLECTIVE]
    # 2 devices x 2 profiled executions, one psum -> all-reduce each
    assert len(colls) == 4
    assert {e.collective for e in colls} == {"all-reduce"}
    assert {e.module for e in colls} == {"jit_fixture_step"}
    comps = [e for e in events if e.kind == KIND_COMPUTE]
    assert any(e.name == "dot.1" for e in comps)
    # the python dispatch spans classified host, not compute
    assert any(e.kind == KIND_HOST and "fixture_step" in e.name
               for e in events)


def test_fixture_analysis_report():
    from megatron_tpu.telemetry.tracing import analyze_events

    report = analyze_events(_fixture_events())
    assert report.module == "jit_fixture_step"
    assert report.compute_s > 0
    assert report.collective_s > 0
    assert report.wall_s > 0
    [ar] = [c for c in report.collectives if c.op == "all-reduce"]
    assert ar.count == 4
    # exposure is a subset of the total, never negative
    assert 0 <= ar.exposed_ps <= ar.total_ps
    # dispatch markers dedup the nested python/C++ TraceMe pair:
    # exactly 2 profiled executions
    assert report.steps["fixture_step"]["count"] == 2
    d = report.to_dict(top=5)
    assert d["collectives"][0]["op"] == "all-reduce"
    assert json.dumps(d)  # JSON-serializable end to end


# ---------------------------------------------------------------------------
# interval / nesting arithmetic
# ---------------------------------------------------------------------------


def test_interval_merge_and_overlap():
    from megatron_tpu.telemetry.tracing.analyze import (
        merge_intervals, overlap_ps,
    )

    merged = merge_intervals([(5, 9), (0, 3), (2, 4), (9, 9)])
    assert merged == [(0, 4), (5, 9)]
    assert overlap_ps(1, 8, merged) == 3 + 3
    assert overlap_ps(4, 5, merged) == 0
    assert overlap_ps(0, 100, merged) == 8
    assert overlap_ps(3, 3, merged) == 0


def test_self_time_nesting():
    """A collective nested inside a while-loop compute event must not be
    masked by its own parent: the parent's self time excludes the child,
    and the compute union is built from SELF segments."""
    from megatron_tpu.telemetry.tracing.analyze import analyze_events
    from megatron_tpu.telemetry.tracing.events import OpEvent

    def op(name, kind, s, e, coll=None):
        return OpEvent(name=name, kind=kind, start_ps=s, duration_ps=e - s,
                       plane="/host:CPU", line="t0", module="jit_m",
                       collective=coll)

    events = [
        op("while.1", "compute", 0, 100),
        op("all-reduce.1", "collective", 20, 60, coll="all-reduce"),
        op("dot.1", "compute", 70, 90),
    ]
    report = analyze_events(events, module="jit_m")
    [ar] = report.collectives
    # exposed: the while's self segments are [0,20), [60,70), [90,100)
    # — none overlap the collective, dot is nested too -> fully exposed
    assert ar.total_ps == 40
    assert ar.exposed_ps == 40
    # while self time excludes both children
    while_agg = next(o for o in report.ops if o.name == "while.1")
    assert while_agg.self_ps == 100 - 40 - 20
    assert while_agg.total_ps == 100
    # a genuinely concurrent compute on ANOTHER line does hide it
    events.append(op("dot.2", "compute", 0, 100))
    events[-1].line = "t1"
    report2 = analyze_events(events, module="jit_m")
    [ar2] = report2.collectives
    assert ar2.exposed_ps == 0


def test_tpu_marker_lines_are_not_compute():
    """TPU 'Steps'/'XLA Modules' lines carry whole-step/whole-module
    ENVELOPE events; classified as compute they would blanket the plane
    and zero out every collective's exposed time. They stay host-kind
    (and 'Steps' envelopes still feed the step-marker table); 'XLA Ops'
    line events are the real ops."""
    from megatron_tpu.telemetry.tracing.analyze import analyze_events
    from megatron_tpu.telemetry.tracing.events import (
        KIND_COLLECTIVE, KIND_HOST, classify_xspace,
    )
    from megatron_tpu.telemetry.tracing.xplane import (
        XEvent, XLine, XPlane, XSpace,
    )

    def line(name, events):
        return XLine(id=0, name=name, timestamp_ns=0, events=events)

    def ev(name, start, dur, stats=None):
        return XEvent(name=name, start_ps=start, duration_ps=dur,
                      stats=stats or {})

    space = XSpace(planes=[XPlane(
        name="/device:TPU:0",
        lines=[
            line("Steps", [ev("1", 0, 1000)]),           # step envelope
            line("XLA Modules", [ev("jit_step(9)", 0, 1000,
                                    {"hlo_module": "jit_step"})]),
            line("XLA Ops", [
                ev("fusion.1", 0, 100, {"hlo_module": "jit_step"}),
                ev("all-reduce.1", 200, 300,
                   {"hlo_module": "jit_step"}),
            ]),
        ],
        stats={}, event_names={}, stat_names={})], hostnames=[])
    events = classify_xspace(space)
    kinds = {e.name: e.kind for e in events}
    assert kinds["1"] == KIND_HOST
    assert kinds["jit_step(9)"] == KIND_HOST
    assert kinds["all-reduce.1"] == KIND_COLLECTIVE
    report = analyze_events(events, module="jit_step")
    [ar] = report.collectives
    # the envelopes span [0,1000) but must NOT hide the collective —
    # only the real fusion op (disjoint from it) counts as compute
    assert ar.exposed_ps == ar.total_ps == 300
    # the Steps envelope still reads as a step marker, under the line's
    # name (one marker, not one a step number)
    assert report.steps["Steps"]["count"] == 1


def test_async_collective_pair_counts_once():
    """TPU backends trace async collectives as -start/-done pairs: both
    halves' time is communication, but the PAIR must count once or
    measured-vs-expected reads ~2x the static contract."""
    from megatron_tpu.telemetry.tracing.analyze import analyze_events
    from megatron_tpu.telemetry.tracing.events import OpEvent

    def coll(name, s, e):
        return OpEvent(name=name, kind="collective", start_ps=s,
                       duration_ps=e - s, plane="/device:TPU:0",
                       line="XLA Ops", module="jit_m",
                       collective="all-gather")

    report = analyze_events([
        coll("all-gather-start.3", 0, 10),
        coll("all-gather-done.3", 50, 90),
        coll("all-gather.7", 100, 120),   # sync form still counts
    ], module="jit_m")
    [ag] = report.collectives
    assert ag.count == 2                    # one pair + one sync op
    assert ag.total_ps == 10 + 40 + 20      # both halves' time kept


# ---------------------------------------------------------------------------
# contract comparison (unit level)
# ---------------------------------------------------------------------------


def _manifest(hlo_counts, hlo_bytes=None):
    return {"hlo": {"collectives": {
        op: {"count": n, "total_bytes": (hlo_bytes or {}).get(op, 0)}
        for op, n in hlo_counts.items()}}}


def _coll_report(counts):
    from megatron_tpu.telemetry.tracing.analyze import (
        CollectiveAgg, TraceReport,
    )

    return TraceReport(
        module="jit_m", wall_s=1.0, busy_s={}, ops=[],
        collectives=[CollectiveAgg(op, n, n * 1000, n * 500)
                     for op, n in counts.items()],
        steps={}, all_modules={})


def test_compare_contract_matches_and_infers_executions():
    from megatron_tpu.telemetry.tracing.analyze import compare_contract

    cmp = compare_contract(
        _coll_report({"all-reduce": 48, "all-to-all": 112}),
        _manifest({"all-reduce": 3, "all-to-all": 7},
                  {"all-reduce": 8192}), "ulysses_cp2")
    assert cmp.matches and cmp.executions == 16
    assert cmp.bandwidth["all-reduce"]["bytes_total"] == 8192 * 16
    assert cmp.bandwidth["all-reduce"]["bus_gbps"] > 0


def test_compare_contract_flags_mismatches():
    from megatron_tpu.telemetry.tracing.analyze import compare_contract

    # unexpected collective (contract pins none of that kind)
    cmp = compare_contract(_coll_report({"all-gather": 4}),
                           _manifest({"all-reduce": 1}), "c")
    assert not cmp.matches
    assert any("UNEXPECTED" in p for p in cmp.problems)
    assert any("NEVER RAN" in p for p in cmp.problems)
    # inconsistent ratio (loop-carried collective): inference anchors on
    # the SMALLEST divisible ratio — loop-carried ops only ever run MORE
    # than the static count — so the inflated op is the one flagged,
    # even when it sorts alphabetically first
    cmp2 = compare_contract(
        _coll_report({"all-reduce": 16, "all-gather": 48}),
        _manifest({"all-reduce": 2, "all-gather": 2}), "c")
    assert not cmp2.matches and cmp2.executions == 8
    rows = {r["op"]: r["ok"] for r in cmp2.rows}
    assert rows["all-reduce"] is True      # the top-level op stays ok
    assert rows["all-gather"] is False     # the loop-carried one flagged
    # explicit executions overrides inference
    cmp3 = compare_contract(_coll_report({"all-reduce": 16}),
                            _manifest({"all-reduce": 2}), "c",
                            executions=8)
    assert cmp3.matches


def test_compare_contract_jaxpr_level():
    """Manifests without an hlo section (can_compile=False configs) map
    their jaxpr primitives onto the HLO mnemonics the thunks trace as."""
    from megatron_tpu.telemetry.tracing.analyze import compare_contract

    manifest = {"jaxpr": {"collectives": {
        "psum[data] float32[2x4] @shard_map": {"count": 2,
                                               "total_bytes": 64},
        "all_gather[expert] float32[8] @shard_map": {"count": 1,
                                                     "total_bytes": 32},
    }}}
    cmp = compare_contract(
        _coll_report({"all-reduce": 4, "all-gather": 2}), manifest, "c")
    assert cmp.level == "jaxpr"
    assert cmp.matches and cmp.executions == 2


# ---------------------------------------------------------------------------
# live captures: the real programs on CPU
# ---------------------------------------------------------------------------


def _xplane_under(d):
    from megatron_tpu.telemetry.tracing import find_xplane_files

    files = find_xplane_files(str(d))
    assert files, f"no xplane written under {d}"
    return files


def test_live_capture_real_train_step(tmp_path):
    """--profile on a REAL train-loop run (train_tp2_sp contract
    geometry): the trace must contain the jitted step's op events with
    nonzero compute time, and the report must find the step markers."""
    from megatron_tpu.analysis.targets import tiny_model
    from megatron_tpu.config import (
        OptimizerConfig, ParallelConfig, RunConfig, TrainingConfig,
    )
    from megatron_tpu.telemetry.tracing import (
        analyze_events, classify_xspace, load_xspace,
    )
    from megatron_tpu.training.pretrain import TrainLoop

    trace_dir = tmp_path / "trace"
    cfg = RunConfig(
        model=tiny_model(),
        parallel=ParallelConfig(tensor_parallel=2, sequence_parallel=True),
        optimizer=OptimizerConfig(lr=1e-3, lr_decay_style="constant"),
        training=TrainingConfig(
            micro_batch_size=1, global_batch_size=8, train_iters=4,
            log_interval=1, recompute_granularity="full",
            profile=True, profile_step_start=3, profile_step_end=5,
            profile_dir=str(trace_dir)))
    rng = np.random.default_rng(0)
    pool = rng.integers(0, 128, (64, 33))

    def factory(consumed, gbs):
        def gen():
            i = 0
            while True:
                rows = pool[i % 56:i % 56 + gbs]
                yield {"tokens": rows[:, :-1].astype(np.int64),
                       "labels": rows[:, 1:].astype(np.int64),
                       "loss_mask": np.ones((gbs, 32), np.float32)}
                i += gbs
        return gen()

    logs = []
    loop = TrainLoop(cfg, log=logs.append)
    loop.train(factory)
    assert any("profiler: tracing steps [3, 5)" in ln for ln in logs)
    assert any("profiler: trace written" in ln for ln in logs)

    events = []
    for f in _xplane_under(trace_dir):
        events.extend(classify_xspace(load_xspace(f)))
    report = analyze_events(events)
    # the dominant module IS the jitted train step, with real compute
    assert report.module == "jit_train_step"
    assert report.compute_s > 0
    assert report.collective_s > 0
    assert report.steps["train_step"]["count"] == 2
    # tp2+sp: the measured collectives are exactly the kinds the golden
    # contract lists (this XLA:CPU partitions the step without the
    # all-to-all the jax 0.4.37 one emitted; the contract was regenerated)
    measured = report.collective_counts()
    golden = json.loads(open(os.path.join(
        REPO, "megatron_tpu", "analysis", "golden",
        "train_tp2_sp.json")).read())["hlo"]["collectives"]
    assert set(measured) == set(golden), (measured, sorted(golden))
    # every static op runs at least once per device per step (8 devices x
    # 2 profiled steps); the layer scan only multiplies
    for op, row in golden.items():
        assert measured[op] >= row["count"] * 8 * 2, (op, measured)


def test_capture_holds_the_loops_spans_and_no_python_tracer(tmp_path):
    """A --profile window over three whole loop passes, through the one
    capture helper: the loop thread's line holds a `train-pass` a pass,
    numbered by the iteration it dispatches, with the timers' spans
    nested inside under their own names; the prefetcher's transfer is
    annotated on its worker's line; and no event of the Python tracer
    (`$file.py:12 fn`) is in the file. The journal gains `dispatch_ms` a
    step and, after the loop, one `step_program`."""
    from megatron_tpu.analysis.targets import tiny_model
    from megatron_tpu.config import (
        OptimizerConfig, ParallelConfig, RunConfig, TrainingConfig,
    )
    from megatron_tpu.telemetry.tracing import (
        analyze_events, classify_xspace, load_xspace,
    )
    from megatron_tpu.training.pretrain import TrainLoop

    trace_dir, tele_dir = tmp_path / "trace", tmp_path / "tele"
    cfg = RunConfig(
        model=tiny_model(num_layers=2),
        parallel=ParallelConfig(),
        optimizer=OptimizerConfig(lr=1e-3, lr_decay_style="constant"),
        training=TrainingConfig(
            micro_batch_size=1, global_batch_size=8, train_iters=7,
            log_interval=1, profile=True, profile_step_start=3,
            profile_step_end=7, profile_dir=str(trace_dir),
            telemetry_dir=str(tele_dir), timing_log_level=1))
    rows = np.random.default_rng(0).integers(0, 128, (8, 33))

    def factory(consumed, gbs):
        while True:
            yield {"tokens": rows[:, :-1].astype(np.int64),
                   "labels": rows[:, 1:].astype(np.int64),
                   "loss_mask": np.ones((gbs, 32), np.float32)}

    TrainLoop(cfg, log=lambda s: None).train(factory)

    [path] = _xplane_under(trace_dir)
    space = load_xspace(path)
    host = space.plane("/host:CPU")
    names = {e.name for ln in host.lines for e in ln.events}
    assert not [n for n in names if n.startswith("$")]
    [loop_line] = [ln for ln in host.lines
                   if any(e.name == "train-pass" for e in ln.events)]
    passes = sorted((e for e in loop_line.events if e.name == "train-pass"),
                    key=lambda e: e.start_ps)
    # the window opens inside pass 3 and closes inside pass 7
    assert [e.stats["step_num"] for e in passes] == [4, 5, 6]
    for p in passes:
        inside = {e.name for e in loop_line.events
                  if p.start_ps <= e.start_ps and e.end_ps <= p.end_ps}
        assert {"batch-generator", "forward-backward-optimizer",
                "metrics-fetch"} <= inside
    [worker_line] = [ln for ln in host.lines
                     if any(e.name == "batch-transfer" for e in ln.events)]
    assert worker_line is not loop_line
    assert any(e.name == "batch-transfer-dispatch"
               for e in worker_line.events)
    # the operator's reader takes the annotation as a step marker
    report = analyze_events(classify_xspace(space))
    assert report.steps["train-pass"]["count"] == 3

    with open(tele_dir / "events.jsonl") as f:
        records = [json.loads(line) for line in f]
    steps = [r for r in records if r["kind"] == "step"]
    assert len(steps) == 7
    assert all(0 < r["dispatch_ms"] <= r["step_ms"] for r in steps)
    [program] = [r for r in records if r["kind"] == "step_program"]
    # global batch 8 over the fake mesh's 8 data-parallel devices
    assert program["num_microbatches"] == 1 and program["temp_bytes"] > 0
    kinds = [r["kind"] for r in records]
    assert kinds.index("profile_end") < kinds.index("step_program")
    assert "step" not in kinds[kinds.index("step_program"):]


def test_no_profile_window_lowers_nothing(tmp_path):
    """Without a trace window the trainer journals no `step_program`:
    the extra lowering is paid only by a run that traced."""
    from megatron_tpu.analysis.targets import tiny_model
    from megatron_tpu.config import (
        OptimizerConfig, ParallelConfig, RunConfig, TrainingConfig,
    )
    from megatron_tpu.training.pretrain import TrainLoop

    cfg = RunConfig(
        model=tiny_model(num_layers=2), parallel=ParallelConfig(),
        optimizer=OptimizerConfig(lr=1e-3, lr_decay_style="constant"),
        training=TrainingConfig(
            micro_batch_size=1, global_batch_size=8, train_iters=2,
            log_interval=1, telemetry_dir=str(tmp_path / "tele")))
    rows = np.random.default_rng(0).integers(0, 128, (8, 33))

    def factory(consumed, gbs):
        while True:
            yield {"tokens": rows[:, :-1].astype(np.int64),
                   "labels": rows[:, 1:].astype(np.int64),
                   "loss_mask": np.ones((gbs, 32), np.float32)}

    loop = TrainLoop(cfg, log=lambda s: None)
    loop.train(factory)
    assert loop._profiled_step is None
    with open(tmp_path / "tele" / "events.jsonl") as f:
        kinds = [json.loads(line)["kind"] for line in f]
    assert kinds.count("step") == 2 and "step_program" not in kinds


def test_live_contract_measured_equals_expected_ulysses(tmp_path):
    """The acceptance gate: a fake-mesh CPU run of the ulysses_cp2
    contract target reconciles measured==expected for EVERY collective
    (no runtime loops in this program, so dynamic == static)."""
    import jax
    import jax.numpy as jnp
    from megatron_tpu.analysis import targets as T
    from megatron_tpu.telemetry.tracing import (
        analyze_events, classify_xspace, compare_contract, load_xspace,
    )

    t = T.ulysses_attention_target("ulysses_cp2")

    def ulysses_fwdbwd(q, k, v):
        return t.fn(q, k, v)

    fn = jax.jit(ulysses_fwdbwd)
    rng = np.random.default_rng(0)
    args = [jnp.asarray(rng.standard_normal(a.shape), a.dtype)
            for a in t.args]
    trace_dir = tmp_path / "trace"
    with jax.sharding.set_mesh(t.mesh):
        jax.block_until_ready(fn(*args))  # compile outside the window
        jax.profiler.start_trace(str(trace_dir))
        try:
            for _ in range(2):
                jax.block_until_ready(fn(*args))
        finally:
            jax.profiler.stop_trace()

    events = []
    for f in _xplane_under(trace_dir):
        events.extend(classify_xspace(load_xspace(f)))
    report = analyze_events(events, module="jit_ulysses_fwdbwd")
    golden = json.loads(open(os.path.join(
        REPO, "megatron_tpu", "analysis", "golden",
        "ulysses_cp2.json")).read())
    cmp = compare_contract(report, golden, "ulysses_cp2")
    assert cmp.matches, cmp.problems
    # 8 mesh devices x 2 profiled executions
    assert cmp.executions == t.mesh.devices.size * 2
    assert {r["op"] for r in cmp.rows} == set(
        golden["hlo"]["collectives"]) == {"all-to-all"}
    # the manifest's byte volumes joined in: effective bus bandwidth
    assert cmp.bandwidth["all-to-all"]["bus_gbps"] > 0


# ---------------------------------------------------------------------------
# on-demand capture: SIGUSR1 window + abort-path flush
# ---------------------------------------------------------------------------


def test_sigusr1_arms_bounded_window(tmp_path):
    """SIGUSR1 mid-run opens a --profile_signal_steps window with no
    --profile and no restart: begin/end journaled, the trace readable,
    the run otherwise untouched."""
    import signal as signal_module

    from megatron_tpu.config import (
        ModelConfig, OptimizerConfig, ParallelConfig, RunConfig,
        TrainingConfig,
    )
    from megatron_tpu.telemetry.journal import read_events
    from megatron_tpu.telemetry.tracing import (
        analyze_events, classify_xspace, find_xplane_files, load_xspace,
    )
    from megatron_tpu.training.pretrain import TrainLoop

    model = ModelConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4,
        num_kv_heads=2, ffn_hidden_size=64, vocab_size=64, seq_length=16,
        params_dtype="float32").validate()
    trace_dir = tmp_path / "sigtrace"
    cfg = RunConfig(
        model=model, parallel=ParallelConfig(),
        optimizer=OptimizerConfig(lr=1e-3, lr_decay_style="constant"),
        training=TrainingConfig(
            micro_batch_size=2, global_batch_size=16, train_iters=6,
            log_interval=1, seed=3, telemetry_dir=str(tmp_path / "tele"),
            profile_dir=str(trace_dir), profile_signal_steps=2))
    rng = np.random.default_rng(0)
    pool = rng.integers(0, 64, (96, 17))
    fired = []

    def factory(consumed, gbs):
        def gen():
            i = 0
            while True:
                if i == 2 * gbs and not fired:
                    # the 3rd batch pop delivers the signal (possibly
                    # from the prefetcher thread — os.kill targets the
                    # process; the main-thread handler just sets a flag)
                    fired.append(True)
                    os.kill(os.getpid(), signal_module.SIGUSR1)
                rows = pool[i % 80:i % 80 + gbs]
                yield {"tokens": rows[:, :-1].astype(np.int64),
                       "labels": rows[:, 1:].astype(np.int64),
                       "loss_mask": np.ones((gbs, 16), np.float32)}
                i += gbs
        return gen()

    logs = []
    loop = TrainLoop(cfg, log=logs.append)
    loop.train(factory)
    assert loop.iteration == 6  # the run completed normally
    assert any("profiler: tracing steps" in ln for ln in logs)
    assert any("profiler: trace written" in ln for ln in logs)
    events, _ = read_events(str(tmp_path / "tele" / "events.jsonl"))
    begins = [e for e in events if e["kind"] == "profile_begin"]
    ends = [e for e in events if e["kind"] == "profile_end"]
    assert len(begins) == 1 and begins[0]["source"] == "SIGUSR1"
    assert begins[0]["until"] - begins[0]["iteration"] == 2
    assert len(ends) == 1
    files = find_xplane_files(str(trace_dir))
    assert files
    tevents = []
    for f in files:
        tevents.extend(classify_xspace(load_xspace(f)))
    report = analyze_events(tevents)
    assert report.module == "jit_train_step"
    assert report.compute_s > 0


def test_profile_abort_flushes_and_journals(tmp_path):
    """The abort paths (hang watchdog, preemption, peer abort) close a
    live window instead of leaving a torn trace across os._exit: the
    flush is bounded, `profile_aborted` is journaled either way, and the
    flushed trace is readable."""
    import types

    import jax
    import jax.numpy as jnp
    from megatron_tpu.telemetry.goodput import GoodputTracker
    from megatron_tpu.telemetry.journal import EventJournal
    from megatron_tpu.telemetry.metrics import MetricsRegistry
    from megatron_tpu.telemetry.run import RunTelemetry
    from megatron_tpu.telemetry.tracing import find_xplane_files
    from megatron_tpu.training.pretrain import TrainLoop

    journal = EventJournal(str(tmp_path / "events.jsonl"))
    rt = RunTelemetry(journal, GoodputTracker(), MetricsRegistry(),
                      None, None)
    logs = []
    ns = types.SimpleNamespace(_profiling=True, _profile_until=99,
                               telemetry=rt, log=logs.append,
                               iteration=4)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    TrainLoop._profile_abort(ns, "hang")
    assert ns._profiling is False and ns._profile_until is None
    assert find_xplane_files(str(tmp_path / "trace"))  # flushed=readable
    # idempotent: a second abort (peer_abort racing the hang) is a no-op
    TrainLoop._profile_abort(ns, "peer_abort")
    aborted = [e for e in journal.events()
               if e["kind"] == "profile_aborted"]
    assert len(aborted) == 1
    assert aborted[0]["reason"] == "hang" and aborted[0]["flushed"] is True
    # the journal-only path (wedged-filesystem callers): no stop_trace,
    # flushed=False recorded
    ns2 = types.SimpleNamespace(_profiling=True, _profile_until=None,
                                telemetry=rt, log=logs.append,
                                iteration=5)
    TrainLoop._profile_abort(ns2, "peer_abort", flush=False)
    aborted = [e for e in journal.events()
               if e["kind"] == "profile_aborted"]
    assert len(aborted) == 2 and aborted[1]["flushed"] is False
    journal.close()


def test_engine_capture_trace_busy_raises():
    """The process-global profiler session serializes: a capture while
    another is live raises instead of corrupting it."""
    import pytest as _pytest

    from megatron_tpu.inference import engine as engine_mod

    eng = object.__new__(engine_mod.InferenceEngine)
    eng.stats = {"ticks": 0}
    with engine_mod._PROFILE_LOCK:
        with _pytest.raises(RuntimeError, match="already in progress"):
            eng.capture_trace("/tmp/unused", ticks=1, timeout_s=0.1)


# ---------------------------------------------------------------------------
# recorded TPU traces (benchmark/fixtures): what a real device plane holds
# ---------------------------------------------------------------------------

TPU_FIXTURES = os.path.join(REPO, "benchmark", "fixtures")


def _tpu_report(name):
    from megatron_tpu.telemetry.tracing import (
        analyze_events, classify_xspace, load_xspace,
    )

    events = classify_xspace(load_xspace(os.path.join(TPU_FIXTURES, name)))
    return events, analyze_events(events)


def test_tpu_trace_ops_come_from_the_xla_ops_line_alone():
    """PR 22's recording of the one-chip train step, whose program has no
    scopes. Before PR 23 this read as module <none>, infeed 228.9 ms
    against compute 141.7 ms (the `Async XLA Ops` line's `copy-done`s
    counted as operations), steps "0".."8", and ops named by their whole
    HLO text."""
    from megatron_tpu.telemetry.tracing.events import KIND_HOST

    events, report = _tpu_report("train_seq4k_tpu_v5e.xplane.pb")
    ops = [e for e in events if e.kind != KIND_HOST]
    assert {e.line for e in ops} == {"XLA Ops"}
    assert {e.module for e in ops} == {"jit_train_step"}
    assert report.module == "jit_train_step"
    assert report.compute_s == pytest.approx(0.1264, rel=0.01)
    assert report.busy_s["infeed"] < 0.001        # real copies on XLA Ops
    assert report.collective_s == 0
    # one marker for the device's step envelopes, a step long
    assert set(report.steps) == {"Steps"}
    assert report.steps["Steps"]["count"] == 9
    assert report.steps["Steps"]["p50_ms"] == pytest.approx(170.5, abs=0.5)
    top = report.ops[0]
    assert (top.name, top.detail) == (
        "checkpoint.20", "bf16[1,32,4096,128] custom-call")
    assert all(len(o.name) < 60 and " = " not in o.name
               for o in report.ops)
    # no names in this program: no scope table, gaps under no span
    assert report.scopes == {} and report.kernels == {}
    assert [g["span"] for g in report.idle_gaps] == ["<no host span>"]


@pytest.mark.parametrize("name, planes, kernel_calls", [
    ("named_seq4k_tpu_v5e.xplane.pb", 1, {"flash_fwd": 8,
                                          "flash_bwd_dq": 4,
                                          "flash_bwd_dkv": 4}),
    ("named_tp2dp2_tpu_v5e.xplane.pb", 2, {"flash_fwd": 16,
                                           "flash_bwd_dq": 8,
                                           "flash_bwd_dkv": 8}),
])
def test_named_tpu_trace_reads_by_scope_kernel_and_host_span(
        name, planes, kernel_calls):
    """PR 23's recordings (two whole runs a device, the loop thread's
    spans): own time by the program's scopes, kernels by name, idle gaps
    by what the loop thread was inside."""
    from megatron_tpu.telemetry.tracing.events import (
        KIND_HOST, REGION_SCOPES,
    )

    events, report = _tpu_report(name)
    ops = [e for e in events if e.kind != KIND_HOST]
    assert len({e.plane for e in ops}) == planes
    assert report.module == "jit_train_step"
    assert set(report.scopes) == set(REGION_SCOPES) | {"other"}
    whole = sum(report.scopes.values())
    # every op's own time is under exactly one scope
    assert whole == pytest.approx(
        sum(v for k, v in report.busy_s.items() if k != KIND_HOST))
    assert list(report.scopes)[:2] == ["attention", "mlp"]
    assert (report.scopes["other"] + report.scopes["embed"]) < 0.06 * whole
    assert {k: v["count"] for k, v in report.kernels.items()} == kernel_calls
    assert sum(k["self_s"] for k in report.kernels.values()) == (
        pytest.approx(sum(o.self_s for o in report.ops
                          if o.detail.endswith("custom-call")
                          and o.name.startswith("flash_")), rel=1e-4))
    assert report.ops[0].name.startswith("flash_bwd_dkv")
    # host and device share a clock: the loop's passes are a step long,
    # and the device ran dry only while the loop waited for its metrics
    assert report.steps["train-pass"]["count"] >= 2
    assert report.idle_gaps[0]["span"] == "np.asarray(jax.Array)"
    assert sum(g["total_s"] for g in report.idle_gaps) < 1e-3
    if planes > 1:
        assert {c.op for c in report.collectives} >= {"all-gather",
                                                      "all-reduce"}
    d = report.to_dict(top=5)
    assert json.dumps(d) and d["scopes"] and d["kernels"] and d["idle_gaps"]
    # the same time by class of work (the profiler's hlo_category): each
    # region's classes sum to the region; the kernels are `attention`'s
    # class `kernel`; what has no name stack is a part of `other`
    from megatron_tpu.telemetry.tracing.analyze import UNNAMED_SCOPE
    from megatron_tpu.telemetry.tracing.events import OP_CLASSES

    table = report.scope_classes
    assert set(table) == set(report.scopes) | {UNNAMED_SCOPE}
    for region, seconds in report.scopes.items():
        assert list(table[region]) == list(OP_CLASSES)
        assert sum(table[region].values()) == pytest.approx(seconds)
    assert table["attention"]["kernel"] == pytest.approx(
        sum(k["self_s"] for k in report.kernels.values()), rel=1e-4)
    assert table["mlp"]["matmul"] > 0.9 * report.scopes["mlp"]
    assert all(table[UNNAMED_SCOPE][c] <= table["other"][c]
               for c in OP_CLASSES)
    fused = sum(row["collective_fused"] for row in table.values())
    if planes > 1:
        # the reduce-scatter behind a projection is a fusion by name and
        # a collective by category: ~15.5 ms a run on each device
        assert table["attention"]["collective_fused"] == pytest.approx(
            4 * 15.52e-3, rel=0.01)
        assert table[UNNAMED_SCOPE]["collective"] > 0
    else:
        assert fused == 0 and not any(row["collective"]
                                      for row in table.values())
    assert d["scope_classes"]["mlp"]["matmul"] > 0


@pytest.mark.parametrize("backward", [
    ("flash_bwd",), ("flash_bwd_dq", "flash_bwd_dkv"),
], ids=["fused", "split_pair"])
def test_report_books_the_backward_kernels_a_step_runs(backward):
    """The kernel table is keyed by what stands in front of the name
    stack's closing `pallas_call`: a step that runs the fused backward
    reads one `flash_bwd` row (own time under `attention`, class
    `kernel`), one that still runs the split pair reads its two names
    (PR 23's recordings above: made before PR 40 fused them)."""
    from megatron_tpu.telemetry.tracing.analyze import analyze_events
    from megatron_tpu.telemetry.tracing.events import KIND_COMPUTE, OpEvent

    US = 1_000_000  # picoseconds
    stack = ("jit(train_step)/while/body/closed_call/transpose(jvp("
             "attention))/attn_core/{}/pallas_call:")

    def call(kernel, start, dur):
        return OpEvent(f"{kernel}.3", KIND_COMPUTE, start * US, dur * US,
                       "/device:TPU:0", "XLA Ops", module="jit_train_step",
                       detail="bf16[1,32,4096,128] custom-call",
                       tf_op=stack.format(kernel), category="custom-call")

    events = [call("flash_fwd", 0, 1800)]
    for layer in range(2):
        for i, kernel in enumerate(backward):
            events.append(call(kernel, 2000 * (1 + 2 * layer + i), 1500))
    report = analyze_events(events)
    assert {k: v["count"] for k, v in report.kernels.items()} == dict(
        {"flash_fwd": 1}, **{k: 2 for k in backward})
    for kernel in backward:
        assert report.kernels[kernel]["self_s"] == pytest.approx(3000e-6)
    assert set(report.scopes) == {"attention"}
    assert report.to_dict()["scope_classes"]["attention"]["kernel"] == (
        pytest.approx(sum(k["self_s"] for k in report.kernels.values())))


@pytest.mark.parametrize("name, category, kernel, want", [
    ("flash_fwd.13", "custom-call", True, "kernel"),
    ("flash_bwd.7", "custom-call", True, "kernel"),
    ("custom-call.5", "custom-call", False, "rest"),
    ("fusion.472", "convolution fusion", False, "matmul"),
    ("all-gather-start.3", "all-gather-start", False, "collective"),
    ("all-reduce.93", "all-reduce", False, "collective"),
    ("reduce_scatter.13", "reduce-scatter", False, "collective"),
    ("fusion.396", "all-reduce-scatter fusion", False, "collective_fused"),
    ("async-collective-start", "custom fusion", False, "collective_fused"),
    ("fusion.389", "loop fusion", False, "elementwise"),
    ("copy.182", "data formatting", False, "data_movement"),
    ("copy-done.3", "copy-done", False, "data_movement"),
    ("while.143", "while", False, "rest"),
    ("fusion.1", None, False, "rest"),
    ("fft.1", "a category of tomorrow", False, "rest"),
])
def test_an_operation_gets_its_class_from_name_and_category(
        name, category, kernel, want):
    from megatron_tpu.telemetry.tracing.events import OP_CLASSES, op_class

    assert op_class(name, category, kernel) == want
    assert want in OP_CLASSES


def test_idle_gaps_fall_under_the_innermost_loop_thread_span():
    from megatron_tpu.telemetry.tracing.analyze import idle_gaps_by_host_span
    from megatron_tpu.telemetry.tracing.events import (
        KIND_COMPUTE, KIND_HOST, OpEvent,
    )

    US = 1_000_000  # picoseconds

    def op(start, dur):
        return OpEvent("fusion.1", KIND_COMPUTE, start * US, dur * US,
                       "/device:TPU:0", "XLA Ops")

    def span(name, start, dur, line="loop", step=None):
        return OpEvent(name, KIND_HOST, start * US, dur * US, "/host:CPU",
                       line, step_num=step)

    events = [
        op(0, 100), op(150, 100), op(300, 100), op(1000, 50),
        span("train-pass", 0, 600, step=1),
        span("batch-generator", 90, 30),            # open at 100
        span("forward-backward-optimizer", 200, 200),
        span("PjitFunction(train_step)", 240, 40),  # open at 250
        span("noise", 390, 100, line="another thread"),
        # the gap that opens at 400: train-pass alone; nothing at 1050
    ]
    gaps = {g["span"]: g for g in idle_gaps_by_host_span(events)}
    assert gaps["batch-generator"]["total_s"] == pytest.approx(50e-6)
    assert gaps["PjitFunction(train_step)"]["count"] == 1
    assert gaps["train-pass"]["total_s"] == pytest.approx(600e-6)
    assert "noise" not in gaps and len(gaps) == 3
    assert idle_gaps_by_host_span([e for e in events
                                   if e.kind == KIND_HOST]) == []


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_trace_report_cli_text_and_json(capsys):
    from tools import trace_report

    assert trace_report.main([FIXTURE]) == 0
    out = capsys.readouterr().out
    assert "jit_fixture_step" in out
    assert "all-reduce" in out
    assert "exposed" in out

    assert trace_report.main([FIXTURE, "--format", "json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["report"]["module"] == "jit_fixture_step"
    assert data["report"]["busy_s"]["compute"] > 0


@pytest.mark.parametrize("name, wants, never", [
    ("train_seq4k_tpu_v5e.xplane.pb",
     ["module jit_train_step", "infeed 378.8us",
      "checkpoint.20 bf16[1,32,4096,128] custom-call"],
     ["<none>", "own time by scope", " = "]),
    ("named_seq4k_tpu_v5e.xplane.pb",
     ["module jit_train_step", "own time by scope", "kernel flash_fwd",
      "kernel flash_bwd_dkv", "idle gaps of the first device",
      "np.asarray(jax.Array)", "train-pass",
      # the class columns, and the row of what carries no name at all
      "      kernel       matmul  elementwise data_movemen", "(unnamed)"],
     ["<none>", " = ", "collective_f"]),
    ("named_tp2dp2_tpu_v5e.xplane.pb",
     ["   collective collective_f  elementwise", "(unnamed)"],
     ["<none>", " = bf16", "%fusion"]),
])
def test_trace_report_cli_on_tpu_traces(capsys, name, wants, never):
    from tools import trace_report

    path = os.path.join(REPO, "benchmark", "fixtures", name)
    assert trace_report.main([path]) == 0
    out = capsys.readouterr().out
    for text in wants:
        assert text in out, (text, out)
    for text in never:
        assert text not in out, (text, out)


def test_trace_report_cli_never_imports_jax(tmp_path):
    """The jaxlint contract: reading a trace works on a machine with
    nothing but python + the .pb — jax must never be imported."""
    code = (
        "import sys, runpy\n"
        f"sys.argv = ['trace_report.py', {FIXTURE!r}]\n"
        "try:\n"
        f"    runpy.run_path({os.path.join(REPO, 'tools', 'trace_report.py')!r},"
        " run_name='__main__')\n"
        "except SystemExit as e:\n"
        "    assert e.code == 0, e.code\n"
        "assert 'jax' not in sys.modules, 'trace_report imported jax'\n"
        "print('NOJAX_OK')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "NOJAX_OK" in out.stdout
