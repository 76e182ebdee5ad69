"""Unified telemetry subsystem tests (ISSUE 4).

Covers the tentpole's acceptance surface:
  * journal write / rotation / crash-replay (torn final line tolerated);
  * Prometheus text exposition (counters/gauges/histograms, labels,
    escaping, get-or-create registration);
  * goodput accounting — including the REAL train-loop path: a subprocess
    pretrain run under the `slow_save` fault whose journal must show the
    checkpoint stall attributed to non-productive time;
  * recompile tracking: the serving engine's zero-recompiles-after-warmup
    invariant as a runtime counter over a real jitted decode step;
  * the flight recorder firing deterministically on a stalled heartbeat
    (short deadline, bundle contents checked);
  * GET /metrics on a running serving HTTP server returning Prometheus
    text with slot/queue/latency metrics;
  * tools/telemetry_report.py summarizing a journal.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request
from http.server import ThreadingHTTPServer

import numpy as np
import pytest

from megatron_tpu import telemetry
from megatron_tpu.telemetry import (
    EventJournal, FlightRecorder, GoodputTracker, MetricsRegistry,
    read_events, recompile_tracker,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# metrics registry + Prometheus exposition


def test_prometheus_exposition_format():
    r = MetricsRegistry()
    c = r.counter("http_requests_total", "requests served",
                  label_names=("status",))
    c.inc(status="200")
    c.inc(2, status="500")
    g = r.gauge("slots_active", "live slots")
    g.set(3)
    h = r.histogram("tick_seconds", "tick time", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    text = r.render()
    # HELP/TYPE headers precede each family, one family per metric name
    assert "# HELP http_requests_total requests served" in text
    assert "# TYPE http_requests_total counter" in text
    assert '# TYPE tick_seconds histogram' in text
    assert 'http_requests_total{status="200"} 1' in text
    assert 'http_requests_total{status="500"} 2' in text
    assert "slots_active 3" in text
    # cumulative le buckets + +Inf + sum/count
    assert 'tick_seconds_bucket{le="0.01"} 1' in text
    assert 'tick_seconds_bucket{le="0.1"} 2' in text
    assert 'tick_seconds_bucket{le="1"} 3' in text
    assert 'tick_seconds_bucket{le="+Inf"} 4' in text
    assert "tick_seconds_count 4" in text
    assert re.search(r"tick_seconds_sum 5\.55\d*", text)


def test_prometheus_label_escaping():
    r = MetricsRegistry()
    c = r.counter("errors_total", "errors", label_names=("message",))
    c.inc(message='bad "quote"\nand\\slash')
    text = r.render()
    assert r'message="bad \"quote\"\nand\\slash"' in text


def test_registry_get_or_create_and_conflicts():
    r = MetricsRegistry()
    a = r.counter("x_total", "x")
    b = r.counter("x_total", "x")
    assert a is b  # two subsystems sharing a name share the collector
    with pytest.raises(ValueError):
        r.gauge("x_total", "x")  # same name, different type = a bug
    with pytest.raises(ValueError):
        r.counter("x_total", "x", label_names=("k",))  # schema change too
    with pytest.raises(ValueError):
        a.inc(-1)  # counters are monotonic
    with pytest.raises(ValueError):
        a.inc(1, nope="v")  # undeclared label


def test_labeled_registry_view():
    """The CP x DP lane facade: constant labels stamped onto every
    collector a lane registers, so N lanes share one host registry
    while the exposition keeps per-lane series."""
    from megatron_tpu.telemetry.metrics import LabeledRegistryView

    r = MetricsRegistry()
    lane0 = LabeledRegistryView(r, lane="0")
    lane1 = LabeledRegistryView(r, lane="1")
    c0 = lane0.counter("engine_steps_total", "steps")
    c1 = lane1.counter("engine_steps_total", "steps")
    c0.inc(3)
    c1.inc(5)
    assert c0.value() == 3.0 and c1.value() == 5.0
    # per-call labels merge with the pinned one
    g0 = lane0.gauge("engine_free", "free", label_names=("shard",))
    g0.set(7, shard="1")
    assert g0.value(shard="1") == 7.0
    text = r.render()
    assert 'engine_steps_total{lane="0"} 3' in text
    assert 'engine_steps_total{lane="1"} 5' in text
    assert 'engine_free{lane="0",shard="1"} 7' in text or \
        'engine_free{shard="1",lane="0"} 7' in text
    # passing the pinned label per-call is a collision, not a silent
    # override
    with pytest.raises(ValueError, match="pinned"):
        c0.inc(lane="9")
    with pytest.raises(ValueError):
        LabeledRegistryView(r)  # a view without labels is pointless
    # histograms proxy too (the latency series the router percentiles)
    h = lane1.histogram("engine_tick_seconds", "tick")
    h.observe(0.5)
    assert 'engine_tick_seconds_count{lane="1"} 1' in r.render()


# ---------------------------------------------------------------------------
# event journal


def test_journal_write_and_replay(tmp_path):
    j = EventJournal(str(tmp_path / "events.jsonl"))
    j.emit("step", iteration=1, loss=2.5)
    j.emit("checkpoint_begin", iteration=1, async_save=True)
    j.close()
    evs, torn = read_events(str(tmp_path / "events.jsonl"))
    assert torn is None
    assert [e["kind"] for e in evs] == ["step", "checkpoint_begin"]
    assert evs[0]["loss"] == 2.5 and evs[0]["ts"] > 0
    # numpy scalars must serialize (journal fields come from jax/numpy)
    j2 = EventJournal(str(tmp_path / "events.jsonl"))
    j2.emit("step", loss=np.float32(1.5), n=np.int64(3))
    j2.close()
    evs, _ = read_events(str(tmp_path / "events.jsonl"))
    assert evs[-1]["loss"] == 1.5 and evs[-1]["n"] == 3


def test_journal_rotation_bounds_disk(tmp_path):
    path = str(tmp_path / "events.jsonl")
    j = EventJournal(path, max_bytes=500, keep_segments=2)
    for i in range(60):
        j.emit("step", iteration=i)
    j.close()
    segs = j.segments()
    assert len(segs) <= 3  # live + keep_segments
    assert all(os.path.getsize(s) <= 600 for s in segs)
    # replay across segments is oldest-first and contiguous at the tail
    its = [e["iteration"] for e in j.events()]
    assert its == sorted(its)
    assert its[-1] == 59
    assert j.tail(3) == j.events()[-3:]


def test_journal_crash_replay_tolerates_torn_line(tmp_path):
    path = str(tmp_path / "events.jsonl")
    j = EventJournal(path)
    j.emit("step", iteration=1)
    j.emit("step", iteration=2)
    j.close()
    with open(path, "a") as f:
        f.write('{"ts": 3, "kind": "step", "iterat')  # SIGKILL mid-write
    evs, torn = read_events(path)
    assert [e["iteration"] for e in evs] == [1, 2]
    assert torn is not None and torn.startswith('{"ts": 3')


# ---------------------------------------------------------------------------
# goodput accounting


def test_goodput_tracker_split_and_report():
    now = [100.0]
    gp = GoodputTracker(clock=lambda: now[0])
    gp.attribute("productive", 6.0)
    gp.attribute("checkpoint_stall", 2.0)
    with gp.track("eval"):
        now[0] += 1.0
    now[0] = 110.0
    rep = gp.report()
    assert rep["wall_s"] == 10.0
    assert rep["goodput"] == pytest.approx(0.6)
    assert rep["checkpoint_stall_s"] == 2.0
    assert rep["eval_s"] == 1.0
    # the unattributed remainder lands in `other`; the split sums to wall
    assert rep["other_s"] == pytest.approx(1.0)
    total = sum(rep[f"{c}_s"] for c in telemetry.CATEGORIES)
    assert total == pytest.approx(rep["wall_s"])
    with pytest.raises(ValueError):
        gp.attribute("napping", 1.0)


def test_recompile_tracker_counts_backend_compiles():
    import jax
    import jax.numpy as jnp

    t = recompile_tracker()
    f = jax.jit(lambda x: x * 3 + 1)
    f(jnp.zeros(7)).block_until_ready()
    snap = t.snapshot()
    f(jnp.ones(7)).block_until_ready()     # cache hit: no new compile
    assert t.delta(snap)["compiles"] == 0
    f(jnp.ones(13)).block_until_ready()    # new shape: recompile
    d = t.delta(snap)
    assert d["compiles"] >= 1
    assert d["compile_seconds"] > 0


# ---------------------------------------------------------------------------
# flight recorder


def test_flight_recorder_fires_deterministically_on_stall(tmp_path):
    """Short deadline + stalled heartbeat => exactly one bundle, with
    all-thread stacks and the journal tail (the ISSUE acceptance test)."""
    j = EventJournal(str(tmp_path / "events.jsonl"))
    for i in range(5):
        j.emit("step", iteration=i)
    logs = []
    fr = FlightRecorder(out_dir=str(tmp_path / "bundles"), deadline_s=0.25,
                        journal=j, tail_events=3, poll_s=0.05,
                        log=logs.append)
    with fr:
        fr.heartbeat("iteration 5")
        deadline = time.monotonic() + 10.0
        while not fr.bundles and time.monotonic() < deadline:
            time.sleep(0.05)  # heartbeat stalls; watchdog must fire
        # one bundle per stall, not one per poll tick
        time.sleep(0.4)
    assert len(fr.bundles) == 1, logs
    bundle = fr.bundles[0]
    meta = json.load(open(os.path.join(bundle, "meta.json")))
    assert meta["deadline_s"] == 0.25
    assert meta["heartbeat_age_s"] >= 0.25
    assert meta["last_note"] == "iteration 5"
    stacks = open(os.path.join(bundle, "stacks.txt")).read()
    assert "--- thread MainThread" in stacks
    assert "flight-recorder" in stacks  # every thread, watchdog included
    evs, _ = read_events(os.path.join(bundle, "events.jsonl"))
    assert [e["iteration"] for e in evs] == [2, 3, 4]  # last N only


def test_flight_recorder_heartbeat_keeps_it_quiet(tmp_path):
    fr = FlightRecorder(out_dir=str(tmp_path), deadline_s=0.3, poll_s=0.05,
                        log=lambda m: None)
    with fr:
        for _ in range(12):
            fr.heartbeat()
            time.sleep(0.05)  # 0.6s total, never 0.3s without a beat
    assert fr.bundles == []


def test_flight_recorder_not_live_before_first_heartbeat(tmp_path):
    """The window between arming and the first heartbeat holds the
    initial multi-minute XLA compile — it must never be judged against a
    steady-state step deadline (abort=True would crash-loop there)."""
    fr = FlightRecorder(out_dir=str(tmp_path), deadline_s=0.15, poll_s=0.03,
                        log=lambda m: None)
    with fr:
        time.sleep(0.6)  # way past the deadline, zero heartbeats
        assert fr.bundles == []
        fr.heartbeat("first step")  # live now; a stall past here fires
        deadline = time.monotonic() + 10.0
        while not fr.bundles and time.monotonic() < deadline:
            time.sleep(0.03)
    assert len(fr.bundles) == 1


def test_flight_recorder_refires_after_recovery(tmp_path):
    """A fresh heartbeat after a dumped stall re-arms the watchdog."""
    fr = FlightRecorder(out_dir=str(tmp_path), deadline_s=0.2, poll_s=0.04,
                        log=lambda m: None)
    with fr:
        fr.heartbeat("first step")  # the watchdog goes live here
        deadline = time.monotonic() + 10.0
        while len(fr.bundles) < 1 and time.monotonic() < deadline:
            time.sleep(0.04)
        fr.heartbeat("recovered")  # re-arm
        while len(fr.bundles) < 2 and time.monotonic() < deadline:
            time.sleep(0.04)
    assert len(fr.bundles) == 2


# ---------------------------------------------------------------------------
# serving engine: metrics + the zero-recompiles-after-warmup invariant


def _tiny_cfg():
    from megatron_tpu.models import presets

    return presets.tiny(vocab_size=64, seq_length=64)


def test_engine_metrics_and_zero_recompiles_after_warmup():
    """Two waves of heterogeneous traffic through a REAL jitted decode
    step: the decode jit cache must hold exactly the warmup entry, the
    runtime counter must stay 0, and the latency/occupancy collectors
    must have observed the traffic."""
    import jax

    from megatron_tpu.inference.engine import InferenceEngine, Request
    from megatron_tpu.models.params import init_params

    cfg = _tiny_cfg()
    # COMMITTED params, like every checkpoint-loaded serving deployment
    # (load_params_only restores with explicit shardings): with any
    # committed argument, an uncommitted host-uploaded carry/cache once
    # split the decode step into two compiled signatures — this counter
    # is the regression gate for that (engine._commit)
    params = jax.device_put(
        init_params(cfg, jax.random.PRNGKey(0)),
        jax.sharding.SingleDeviceSharding(jax.devices()[0]))
    reg = MetricsRegistry()
    eng = InferenceEngine(cfg, params, num_slots=2, max_seq_len=48,
                          metrics=reg)
    rng = np.random.default_rng(0)

    def wave(n, temp):
        reqs = [eng.submit(Request(
            prompt=rng.integers(1, 64, 5).astype(np.int32),
            max_new_tokens=4, temperature=temp, top_k=3 if temp else 0,
            seed=i)) for i in range(n)]
        eng.run_until_idle()
        for r in reqs:
            assert r.error is None, r.error

    wave(3, 0.0)          # warmup + greedy traffic
    wave(3, 1.0)          # heterogeneous sampling knobs: SAME compiled step
    assert eng.stats["decode_recompiles"] == 0
    assert eng._decode_step._cache_size() == 1  # warmup entry only
    assert eng.stats["admitted"] == 6 and eng.stats["retired"] == 6

    text = reg.render()
    assert "engine_slots_total 2" in text
    assert "engine_requests_admitted_total 6" in text
    assert "engine_decode_recompiles_total 0" in text
    assert reg.get("engine_ttft_seconds").count() == 6
    assert reg.get("engine_decode_tick_seconds").count() == eng.stats["ticks"]
    assert reg.get("engine_time_per_output_token_seconds").count() == 6
    # idle engine: occupancy gauges back to zero
    assert "engine_slots_active 0" in text
    assert "engine_queue_depth 0" in text


def test_engine_tick_heartbeats_flight_recorder():
    """The engine's step loop feeds the watchdog (fake model: the wiring
    is scheduler-side, no compiles needed)."""
    from test_serving_engine import _fake_steps, make_engine

    from megatron_tpu.inference.engine import Request

    fr = FlightRecorder(out_dir="unused", deadline_s=60.0, log=lambda m: None)
    eng = _fake_steps(make_engine(metrics=MetricsRegistry(),
                                  flight_recorder=fr))
    eng.submit(Request(prompt=np.array([1, 2], np.int32), max_new_tokens=3))
    eng.run_until_idle()
    with fr._lock:
        assert fr._beat_count >= eng.stats["ticks"] > 0


def test_server_metrics_endpoint():
    """Acceptance: GET /metrics on a running serving engine returns
    Prometheus text with slot/queue/latency metrics."""
    import jax

    from megatron_tpu.inference.server import GenerationService, make_handler
    from megatron_tpu.models.params import init_params
    from megatron_tpu.tokenizer.tokenizer import NullTokenizer

    cfg = _tiny_cfg()
    params = init_params(cfg, jax.random.PRNGKey(1))
    reg = MetricsRegistry()
    service = GenerationService(cfg, params, NullTokenizer(63),
                                engine_slots=2, engine_max_seq_len=48,
                                metrics=reg)
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(service))
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        body = json.dumps({"prompts": ["3 7 11"], "tokens_to_generate": 4,
                           "top_k": 1}).encode()
        req = urllib.request.Request(f"http://127.0.0.1:{port}/api",
                                     data=body, method="PUT")
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert json.loads(resp.read())["text"]

        with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                    timeout=30) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        for family in ("engine_slots_total 2", "engine_slots_active",
                       "engine_queue_depth", "engine_ttft_seconds_bucket",
                       "engine_time_per_output_token_seconds_count",
                       'server_requests_total{status="200"} 1',
                       "server_request_seconds_count"):
            assert family in text, f"{family!r} missing from /metrics"

        with urllib.request.urlopen(f"http://127.0.0.1:{port}/healthz",
                                    timeout=30) as resp:
            assert json.loads(resp.read()) == {"ok": True, "engine": True}
    finally:
        server.shutdown()
        service.shutdown()


# ---------------------------------------------------------------------------
# train loop: goodput under the slow_save fault (REAL subprocess run)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    from tools import preprocess_data

    tmp = tmp_path_factory.mktemp("corpus")
    rng = np.random.default_rng(0)
    jsonl = tmp / "docs.jsonl"
    with open(jsonl, "w") as f:
        for _ in range(80):
            n = int(rng.integers(20, 60))
            f.write(json.dumps({"text": " ".join(
                str(int(x)) for x in rng.integers(0, 97, n))}) + "\n")
    prefix = str(tmp / "corpus")
    preprocess_data.main(["--input", str(jsonl), "--output_prefix", prefix,
                          "--tokenizer_type", "null", "--vocab_size", "97",
                          "--append_eod"])
    return prefix


@pytest.mark.slow  # 27s subprocess run measured cacheless (PR 4
# re-budget); the in-process goodput/journal units above stay tier-1
def test_train_goodput_attributes_slow_save_stall(tmp_path, corpus):
    """Acceptance: a faulted (slow_save) training run's journal shows the
    checkpoint stall attributed to non-productive time. --no_async_save
    keeps the injected sleep inside the train-loop stall span (async
    saves overlap it with compute by design)."""
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               MEGATRON_TPU_FAULT="slow_save:400")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    tele = str(tmp_path / "tele")
    r = subprocess.run([
        sys.executable, os.path.join(REPO, "pretrain_gpt.py"),
        "--num_layers", "2", "--hidden_size", "32",
        "--num_attention_heads", "4", "--vocab_size", "128",
        "--seq_length", "32", "--use_rms_norm", "--glu_activation", "swiglu",
        "--fp32", "--micro_batch_size", "2", "--global_batch_size", "2",
        "--train_iters", "4", "--log_interval", "1",
        "--lr", "1e-3", "--lr_decay_style", "constant",
        "--data_path", corpus, "--split", "95,5,0", "--eval_interval", "100",
        "--save", str(tmp_path / "ckpt"), "--save_interval", "2",
        "--no_async_save", "--telemetry_dir", tele],
        env=env, capture_output=True, text=True, cwd=REPO, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]

    evs, torn = read_events(os.path.join(tele, "events.jsonl"))
    assert torn is None
    kinds = [e["kind"] for e in evs]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    # the injected sleep is visible as a fault event AND in the stall
    assert [e for e in evs if e["kind"] == "fault_injection"
            and e["fault"] == "slow_save"]
    stalls = [e for e in evs if e["kind"] == "checkpoint_stall"]
    assert stalls and max(e["seconds"] for e in stalls) >= 0.4
    steps = [e for e in evs if e["kind"] == "step"]
    assert len(steps) == 4
    assert all(np.isfinite(e["loss"]) for e in steps)
    final = [e for e in evs if e["kind"] == "goodput"][-1]
    assert final["checkpoint_stall_s"] >= 0.4  # stall is NON-productive
    assert final["productive_s"] > 0
    assert final["goodput"] < 1.0
    assert [e for e in evs if e["kind"] == "checkpoint_commit"]

    # the report tool reads the same journal and surfaces the stall
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    summary = telemetry_report.summarize(telemetry_report.load_journal(tele))
    assert summary["steps"] == 4
    assert summary["faults"] == ["slow_save", "slow_save"]
    assert summary["goodput_pct"] < 100.0
    assert summary["stall_top"][0]["kind"] == "checkpoint_stall"
    assert summary["stall_top"][0]["seconds"] >= 0.4
    assert summary["step_ms"]["p50"] > 0
    text = telemetry_report.render(summary)
    assert "goodput:" in text and "checkpoint_stall" in text


# ---------------------------------------------------------------------------
# perfetto timeline + --format json (ISSUE 13)
# ---------------------------------------------------------------------------


def _import_telemetry_report():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    return telemetry_report


def validate_trace_events(trace):
    """Strict structural check against the Chrome trace-event JSON
     schema (the subset the converter emits): ``traceEvents`` list where
    every event has a phase, pid and microsecond timestamp; complete
    events carry a duration, metadata events carry args.name. Shared
    with test_coordination's multi-host round-trip."""
    assert isinstance(trace, dict)
    assert isinstance(trace["traceEvents"], list)
    assert trace.get("displayTimeUnit") in ("ms", "ns")
    for ev in trace["traceEvents"]:
        assert ev["ph"] in ("X", "i", "M"), ev
        assert isinstance(ev["pid"], int), ev
        assert isinstance(ev["name"], str) and ev["name"], ev
        assert isinstance(ev["ts"], (int, float)), ev
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
            assert isinstance(ev["tid"], int)
            assert ev["ts"] >= 0
        elif ev["ph"] == "i":
            assert ev["s"] in ("g", "p", "t")
            assert isinstance(ev["tid"], int)
        elif ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name")
            assert isinstance(ev["args"]["name"], str)
    return True


def test_perfetto_converter_lanes_and_schema(tmp_path):
    """Journal -> trace events: steps/data-waits/checkpoints/serve
    requests/profile windows become complete spans drawn back from
    their completion timestamps, incidents become instants, and the
    whole object validates against the trace-event schema."""
    from megatron_tpu.telemetry.perfetto import journals_to_trace_events

    t0 = 1000.0
    events = [
        {"ts": t0, "kind": "run_start", "iteration": 0, "host": 3},
        {"ts": t0 + 1.0, "kind": "step", "iteration": 1, "step_ms": 100.0,
         "data_wait_ms": 20.0, "loss": 2.5},
        {"ts": t0 + 1.5, "kind": "checkpoint_begin", "iteration": 1},
        {"ts": t0 + 2.0, "kind": "checkpoint_commit", "iteration": 1,
         "seconds": 0.4},
        {"ts": t0 + 2.1, "kind": "checkpoint_stall", "iteration": 1,
         "seconds": 0.1},
        {"ts": t0 + 2.5, "kind": "eval", "seconds": 0.2},
        {"ts": t0 + 3.0, "kind": "serve_request", "status": "ok",
         "wall_s": 0.8, "ttft_s": 0.1},
        {"ts": t0 + 3.2, "kind": "profile_begin", "iteration": 2,
         "until": 4, "dir": "/t", "source": "SIGUSR1"},
        {"ts": t0 + 3.9, "kind": "profile_end", "iteration": 4},
        {"ts": t0 + 4.0, "kind": "preemption", "iteration": 4,
         "notice_host": 3},
        {"ts": t0 + 4.1, "kind": "profile_begin", "iteration": 5,
         "until": 7, "dir": "/t", "source": "--profile"},
        {"ts": t0 + 4.2, "kind": "profile_aborted", "reason": "hang",
         "flushed": True},
        {"ts": t0 + 4.3, "kind": "profile_begin", "iteration": 8,
         "until": 9, "dir": "/t", "source": "--profile"},
    ]
    trace = journals_to_trace_events([("h3/events.jsonl", events)])
    assert validate_trace_events(trace)
    evs = trace["traceEvents"]
    # pid = the coordination host id off run_start
    assert all(e["pid"] == 3 for e in evs)
    procs = [e for e in evs if e["ph"] == "M"
             and e["name"] == "process_name"]
    assert len(procs) == 1 and "host 3" in procs[0]["args"]["name"]

    def lane(name):
        [m] = [e for e in evs if e["ph"] == "M"
               and e["name"] == "thread_name"
               and e["args"]["name"] == name]
        return m["tid"]

    step = next(e for e in evs if e["ph"] == "X"
                and e["name"] == "step 1")
    assert step["dur"] == pytest.approx(100e3)       # µs
    assert step["ts"] == pytest.approx((1.0 - 0.1) * 1e6)  # drawn back
    assert step["tid"] == lane("train steps")
    wait = next(e for e in evs if e["name"] == "data_wait")
    assert wait["dur"] == pytest.approx(20e3)
    # the wait lane precedes the step span it fed
    assert wait["ts"] + wait["dur"] == pytest.approx(step["ts"])
    ckpt = next(e for e in evs if e["name"] == "checkpoint 1")
    # begin->commit pairing wins over the commit's own `seconds`
    assert ckpt["dur"] == pytest.approx(0.5e6)
    req = next(e for e in evs if e["name"] == "req ok")
    assert req["dur"] == pytest.approx(0.8e6)
    prof = next(e for e in evs if e["name"] == "profile window")
    assert prof["dur"] == pytest.approx(0.7e6, rel=1e-3)
    # an abort CLOSES the open window (drawn up to the abort) so later
    # begin/end pairs aren't mis-paired across it; the last begin with
    # no close at all renders as an unclosed instant
    aborted = next(e for e in evs
                   if e["name"] == "profile window (aborted)")
    assert aborted["ph"] == "X"
    assert aborted["dur"] == pytest.approx(0.1e6, rel=1e-3)
    names_i = {e["name"] for e in evs if e["ph"] == "i"}
    assert {"run_start", "preemption", "profile_aborted",
            "profile window (unclosed)"} <= names_i


def test_perfetto_multi_journal_pids(tmp_path):
    from megatron_tpu.telemetry.perfetto import journals_to_trace_events

    j0 = [{"ts": 1.0, "kind": "run_start", "host": 0},
          {"ts": 2.0, "kind": "step", "iteration": 1, "step_ms": 5.0}]
    j1 = [{"ts": 1.0, "kind": "run_start", "host": 1},
          {"ts": 2.5, "kind": "peer_abort", "host": 0, "cause": "hang"}]
    trace = journals_to_trace_events([("h0", j0), ("h1", j1)])
    validate_trace_events(trace)
    pids = {e["pid"] for e in trace["traceEvents"]}
    assert pids == {0, 1}
    # journals without host attribution fall back to their index,
    # colliding pids are reassigned
    trace2 = journals_to_trace_events([("a", j0), ("b", j0)])
    validate_trace_events(trace2)
    assert len({e["pid"] for e in trace2["traceEvents"]}) == 2


def test_telemetry_report_format_json_and_perfetto_cli(tmp_path, capsys):
    """--format json emits per-section dicts (CI consumes goodput/
    serving numbers without scraping tables); --perfetto writes the
    timeline file alongside."""
    telemetry_report = _import_telemetry_report()
    journal = tmp_path / "events.jsonl"
    events = [
        {"ts": 1.0, "kind": "run_start", "iteration": 0},
        {"ts": 2.0, "kind": "step", "iteration": 1, "step_ms": 10.0,
         "loss": 1.5, "tokens_per_s": 100.0, "data_wait_ms": 1.0},
        {"ts": 3.0, "kind": "goodput", "wall_s": 2.0, "productive_s": 1.5},
        {"ts": 4.0, "kind": "serve_request", "status": "ok",
         "wall_s": 0.5, "ttft_s": 0.1},
        {"ts": 5.0, "kind": "preemption", "iteration": 1,
         "notice_host": 0},
    ]
    with open(journal, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    out_trace = tmp_path / "trace.json"
    rc = telemetry_report.main([str(journal), "--format", "json",
                                "--perfetto", str(out_trace)])
    assert rc == 0
    sections = json.loads(capsys.readouterr().out)
    assert sections["run"]["steps"] == 1
    assert sections["goodput"]["goodput_pct"] == 75.0
    assert sections["steps"]["step_ms"]["p50"] == 10.0
    assert sections["serving"]["requests"]["total"] == 1
    assert sections["resilience"]["preemptions"] == 1
    trace = json.loads(out_trace.read_text())
    assert validate_trace_events(trace)
    assert any(e["name"] == "step 1" for e in trace["traceEvents"])
    # legacy --json still prints the flat summary
    rc = telemetry_report.main([str(journal), "--json"])
    assert rc == 0
    flat = json.loads(capsys.readouterr().out)
    assert flat["steps"] == 1 and "goodput_pct" in flat


# ---------------------------------------------------------------------------
# CLI flags


def test_telemetry_flags_parse_into_config():
    from megatron_tpu.arguments import args_to_run_config, parse_args

    args = parse_args([
        "--num_layers", "2", "--hidden_size", "64",
        "--num_attention_heads", "4", "--telemetry_dir", "/tmp/tele",
        "--journal_max_mb", "8", "--metrics_port", "0",
        "--flight_recorder", "--flight_recorder_deadline_s", "120",
        "--flight_recorder_abort"])
    t = args_to_run_config(args).training
    assert t.telemetry_dir == "/tmp/tele"
    assert t.journal_max_mb == 8.0
    assert t.metrics_port == 0
    assert t.flight_recorder and t.flight_recorder_abort
    assert t.flight_recorder_deadline_s == 120.0
    # defaults: everything off
    args = parse_args(["--num_layers", "2", "--hidden_size", "64",
                       "--num_attention_heads", "4"])
    t = args_to_run_config(args).training
    assert t.telemetry_dir is None and t.metrics_port is None
    assert not t.flight_recorder


def test_resilience_flags_parse_into_config():
    """ISSUE 11 knobs: preemption deadline, hang watchdog, SDC replay
    check, batch fingerprinting."""
    from megatron_tpu.arguments import args_to_run_config, parse_args

    args = parse_args([
        "--num_layers", "2", "--hidden_size", "64",
        "--num_attention_heads", "4",
        "--preempt_save_timeout", "45", "--step_timeout_s", "30",
        "--replay_check_interval", "500", "--log_data_fingerprint"])
    t = args_to_run_config(args).training
    assert t.preempt_save_timeout == 45.0
    assert t.step_timeout_s == 30.0
    assert t.replay_check_interval == 500
    assert t.log_data_fingerprint
    # defaults: deadline on, sentinels off
    args = parse_args(["--num_layers", "2", "--hidden_size", "64",
                       "--num_attention_heads", "4"])
    t = args_to_run_config(args).training
    assert t.preempt_save_timeout == 600.0
    assert t.step_timeout_s == 0.0 and t.replay_check_interval == 0
    assert not t.log_data_fingerprint
    # negatives refuse loudly
    import pytest as _pytest

    from megatron_tpu.config import TrainingConfig

    for bad in ({"step_timeout_s": -1.0}, {"replay_check_interval": -2},
                {"preempt_save_timeout": -0.5}):
        with _pytest.raises(ValueError):
            TrainingConfig(**bad).validate()


def test_telemetry_report_counts_resilience_events(tmp_path):
    """tools/telemetry_report.py surfaces preemption/hang/SDC/elastic
    event counts (ISSUE 11 satellite)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)

    journal = tmp_path / "events.jsonl"
    events = [
        {"ts": 1.0, "kind": "run_start", "iteration": 0},
        {"ts": 2.0, "kind": "step", "iteration": 1, "step_ms": 10.0,
         "loss": 1.5},
        {"ts": 3.0, "kind": "preemption", "iteration": 1,
         "signal": "SIGTERM", "notice_to_commit_ms": 80.0},
        {"ts": 4.0, "kind": "run_end", "received_signal": "SIGTERM"},
        {"ts": 5.0, "kind": "run_start", "iteration": 1},
        {"ts": 6.0, "kind": "elastic_resume", "from_dp": 4, "to_dp": 2},
        {"ts": 7.0, "kind": "hang_detected", "iteration": 3,
         "heartbeat_age_s": 12.0},
        {"ts": 8.0, "kind": "sdc_detected", "iteration": 5,
         "leaves": ["params['embed']"]},
        {"ts": 9.0, "kind": "preemption_timeout", "iteration": 7},
    ]
    with open(journal, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    summary = telemetry_report.summarize(
        telemetry_report.load_journal(str(journal)))
    assert summary["preemptions"] == 1
    assert summary["preemption_timeouts"] == 1
    assert summary["hangs"] == 1
    assert summary["sdc_detected"] == 1
    assert summary["elastic_resumes"] == 1
    text = telemetry_report.render(summary)
    assert "1 preemptions" in text
    assert "1 hangs detected" in text
    assert "1 SDC detected" in text
    assert "1 elastic resumes" in text
    assert "1 preempt-save timeouts" in text
