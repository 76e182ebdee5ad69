"""The serving engine measures itself from inside (docs/observability.md
"The names in a trace", docs/serving.md "A request's time"):

  * every tick is a `serve-tick` span of the one profiler session, its
    phases nested inside it, and `tick-read` the only one that waits for
    the device;
  * the same pairs feed `engine_tick_phase_seconds_total{phase}` and the
    journal's `serve_ticks.phase_s`, own time, so the phases sum to the
    loop's time;
  * a tick that stands leaves a `serve_slow_tick` that names the phase;
  * a request has an id and its time is split where it changes hands:
    `queue_s + prefill_s = ttft_s`, `ttft_s + (new_tokens - 1) * tpot_s =
    wall_s`, for a preempted and for a prefix-hit request too;
  * the server's `serve_reply` joins the engine's `serve_request` by id,
    and a client's X-Request-Id comes back.

All CPU: a toy paged engine, captured through `capture_trace`.
"""

import gc
import json
import threading
import time
import urllib.request
from http.server import ThreadingHTTPServer

import jax
import numpy as np
import pytest

from megatron_tpu.inference import engine as engine_mod
from megatron_tpu.inference.engine import InferenceEngine, Request
from megatron_tpu.models import presets
from megatron_tpu.models.params import init_params
from megatron_tpu.telemetry.journal import EventJournal, set_global_journal
from megatron_tpu.telemetry.metrics import MetricsRegistry
from megatron_tpu.telemetry.tracing import xplane
from megatron_tpu.tokenizer.tokenizer import NullTokenizer

CFG = presets.tiny(vocab_size=64, seq_length=64)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
#: (prompt length, new tokens); three prompts cross an 8-token chunk
SHAPES = [(4, 40), (9, 35), (14, 30), (6, 38), (12, 32), (17, 27)]
TOP_PHASES = [engine_mod.PRE, engine_mod.ADMIT, engine_mod.PREFILL,
              engine_mod.PAGES, engine_mod.DECODE]


def make_paged(**kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("metrics", MetricsRegistry())
    return InferenceEngine(CFG, PARAMS, **kw)


def submit_all(eng, shapes=SHAPES, seed=0, prefix=""):
    rng = np.random.default_rng(seed)
    return [eng.submit(Request(
        prompt=rng.integers(1, 64, p).astype(np.int32), max_new_tokens=n,
        id=f"{prefix}r{i}")) for i, (p, n) in enumerate(shapes)]


@pytest.fixture
def journal(tmp_path):
    path = tmp_path / "events.jsonl"
    set_global_journal(EventJournal(str(path)))
    try:
        yield lambda: [json.loads(line) for line in open(path)]
    finally:
        set_global_journal(None)


def of_kind(records, kind):
    return [r for r in records if r["kind"] == kind]


def check_identities(rec):
    """The two sums a `serve_request` record promises."""
    assert rec["queue_s"] >= 0 and rec["prefill_s"] >= 0
    assert rec["queue_s"] + rec["prefill_s"] == pytest.approx(
        rec["ttft_s"], abs=2e-6)
    n = rec["new_tokens"]
    rest = (n - 1) * rec["tpot_s"] if n > 1 else 0.0
    assert rec["ttft_s"] + rest == pytest.approx(rec["wall_s"], abs=1e-4)


# ---------------------------------------------------------------------------
# the tick on the trace's clock


@pytest.fixture(scope="module")
def trace_dir(tmp_path_factory):
    """A toy engine under load, captured through `capture_trace`."""
    # wide enough that a tick is milliseconds, as a served tick is: the
    # glue between the phases is microseconds whatever the model
    cfg = presets.tiny(vocab_size=64, seq_length=64, hidden_size=512,
                       ffn_hidden_size=2048, num_layers=4)
    eng = InferenceEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)), num_slots=4,
        max_seq_len=64, page_size=8, prefill_chunk=8,
        metrics=MetricsRegistry())
    submit_all(eng)                 # every shape compiled before the trace
    eng.run_until_idle()
    out = str(tmp_path_factory.mktemp("trace"))
    eng.start()
    try:
        reqs = submit_all(eng, seed=1)
        result = eng.capture_trace(out, ticks=30, timeout_s=60.0)
        for r in reqs:
            assert r.done.wait(60.0)
    finally:
        eng.stop()
    assert result["complete"]
    return out


@pytest.fixture(scope="module")
def traced(trace_dir):
    """The loop thread's line of the captured host plane, in order."""
    space = xplane.load_xspace(xplane.find_xplane_files(trace_dir)[0])
    lines = [line for plane in space.planes if plane.name == "/host:CPU"
             for line in plane.lines
             if any(ev.name == engine_mod.TICK for ev in line.events)]
    assert len(lines) == 1, "one loop thread holds every serve-tick"
    return sorted(lines[0].events, key=lambda ev: (ev.start_ps, -ev.end_ps))


def inside(events, outer):
    return [ev for ev in events if ev is not outer
            and ev.start_ps >= outer.start_ps and ev.end_ps <= outer.end_ps]


def top_spans(events, tick):
    """The program's spans inside a tick that lie under no other."""
    spans = [ev for ev in inside(events, tick)
             if ev.name in engine_mod._PHASE_OF]
    return [ev for ev in spans
            if not any(ev in inside(spans, other) for other in spans)]


def whole_ticks(events):
    ticks = [ev for ev in events if ev.name == engine_mod.TICK]
    assert len(ticks) >= 20
    return ticks[1:-1]   # the capture may have cut the first and the last


def test_every_tick_is_a_step_marker_with_its_phases_in_order(traced):
    ticks = whole_ticks(traced)
    numbers = [t.stats["step_num"] for t in ticks]
    assert numbers == list(range(numbers[0], numbers[0] + len(ticks)))
    for tick in ticks:
        names = [ev.name for ev in top_spans(traced, tick)]
        # the five phases that dispatch, once each and in the order of
        # InferenceEngine._tick; then what _read_behind read
        assert names[:5] == TOP_PHASES, names
        assert names[5:] and set(names[5:]) <= {engine_mod.READ,
                                                engine_mod.APPLY}
        assert names[5::2] == [engine_mod.READ] * len(names[5::2])


def test_phases_sum_to_the_tick(traced):
    ticks = whole_ticks(traced)
    covered = total = 0
    for tick in ticks:
        covered += sum(ev.duration_ps for ev in top_spans(traced, tick))
        total += tick.duration_ps
    # what is left is the glue between the phases, microseconds a tick
    assert 0.98 * total <= covered <= total, (covered, total)


def test_tick_read_is_the_only_span_that_waits_for_the_device(traced):
    """The runtime marks the host's waits for a device buffer
    (`np.asarray(jax.Array)` around a blocking copy): inside a tick each
    lies inside a `tick-read`."""
    reads = [ev for ev in traced if ev.name == engine_mod.READ]
    waits = [ev for tick in whole_ticks(traced) for ev in inside(traced, tick)
             if ev.name == "np.asarray(jax.Array)"]
    assert waits, "the toy's reads show as the runtime's own events"
    for ev in waits:
        assert any(r.start_ps <= ev.start_ps and ev.end_ps <= r.end_ps
                   for r in reads), ev


def test_trace_report_books_the_loop_threads_time_by_span(trace_dir):
    """tools/trace_report.py's table of the loop thread (own time by
    event of the line that holds the step markers): the engine's spans
    stand beside the runtime's events, and `serve-tick` is a step."""
    from megatron_tpu.telemetry.tracing import analyze, events

    space = xplane.load_xspace(xplane.find_xplane_files(trace_dir)[0])
    report = analyze.analyze_events(events.classify_xspace(space))
    assert engine_mod.TICK in report.steps
    rows = {r["span"]: r for r in report.loop_thread}
    assert set(TOP_PHASES) | {engine_mod.READ, engine_mod.APPLY,
                              engine_mod.TICK} <= set(rows)
    # own time partitions the line: the tick's own is its glue alone
    tick = rows[engine_mod.TICK]
    assert tick["self_s"] < 0.02 * tick["total_s"]
    assert sum(r["self_s"] for r in rows.values()) == pytest.approx(
        tick["total_s"], rel=0.05)
    assert rows[engine_mod.READ]["total_s"] > 0.5 * tick["total_s"]


# ---------------------------------------------------------------------------
# the same pairs as counters


def test_phase_counters_sum_to_the_loops_time(journal):
    eng = make_paged()
    submit_all(eng)
    eng.run_until_idle()
    phases = eng.stats["tick_phase_s"]
    assert {"pre", "admit", "prefill", "pages", "decode", "read",
            "apply", "other", "loop"} <= set(phases)
    # own time: every second of every tick is some phase's, once
    tick = eng.timers(engine_mod.TICK)
    spans = tick.elapsed(reset=False) + eng.timers(engine_mod.LOOP).own()
    assert sum(phases.values()) == pytest.approx(spans, rel=1e-9)
    for phase, seconds in phases.items():
        assert eng.metrics.get("engine_tick_phase_seconds_total").value(
            phase=phase) == pytest.approx(seconds)
    assert eng.metrics.get("engine_decode_rows_total").value() \
        == eng.stats["decode_rows"]
    # rows over ticks is the mean decoding batch: every token but a
    # prompt's first comes from a decode tick
    assert eng.stats["decode_rows"] == sum(n - 1 for _, n in SHAPES)
    last = of_kind(journal(), "serve_ticks")[-1]
    assert last["rows"] <= eng.stats["decode_rows"]
    assert set(last["phase_s"]) <= set(phases)
    assert last["evicted"] == 0


def test_default_geometry_and_speculative_engines_take_the_same_names():
    from megatron_tpu.inference.speculative import SpecConfig

    plain = InferenceEngine(CFG, PARAMS, num_slots=2, max_seq_len=64,
                            metrics=MetricsRegistry())
    submit_all(plain, SHAPES[:3])
    plain.run_until_idle()
    assert {"pre", "admit", "prefill", "pages", "decode", "read",
            "apply"} <= set(plain.stats["tick_phase_s"])
    spec = InferenceEngine(
        CFG, PARAMS, num_slots=2, max_seq_len=64, metrics=MetricsRegistry(),
        speculative=SpecConfig(k=2, drafter="ngram"))
    submit_all(spec, [(9, 12), (6, 10)])
    spec.run_until_idle()
    phases = spec.stats["tick_phase_s"]
    assert {"propose", "decode", "read", "apply"} <= set(phases)
    assert sum(phases.values()) == pytest.approx(
        spec.timers(engine_mod.TICK).elapsed(reset=False)
        + spec.timers(engine_mod.LOOP).own(), rel=1e-9)


def test_eviction_and_preemption_are_spans_and_counted(journal):
    """A pool of 10 pages under four long answers: the radix tree gives
    its pages back first (`page-evict`), then the youngest slot yields
    (`page-preempt`, its read inside a `tick-drain`)."""
    eng = make_paged(num_pages=11)
    submit_all(eng, [(16, 8)] * 2)           # two prompts enter the tree
    eng.run_until_idle()
    reqs = submit_all(eng, [(9, 40)] * 4, seed=5, prefix="p")
    eng.run_until_idle()
    assert all(r.error is None for r in reqs)
    assert eng.stats["pages_evicted"] > 0 and eng.stats["preemptions"] > 0
    assert eng.metrics.get("engine_pages_evicted_total").value() \
        == eng.stats["pages_evicted"]
    phases = eng.stats["tick_phase_s"]
    assert phases["evict"] > 0 and phases["preempt"] > 0
    assert "drain" in phases
    records = journal()
    assert of_kind(records, "serve_ticks")[-1]["evicted"] \
        == eng.stats["pages_evicted"]
    served = {r["id"]: r for r in of_kind(records, "serve_request")}
    assert sum(served[r.id]["preemptions"] for r in reqs) \
        == eng.stats["preemptions"]
    for r in reqs:
        check_identities(served[r.id])


# ---------------------------------------------------------------------------
# a slow tick leaves a record


def test_a_slow_tick_names_its_phase(journal, monkeypatch):
    eng = make_paged()
    submit_all(eng, [(4, 60), (9, 50)])
    for _ in range(24):
        eng.step()
    assert not of_kind(journal(), "serve_slow_tick")
    monkeypatch.setenv("MEGATRON_TPU_FAULT", "slow_tick:300")
    eng.step()
    monkeypatch.delenv("MEGATRON_TPU_FAULT")
    eng.run_until_idle()
    slow = of_kind(journal(), "serve_slow_tick")
    assert len(slow) == 1 and eng.stats["slow_ticks"] == 1
    rec = slow[0]
    assert rec["tick"] == 25 and rec["wall_s"] >= 0.3
    assert max(rec["phase_s"], key=rec["phase_s"].get) == "pre"
    assert sum(rec["phase_s"].values()) == pytest.approx(rec["wall_s"],
                                                         abs=1e-5)
    assert rec["active"] == 2 and rec["queue"] == 0 and rec["drains"] == []
    assert 0 < rec["pages_free"] < eng.num_pages - 1   # two rows hold some
    assert rec["gc_s"] >= 0.0


def test_no_journal_no_capture_nothing_registered_nothing_written():
    set_global_journal(None)
    before = list(gc.callbacks)
    eng = make_paged()
    submit_all(eng, SHAPES[:2])
    eng.run_until_idle()
    assert gc.callbacks == before and not eng._gc.watching


def test_gc_pauses_are_watched_only_while_a_journal_is_set(journal):
    before = list(gc.callbacks)
    eng = make_paged()
    submit_all(eng, SHAPES[:2])
    eng.run_until_idle()
    assert eng._gc.watching and gc.callbacks == before + [eng._gc]
    gc.collect()
    assert eng._gc.seconds > 0.0
    set_global_journal(None)
    submit_all(eng, SHAPES[:1])
    eng.run_until_idle()
    assert gc.callbacks == before


# ---------------------------------------------------------------------------
# a request's life


def test_a_requests_time_is_split_where_it_changes_hands(journal):
    eng = make_paged(num_slots=2)
    reqs = submit_all(eng)          # six requests over two slots: four wait
    eng.run_until_idle()
    served = {r["id"]: r for r in of_kind(journal(), "serve_request")}
    assert set(served) == {r.id for r in reqs}
    for req, (p, n) in zip(reqs, SHAPES):
        rec = served[req.id]
        check_identities(rec)
        assert rec["chunks"] == -(-p // 8) and rec["new_tokens"] == n
        assert rec["prefix_tokens"] == 0 and rec["preemptions"] == 0
    waited = sorted(r["queue_s"] for r in served.values())
    assert waited[2] > 10 * max(waited[1], 1e-5), "four waited for a slot"


def test_a_prefix_hit_says_what_it_saved(journal):
    eng = make_paged()
    prompt = np.arange(1, 21, dtype=np.int32)       # two full pages + 4
    first = eng.submit(Request(prompt=prompt, max_new_tokens=4, id="cold"))
    eng.run_until_idle()
    again = eng.submit(Request(prompt=prompt, max_new_tokens=4, id="warm"))
    eng.run_until_idle()
    assert first.generated == again.generated
    served = {r["id"]: r for r in of_kind(journal(), "serve_request")}
    assert served["cold"]["prefix_tokens"] == 0
    assert served["cold"]["chunks"] == 3
    assert served["warm"]["prefix_tokens"] == 15    # 16 cached, one redone
    assert served["warm"]["chunks"] == 1
    check_identities(served["cold"])
    check_identities(served["warm"])


# ---------------------------------------------------------------------------
# socket to socket


@pytest.fixture
def server(journal):
    from megatron_tpu.inference.server import GenerationService, make_handler

    service = GenerationService(
        CFG, PARAMS, NullTokenizer(64), engine_slots=4, engine_max_seq_len=64,
        page_size=8, prefill_chunk=8)
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(service))
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        yield f"http://127.0.0.1:{httpd.server_address[1]}/api"
    finally:
        httpd.shutdown()
        httpd.server_close()
        service.shutdown()


def put(url, body, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="PUT",
        headers=dict({"Content-Type": "application/json"}, **(headers or {})))
    try:
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, resp.headers, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, e.headers, json.loads(e.read())


def test_reply_joins_request_by_id_and_the_clients_id_comes_back(
        server, journal):
    status, headers, _ = put(server, {"prompts": ["5 6 7 8 9"],
                                      "tokens_to_generate": 6},
                             {"X-Request-Id": "client-7"})
    assert status == 200 and headers["X-Request-Id"] == "client-7"
    status, headers, _ = put(server, {"prompts": ["5 6 7", "9 10 11 12"],
                                      "tokens_to_generate": 4})
    made = headers["X-Request-Id"]
    assert status == 200 and made and made != "client-7"
    status, headers, _ = put(server, {"prompts": []},
                             {"X-Request-Id": "client-8"})
    assert status == 400 and headers["X-Request-Id"] == "client-8"

    # the handler writes a reply's record after the reply's last byte, so
    # the last one may still be on its way when the client has its answer
    deadline = time.monotonic() + 5.0
    records = journal()
    while (len(of_kind(records, "serve_reply")) < 3
           and time.monotonic() < deadline):
        time.sleep(0.01)
        records = journal()
    replies = {r["id"]: r for r in of_kind(records, "serve_reply")}
    served = {r["id"]: r for r in of_kind(records, "serve_request")}
    assert set(replies) == {"client-7", made, "client-8"}
    assert set(served) == {"client-7", f"{made}/0", f"{made}/1"}
    one = replies["client-7"]
    assert one["status"] == "200" and one["prompts"] == 1
    assert one["engine_s"] == pytest.approx(served["client-7"]["wall_s"],
                                            abs=2e-6)
    assert one["handler_s"] >= one["engine_s"]
    two = replies[made]
    assert two["prompts"] == 2
    assert two["engine_s"] >= max(served[f"{made}/{k}"]["wall_s"]
                                  for k in (0, 1)) - 2e-6
    assert two["handler_s"] >= two["engine_s"]
    bad = replies["client-8"]
    assert bad["status"] == "400" and "engine_s" not in bad
    for rec in served.values():
        check_identities(rec)
