"""Serving fleet tests: router, hot reload, drain, deadlines, chaos.

Fast tier-1 coverage:
  * Prometheus text scraping round-trips the registry's own exposition
    (the cross-process contract the router/SLO harness depend on);
  * router dispatch units against stub HTTP replicas (no jax): least
    loaded pick, failover on a dead replica, 503 routed around without
    breaker penalty, 4xx passthrough, breaker open + probe readmit,
    rolling-update admin choreography;
  * deadline expiry (queued and mid-decode), injected admission
    rejection, readiness/drain/hot-reload on one in-process engine-backed
    server (one compile shared by the whole block);
  * the SLO trace/report math on synthetic inputs, and the
    telemetry-report serving section.

Slow (real subprocess) coverage — the acceptance gates:
  * SIGKILL one of 2 replicas mid-stream under concurrent traffic
    (`kill_replica` fault): every request completes via failover,
    token-identical to the survivor's solo answers; the router marks the
    replica dead and readmits it after a respawn;
  * rolling weight update under live traffic: zero dropped requests,
    zero decode recompiles, responses token-identical to solo runs of
    whichever weight version served them;
  * graceful drain on SIGTERM; hung-replica readiness (`hang_replica`);
    the paged-engine variant of router failover; the
    serve_slo_offered_load bench line;
  * serving churn (docs/fault_tolerance.md "Serving state migration"):
    SIGTERM-drain and `preempt_replica` hand in-flight/queued requests
    to a peer over the KV fabric — zero client-visible failures,
    token-identical answers (greedy AND seeded-sampled), zero decode
    recompiles on the importer; `migrate_fail` torn transfers walk the
    migrate -> recompute -> retry degradation ladder with every step
    journaled. The engine-level migration tests live in
    test_migration.py.
"""

import json
import os
import socket
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from megatron_tpu.inference.fleet import scrape, slo
from megatron_tpu.inference.fleet.router import ReplicaRouter, RouterServer
from megatron_tpu.telemetry.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# scrape: the cross-process metrics contract


def test_scrape_roundtrips_registry_exposition():
    reg = MetricsRegistry()
    g = reg.gauge("engine_slots_active", "busy slots")
    c = reg.counter("engine_requests_admitted_total", "admissions",
                    label_names=("status",))
    h = reg.histogram("engine_ttft_seconds", "ttft")
    g.set(3)
    c.inc(status="200")
    c.inc(status="200")
    for v in (0.002, 0.02, 0.02, 0.2, 2.0):
        h.observe(v)
    samples = scrape.parse_prom_text(reg.render())
    assert scrape.sample_value(samples, "engine_slots_active") == 3
    assert scrape.sample_value(samples, "engine_requests_admitted_total",
                               status="200") == 2
    # bucket-quantile semantics must agree with the in-process helper
    for q in (0.5, 0.95, 0.99):
        assert (scrape.histogram_percentile(samples, "engine_ttft_seconds",
                                            q)
                == h.percentile(q))
    # label unescaping is single-pass: an escaped backslash before 'n'
    # must not collapse into a newline
    esc = scrape.parse_prom_text(r'm{p="C:\\new",q="a\nb"} 1')
    labels = esc["m"][0][0]
    assert labels == {"p": "C:\\new", "q": "a\nb"}


def test_strict_scrape_roundtrips_every_family(tmp_path):
    """ISSUE 13 satellite: the registry's exposition round-trips through
    parse_prom_text(strict=True) — every family declared by # HELP +
    # TYPE, label values with every legal escape surviving byte-exact,
    HELP text escaped symmetrically — and format violations raise
    instead of silently dropping series."""
    reg = MetricsRegistry()
    nasty = 'quo"te\nnew\\line\\nliteral'
    help_nasty = "first line\nsecond \\ line"
    c = reg.counter("requests_total", help_nasty, label_names=("path",))
    c.inc(path=nasty)
    c.inc(path="plain")
    reg.gauge("depth", "").set(7)  # empty help still gets a HELP line
    h = reg.histogram("latency_seconds", "lat")
    h.observe(0.3)
    text = reg.render()

    samples = scrape.parse_prom_text(text, strict=True)
    assert scrape.sample_value(samples, "requests_total", path=nasty) == 1
    assert scrape.sample_value(samples, "requests_total",
                               path="plain") == 1
    assert scrape.sample_value(samples, "depth") == 7
    assert scrape.histogram_percentile(samples, "latency_seconds",
                                       0.5) == h.percentile(0.5)

    meta = scrape.parse_prom_metadata(text)
    assert meta["requests_total"] == {"help": help_nasty,
                                      "type": "counter"}
    assert meta["depth"]["type"] == "gauge"
    assert meta["depth"]["help"]  # non-empty fallback
    assert meta["latency_seconds"]["type"] == "histogram"
    # every sample family is declared (the strict parse above proved it;
    # cross-check: no family without both comment lines)
    for family in samples:
        base = family
        for suffix in ("_bucket", "_sum", "_count"):
            if family.endswith(suffix) and family[:-len(suffix)] in meta:
                base = family[:-len(suffix)]
        assert set(meta[base]) == {"help", "type"}, family

    # violations raise in strict mode (and only there)
    for bad in (
            "garbage line here",
            "undeclared_metric 1",
            "# TYPE m counter\nm{x=\"a\\qb\"} 1",   # illegal escape
            "# TYPE m counter\nm{x=\"a\" junk} 1",  # malformed labels
            "# TYPE m counter\nm not_a_number",
            "# TYPE m counter\n# TYPE m gauge\nm 1"):
        with pytest.raises(scrape.ScrapeFormatError):
            scrape.parse_prom_text(bad, strict=True)
        scrape.parse_prom_text(bad)  # lenient mode shrugs
    # lenient mode keeps a third-party exposition's unknown escape
    # VERBATIM — the label value must not silently lose its backslash
    lenient = scrape.parse_prom_text(r'm{x="a\tb"} 1')
    assert lenient["m"][0][0] == {"x": r"a\tb"}


def test_scrape_diff_and_merge():
    reg = MetricsRegistry()
    h = reg.histogram("engine_ttft_seconds", "ttft")
    h.observe(5.0)  # "warmup" observation that a window diff must drop
    before = scrape.parse_prom_text(reg.render())
    for _ in range(10):
        h.observe(0.01)
    after = scrape.parse_prom_text(reg.render())
    delta = scrape.diff_samples(before, after)
    # the 5s warmup sample is outside the window: p99 reads the 10ms
    # bucket, not the warmup's
    assert scrape.histogram_percentile(delta, "engine_ttft_seconds",
                                       0.99) == 0.01
    # fleet-wide merge: two replicas' windows sum per bucket
    merged = scrape.merged_histogram_percentile([delta, delta],
                                                "engine_ttft_seconds", 0.5)
    assert merged == 0.01
    assert scrape.replica_load(
        {"engine_slots_active": [({}, 2.0)],
         "engine_queue_depth": [({}, 3.0)]}) == 5.0
    assert scrape.replica_load({}) == float("inf")
    # a CP x DP replica exposes one series per engine lane: the load
    # score SUMS lanes (sample_sum), not first-match-wins
    assert scrape.replica_load(
        {"engine_slots_active": [({"lane": "0"}, 2.0),
                                 ({"lane": "1"}, 1.0)],
         "engine_queue_depth": [({"lane": "0"}, 3.0)]}) == 6.0
    assert scrape.sample_sum(
        {"m": [({"lane": "0"}, 1.0), ({"lane": "1"}, 2.5)]}, "m") == 3.5
    assert scrape.sample_sum({}, "m", default=0.0) == 0.0


def test_slo_trace_deterministic_and_report_math():
    t1 = slo.make_trace(32, 8.0, seed=3)
    t2 = slo.make_trace(32, 8.0, seed=3)
    assert t1 == t2
    assert t1 != slo.make_trace(32, 8.0, seed=4)
    gaps = [b["at_s"] - a["at_s"] for a, b in zip(t1, t1[1:])]
    assert 0.02 < sum(gaps) / len(gaps) < 0.5  # ~1/8 s mean inter-arrival

    results = [{"at_s": 0.1 * i, "wall_s": 0.2, "status": 200, "ok": True}
               for i in range(10)]
    results.append({"at_s": 1.1, "wall_s": 0.1, "status": 502, "ok": False})
    reg = MetricsRegistry()
    h = reg.histogram("engine_ttft_seconds", "ttft")
    before = scrape.parse_prom_text(reg.render())
    for _ in range(10):
        h.observe(0.05)
    after = scrape.parse_prom_text(reg.render())
    report = slo.slo_report(results, [before], [after], offered_rps=8.0)
    assert report["completed"] == 10 and report["failed"] == 1
    assert report["status_counts"]["502"] == 1
    assert report["ttft_s"]["p50"] == 0.05
    assert report["client_wall_s"]["p50"] == 0.2


def test_telemetry_report_serving_section():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    events = (
        [{"kind": "serve_request", "status": "ok", "ttft_s": 0.05,
          "tpot_s": 0.01, "wall_s": 0.3}] * 9
        + [{"kind": "serve_request", "status": "timeout", "wall_s": 1.0}]
        + [{"kind": "serve_route", "status": 200, "attempts": 1}] * 8
        + [{"kind": "serve_route", "status": 200, "attempts": 2}]
        + [{"kind": "serve_route", "status": 503, "attempts": 3,
            "exhausted": True}]
        + [{"kind": "replica_breaker_open", "replica": "u"},
           {"kind": "replica_readmitted", "replica": "u"},
           {"kind": "serve_drain_begin", "timeout_s": 5},
           {"kind": "weight_reload", "version": 2}]
        # cumulative speculative snapshots: the LAST one is the totals
        + [{"kind": "serve_spec", "proposed": 10, "accepted": 2,
            "emitted": 6, "ticks": 4, "k": 4, "drafter": "ngram"},
           {"kind": "serve_spec", "proposed": 40, "accepted": 30,
            "emitted": 50, "ticks": 10, "k": 4, "drafter": "ngram"}])
    # cumulative snapshots of the loop, and two ticks that stood
    events += [
        {"kind": "serve_request", "status": "ok", "ttft_s": 0.05,
         "queue_s": 0.01, "prefill_s": 0.04, "tpot_s": 0.01,
         "wall_s": 0.3},
        {"kind": "serve_ticks", "ticks": 10, "rows": 30,
         "phase_s": {"read": 0.5, "decode": 0.5}},
        {"kind": "serve_ticks", "ticks": 100, "rows": 850,
         "phase_s": {"read": 6.0, "decode": 1.0, "evict": 3.0}},
        {"kind": "serve_slow_tick", "tick": 40, "wall_s": 0.5,
         "phase_s": {"read": 0.45, "pre": 0.05}},
        {"kind": "serve_slow_tick", "tick": 70, "wall_s": 2.5,
         "phase_s": {"evict": 2.4, "read": 0.1}}]
    summary = telemetry_report.summarize(events)
    sv = summary["serving"]
    assert sv["queue_s"]["p50"] == 0.01 and sv["prefill_s"]["p50"] == 0.04
    assert sv["loop"] == {
        "ticks": 100, "rows_per_tick": 8.5,
        "phase_share": {"read": 0.6, "evict": 0.3, "decode": 0.1},
        "slow_ticks": 2, "slow_tick_s": 3.0,
        "worst_slow_tick": {"wall_s": 2.5, "phase": "evict"}}
    text = telemetry_report.render(summary)
    assert "8.5 rows a tick; time by phase read 60.0%, evict 30.0%" in text
    assert "slow ticks: 2 (3.0 s); the worst 2.5 s in `evict`" in text
    assert sv["speculative"]["accept_rate"] == 0.75
    assert sv["speculative"]["tokens_per_forward"] == 5.0
    assert sv["speculative"]["drafter"] == "ngram"
    assert sv["requests"]["total"] == 11
    assert sv["requests"]["by_status"] == {"ok": 10, "timeout": 1}
    assert sv["ttft_s"]["p50"] == 0.05
    assert sv["router"] == {"routed": 10, "retries": 3, "failovers": 1,
                            "exhausted": 1}
    assert sv["fleet"] == {"breaker_opens": 1, "readmits": 1, "drains": 1,
                           "weight_reloads": 1}
    text = telemetry_report.render(summary)
    assert "failovers" in text and "tpot" in text
    assert "accept rate 0.75" in text and "tokens/forward" in text


# ---------------------------------------------------------------------------
# router units against stub replicas (pure host — no jax, no engine)


class StubReplica:
    """Configurable fake replica: /readyz, /metrics gauges, /api, /admin."""

    def __init__(self, ready=True, load=0.0, api_status=200,
                 api_delay=0.0):
        self.ready = ready
        self.load = load
        self.api_status = api_status
        self.api_delay = api_delay
        self.api_calls = 0
        self.admin_calls = []
        stub = self

        class Handler(BaseHTTPRequestHandler):
            def _reply(self, code, payload, ctype="application/json"):
                body = (payload if isinstance(payload, bytes)
                        else json.dumps(payload).encode())
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path == "/readyz":
                    self._reply(200 if stub.ready else 503,
                                {"ok": stub.ready})
                elif path == "/metrics":
                    self._reply(200,
                                (f"engine_slots_active {stub.load}\n"
                                 "engine_queue_depth 0\n").encode(),
                                ctype="text/plain")
                else:
                    self._reply(404, {})

            def do_POST(self):
                path = self.path.split("?", 1)[0]
                length = int(self.headers.get("Content-Length", 0))
                self.rfile.read(length)
                if path == "/api":
                    stub.api_calls += 1
                    if stub.api_delay:
                        time.sleep(stub.api_delay)
                    self._reply(stub.api_status,
                                {"text": [f"stub:{stub.port}"]})
                elif path.startswith("/admin/"):
                    stub.admin_calls.append(path)
                    if path == "/admin/drain":
                        self._reply(200, {"drained": True})
                    elif path == "/admin/reload":
                        self._reply(200, {"version": 42})
                    else:
                        self._reply(200, {})
                else:
                    self._reply(404, {})

            def log_message(self, *a):
                pass

        self.server = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.port = self.server.server_address[1]
        self.url = f"http://127.0.0.1:{self.port}"
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True)
        self._thread.start()

    def close(self):
        self.server.shutdown()
        self.server.server_close()


def _dead_url():
    """A URL nothing listens on (bind an ephemeral port, then free it)."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return f"http://127.0.0.1:{port}"


BODY = json.dumps({"prompts": ["1 2"], "tokens_to_generate": 2}).encode()


def _router_counter(router, name, **labels):
    samples = scrape.parse_prom_text(router.metrics.render())
    return scrape.sample_value(samples, name, default=0.0, **labels)


def test_router_picks_least_loaded():
    busy, idle = StubReplica(load=5.0), StubReplica(load=0.0)
    try:
        router = ReplicaRouter([busy.url, idle.url],
                               metrics=MetricsRegistry())
        router.probe_once()  # reads the stub gauges
        status, _, body = router.dispatch(BODY)
        assert status == 200
        assert idle.api_calls == 1 and busy.api_calls == 0
        assert f"stub:{idle.port}" in body.decode()
    finally:
        busy.close()
        idle.close()


def test_router_failover_on_dead_replica():
    live = StubReplica()
    try:
        # dead listed first: equal load scores tie-break to list order,
        # so the first attempt hits the dead one and must fail over
        router = ReplicaRouter([_dead_url(), live.url], retry_backoff_s=0.0,
                               metrics=MetricsRegistry())
        status, _, _ = router.dispatch(BODY)
        assert status == 200
        assert live.api_calls == 1
        assert _router_counter(router, "router_failovers_total") == 1
        assert _router_counter(router, "router_retries_total") == 1
    finally:
        live.close()


def test_router_routes_around_503_without_breaker_penalty():
    full = StubReplica(api_status=503)
    live = StubReplica(load=1.0)  # higher load: 503 stub is tried first
    try:
        router = ReplicaRouter([full.url, live.url], retry_backoff_s=0.0,
                               metrics=MetricsRegistry())
        router.probe_once()
        status, _, _ = router.dispatch(BODY)
        assert status == 200
        assert full.api_calls == 1 and live.api_calls == 1
        # overloaded != broken: no failure recorded, breaker stays closed
        assert router.replicas[0].failures == 0
        assert _router_counter(router, "router_breaker_opens_total") == 0
    finally:
        full.close()
        live.close()


def test_router_passes_4xx_through_without_retry():
    bad = StubReplica(api_status=400)
    other = StubReplica(load=9.0)
    try:
        router = ReplicaRouter([bad.url, other.url], retry_backoff_s=0.0,
                               metrics=MetricsRegistry())
        router.probe_once()
        status, _, _ = router.dispatch(BODY)
        # a malformed request fails identically everywhere: retrying would
        # only multiply the error rate
        assert status == 400
        assert bad.api_calls == 1 and other.api_calls == 0
    finally:
        bad.close()
        other.close()


def test_router_passes_504_through_without_retry_or_penalty():
    slow = StubReplica(api_status=504)
    other = StubReplica(load=9.0)
    try:
        router = ReplicaRouter([slow.url, other.url], retry_backoff_s=0.0,
                               metrics=MetricsRegistry())
        router.probe_once()
        status, _, _ = router.dispatch(BODY)
        # an expired deadline means the client's budget is spent: no
        # retry (it would double the wasted compute), no breaker penalty
        # (the replica is healthy)
        assert status == 504
        assert slow.api_calls == 1 and other.api_calls == 0
        assert router.replicas[0].failures == 0
    finally:
        slow.close()
        other.close()


def test_rolling_update_survives_unreachable_replica():
    live = StubReplica()
    try:
        router = ReplicaRouter([_dead_url(), live.url], retry_backoff_s=0.0,
                               metrics=MetricsRegistry())
        # ready_timeout=1.0: the always-readmit cleanup polls the DEAD
        # replica's /readyz for the full ready_timeout — the default 60s
        # is pure tier-1 wall time here (the semantics under test are
        # "cleanup ran and the fleet keeps serving", not the wait)
        results = router.rolling_update(load="ckpts", drain_timeout=1.0,
                                        ready_timeout=1.0)
        # stops at the first failing replica; cleanup still ran, so the
        # dead replica is NOT stuck excluded from dispatch forever
        assert len(results) == 1 and "error" in results[0]
        assert not router.replicas[0].updating
        assert not router.replicas[1].updating
        assert live.admin_calls == []  # rollout never reached it
        assert router.dispatch(BODY)[0] == 200  # the fleet keeps serving
    finally:
        live.close()


def test_router_breaker_opens_then_probe_readmits():
    stub = StubReplica(api_status=500)
    try:
        router = ReplicaRouter([stub.url], retry_backoff_s=0.0,
                               breaker_failures=3, breaker_base_s=60.0,
                               readmit_streak=2, metrics=MetricsRegistry())
        assert router.dispatch(BODY)[0] == 500
        assert router.dispatch(BODY)[0] in (500, 503)
        rep = router.replicas[0]
        assert rep.breaker_open(time.monotonic())
        assert _router_counter(router, "router_breaker_opens_total") == 1
        assert router._num_routable() == 0
        # breaker open: dispatch answers 503 without touching the replica
        calls = stub.api_calls
        status, headers, _ = router.dispatch(BODY)
        assert status == 503 and "Retry-After" in headers
        assert stub.api_calls == calls
        # the replica recovers; consecutive readiness probes readmit it
        # without burning a client request as the half-open trial
        stub.api_status = 200
        router.probe_once()
        assert router._num_routable() == 0  # streak 1 of 2
        router.probe_once()
        assert router._num_routable() == 1
        assert not rep.breaker_open(time.monotonic())
        assert router.dispatch(BODY)[0] == 200
    finally:
        stub.close()


def test_router_all_dead_answers_503_with_retry_after():
    router = ReplicaRouter([_dead_url()], retry_backoff_s=0.0,
                           metrics=MetricsRegistry())
    status, headers, body = router.dispatch(BODY)
    # bounded: attempts exhausted, last transport failure reported
    assert status == 502
    router.replicas[0].breaker_open_until = time.monotonic() + 60
    status, headers, _ = router.dispatch(BODY)
    assert status == 503 and "Retry-After" in headers


def test_rolling_update_admin_choreography():
    a, b = StubReplica(), StubReplica()
    try:
        router = ReplicaRouter([a.url, b.url], metrics=MetricsRegistry())
        results = router.rolling_update(load="ckpts", iteration=2,
                                        drain_timeout=5.0)
        assert len(results) == 2
        for stub, res in zip((a, b), results):
            assert "error" not in res
            assert res["version"] == 42
            assert res["ready"] is True
            # one replica at a time, in order: drain -> reload -> readmit
            assert stub.admin_calls == ["/admin/drain", "/admin/reload",
                                        "/admin/readmit"]
            assert not router.replicas[results.index(res)].updating
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# engine-backed server: readiness, drain, deadlines, hot reload (one
# in-process service — a single decode compile covers the whole block)


import jax  # noqa: E402
import numpy as np  # noqa: E402

from megatron_tpu.inference.engine import InferenceEngine, Request  # noqa: E402
from megatron_tpu.inference.fleet.reload import (  # noqa: E402
    save_params_checkpoint,
)
from megatron_tpu.inference.server import (  # noqa: E402
    GenerationService, make_handler,
)
from megatron_tpu.models import presets  # noqa: E402
from megatron_tpu.models.params import init_params  # noqa: E402
from megatron_tpu.tokenizer.tokenizer import NullTokenizer  # noqa: E402

CFG = presets.tiny(vocab_size=64, seq_length=64)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def fleet_service():
    svc = GenerationService(CFG, PARAMS, NullTokenizer(CFG.vocab_size - 1),
                            engine_slots=2, engine_max_seq_len=64,
                            metrics=MetricsRegistry(), warmup=True)
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(svc))
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        yield svc, f"http://127.0.0.1:{server.server_address[1]}"
    finally:
        server.shutdown()
        server.server_close()
        svc.shutdown()


def _get(url, path):
    try:
        with urllib.request.urlopen(url + path, timeout=60) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _post(url, path, payload, timeout=120):
    req = urllib.request.Request(url + path,
                                 data=json.dumps(payload).encode(),
                                 method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_readiness_gates_on_warmup(fleet_service):
    svc, url = fleet_service
    if not svc._warmed.is_set():  # first test in the block sees unwarmed
        code, body = _get(url, "/readyz")
        assert code == 503 and body["warmed"] is False
        # liveness stays green while unwarmed — restart would not help
        assert _get(url, "/healthz")[0] == 200
    svc.warmup()
    code, body = _get(url, "/readyz")
    assert code == 200 and body["ok"] is True


def test_drain_and_readmit_over_http(fleet_service):
    svc, url = fleet_service
    svc.warmup()
    code, body = _post(url, "/admin/drain", {"timeout_s": 10})
    assert code == 200 and body["drained"] is True
    code, body = _post(url, "/api", {"prompts": ["3 4"],
                                     "tokens_to_generate": 2})
    assert code == 503 and body.get("draining")
    assert _get(url, "/readyz")[0] == 503
    assert _get(url, "/healthz")[0] == 200  # liveness green through drain
    assert _post(url, "/admin/readmit", {})[0] == 200
    assert _get(url, "/readyz")[0] == 200
    assert _post(url, "/api", {"prompts": ["3 4"],
                               "tokens_to_generate": 2})[0] == 200


def test_slo_replay_through_router_completes_every_request(fleet_service):
    """The open-loop replay (fleet/slo.py) against a live front door: a
    router over the one in-process replica. Every request completes and
    the engine's histograms fill the percentile blocks — counts and
    presence, never a latency."""
    svc, url = fleet_service
    svc.warmup()
    router = RouterServer([url]).start()
    try:
        trace = slo.make_trace(8, 16.0, vocab=CFG.vocab_size, new_tokens=4)
        report = slo.run_slo(router.url + "/api", [url + "/metrics"], trace,
                             16.0, timeout=60.0)
    finally:
        router.close()
    assert report["failed"] == 0 and report["scrape_errors"] == 0
    assert report["completed"] == report["requests"] == 8
    for key in ("ttft_s", "tpot_s", "client_wall_s"):
        assert set(report[key]) >= {"p50", "p95", "p99"}
        assert all(v == v and v >= 0 for v in report[key].values())


def test_injected_admission_rejection_maps_503(fleet_service, monkeypatch):
    svc, url = fleet_service
    svc.warmup()
    monkeypatch.setenv("MEGATRON_TPU_FAULT", "reject_admission")
    code, body = _post(url, "/api", {"prompts": ["5"],
                                     "tokens_to_generate": 2})
    assert code == 503 and "reject_admission" in body["message"]
    monkeypatch.setenv("MEGATRON_TPU_FAULT", "")
    assert _post(url, "/api", {"prompts": ["5"],
                               "tokens_to_generate": 2})[0] == 200


def test_deadline_expires_queued_request(fleet_service, monkeypatch):
    svc, url = fleet_service
    svc.warmup()
    eng = svc.engine
    timeouts0 = eng.stats["timeouts"]
    monkeypatch.setenv("MEGATRON_TPU_FAULT", "slow_tick:50")
    # fill both slots with slow long requests, then queue one with a
    # deadline shorter than the slot wait: it must fail while QUEUED
    long = [eng.submit(Request(prompt=np.array([7, 8], np.int32),
                               max_new_tokens=30))
            for _ in range(2)]
    victim = eng.submit(Request(prompt=np.array([9], np.int32),
                                max_new_tokens=4, deadline_s=0.3))
    assert victim.done.wait(timeout=10)
    assert victim.timed_out and "queued" in victim.error
    assert eng.stats["timeouts"] == timeouts0 + 1
    monkeypatch.setenv("MEGATRON_TPU_FAULT", "")
    for r in long:
        assert r.done.wait(timeout=30) and r.error is None


def test_deadline_expires_mid_decode(fleet_service, monkeypatch):
    svc, url = fleet_service
    svc.warmup()
    monkeypatch.setenv("MEGATRON_TPU_FAULT", "slow_tick:50")
    code, body = _post(url, "/api", {"prompts": ["3 4"],
                                     "tokens_to_generate": 60,
                                     "deadline_s": 0.4})
    assert code == 504 and "mid-decode" in body["message"]
    monkeypatch.setenv("MEGATRON_TPU_FAULT", "")
    # the slot was reclaimed; the engine keeps serving
    assert _post(url, "/api", {"prompts": ["3 4"],
                               "tokens_to_generate": 2})[0] == 200


def test_deadline_client_cannot_extend_server_bound(fleet_service,
                                                    monkeypatch):
    svc, url = fleet_service
    svc.warmup()
    monkeypatch.setattr(svc, "request_timeout", 0.3)
    monkeypatch.setenv("MEGATRON_TPU_FAULT", "slow_tick:50")
    # explicit null and an absurd client deadline both stay bounded by
    # the operator cap — a client cannot opt out of the protection
    for client_deadline in (None, 1e9):
        code, body = _post(url, "/api",
                           {"prompts": ["3 4"], "tokens_to_generate": 60,
                            "deadline_s": client_deadline})
        assert code == 504, (client_deadline, code, body)
    monkeypatch.setenv("MEGATRON_TPU_FAULT", "")
    # a non-numeric deadline is a client error, not a 500
    code, body = _post(url, "/api", {"prompts": ["3"],
                                     "tokens_to_generate": 2,
                                     "deadline_s": []})
    assert code == 400 and "deadline_s" in body["message"]


def test_deadline_must_be_positive():
    eng = InferenceEngine(CFG, PARAMS, num_slots=1, max_seq_len=64)
    req = eng.submit(Request(prompt=np.array([3], np.int32),
                             max_new_tokens=2, deadline_s=0.0))
    assert req.done.is_set() and "deadline_s" in req.error


def test_stalled_requires_pending_work():
    eng = InferenceEngine(CFG, PARAMS, num_slots=1, max_seq_len=64)
    # idle forever is healthy, not stalled
    eng.last_progress_time -= 1000
    assert not eng.stalled(1.0)
    # pending work + no progress = stalled (the hung-step-loop signal
    # /readyz uses; the step loop was never started here)
    eng.submit(Request(prompt=np.array([3], np.int32), max_new_tokens=2))
    assert eng.stalled(1.0)
    assert not eng.stalled(1e6)


def test_hot_reload_over_http(fleet_service, tmp_path):
    svc, url = fleet_service
    svc.warmup()
    eng = svc.engine
    # greedy, so that the text is a function of the weights: sampling a
    # near-uniform random model with one fixed seed draws the same tokens
    # from either checkpoint
    prompt = {"prompts": ["9 10 11 12"], "tokens_to_generate": 8,
              "top_k": 1}
    before = _post(url, "/api", prompt)[1]
    reloads0 = eng.stats["weight_reloads"]
    recompiles0 = eng.stats["decode_recompiles"]
    # a checkpoint with genuinely different weights
    save_params_checkpoint(str(tmp_path), 3,
                           init_params(CFG, jax.random.PRNGKey(7)))
    code, body = _post(url, "/admin/reload", {"load": str(tmp_path)})
    assert code == 200 and body["version"] == 3
    code, status = _get(url, "/admin/status")
    assert status["weights_version"] == 3
    after = _post(url, "/api", prompt)[1]
    assert after.get("weights_version") == 3
    assert after["text"] != before["text"]  # the new weights answered
    assert eng.stats["weight_reloads"] == reloads0 + 1
    # the swap must not split the decode step's jit cache key
    assert eng.stats["decode_recompiles"] == recompiles0
    # a reload from nowhere is refused verifiably, weights unchanged
    code, body = _post(url, "/admin/reload",
                       {"load": str(tmp_path / "missing")})
    assert code == 409
    assert _get(url, "/admin/status")[1]["weights_version"] == 3


def test_admin_profile_captures_under_live_traffic(fleet_service,
                                                   tmp_path):
    """POST /admin/profile traces N decode ticks under live traffic
    without a restart: the capture brackets the step loop from the admin
    thread (no per-tick check, no extra traced args), so it costs zero
    decode recompiles, the trace is readable by tools/trace_report.py,
    and begin/end land in the journal."""
    from megatron_tpu.inference import engine as engine_mod
    from megatron_tpu.telemetry.journal import (
        EventJournal, set_global_journal,
    )
    from megatron_tpu.telemetry.tracing import (
        analyze_events, classify_xspace, find_xplane_files, load_xspace,
    )

    svc, url = fleet_service
    svc.warmup()
    journal = EventJournal(str(tmp_path / "events.jsonl"))
    set_global_journal(journal)
    recompiles0 = svc.engine.stats["decode_recompiles"]
    stop = threading.Event()
    statuses = []

    def traffic():
        while not stop.is_set():
            statuses.append(_post(url, "/api", {
                "prompts": ["3 4 5"], "tokens_to_generate": 16})[0])

    t = threading.Thread(target=traffic, daemon=True)
    t.start()
    try:
        code, body = _post(url, "/admin/profile",
                           {"steps": 3, "dir": str(tmp_path / "prof"),
                            "timeout_s": 60})
    finally:
        stop.set()
        t.join(timeout=120)
        set_global_journal(None)
    assert code == 200, body
    assert body["complete"] and body["ticks"] >= 3
    assert statuses and all(s == 200 for s in statuses)
    # the capture cost no decode recompiles (same traced args)
    assert svc.engine.stats["decode_recompiles"] == recompiles0
    # the trace is a real xplane the decoder reads: the jitted decode
    # step's op events are in it with nonzero compute time
    files = find_xplane_files(str(tmp_path / "prof"))
    assert files
    events = []
    for f in files:
        events.extend(classify_xspace(load_xspace(f)))
    report = analyze_events(events)
    assert "jit_decode_step" in report.all_modules
    assert report.compute_s > 0
    kinds = [e["kind"] for e in journal.events()]
    assert "profile_begin" in kinds and "profile_end" in kinds
    journal.close()
    # the profiler session is process-global: a concurrent second
    # capture answers 409, not a corrupted trace
    with engine_mod._PROFILE_LOCK:
        code, body = _post(url, "/admin/profile",
                           {"steps": 1, "dir": str(tmp_path / "p2")})
        assert code == 409
    # bad input still 400s
    assert _post(url, "/admin/profile", {"steps": 0})[0] == 400


@pytest.mark.slow  # 6s measured cacheless (one speculating engine
# compile behind a live router); the engine-level knob parity stays
# tier-1 in test_speculative.py and the server-side parse is pure code
def test_spec_knob_passes_through_router_and_replica():
    """Per-request speculative knob (the 'spec' JSON field) flows
    router -> replica -> engine untouched: a speculating in-process
    service behind a real RouterServer answers {"spec": false} and
    {"spec": true} with the SAME greedy text as a plain service (greedy
    purity is unchanged by speculation), and the engine's proposal
    counter moves only for the spec=true request."""
    from megatron_tpu.inference.fleet.router import RouterServer

    tok = NullTokenizer(CFG.vocab_size - 1)
    svc = GenerationService(CFG, PARAMS, tok, engine_slots=2,
                            engine_max_seq_len=64,
                            metrics=MetricsRegistry(),
                            speculative="ngram", spec_k=3)
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(svc))
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    router = RouterServer([url], probe_interval=0.2,
                          metrics=MetricsRegistry()).start()
    try:
        req = {"prompts": ["3 7 11"], "tokens_to_generate": 6,
               "temperature": 0.0}
        # spec=False through the router reaches the engine (zero
        # proposals counted); spec-off == plain decode is pinned at the
        # engine level by test_speculative.py, so it serves as the
        # greedy reference here
        code, body = _post(router.url, "/api", {**req, "spec": False})
        assert code == 200
        want = body["text"]
        assert svc.engine.stats["spec_proposed"] == 0
        code, body = _post(router.url, "/api", {**req, "spec": True})
        assert code == 200 and body["text"] == want
        assert svc.engine.stats["spec_proposed"] > 0
        # malformed knob is a client error, not a 500
        assert _post(router.url, "/api", {**req, "spec": "yes"})[0] == 400
    finally:
        router.close()
        server.shutdown()
        server.server_close()
        svc.shutdown()


# ---------------------------------------------------------------------------
# real-subprocess chaos suite (slow): the acceptance gates


def _spec(tmp_path, name, **kw):
    spec = {"preset": "tiny", "cfg": {"vocab_size": 64, "seq_length": 64},
            "seed": 0, "engine_slots": 2, "port": 0, "warmup": True,
            "port_file": str(tmp_path / f"{name}.port")}
    spec.update(kw)
    return spec


def _spawn(tmp_path, name, fault="", **kw):
    from megatron_tpu.inference.fleet.replica import ReplicaProcess

    env = dict(os.environ, MEGATRON_TPU_FAULT=fault, JAX_PLATFORMS="cpu")
    return ReplicaProcess(_spec(tmp_path, name, **kw), env=env,
                          log_path=str(tmp_path / f"{name}.log")).spawn()


def _wait_routable(router, n, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if router._num_routable() == n:
            return True
        time.sleep(0.1)
    return False


@pytest.mark.slow  # ~40s solo (two subprocess warmup compiles +
# slowed-tick traffic + respawn); the fast router units + in-process
# engine block keep dispatch, breaker, drain and reload logic in tier-1
def test_chaos_sigkill_failover_and_readmit(tmp_path):
    """SIGKILL one of 2 replicas mid-stream under concurrent traffic:
    every request completes via failover (token-identical to the
    survivor's solo answers), the router marks the replica dead, and a
    respawn on the same port is readmitted by the prober."""
    # r0 dies at decode tick 25 (mid-traffic: warmup costs ~2 ticks, each
    # request ~16); slow ticks stretch requests so the kill lands
    # mid-stream with several requests in flight
    r0 = _spawn(tmp_path, "r0", fault="kill_replica:25,slow_tick:30")
    r1 = _spawn(tmp_path, "r1", fault="slow_tick:30")
    router = None
    try:
        r0.wait_ready(timeout=300)
        r1.wait_ready(timeout=300)
        prompts = [f"{3 + i} {4 + i} {5 + i}" for i in range(10)]
        # greedy references from the survivor (identical seed weights on
        # both replicas => any replica's solo answer is THE answer)
        refs = {}
        for p in prompts:
            code, body = _post(r1.url, "/api",
                               {"prompts": [p], "tokens_to_generate": 16,
                                "temperature": 0.0})
            assert code == 200
            refs[p] = body["text"]

        router = ReplicaRouter([r0.url, r1.url], probe_interval=0.2,
                               request_timeout=60.0,
                               metrics=MetricsRegistry()).start()
        results = {}

        def client(p):
            body = json.dumps({"prompts": [p], "tokens_to_generate": 16,
                               "temperature": 0.0}).encode()
            results[p] = router.dispatch(body)

        threads = [threading.Thread(target=client, args=(p,))
                   for p in prompts]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)

        # zero lost requests, token-identical to the solo run
        for p in prompts:
            status, _, rbody = results[p]
            assert status == 200, (p, status, rbody)
            assert json.loads(rbody)["text"] == refs[p]
        # the kill really happened (SIGKILL, not a graceful exit)
        deadline = time.monotonic() + 10
        while r0.poll() is None and time.monotonic() < deadline:
            time.sleep(0.1)
        assert r0.poll() == -9, f"r0 rc={r0.poll()}"
        assert _router_counter(router, "router_failovers_total") >= 1
        # the prober marks the dead replica unroutable...
        assert _wait_routable(router, 1), router.status()
        # ...and readmits it after a respawn on the SAME port (pin the
        # port BEFORE spawning so the router's URL stays valid)
        from megatron_tpu.inference.fleet.replica import ReplicaProcess

        r0b = ReplicaProcess(
            _spec(tmp_path, "r0b", port=r0.port),
            env=dict(os.environ, MEGATRON_TPU_FAULT="",
                     JAX_PLATFORMS="cpu"),
            log_path=str(tmp_path / "r0b.log"))
        r0b.spawn()
        try:
            r0b.wait_ready(timeout=300)
            assert _wait_routable(router, 2), router.status()
            for p in prompts[:2]:
                body = json.dumps({"prompts": [p],
                                   "tokens_to_generate": 16,
                                   "temperature": 0.0}).encode()
                status, _, rbody = router.dispatch(body)
                assert status == 200
                assert json.loads(rbody)["text"] == refs[p]
        finally:
            r0b.close()
    finally:
        if router is not None:
            router.close()
        r0.close()
        r1.close()


@pytest.mark.slow  # ~120s: two subprocess warmups + live traffic through
# a rolling update; the in-process hot-reload test keeps the
# zero-recompile swap gate in tier-1
def test_rolling_update_under_live_traffic(tmp_path):
    """Ship new weights across the fleet under live traffic: zero dropped
    requests, zero decode recompiles, and every response token-identical
    to a solo run of whichever weight version served it."""
    ckpts = tmp_path / "ckpts"
    os.makedirs(ckpts)
    save_params_checkpoint(str(ckpts), 1,
                           init_params(CFG, jax.random.PRNGKey(1)))
    save_params_checkpoint(str(ckpts), 2,
                           init_params(CFG, jax.random.PRNGKey(2)))
    r0 = _spawn(tmp_path, "r0", load=str(ckpts), iteration=1,
                reload_dir=str(ckpts))
    r1 = _spawn(tmp_path, "r1", load=str(ckpts), iteration=1,
                reload_dir=str(ckpts))
    router = None
    prompts = [f"{5 + i} {6 + i}" for i in range(6)]

    def solo_refs(url):
        out = {}
        for p in prompts:
            code, body = _post(url, "/api",
                               {"prompts": [p], "tokens_to_generate": 10,
                                "temperature": 0.0})
            assert code == 200
            out[p] = body["text"]
        return out

    try:
        r0.wait_ready(timeout=300)
        r1.wait_ready(timeout=300)
        refs = {1: solo_refs(r0.url)}
        router = ReplicaRouter([r0.url, r1.url], probe_interval=0.2,
                               request_timeout=60.0,
                               metrics=MetricsRegistry()).start()
        stop = threading.Event()
        traffic = []

        def worker(wid):
            i = wid
            while not stop.is_set():
                p = prompts[i % len(prompts)]
                i += 1
                body = json.dumps({"prompts": [p],
                                   "tokens_to_generate": 10,
                                   "temperature": 0.0}).encode()
                status, _, rbody = router.dispatch(body)
                traffic.append((p, status, rbody))

        workers = [threading.Thread(target=worker, args=(w,))
                   for w in range(3)]
        for th in workers:
            th.start()
        time.sleep(1.0)  # traffic flowing before the update starts
        results = router.rolling_update(load=str(ckpts), iteration=2,
                                        drain_timeout=60.0)
        time.sleep(1.0)  # and after it finishes
        stop.set()
        for th in workers:
            th.join(timeout=120)

        assert len(results) == 2
        for res in results:
            assert "error" not in res, res
            assert res["version"] == 2
        refs[2] = solo_refs(r0.url)  # r0 now serves v2
        assert refs[1] != refs[2]    # the versions genuinely differ

        assert traffic, "no traffic flowed"
        for p, status, rbody in traffic:
            assert status == 200, (p, status, rbody)  # zero dropped
            body = json.loads(rbody)
            wv = body.get("weights_version")
            # a drained update serves every request end-to-end on ONE
            # version, and the response says which
            assert wv in (1, 2), body
            assert body["text"] == refs[wv][p], (p, wv)
        # zero decode recompiles and exactly one swap per replica
        for rep in (r0, r1):
            samples = scrape.scrape(rep.url + "/metrics")
            assert scrape.sample_value(
                samples, "engine_decode_recompiles_total") == 0
            assert scrape.sample_value(
                samples, "engine_weight_reloads_total") == 1
    finally:
        if router is not None:
            router.close()
        r0.close()
        r1.close()


@pytest.mark.slow  # ~45s: one subprocess warmup compile; SIGTERM-drain
# semantics (503 while draining, in-flight completion, rc=0)
def test_graceful_drain_on_sigterm(tmp_path):
    rep = _spawn(tmp_path, "r0", fault="slow_tick:100", drain_timeout=30.0)
    try:
        rep.wait_ready(timeout=300)
        result = {}

        def long_req():
            result["r"] = _post(rep.url, "/api",
                                {"prompts": ["5 6"],
                                 "tokens_to_generate": 30})

        th = threading.Thread(target=long_req)
        th.start()
        time.sleep(0.8)  # mid-decode at 100ms/tick
        rep.terminate()
        time.sleep(0.3)
        code, body = _post(rep.url, "/api", {"prompts": ["4"],
                                             "tokens_to_generate": 2})
        assert code == 503 and body.get("draining"), (code, body)
        th.join(timeout=60)
        code, body = result["r"]
        assert code == 200, (code, body)  # in-flight finished through drain
        assert rep.wait(timeout=30) == 0  # clean exit after the drain
    finally:
        rep.close()


@pytest.mark.slow  # ~40s: one subprocess warmup; hang_replica wedges the
# step loop — only readiness (progress stall) may flip, liveness stays up
def test_hung_replica_flips_readiness_not_liveness(tmp_path):
    rep = _spawn(tmp_path, "r0", fault="hang_replica:8,slow_tick:30",
                 stall_threshold_s=0.5)
    try:
        rep.wait_ready(timeout=300)

        def doomed():
            try:
                _post(rep.url, "/api", {"prompts": ["3 4"],
                                        "tokens_to_generate": 30},
                      timeout=5)
            except (OSError, urllib.error.URLError):
                pass  # the request never completes — that's the point

        threading.Thread(target=doomed, daemon=True).start()
        deadline = time.monotonic() + 30
        stalled = None
        while time.monotonic() < deadline:
            code, body = _get(rep.url, "/readyz")
            if code == 503 and body.get("stalled"):
                stalled = body
                break
            time.sleep(0.2)
        assert stalled, "readiness never flagged the hung step loop"
        # liveness can't see a hang: the thread is alive, just wedged —
        # exactly why the router keys off /readyz
        assert _get(rep.url, "/healthz")[0] == 200
        assert rep.poll() is None
    finally:
        rep.close()


@pytest.mark.slow  # ~110s: the SIGKILL failover at pages and chunks of 8
# (prompts of several chunks, pages crossed in mid-decode)
def test_chaos_failover_small_pages(tmp_path):
    r0 = _spawn(tmp_path, "r0", fault="kill_replica:20,slow_tick:30",
                page_size=8, prefill_chunk=8)
    r1 = _spawn(tmp_path, "r1", fault="slow_tick:30",
                page_size=8, prefill_chunk=8)
    router = None
    try:
        r0.wait_ready(timeout=300)
        r1.wait_ready(timeout=300)
        prompts = [f"{3 + i} {4 + i} {5 + i}" for i in range(6)]
        refs = {}
        for p in prompts:
            code, body = _post(r1.url, "/api",
                               {"prompts": [p], "tokens_to_generate": 12,
                                "temperature": 0.0})
            assert code == 200
            refs[p] = body["text"]
        router = ReplicaRouter([r0.url, r1.url], probe_interval=0.2,
                               request_timeout=60.0,
                               metrics=MetricsRegistry()).start()
        results = {}

        def client(p):
            body = json.dumps({"prompts": [p], "tokens_to_generate": 12,
                               "temperature": 0.0}).encode()
            results[p] = router.dispatch(body)

        threads = [threading.Thread(target=client, args=(p,))
                   for p in prompts]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        for p in prompts:
            status, _, rbody = results[p]
            assert status == 200, (p, status, rbody)
            assert json.loads(rbody)["text"] == refs[p]
        deadline = time.monotonic() + 10
        while r0.poll() is None and time.monotonic() < deadline:
            time.sleep(0.1)
        assert r0.poll() == -9
    finally:
        if router is not None:
            router.close()
        r0.close()
        r1.close()


@pytest.mark.slow  # ~90s: two subprocess warmups of SPECULATING
# replicas + slowed-tick traffic; the in-process spec-knob passthrough
# test keeps the router/replica plumbing in tier-1
def test_chaos_failover_speculating_replica(tmp_path):
    """SIGKILL a replica running speculative decoding mid-stream: the
    router's retry completes every request token-identically (greedy
    purity is unchanged by speculation — a retried request re-derives
    the same accept/reject outcome on the survivor)."""
    # kill at tick 12: warmup costs ~2 ticks and a speculating engine
    # can emit SEVERAL tokens per tick, so the kill must land early
    # enough that r0 still has requests in flight
    spec_kw = dict(speculative="ngram", spec_k=3)
    r0 = _spawn(tmp_path, "r0", fault="kill_replica:12,slow_tick:30",
                **spec_kw)
    r1 = _spawn(tmp_path, "r1", fault="slow_tick:30", **spec_kw)
    router = None
    try:
        r0.wait_ready(timeout=300)
        r1.wait_ready(timeout=300)
        prompts = [f"{3 + i} {4 + i} {5 + i}" for i in range(8)]
        refs = {}
        for p in prompts:
            code, body = _post(r1.url, "/api",
                               {"prompts": [p], "tokens_to_generate": 12,
                                "temperature": 0.0})
            assert code == 200
            refs[p] = body["text"]
        router = ReplicaRouter([r0.url, r1.url], probe_interval=0.2,
                               request_timeout=60.0,
                               metrics=MetricsRegistry()).start()
        results = {}

        def client(p):
            body = json.dumps({"prompts": [p], "tokens_to_generate": 12,
                               "temperature": 0.0}).encode()
            results[p] = router.dispatch(body)

        threads = [threading.Thread(target=client, args=(p,))
                   for p in prompts]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        for p in prompts:
            status, _, rbody = results[p]
            assert status == 200, (p, status, rbody)
            assert json.loads(rbody)["text"] == refs[p]
        deadline = time.monotonic() + 10
        while r0.poll() is None and time.monotonic() < deadline:
            time.sleep(0.1)
        assert r0.poll() == -9, f"r0 rc={r0.poll()}"
    finally:
        if router is not None:
            router.close()
        r0.close()
        r1.close()


@pytest.mark.slow  # ~60s: two subprocess replica warmups + a ~6s replay;
# the SLO math and a one-replica live replay are tier-1
# (test_slo_trace_deterministic..., test_slo_replay_through_router...)
def test_slo_harness_spawned_fleet_reports_percentiles():
    """tools/slo_harness.py --spawn 2: the open-loop trace through a
    router over two replica processes; every request completes and the
    percentile blocks are populated."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "slo_harness", os.path.join(REPO, "tools", "slo_harness.py"))
    harness = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(harness)
    report = harness.run_spawned(harness.parse_args(
        ["--spawn", "2", "--requests", "18", "--offered_rps", "3",
         "--new_tokens", "8"]))
    assert report["failed"] == 0
    assert report["completed"] == report["requests"] == 18
    assert report["achieved_rps"] > 0
    for key in ("ttft_s", "tpot_s", "client_wall_s"):
        for q in ("p50", "p95", "p99"):
            v = report[key][q]
            assert v == v and v >= 0, (key, q, v)  # finite, not NaN


# ---------------------------------------------------------------------------
# serving churn: KV-state migration handoff (docs/fault_tolerance.md
# "Serving state migration")


def _journal_events(tel_dir):
    path = os.path.join(tel_dir, "events.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _scrape_metrics(url):
    with urllib.request.urlopen(url + "/metrics", timeout=10) as r:
        return scrape.parse_prom_text(r.read().decode())


@pytest.mark.slow  # ~90s: two subprocess warmups + migrated live traffic
def test_chaos_sigterm_handoff_zero_failures(tmp_path):
    """SIGTERM one of 2 replicas mid-stream under concurrent traffic: its
    graceful drain MIGRATES in-flight and queued requests to the peer
    over the KV fabric — proxy completion keeps every client connection
    alive, so ZERO requests fail and every answer is token-identical to
    a solo run, greedy AND seeded-sampled. The source's journal names
    each handoff outcome; the peer imported real KV bytes and its decode
    loop never recompiled."""
    tel0 = str(tmp_path / "tel0")
    r1 = _spawn(tmp_path, "r1", fault="slow_tick:30")
    r1.wait_ready(timeout=300)
    r0 = _spawn(tmp_path, "r0", fault="slow_tick:30", peers=[r1.url],
                telemetry_dir=tel0, drain_timeout=30.0)
    router = None
    try:
        r0.wait_ready(timeout=300)
        cases = []
        for i in range(8):
            case = {"prompts": [f"{3 + i} {4 + i} {5 + i}"],
                    "tokens_to_generate": 16}
            if i % 2:  # half sampled — but SEEDED, so replay-exact
                case.update(temperature=0.8, random_seed=100 + i)
            else:
                case["temperature"] = 0.0
            cases.append(case)
        # solo references from the peer (identical seed weights on both
        # replicas => any replica's solo answer is THE answer)
        refs = []
        for c in cases:
            code, body = _post(r1.url, "/api", c)
            assert code == 200
            refs.append(body["text"])

        router = ReplicaRouter([r0.url, r1.url], probe_interval=0.2,
                               request_timeout=120.0,
                               metrics=MetricsRegistry()).start()
        results = [None] * len(cases)

        def client(i):
            results[i] = router.dispatch(json.dumps(cases[i]).encode())

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(cases))]
        for th in threads:
            th.start()
        # ~16 slow ticks per request over 2 slots: 0.6s lands the SIGTERM
        # with requests both decoding and queued on the victim
        time.sleep(0.6)
        r0.terminate()
        for th in threads:
            th.join(timeout=300)

        for i in range(len(cases)):
            status, _, rbody = results[i]
            assert status == 200, (i, status, rbody)
            assert json.loads(rbody)["text"] == refs[i], i
        assert r0.wait(timeout=60) == 0  # graceful exit after the handoff

        # the journal proves the handoff happened and succeeded: every
        # exported request landed via the lossless rungs of the ladder
        events = _journal_events(tel0)
        done = [e for e in events if e.get("kind") == "serve_migrate"
                and e.get("stage") == "handoff_done"]
        assert done, "SIGTERM landed after the traffic window"
        assert all(e["outcome"] in ("migrated", "recomputed")
                   for e in done), done
        wire = sum(e.get("wire_bytes", 0) for e in events
                   if e.get("kind") == "serve_migrate"
                   and e.get("stage") == "handoff" and e.get("ok"))
        assert wire > 0  # KV bytes actually crossed the wire
        assert any(e.get("kind") == "serve_handoff" for e in events)

        # peer side: imports were charged to the migration comm ledger
        # and the decode loop never recompiled (imported state enters
        # through the separately-jitted KV writer)
        samples = _scrape_metrics(r1.url)
        assert scrape.sample_value(
            samples, "server_migrate_wire_bytes_total", direction="in") > 0
        assert scrape.sample_value(
            samples, "engine_decode_recompiles_total") == 0
    finally:
        if router is not None:
            router.close()
        r0.close()
        r1.close()


@pytest.mark.slow  # ~80s: preempt_replica self-delivers the SIGTERM
def test_chaos_preempt_replica_fault_migrates(tmp_path):
    """`preempt_replica:N` — a preemption notice mid-decode. The replica
    SIGTERMs itself right before decode tick N; the drain hands its
    live requests to the peer, so router-fronted clients see zero
    failures and token-identical answers."""
    tel0 = str(tmp_path / "tel0")
    r1 = _spawn(tmp_path, "r1", fault="slow_tick:30")
    r1.wait_ready(timeout=300)
    r0 = _spawn(tmp_path, "r0", fault="preempt_replica:12,slow_tick:30",
                peers=[r1.url], telemetry_dir=tel0, drain_timeout=30.0)
    router = None
    try:
        r0.wait_ready(timeout=300)
        prompts = [f"{7 + i} {8 + i}" for i in range(4)]
        refs = {}
        for p in prompts:
            code, body = _post(r1.url, "/api",
                               {"prompts": [p], "tokens_to_generate": 16,
                                "temperature": 0.0})
            assert code == 200
            refs[p] = body["text"]
        router = ReplicaRouter([r0.url, r1.url], probe_interval=0.2,
                               request_timeout=120.0,
                               metrics=MetricsRegistry()).start()
        results = {}

        def client(p):
            body = json.dumps({"prompts": [p], "tokens_to_generate": 16,
                               "temperature": 0.0}).encode()
            results[p] = router.dispatch(body)

        threads = [threading.Thread(target=client, args=(p,))
                   for p in prompts]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        for p in prompts:
            status, _, rbody = results[p]
            assert status == 200, (p, status, rbody)
            assert json.loads(rbody)["text"] == refs[p], p
        # the preemption really fired and the exit was graceful
        assert r0.wait(timeout=120) == 0
        done = [e for e in _journal_events(tel0)
                if e.get("kind") == "serve_migrate"
                and e.get("stage") == "handoff_done"]
        assert done, "preempt fired with nothing in flight"
        assert all(e["outcome"] in ("migrated", "recomputed")
                   for e in done), done
    finally:
        if router is not None:
            router.close()
        r0.close()
        r1.close()


@pytest.mark.slow  # ~80s: torn-wire fault walks the degradation ladder
def test_chaos_migrate_fail_walks_degradation_ladder(tmp_path):
    """`migrate_fail:N` truncates every outbound migration frame. The
    peer's manifest+crc commit check rejects each rung (migrate, then
    recompute) — nothing is half-imported — and the source degrades to
    the honest-retry rung: the client gets a retryable 503, replays on
    the peer token-identically, and the journal names every step."""
    tel0 = str(tmp_path / "tel0")
    r1 = _spawn(tmp_path, "r1")
    r1.wait_ready(timeout=300)
    r0 = _spawn(tmp_path, "r0", fault="migrate_fail:8,slow_tick:30",
                peers=[r1.url], telemetry_dir=tel0, drain_timeout=30.0)
    try:
        r0.wait_ready(timeout=300)
        case = {"prompts": ["5 6 7"], "tokens_to_generate": 30,
                "temperature": 0.0}
        code, ref = _post(r1.url, "/api", case)
        assert code == 200
        result = {}

        def client():
            result["r"] = _post(r0.url, "/api", case)

        th = threading.Thread(target=client)
        th.start()
        time.sleep(0.4)  # mid-decode: 30 tokens at 30ms/tick ~= 0.9s
        r0.terminate()
        th.join(timeout=120)
        code, body = result["r"]
        # both lossless rungs were torn => honest retryable rejection,
        # NOT a silent half-import
        assert code == 503, (code, body)
        # the replay (what the router does on a 503) is token-identical
        code, body = _post(r1.url, "/api", case)
        assert code == 200 and body["text"] == ref["text"]
        assert r0.wait(timeout=60) == 0

        events = _journal_events(tel0)
        hand = [e for e in events if e.get("kind") == "serve_migrate"
                and e.get("stage") == "handoff"
                and e.get("rung") in ("migrate", "recompute")]
        assert {e.get("rung") for e in hand} >= {"migrate", "recompute"}
        # every torn transfer was rejected by the peer's crc check
        assert not any(e.get("ok") for e in hand), hand
        done = [e for e in events if e.get("kind") == "serve_migrate"
                and e.get("stage") == "handoff_done"]
        assert done and done[0]["outcome"] == "retried", done
        retry_rows = [e for e in events
                      if e.get("kind") == "serve_migrate"
                      and e.get("rung") == "retry"]
        assert retry_rows, "ladder's retry rung was not journaled"
    finally:
        r0.close()
        r1.close()
