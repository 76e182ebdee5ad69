"""KV-state migration tests (docs/fault_tolerance.md "Serving state
migration").

Pins the lossless-under-churn contract from the engine up:
  * wire format: manifest + per-section crc commit — round trips exactly
    (including bf16 via ml_dtypes), and EVERY torn/corrupted transfer is
    rejected loudly (MigrationIntegrityError), never half-imported;
  * mid-flight export/import is token-identical to an uninterrupted solo
    run — greedy AND sampled (the per-request PRNG chain resumes at the
    exported absolute position), across page-geometry changes, with
    int8 KV caches, and mid-speculation;
  * lossy wire codecs and sliding-window page release (no exact KV left
    to ship) degrade to recompute-resume and STAY exact;
  * export_all_requests atomically empties the engine (the SIGTERM drain
    primitive) while the original waiters stay parked on req.done;
  * the fleet-level prefix directory: a prefix primed on replica A
    becomes a radix hit on replica B via page export/import;
  * router global admission: fleet at the bound answers 503 with the
    fleet-derived Retry-After (fleet_retry_after math unit-tested);
  * tools/telemetry_report.py counts migrations by ladder outcome.

The real-subprocess churn drills (SIGTERM drain with live handoff,
preempt_replica, migrate_fail torn transfers) live in test_fleet.py.
"""

import json

import jax
import numpy as np
import pytest

from megatron_tpu.inference.engine import InferenceEngine, Request
from megatron_tpu.inference.fleet import migration
from megatron_tpu.inference.fleet.migration import (
    MigrationIntegrityError, PrefixDirectory, pack_state, unpack_state,
)
from megatron_tpu.inference.fleet.router import (
    ReplicaRouter, fleet_retry_after,
)
from megatron_tpu.models import presets
from megatron_tpu.models.params import init_params
from megatron_tpu.telemetry import MetricsRegistry
from test_serving_engine import _fake_steps

CFG = presets.tiny(vocab_size=64, seq_length=64)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
PROMPT = np.array([3, 7, 11, 2, 9], np.int32)


def mk(**kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return InferenceEngine(CFG, PARAMS, **kw)


def run_solo(temperature, **ekw):
    """Uninterrupted reference run — THE answer migration must match."""
    eng = mk(**ekw)
    r = Request(prompt=PROMPT.copy(), max_new_tokens=12,
                temperature=temperature, seed=5)
    eng.submit(r)
    eng.run_until_idle()
    return r.generated


def mid_export(temperature, ticks, src_kw=None, dst_kw=None):
    """Interrupt a request mid-decode, ship it, resume on a fresh
    engine; returns (generated tokens, import path taken)."""
    src = mk(**(src_kw or {}))
    r = Request(prompt=PROMPT.copy(), max_new_tokens=12,
                temperature=temperature, seed=5)
    src.submit(r)
    for _ in range(ticks):
        src.step()
    assert not r.done.is_set(), f"done after {ticks} ticks: {r.generated}"
    meta, sections = src.export_request_state(r)
    # round-trip through the actual wire bytes, not in-process objects
    meta, sections = unpack_state(pack_state(meta, sections))
    dst = mk(**(dst_kw or {}))
    req2, path = dst.import_request_state(meta, sections)
    dst.run_until_idle()
    assert req2.done.is_set() and req2.error is None, req2.error
    return req2.generated, path


# ---------------------------------------------------------------------------
# wire format: commit contract (pure numpy — no engine, no compiles)


def test_wire_roundtrip_exact():
    meta = {"kind": "request", "position": 7, "knobs": {"t": 0.5}}
    sections = {
        "a": np.arange(24, dtype=np.float32).reshape(2, 3, 4),
        "b": np.array([1, -2, 3], np.int32),
        "empty": np.zeros((0,), np.float32),
    }
    m2, s2 = unpack_state(pack_state(meta, sections))
    assert m2 == meta
    assert set(s2) == set(sections)
    for k in sections:
        assert s2[k].dtype == sections[k].dtype
        assert s2[k].shape == sections[k].shape
        np.testing.assert_array_equal(s2[k], sections[k])


def test_wire_roundtrip_ml_dtypes():
    """bf16 (and the fp8 wire codec's scale arrays) aren't numpy-native
    dtypes — the manifest's dtype names must resolve via ml_dtypes."""
    import ml_dtypes

    sections = {"kv": np.arange(8).astype(ml_dtypes.bfloat16)}
    _, s2 = unpack_state(pack_state({"kind": "request"}, sections))
    assert s2["kv"].dtype == ml_dtypes.bfloat16
    np.testing.assert_array_equal(
        s2["kv"].astype(np.float32), sections["kv"].astype(np.float32))


def test_wire_torn_and_corrupt_rejected():
    blob = pack_state(
        {"kind": "request"},
        {"kv": np.arange(100, dtype=np.float32),
         "tok": np.array([1, 2, 3], np.int32)})
    # truncations anywhere in the frame: header, manifest, payload, tail
    for cut in (3, len(blob) // 2, len(blob) - 1):
        with pytest.raises(MigrationIntegrityError):
            unpack_state(blob[:cut])
    # a single flipped payload bit fails the per-section crc
    flipped = bytearray(blob)
    flipped[-10] ^= 0x40
    with pytest.raises(MigrationIntegrityError):
        unpack_state(bytes(flipped))
    # wrong magic (a stray HTTP body, say) is rejected up front
    with pytest.raises(MigrationIntegrityError):
        unpack_state(b"HTTP" + blob[4:])
    # the pristine blob still imports — the checks above weren't flaky
    unpack_state(blob)


# ---------------------------------------------------------------------------
# token-identical resume (real model — tiny, CPU)


@pytest.mark.slow  # ~13s: six compiled tiny engines; tier-1 keeps the
# wire-format + fake-model scheduler coverage (the 870s budget is tight)
def test_migration_token_identity_greedy_and_sampled():
    """Interrupt at tick 4 of 12, ship over the wire, resume elsewhere:
    byte-identical output for greedy AND sampled (seeded PRNG chain
    resumes at the exported absolute position), via direct KV import."""
    for temp in (0.0, 0.8):
        want = run_solo(temp)
        got, path = mid_export(temp, ticks=4)
        assert path == "kv_import", path
        assert got == want, (temp, got, want)


@pytest.mark.slow  # ~7s: three compiled tiny engines
def test_lossy_wire_codec_falls_back_to_recompute():
    """kv_wire='int8' quantizes the shipped KV — the importer must NOT
    install inexact state; it recompute-resumes from the migrated
    tokens and stays token-identical."""
    want = run_solo(0.8)
    src = mk()
    src.kv_wire = "int8"
    r = Request(prompt=PROMPT.copy(), max_new_tokens=12,
                temperature=0.8, seed=5)
    src.submit(r)
    for _ in range(4):
        src.step()
    meta, sections = unpack_state(
        pack_state(*src.export_request_state(r)))
    dst = mk()
    req2, path = dst.import_request_state(meta, sections)
    dst.run_until_idle()
    assert path == "recompute"
    assert req2.generated == want


@pytest.mark.slow  # ~8s: three compiled int8-cache engines
def test_int8_kv_cache_migration_token_identity():
    """Quantized (int8) caches ship natively — scales ride alongside in
    the manifest and the importer installs them exactly."""
    want = run_solo(0.8, kv_cache_int8=True)
    got, path = mid_export(0.8, 4, {"kv_cache_int8": True},
                           {"kv_cache_int8": True})
    assert path == "kv_import" and got == want


@pytest.mark.slow  # ~20s: six compiled engines
def test_pool_accounting_and_cross_geometry_migration():
    """A migration keeps pool accounting honest; pages of 8 -> pages of
    16 and back both resume token-identically (the canonical wire layout
    is geometry-free)."""
    want = run_solo(0.8)
    src = mk()
    r = Request(prompt=PROMPT.copy(), max_new_tokens=12,
                temperature=0.8, seed=5)
    src.submit(r)
    for _ in range(6):
        src.step()
    meta, sections = unpack_state(pack_state(*src.export_request_state(r)))
    dst = mk()
    free0 = dst.pool.free_pages
    req2, path = dst.import_request_state(meta, sections)
    assert path == "kv_import"
    assert dst.pool.free_pages < free0  # the span's pages are held
    dst.run_until_idle()
    assert req2.generated == want
    assert dst.num_active == 0
    # retirement returned the decode pages (radix may hold prompt pages)
    assert dst.pool.free_pages >= free0 - 1

    got, _ = mid_export(0.8, 4, {"page_size": 16}, {})
    assert got == want
    got, _ = mid_export(0.8, 6, {}, {"page_size": 16})
    assert got == want


@pytest.mark.slow  # ~15s: larger cfg (seq 128) compiles, 3 engines
def test_sliding_window_release_migrates_via_recompute():
    """Sliding-window page release parks behind-the-window pages on
    scratch — no exact KV span exists to ship, so export omits KV and
    the importer recompute-resumes, still token-identical (the window
    mask is a pure function of position)."""
    cfg = presets.tiny(vocab_size=64, seq_length=128, num_layers=2,
                       sliding_window_size=16)
    params = init_params(cfg, jax.random.PRNGKey(0))

    def mkw():
        return InferenceEngine(cfg, params, num_slots=2, max_seq_len=128,
                               page_size=8, prefill_chunk=16)

    prompt = np.arange(1, 13, dtype=np.int32)
    solo = mkw()
    rs = Request(prompt=prompt.copy(), max_new_tokens=40,
                 temperature=0.8, seed=3)
    solo.submit(rs)
    solo.run_until_idle()

    src = mkw()
    r = Request(prompt=prompt.copy(), max_new_tokens=40,
                temperature=0.8, seed=3)
    src.submit(r)
    while src.stats["window_pages_released"] == 0:
        assert src.step() > 0, "request finished before any release"
    assert not r.done.is_set()
    meta, sections = src.export_request_state(r)
    assert "kv" not in meta  # nothing exact to ship
    meta, sections = unpack_state(pack_state(meta, sections))
    dst = mkw()
    req2, path = dst.import_request_state(meta, sections)
    dst.run_until_idle()
    assert path == "recompute"
    assert req2.generated == rs.generated


@pytest.mark.slow  # ~12s: three compiled speculative engines
def test_mid_speculation_migration_token_identity():
    """Interrupting between speculative verify ticks exports committed
    state only (drafts are never state) — the importer, itself running
    the ngram drafter, resumes token-identically."""
    from megatron_tpu.inference.speculative import SpecConfig

    spec = SpecConfig(k=3, drafter="ngram")
    want = run_solo(0.8, speculative=spec)
    got, path = mid_export(0.8, 2, {"speculative": spec},
                           {"speculative": spec})
    assert got == want, (got, want)


# ---------------------------------------------------------------------------
# drain primitive: atomic export of everything in flight


def test_export_all_requests_empties_engine():
    """The SIGTERM-drain primitive: every active AND queued request
    leaves in one atomic sweep, the engine is empty afterwards, and the
    original waiters stay parked on req.done for proxy completion."""
    eng = _fake_steps(mk(num_slots=2))
    reqs = [eng.submit(Request(prompt=np.asarray([i + 1], np.int32),
                               max_new_tokens=8)) for i in range(4)]
    for _ in range(3):
        eng.step()
    exported = eng.export_all_requests()
    assert len(exported) == 4
    assert eng.num_active == 0 and len(eng._queue) == 0
    for req, meta, sections in exported:
        assert req in reqs
        assert not req.done.is_set()  # waiter still parked: proxy owns it
        assert meta["kind"] == "request"
        # the wire frame for each is well-formed
        unpack_state(pack_state(meta, sections))
    # the drained engine still serves new traffic
    r = eng.submit(Request(prompt=np.asarray([9], np.int32),
                           max_new_tokens=2))
    eng.run_until_idle()
    assert r.generated == [10, 11]
    for req in reqs:  # don't leak parked waiters
        req._finish("test cleanup")


def test_export_all_then_import_resumes_on_fake_model():
    """Scheduler-level handoff: drain engine A, import every request
    into engine B, all finish with exactly the tokens an uninterrupted
    run produces."""
    a = _fake_steps(mk(num_slots=2))
    reqs = [a.submit(Request(prompt=np.asarray([10 * (i + 1)], np.int32),
                             max_new_tokens=5)) for i in range(3)]
    for _ in range(2):
        a.step()
    b = _fake_steps(mk(num_slots=2))
    imported = []
    # include_kv=False forces the recompute rung — the fake model has no
    # real caches, and the jitted KV-install writer would compile
    for req, meta, sections in a.export_all_requests(include_kv=False):
        meta, sections = unpack_state(pack_state(meta, sections))
        req2, path = b.import_request_state(meta, sections)
        assert path == "recompute"
        imported.append(req2)
    b.run_until_idle()
    got = sorted(tuple(r.generated) for r in imported)
    want = sorted(tuple((10 * (i + 1) + 1 + j) % 64 for j in range(5))
                  for i in range(3))
    assert got == want
    for req in reqs:
        req._finish("test cleanup")


# ---------------------------------------------------------------------------
# fleet-level prefix directory


@pytest.mark.slow  # ~10s: two compiled engines
def test_prefix_export_import_cross_replica():
    """A system prompt primed on A becomes a radix hit on B after page
    export/import — and B's follower answer is token-identical to A's."""
    a = mk(num_slots=2)
    sys_prompt = np.arange(1, 17, dtype=np.int32)  # two full pages
    lens = np.array([16], np.int32)
    ref = a.generate(sys_prompt[None, :], lens, max_new_tokens=8)
    exported = a.export_prefix_state(sys_prompt.tolist())
    assert exported is not None
    meta, sections = exported
    assert meta["kind"] == "prefix"
    meta, sections = unpack_state(pack_state(meta, sections))
    b = mk(num_slots=2)
    pages = b.import_prefix_state(meta, sections)
    assert pages >= 1
    hits0 = b.stats["prefix_hits"]
    out = b.generate(sys_prompt[None, :], lens, max_new_tokens=8)
    assert b.stats["prefix_hits"] > hits0  # served from imported pages
    np.testing.assert_array_equal(out.tokens, ref.tokens)


def test_prefix_directory_bookkeeping():
    d = PrefixDirectory()
    toks = [1, 2, 3, 4]
    assert d.locations(toks) == []
    d.register(toks, "http://b:1")
    d.register(toks, "http://a:1")
    assert d.locations(toks) == ["http://a:1", "http://b:1"]
    d.forget_replica("http://a:1")
    assert d.locations(toks) == ["http://b:1"]
    snap = d.snapshot()
    assert snap and snap[0]["prefix_len"] == 4
    assert snap[0]["replicas"] == ["http://b:1"]


# ---------------------------------------------------------------------------
# router: global admission + Retry-After math (no replicas needed)


def test_fleet_retry_after_math():
    # empty fleet queue: the floor
    assert fleet_retry_after(0, 2) == 1
    # 10 queued over 2 replicas at 2 rps each: ceil(10/4) = 3
    assert fleet_retry_after(10, 2) == 3
    # massive backlog clamps at the ceiling
    assert fleet_retry_after(1000, 2) == 60
    # no routable replica and no drain ETA: worst case
    assert fleet_retry_after(5, 0) == 60
    # no routable replica but a drain ETA: come back just after it
    assert fleet_retry_after(5, 0, drain_eta_s=7.2) == 8


def test_router_global_admission_rejects_with_retry_after(tmp_path):
    from megatron_tpu.telemetry.journal import (
        EventJournal, set_global_journal,
    )

    set_global_journal(EventJournal(str(tmp_path / "events.jsonl")))
    try:
        router = ReplicaRouter(["http://127.0.0.1:1"],
                               global_max_queue=0,
                               metrics=MetricsRegistry())
        body = json.dumps({"prompts": ["1 2"],
                           "tokens_to_generate": 2}).encode()
        status, headers, rbody = router.dispatch(body)
        assert status == 503
        assert int(headers["Retry-After"]) >= 1
        assert b"admission" in rbody
        assert router.metrics.counter(
            "router_admission_rejected_total").value() == 1.0
    finally:
        set_global_journal(None)
    events = [json.loads(line) for line in
              open(tmp_path / "events.jsonl")]
    adm = [e for e in events if e["kind"] == "serve_admission"]
    assert adm and adm[0]["accepted"] is False
    assert adm[0]["bound"] == 0 and adm[0]["retry_after_s"] >= 1


# ---------------------------------------------------------------------------
# telemetry report: the churn ledger


def test_telemetry_report_migrations_section():
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    events = (
        [{"kind": "serve_migrate", "stage": "handoff_done",
          "outcome": "migrated"}] * 2
        + [{"kind": "serve_migrate", "stage": "handoff_done",
            "outcome": "recomputed"},
           {"kind": "serve_migrate", "stage": "handoff_done",
            "outcome": "retried"},
           {"kind": "serve_migrate", "stage": "handoff",
            "rung": "migrate", "ok": True, "wire_bytes": 1200},
           {"kind": "serve_migrate", "stage": "handoff",
            "rung": "migrate", "ok": False, "wire_bytes": 900},
           {"kind": "serve_migrate", "stage": "handoff",
            "rung": "recompute", "ok": True, "wire_bytes": 300},
           {"kind": "serve_migrate", "stage": "import",
            "path": "kv_import"},
           {"kind": "serve_migrate", "stage": "import",
            "path": "recompute"},
           {"kind": "serve_retry_resampled", "replica": "u",
            "attempts": 2, "seeded": False}])
    sv = telemetry_report.summarize(events)["serving"]
    mig = sv["migrations"]
    assert mig["by_outcome"] == {"migrated": 2, "recomputed": 1,
                                 "retried": 1}
    assert mig["imports_by_path"] == {"kv_import": 1, "recompute": 1}
    assert mig["wire_bytes"] == 1500  # only ok transfers are charged
    assert mig["retries_resampled"] == 1
    text = telemetry_report.render(telemetry_report.summarize(events))
    assert "migrations:" in text and "1500 KV wire bytes" in text
    assert "serve_retry_resampled" in text
    # resampled retries surface even with zero migrations
    sv2 = telemetry_report.summarize(
        [{"kind": "serve_retry_resampled", "seeded": False}])["serving"]
    assert sv2["migrations"]["retries_resampled"] == 1
