"""REST text-generation server.

Observability: GET /metrics returns the process metrics registry in
Prometheus text format (slot occupancy, queue depth, TTFT and per-token
latency histograms, admitted/retired counters, HTTP request counters —
docs/observability.md), GET /healthz a liveness probe ("the process and
its step loop exist") and GET /readyz a readiness probe ("routing a
request here right now would not queue-stall": 503 until the decode step
is warmed, while draining or mid-reload, and when the step loop has
pending work but stopped making progress). The fleet router
(inference/fleet/router.py) and any k8s-style prober key off /readyz;
/healthz deliberately stays green through drains so an orchestrator does
not kill a replica that is merely finishing its in-flight work.

Fleet control plane (POST, docs/serving.md "Fleet"):

  /admin/drain    {"timeout_s": F, "handoff": [urls]?}
                                    stop admitting (new /api requests get
                                    503 + Retry-After); with handoff peers
                                    (the field, or --serve peers) migrate
                                    in-flight + queued requests to them,
                                    else wait for them to finish
  /admin/import   <binary frame>    accept a migrated request's state
                                    (fleet/migration.py wire format), run
                                    it to completion, return its output;
                                    409 on a torn/corrupt frame
  /admin/export_prefix {"tokens": [...]}
                                    pack a cached prefix's KV pages as a
                                    binary frame (404 when not cached)
  /admin/import_prefix <binary frame>
                                    install exported prefix pages into
                                    the local radix cache
  /admin/register_prefix {"tokens": [...]}
                                    ensure a prefix is radix-resident
                                    (prime with one greedy token if not)
  /admin/readmit  {}                resume admission after a drain
  /admin/reload   {"load": DIR, "iteration": N?}
                                    hot weight reload: manifest-verified
                                    committed checkpoint -> engine
                                    update_params between decode ticks
                                    (zero recompiles, zero dropped
                                    requests)
  /admin/profile  {"steps": N}      on-demand profiler capture: trace N
                                    decode ticks under live traffic into
                                    an xplane dir readable by
                                    tools/trace_report.py (also accepts
                                    ?steps=N query form; zero recompiles,
                                    zero overhead while disarmed)
  /admin/status                     (GET) draining/ready/weights_version/
                                    engine stats

Equivalent of megatron/text_generation_server.py (241 LoC,
Flask + flask_restful) on the stdlib http.server — PUT/POST /api with the
same request schema:

  {"prompts": [...], "tokens_to_generate": N, "temperature": T,
   "top_k": K, "top_p": P, "add_BOS": bool, "logprobs": bool,
   "random_seed": S, "beam_width": W?}

beam_width switches to beam search (the reference's separate BEAM choice
int broadcast becomes just a field — no multi-rank choreography).

Two execution models behind the same schema:

  * engine_slots > 0 (default for the CLI): sampling requests go through
    the continuous-batching InferenceEngine — concurrent HTTP handlers
    each submit their prompts and SHARE every batched decode tick instead
    of serializing behind a lock (docs/serving.md). Beam search and
    scoring (tokens_to_generate == 0) still take the one-shot path.
  * engine_slots == 0: the reference's Flask-era shape — a global lock
    serializes whole requests through generate_tokens.
"""

from __future__ import annotations

import json
import contextlib
import os
import signal
import sys
import threading
import time
import uuid

import jax
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional

from megatron_tpu.config import ModelConfig
from megatron_tpu.inference.api import (
    beam_search_and_post_process, generate_and_post_process,
)
from megatron_tpu.inference.engine import (
    EngineOverloadedError, RequestTimeoutError,
)
from megatron_tpu.telemetry.http import PROMETHEUS_CONTENT_TYPE
from megatron_tpu.telemetry.metrics import MetricsRegistry, default_registry

# A bound on one request's size (the reference caps requests similarly, at
# 1024: an answer that reasons before it replies is longer). What a
# sequence may hold is the engine's to say: admission rejects prompt +
# answer past its sequence length.
MAX_TOKENS_TO_GENERATE = 4096
MAX_PROMPTS = 128
#: Retry-After hint on 503 queue-full rejections: one decode tick's
#: worth of backoff is enough for a slot to free in steady traffic
RETRY_AFTER_SECONDS = 1
#: a request's name: taken from the client where it sends one, made here
#: otherwise, echoed on the reply (docs/serving.md "A request's time")
REQUEST_ID_HEADER = "X-Request-Id"
#: engine progress-stall window before readiness flips (a hung device
#: step keeps the thread "alive" — only lack of progress reveals it)
STALL_THRESHOLD_SECONDS = 10.0


class ServiceDrainingError(RuntimeError):
    """The server is draining (SIGTERM grace or a rolling update): new
    requests answer 503 + Retry-After so the router re-routes them."""


def _lane_meshes(mesh, cp_lanes: int) -> list:
    """CP x DP device carving: every lane gets its own context-only
    cp-sized mesh over a distinct device group (the serving mesh's
    devices first, then the host's remaining devices). The incoming
    mesh must not SHARD params — tensor/pipe/expert > 1 refuse, since
    a lane could not replicate its params copy with a plain
    device_put; a `data` axis is pure replication for serving (the CLI
    mesh builder parks unused devices there) and is re-carved into
    lanes."""
    import numpy as np
    from jax.sharding import Mesh

    from megatron_tpu.parallel.mesh import AXIS_CONTEXT

    shape = dict(mesh.shape)
    cp = shape.get(AXIS_CONTEXT, 1)
    sharded = {a: n for a, n in shape.items()
               if n > 1 and a not in (AXIS_CONTEXT, "data")}
    if sharded:
        raise ValueError(
            "cp_lanes > 1 needs a context-only mesh (no tensor/pipe/"
            f"expert sharding); got {shape} — a tensor-sharded lane "
            "cannot replicate its params copy with a plain device_put")
    pool = list(mesh.devices.flat)
    seen = {d.id for d in pool}
    pool += [d for d in jax.devices() if d.id not in seen]
    need = cp_lanes * cp
    if len(pool) < need:
        raise ValueError(
            f"cp_lanes={cp_lanes} x cp={cp} needs {need} devices; "
            f"only {len(pool)} visible")
    return [Mesh(np.array(pool[i * cp:(i + 1) * cp]).reshape((cp,)),
                 (AXIS_CONTEXT,))
            for i in range(cp_lanes)]


class GenerationService:
    def __init__(self, cfg: ModelConfig, params: Any, tokenizer,
                 mesh=None, forward_fn=None, kv_cache_int8=False,
                 engine_slots: int = 0, engine_max_seq_len=None,
                 metrics: Optional[MetricsRegistry] = None,
                 engine_max_queue: Optional[int] = None,
                 page_size: int = 16, prefill_chunk: int = 32,
                 num_pages: Optional[int] = None,
                 request_timeout: Optional[float] = None,
                 reload_dir: Optional[str] = None,
                 weights_version: Optional[int] = None,
                 stall_threshold_s: float = STALL_THRESHOLD_SECONDS,
                 warmup: bool = False,
                 speculative: Optional[str] = None,
                 spec_k: int = 4,
                 draft_cfg=None, draft_params=None,
                 profile_dir: Optional[str] = None,
                 compress_collectives: str = "none",
                 comm_policy: Optional[str] = None,
                 cp_serving: bool = False,
                 cp_collectives: str = "dense",
                 cp_comm_policy: Optional[str] = None,
                 cp_geometry: str = "ring",
                 cp_subgroup: int = 0,
                 cp_overlap: bool = True,
                 cp_lanes: int = 1,
                 peers: Optional[list] = None):
        """mesh + forward_fn serve sharded models: the mesh becomes
        ambient around generation (GSPMD handles tp/cp), forward_fn is the
        pp>1 pipelined forward (ref ForwardStep, forward_step.py:45-204).

        engine_slots > 0 builds a continuous-batching InferenceEngine with
        that many sequences over a shared page pool (page_size,
        prefill_chunk, num_pages: radix prefix cache + chunked prefill,
        docs/serving.md) plus its background step-loop thread; concurrent
        sampling requests then share each decode tick.
        engine_max_queue bounds admission — overload answers 503 with
        Retry-After instead of growing queue latency without bound.

        request_timeout: default per-request deadline (seconds) on the
        engine path — a queued or mid-decode request past it fails with
        HTTP 504 instead of waiting forever (--serve_request_timeout).
        reload_dir: default checkpoint dir for POST /admin/reload;
        weights_version: iteration initially served (when loaded from a
        committed checkpoint), reported in responses + /admin/status.
        warmup=True defers readiness (/readyz stays 503) until warmup()
        has compiled the decode step — run_server drives it on a
        background thread so probes get answered during the compile.
        speculative: "ngram" or "model" turns on speculative decoding
        in the engine (--serve_speculative; docs/serving.md): spec_k
        drafts per slot verified by one multi-token target forward per
        tick, greedy output token-identical to plain decode. "model"
        needs draft_cfg + draft_params (a small draft network with its
        own cache tree). Requests may opt out per call with
        {"spec": false}.

        compress_collectives ("none"|"int8"|"fp8";
        --serve_compress_collectives): low-bit tensor-parallel
        collectives in the engine decode/prefill forward (quant/,
        docs/serving.md) — a no-op unless the mesh has a non-trivial
        tensor axis. comm_policy: path to a site-policy JSON
        (tools/trace_report.py --emit-comm-policy) choosing WHICH
        collectives compress from measured exposed fractions.

        cp_serving (--serve_context_parallel; docs/serving.md
        "Context-parallel long-context serving"): shard every sequence's
        paged KV over the mesh's "context" axis and run decode/prefill
        attention as a ring over the shards — million-token prompts
        whose KV exceeds one device's HBM. Needs a mesh
        with context >= 2; greedy output stays token-identical to the
        single-host engine. cp_collectives ("dense"|"int8"|"fp8")
        picks the ring-hop transport; cp_comm_policy is a site-policy
        JSON gating the "cp_ring" and "cp_a2a" sites.

        cp_geometry (--serve_cp_geometry): "ring" is the flat 1D
        sequence ring; "2d" factors the context axis into
        cp_seq x cp_head (cp_subgroup = cp_head, the node-local device
        count) — head all-to-all inside the subgroup, ring hops only
        across subgroups (docs/serving.md "CP geometry and overlap").
        cp_overlap picks the overlapped ring schedule (default; serial
        kept for A/B trace capture). cp_lanes > 1 (CP x DP): one host
        runs that many INDEPENDENT CP engine lanes, each over its own
        cp-sized device group with its own KV pool and queue; requests
        dispatch to the least-loaded lane and /metrics exposes one
        series per lane (lane="0", ...) that the fleet router's load
        scrape sums. Lanes need a context-only mesh (tp == 1) and do
        not compose with peers (migration handoff) or /admin/reload.

        peers: base URLs of sibling replicas (http://host:port). A drain
        (SIGTERM grace or /admin/drain) HANDS OFF in-flight and queued
        requests to them via the KV migration fabric
        (fleet/migration.py) instead of failing them — the degradation
        ladder per request is migrate -> recompute-resume -> retry ->
        reject, each rung journaled as `serve_migrate`."""
        if kv_cache_int8 and forward_fn is not None:
            # fail at construction, not as a 500 on every request — the
            # pipelined forward threads bf16 cache pairs (the same guard
            # generate_tokens applies per call)
            raise ValueError(
                "kv_cache_int8 is not supported with a pipelined (pp>1) "
                "forward_fn — serve pp>1 models with bf16 KV caches")
        if engine_slots and forward_fn is not None:
            raise ValueError(
                "the continuous-batching engine runs the single-stage "
                "forward only — serve pp>1 models with engine_slots=0")
        self.cfg = cfg
        self.params = params
        self.tokenizer = tokenizer
        self.mesh = mesh
        self.forward_fn = forward_fn
        self.kv_cache_int8 = kv_cache_int8
        self.request_timeout = request_timeout
        self.reload_dir = reload_dir
        # default output dir for /admin/profile captures (each capture
        # lands in its own plugins/profile/<session> subdir)
        self.profile_dir = profile_dir or "runs/serve_profile"
        self.weights_version = weights_version
        self.stall_threshold_s = stall_threshold_s
        self.draining = False
        self.reloading = False
        # readiness gate: set once the decode step is compiled (warmup()
        # ran, or no warmup was requested and first-request compile is
        # acceptable) — /readyz answers 503 until then so the router never
        # routes a request into a multi-second compile stall
        self._warmed = threading.Event()
        # one admin mutation (drain/readmit/reload) at a time — a rolling
        # update racing a second orchestrator must serialize, not interleave
        self._admin_lock = threading.Lock()
        self.lock = threading.Lock()
        # one registry serves /metrics: the engine's slot/latency
        # collectors and the HTTP layer's request counters both land here
        self.metrics = metrics if metrics is not None else default_registry()
        self._m_requests = self.metrics.counter(
            "server_requests_total", "API requests by outcome",
            label_names=("status",))
        self._m_latency = self.metrics.histogram(
            "server_request_seconds", "API request wall time")
        self.peers = [str(p).rstrip("/") for p in (peers or [])]
        self._m_migrations = self.metrics.counter(
            "server_migrations_total",
            "request handoffs by degradation-ladder outcome",
            label_names=("outcome",))
        # the KV-transfer comm ledger (manifest cost model: bytes on the
        # wire per migration frame). Deliberately SEPARATE from the
        # engine_comm_*_bytes_total TP-collective counters so the
        # compressed-collective ratio math stays uncontaminated.
        self._m_migrate_bytes = self.metrics.counter(
            "server_migrate_wire_bytes_total",
            "KV-state migration wire bytes (manifest cost model)",
            label_names=("direction",))
        self.engine = None
        self.engines: list = []
        self.cp_lanes = int(cp_lanes)
        if self.cp_lanes < 1:
            raise ValueError(f"cp_lanes must be >= 1, got {cp_lanes}")
        if self.cp_lanes > 1:
            if not cp_serving:
                raise ValueError(
                    "cp_lanes > 1 is the CP x DP geometry — it needs "
                    "--serve_context_parallel")
            if self.peers:
                raise ValueError(
                    "cp_lanes > 1 does not compose with migration "
                    "handoff peers yet — run one lane per replica to "
                    "keep handoff")
        if speculative and not engine_slots:
            raise ValueError(
                "speculative decoding runs inside the continuous-batching "
                "engine — serve with engine_slots > 0")
        if engine_slots:
            spec_cfg = None
            if speculative:
                from megatron_tpu.inference.speculative import SpecConfig

                spec_cfg = SpecConfig(k=spec_k, drafter=speculative,
                                      draft_cfg=draft_cfg,
                                      draft_params=draft_params)
            if cp_serving:
                from megatron_tpu.inference.context_parallel import (
                    ContextParallelEngine,
                )

                if kv_cache_int8 or spec_cfg is not None:
                    raise ValueError(
                        "context-parallel serving supports neither int8 "
                        "KV pools nor speculative decoding")
                def _cp_engine(lane_mesh, lane_params, lane_metrics):
                    return ContextParallelEngine(
                        cfg, lane_params, num_slots=engine_slots,
                        max_seq_len=engine_max_seq_len,
                        page_size=page_size, prefill_chunk=prefill_chunk,
                        num_pages=num_pages,
                        vocab_size=tokenizer.vocab_size, mesh=lane_mesh,
                        metrics=lane_metrics, max_queue=engine_max_queue,
                        compress_collectives=compress_collectives,
                        comm_policy=comm_policy,
                        cp_collectives=cp_collectives,
                        cp_comm_policy=cp_comm_policy,
                        cp_geometry=cp_geometry,
                        cp_subgroup=cp_subgroup,
                        cp_overlap=cp_overlap)

                if self.cp_lanes > 1:
                    from jax.sharding import NamedSharding, PartitionSpec

                    from megatron_tpu.telemetry.metrics import (
                        LabeledRegistryView,
                    )

                    # every lane mesh is context-only (the serving mesh
                    # may carry a replication-only data axis the lanes
                    # re-carve), so each lane replicates its own params
                    # copy onto its device group
                    for i, lane_mesh in enumerate(
                            _lane_meshes(mesh, self.cp_lanes)):
                        lane_params = jax.device_put(
                            params, NamedSharding(lane_mesh,
                                                  PartitionSpec()))
                        self.engines.append(_cp_engine(
                            lane_mesh, lane_params,
                            LabeledRegistryView(self.metrics,
                                                lane=str(i))))
                    self.engine = self.engines[0]
                else:
                    self.engine = _cp_engine(mesh, params, self.metrics)
            else:
                from megatron_tpu.inference.engine import InferenceEngine

                self.engine = InferenceEngine(
                    cfg, params, num_slots=engine_slots,
                    max_seq_len=engine_max_seq_len,
                    kv_cache_int8=kv_cache_int8,
                    page_size=page_size, prefill_chunk=prefill_chunk,
                    num_pages=num_pages,
                    vocab_size=tokenizer.vocab_size, mesh=mesh,
                    metrics=self.metrics, max_queue=engine_max_queue,
                    speculative=spec_cfg,
                    compress_collectives=compress_collectives,
                    comm_policy=comm_policy)
            if not self.engines:
                self.engines = [self.engine]
            for eng in self.engines:
                eng.start()
        if not (warmup and self.engine is not None):
            # no deferred warmup: the first request pays the compile (the
            # pre-fleet behavior) and readiness is green from the start
            self._warmed.set()

    def shutdown(self) -> None:
        """Stop every engine lane's step-loop thread (no-op without an
        engine)."""
        for eng in self.engines:
            eng.stop()

    # ----- fleet control plane (docs/serving.md "Fleet") -------------------

    def _journal(self, kind: str, **fields) -> None:
        from megatron_tpu.telemetry.journal import get_global_journal

        j = get_global_journal()
        if j is not None:
            j.emit(kind, **fields)

    def warmup(self) -> None:
        """Compile the engine's decode step + smallest prefill bucket with
        a throwaway request, then flip readiness green. Runs on a
        background thread (run_server) so /readyz answers 503 — not a
        connection timeout — during the multi-second compile."""
        if self.engine is not None and not self._warmed.is_set():
            import numpy as np

            t0 = time.monotonic()
            for eng in self.engines:
                eng.generate(np.array([[1]], np.int32),
                             np.array([1], np.int32), max_new_tokens=2)
            self._journal("serve_warmup", lanes=len(self.engines),
                          wall_s=round(time.monotonic() - t0, 3))
        self._warmed.set()

    def ready(self) -> tuple:
        """(ok, detail) for /readyz: would routing a request here right
        now queue-stall? 503 while unwarmed, draining, mid-reload, or when
        the step loop has pending work but stopped making progress."""
        detail: dict = {"warmed": self._warmed.is_set(),
                        "draining": self.draining,
                        "reloading": self.reloading}
        ok = detail["warmed"] and not self.draining and not self.reloading
        if self.engine is not None:
            alive = all(e._thread is None or e._thread.is_alive()
                        for e in self.engines)
            stalled = any(e.stalled(self.stall_threshold_s)
                          for e in self.engines)
            detail["step_loop_alive"] = alive
            detail["stalled"] = stalled
            ok = ok and alive and not stalled
        if self.weights_version is not None:
            detail["weights_version"] = self.weights_version
        detail["ok"] = ok
        return ok, detail

    def drain(self, timeout_s: float = 30.0,
              handoff_urls: Optional[list] = None) -> bool:
        """Stop admitting (new /api requests answer 503 + Retry-After) and
        wait for in-flight work to finish; True when fully drained within
        `timeout_s`. The server keeps serving probes and admin requests —
        readmit() undoes the drain.

        When handoff peers exist (`handoff_urls`, else the server's
        configured `peers`), in-flight and queued engine requests are
        MIGRATED to them first (migrate_out) instead of being waited on —
        their clients get full responses assembled from the peer's
        continuation, so a drain costs zero failed requests and near-zero
        added latency even with minutes of decoding still queued."""
        with self._admin_lock:
            self.draining = True
            peers = [str(p).rstrip("/") for p in
                     (handoff_urls if handoff_urls else self.peers)]
            self._journal("serve_drain_begin", timeout_s=timeout_s,
                          handoff_peers=len(peers))
            deadline = time.monotonic() + timeout_s
            if peers and self.engine is not None:
                self.migrate_out(peers, timeout_s=timeout_s)
            drained = all(
                eng.wait_idle(
                    timeout=max(deadline - time.monotonic(), 0.001))
                for eng in self.engines) if self.engine is not None \
                else True
            if drained:
                # even with an engine, beam-search and scoring requests
                # run one-shot under self.lock — a drain that ignored
                # them would report "drained" with a beam request still
                # mid-generation and let a reload swap params under it
                drained = self.lock.acquire(
                    timeout=max(deadline - time.monotonic(), 0.001))
                if drained:
                    self.lock.release()
            self._journal("serve_drain_done", drained=drained)
            return drained

    def readmit(self) -> None:
        """Resume admission after a drain (rolling-update readmit step)."""
        with self._admin_lock:
            self.draining = False
            self._journal("serve_readmit")

    # ----- KV-state migration (docs/fault_tolerance.md) --------------------

    def migrate_out(self, peers: list, timeout_s: float = 30.0) -> dict:
        """Hand off every in-flight and queued engine request to a peer.

        export_all_requests atomically empties the engine (its waiters
        stay blocked on req.done); each exported request then walks the
        degradation ladder in _handoff_one and its waiter is completed or
        failed accordingly. Returns {outcome: count}."""
        deadline = time.monotonic() + timeout_s
        exported = self.engine.export_all_requests()
        outcomes: dict = {}
        for req, meta, sections in exported:
            budget = max(deadline - time.monotonic(), 0.0)
            outcome = self._handoff_one(req, meta, sections, peers, budget)
            outcomes[outcome] = outcomes.get(outcome, 0) + 1
            self._m_migrations.inc(outcome=outcome)
        if exported:
            self._journal("serve_handoff", requests=len(exported),
                          peers=len(peers), **outcomes)
        return outcomes

    def _handoff_one(self, req, meta: dict, sections: dict, peers: list,
                     budget_s: float) -> str:
        """One request down the degradation ladder:

          migrate    POST the full state (KV pages + scales + chain) to a
                     peer's /admin/import; the peer finishes the request
                     token-identically and we complete the client's
                     response with its output
          recompute  same transfer WITHOUT the KV sections — the peer
                     recompute-resumes (teacher-forced prefill over
                     prompt + generated, exact via the migrated chain)
          retry      no peer accepted: fail the waiter as overloaded
                     (503 + Retry-After) so the router re-runs it — safe
                     for greedy and seeded requests (docs/serving.md)
          reject     the drain budget is already spent: fail as timed out
                     (504, non-retryable — the client's budget went with
                     it)

        Every rung attempt is journaled (`serve_migrate` stage="handoff")
        and the final outcome as stage="handoff_done". Returns the
        outcome label."""
        from megatron_tpu.inference.fleet import migration
        from megatron_tpu.training import resilience

        deadline = time.monotonic() + budget_s

        def _done(outcome: str) -> str:
            self._journal("serve_migrate", stage="handoff_done",
                          outcome=outcome, prompt_len=len(req.prompt),
                          generated=len(req.generated))
            return outcome

        rungs = []
        if "kv" in meta:
            rungs.append(("migrate", meta, sections))
        rungs.append(("recompute",
                      {k: v for k, v in meta.items() if k != "kv"},
                      {k: v for k, v in sections.items()
                       if not k.startswith("kv_")}))
        for rung, m, s in rungs:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            blob = migration.pack_state(m, s)
            # fault injection: migrate_fail:N tears the first N outbound
            # transfers — the peer's crc check must reject each one and
            # this loop must keep walking down the ladder
            blob = resilience.maybe_corrupt("migrate_fail", blob)
            for peer in peers:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                t0 = time.monotonic()
                status, body = migration.post_blob(
                    peer + "/admin/import", blob, timeout=remaining)
                ok = status == 200 and isinstance(body, dict)
                fields = {"stage": "handoff", "rung": rung, "ok": ok,
                          "peer": peer, "status": status,
                          "wire_bytes": len(blob),
                          "wall_s": round(time.monotonic() - t0, 3)}
                if not ok:
                    err = (body or {}).get("message") or (
                        body or {}).get("error")
                    if err:
                        fields["error"] = str(err)[:200]
                self._journal("serve_migrate", **fields)
                if not ok:
                    continue
                self._m_migrate_bytes.inc(len(blob), direction="out")
                req.generated[:] = [int(t) for t in
                                    body.get("generated", [])]
                lp = body.get("logprobs")
                if lp is not None:
                    req.logprobs[:] = [float(x) for x in lp]
                plp = body.get("prompt_logprobs")
                if plp and not req.prompt_logprobs:
                    req.prompt_logprobs = [float(x) for x in plp]
                req._finish()
                return _done("migrated" if body.get("path") == "kv_import"
                             else "recomputed")
        if time.monotonic() >= deadline:
            self._journal("serve_migrate", stage="handoff", rung="reject",
                          ok=False, reason="drain budget spent")
            self.engine._fail_timeout(req, "migrating")
            return _done("rejected")
        self._journal("serve_migrate", stage="handoff", rung="retry",
                      ok=True)
        req.overloaded = True
        req._finish(
            "handoff failed on every peer; request is retryable (the "
            "fleet router re-runs it — greedy and seeded requests replay "
            "identically)")
        return _done("retried")

    def import_state(self, blob: bytes) -> dict:
        """Accept a migration frame (POST /admin/import): verify the
        manifest + crc commit contract, rebuild the request in this
        engine (direct KV install or recompute-resume), run it to
        completion, and return its output for the exporter to complete
        the original client's response with. Torn transfers raise
        MigrationIntegrityError (HTTP 409) BEFORE touching the engine."""
        from megatron_tpu.inference.fleet import migration

        if self.engine is None:
            raise ValueError(
                "state import needs the continuous-batching engine "
                "(engine_slots > 0)")
        if self.draining:
            raise ServiceDrainingError(
                "server is draining; migrate elsewhere")
        meta, sections = migration.unpack_state(blob)
        if meta.get("kind") != "request":
            raise ValueError(
                f"expected a request-state frame, got {meta.get('kind')!r}")
        self._m_migrate_bytes.inc(len(blob), direction="in")
        req, path = self.engine.import_request_state(meta, sections)
        budget = meta.get("deadline_remaining_s")
        if budget is None:
            budget = self.request_timeout or 60.0
        if not req.done.wait(timeout=float(budget) + 5.0):
            raise RequestTimeoutError(
                "imported request did not complete within its migrated "
                "deadline")
        if req.timed_out:
            raise RequestTimeoutError(req.error or "deadline exceeded")
        if req.error:
            raise ValueError(req.error)
        return {"path": path,
                "generated": [int(t) for t in req.generated],
                "logprobs": [float(x) for x in req.logprobs],
                "prompt_logprobs": [float(x) for x in req.prompt_logprobs]}

    # ----- fleet prefix directory (page export) ----------------------------

    def _need_engine(self):
        if self.engine is None:
            raise ValueError(
                "prefix export/import needs the engine (engine_slots > 0)")
        return self.engine

    def export_prefix_blob(self, tokens: list) -> Optional[bytes]:
        """Pack a cached prefix's pages for /admin/export_prefix; None
        when the radix cache holds nothing for it (HTTP 404)."""
        from megatron_tpu.inference.fleet import migration

        out = self._need_engine().export_prefix_state(
            [int(t) for t in tokens])
        if out is None:
            return None
        blob = migration.pack_state(out[0], out[1])
        self._m_migrate_bytes.inc(len(blob), direction="out")
        return blob

    def import_prefix_blob(self, blob: bytes) -> dict:
        """Install a prefix frame into this replica's radix cache (POST
        /admin/import_prefix): the next prompt sharing the prefix radix-
        hits here without this replica ever having prefilled it."""
        from megatron_tpu.inference.fleet import migration

        eng = self._need_engine()
        meta, sections = migration.unpack_state(blob)
        if meta.get("kind") != "prefix":
            raise ValueError(
                f"expected a prefix frame, got {meta.get('kind')!r}")
        self._m_migrate_bytes.inc(len(blob), direction="in")
        pages = eng.import_prefix_state(meta, sections)
        self._journal("serve_prefix_import", pages=pages,
                      wire_bytes=len(blob))
        return {"pages": pages}

    def register_prefix(self, tokens: list) -> dict:
        """Ensure a prefix (system prompt) is resident in this replica's
        radix cache (POST /admin/register_prefix), priming it with one
        greedy token through the engine if needed. The router calls this
        on one replica, then fans the resulting pages out to the rest
        via replicate_prefix (page export, no re-prefill)."""
        import numpy as np

        eng = self._need_engine()
        toks = [int(t) for t in tokens]
        if not toks:
            raise ValueError("tokens: non-empty int list required")
        ps = eng.page_size
        pages, _ = eng.prefix_cache.lookup(toks)
        if len(pages) < len(toks) // ps:
            eng.generate(np.array([toks], np.int32),
                         np.array([len(toks)], np.int32), max_new_tokens=1)
            pages, _ = eng.prefix_cache.lookup(toks)
        self._journal("serve_prefix_register", tokens=len(toks),
                      pages=len(pages))
        return {"pages": len(pages), "tokens": len(toks)}

    def reload(self, load: Optional[str] = None,
               iteration: Optional[int] = None,
               apply_timeout_s: float = 60.0) -> int:
        """Hot weight reload: manifest-verify a committed checkpoint
        (fleet/reload.py — torn or bitrotted saves never reach a serving
        replica), stage it via engine.update_params, and wait for the
        between-tick swap. In-flight slots keep decoding; the jit cache
        key is unchanged so the swap costs zero recompiles (the live
        decode_recompiles counter is the regression gate). Returns the
        iteration now being served."""
        from megatron_tpu.inference.fleet.reload import load_verified_params

        if self.mesh is not None:
            raise ValueError(
                "hot reload on sharded (mesh) serving is not supported in "
                "v1 — the reload path would re-place params without their "
                "shardings; roll the replica instead (restart with the "
                "new checkpoint)")
        with self._admin_lock:
            src = load or self.reload_dir
            if not src:
                raise ValueError(
                    "no checkpoint dir to reload from: pass \"load\" in "
                    "the request or start the server with reload_dir=")
            self.reloading = True
            try:
                t0 = time.monotonic()
                params, it = load_verified_params(src, self.params,
                                                  iteration=iteration)
                if self.engine is not None:
                    applied = self.engine.update_params(params, version=it)
                    if not applied.wait(timeout=apply_timeout_s):
                        raise RuntimeError(
                            f"weight swap staged but not applied within "
                            f"{apply_timeout_s}s — is the step loop "
                            "wedged? (/readyz would say)")
                self.params = params
                self.weights_version = it
                self._journal("serve_weight_reload", version=it, load=src,
                              wall_s=round(time.monotonic() - t0, 3))
                return it
            finally:
                self.reloading = False

    def profile(self, steps: int = 4, timeout_s: float = 30.0,
                out_dir: Optional[str] = None) -> dict:
        """On-demand profiler capture under live traffic (POST
        /admin/profile): trace `steps` decode ticks into the xplane dir
        tools/trace_report.py reads. No restart, no admission pause —
        the step loop never checks a flag (the capture brackets it from
        this thread), so a disarmed server pays nothing and the capture
        itself causes zero decode recompiles. Begin/end are journaled so
        the incident timeline shows when the trace was cut."""
        if self.engine is None:
            raise ValueError(
                "on-demand profiling needs the continuous-batching "
                "engine (engine_slots > 0); one-shot servers can be "
                "traced externally with jax.profiler")
        steps = int(steps)
        if not 1 <= steps <= 10_000:
            raise ValueError("steps must be in [1, 10000]")
        timeout_s = float(timeout_s)
        if not 0 < timeout_s <= 600:
            # the capture holds the process-global profiler session (and
            # its in-memory trace buffer) for up to this long — an
            # unbounded client value could wedge profiling for days
            raise ValueError("timeout_s must be in (0, 600]")
        out = out_dir or self.profile_dir
        self._journal("profile_begin", source="admin", dir=out,
                      steps=steps)
        try:
            result = self.engine.capture_trace(
                out, ticks=steps, timeout_s=timeout_s)
        except BaseException as e:  # noqa: BLE001 - re-raised below: the
            # catch only journals the abort — a begin with no end would
            # mis-pair the NEXT window in the perfetto timeline, so this
            # one closes as aborted (busy lock, profiler error) first
            self._journal("profile_aborted", source="admin",
                          reason=type(e).__name__, flushed=False)
            raise
        self._journal("profile_end", source="admin", **result)
        return result

    def admin_status(self) -> dict:
        ok, detail = self.ready()
        out = {"ready": ok, "detail": detail, "draining": self.draining,
               "reloading": self.reloading,
               "weights_version": self.weights_version}
        if self.engine is not None:
            out["engine"] = dict(self.engine.stats)
            if len(self.engines) > 1:
                out["lanes"] = [dict(e.stats) for e in self.engines]
        return out

    def _mesh_scope(self):
        return (jax.sharding.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _pick_lane(self):
        """Least-loaded engine lane by busy slots + queue depth — the
        same score replica_load computes fleet-side from the lane
        gauges, so in-host and cross-host dispatch agree."""
        if len(self.engines) <= 1:
            return self.engine
        return min(self.engines,
                   key=lambda e: e.num_active + len(e._queue))

    def handle(self, req: dict, request_id: Optional[str] = None,
               timing: Optional[dict] = None) -> dict:
        """One generation request. `request_id` names its prompts in the
        engine's journal records; `timing`, a dict, takes what the
        handler's `serve_reply` record wants of it (`prompts`, and
        `engine_s` where the engine served it)."""
        if self.draining:
            raise ServiceDrainingError(
                "server is draining; retry (the fleet router re-routes "
                "automatically)")
        prompts = req.get("prompts")
        if not isinstance(prompts, list) or not prompts:
            raise ValueError("prompts: non-empty list of strings required")
        if len(prompts) > MAX_PROMPTS:
            raise ValueError(f"at most {MAX_PROMPTS} prompts per request")
        if not all(isinstance(p, str) and p for p in prompts):
            raise ValueError("prompts must be non-empty strings")
        if timing is not None:
            timing["prompts"] = len(prompts)
        n = int(req.get("tokens_to_generate", 64))
        if not 0 <= n <= MAX_TOKENS_TO_GENERATE:
            raise ValueError(f"tokens_to_generate in [0, {MAX_TOKENS_TO_GENERATE}]")

        if req.get("beam_width"):
            with self.lock, self._mesh_scope():
                if self.forward_fn is not None:
                    raise ValueError(
                        "beam search is not supported on pipelined (pp>1) "
                        "serving; use sampling or serve at pp=1")
                texts, segments, scores = beam_search_and_post_process(
                    self.cfg, self.params, self.tokenizer, prompts,
                    tokens_to_generate=n,
                    beam_size=int(req["beam_width"]),
                    add_BOS=bool(req.get("add_BOS", False)),
                    length_penalty=float(req.get("length_penalty", 1.0)),
                    kv_cache_int8=self.kv_cache_int8)
                return {"text": texts, "segments": segments,
                        "scores": [float(s) for s in scores]}

        # continuous batching: no request lock — the engine's slot
        # scheduler interleaves every caller's prompts into shared decode
        # ticks (scoring still needs the one-shot teacher-forced pass);
        # the one-shot path serializes whole requests and makes the mesh
        # ambient here (the engine's driver thread scopes its own)
        use_engine = self.engine is not None and n > 0
        # CP x DP: dispatch this request to the least-loaded engine lane
        # (the in-host analogue of the fleet router's replica_load)
        engine = self._pick_lane() if use_engine else None
        # per-request deadline (engine path): a request may SHORTEN the
        # server default (--serve_request_timeout) but never extend past
        # it — the operator bound caps the router's retry worst case and
        # stops abandoned waiters from blocking slots, so a client
        # (including one sending an explicit null) cannot opt out of it
        deadline_s = req.get("deadline_s")
        if deadline_s is not None:
            try:
                deadline_s = float(deadline_s)
            except (TypeError, ValueError):
                raise ValueError("deadline_s must be a number (seconds)")
        if self.request_timeout is not None:
            deadline_s = (self.request_timeout if deadline_s is None
                          else min(deadline_s, self.request_timeout))
        # per-request speculative-decoding knob: passes through the
        # fleet router untouched (the router proxies request bodies
        # verbatim); a no-op unless the engine runs --serve_speculative.
        # Greedy output is identical either way — the knob only trades
        # per-token latency variance against throughput.
        spec = req.get("spec", True)
        if not isinstance(spec, bool):
            raise ValueError("spec must be a JSON boolean")

        def generate():
            v0 = self.weights_version
            texts, segments, logprobs, _ = generate_and_post_process(
                self.cfg, self.params, self.tokenizer, prompts,
                tokens_to_generate=n,
                temperature=float(req.get("temperature", 1.0)),
                top_k_sampling=int(req.get("top_k", 0)),
                top_p_sampling=float(req.get("top_p", 0.0)),
                add_BOS=bool(req.get("add_BOS", False)),
                return_output_log_probs=bool(req.get("logprobs", False)),
                random_seed=int(req.get("random_seed", 0)),
                forward_fn=self.forward_fn,
                kv_cache_int8=self.kv_cache_int8,
                engine=engine,
                deadline_s=deadline_s if use_engine else None,
                spec=spec, request_id=request_id, timing=timing)
            out = {"text": texts, "segments": segments}
            if logprobs is not None:
                out["logprobs"] = [list(map(float, row)) for row in logprobs]
            # which weight version served this request: only claimed when
            # it cannot lie — the version was the same before submit and
            # after completion (a drained rolling update guarantees it;
            # an undrained swap racing completion reports nothing)
            if v0 is not None and v0 == self.weights_version:
                out["weights_version"] = v0
            return out

        if use_engine:
            return generate()
        with self.lock, self._mesh_scope():
            return generate()


def make_handler(service: GenerationService):
    class Handler(BaseHTTPRequestHandler):
        _request_id: Optional[str] = None  # set for the generation API
        _t_first = 0.0

        def parse_request(self) -> bool:
            # the request line has been read: the request's first bytes
            self._t_first = time.monotonic()
            return super().parse_request()

        def _reply(self, code: int, payload: dict, headers=()):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            if self._request_id is not None:
                self.send_header(REQUEST_ID_HEADER, self._request_id)
            for name, value in headers:
                self.send_header(name, value)
            self.end_headers()
            self.wfile.write(body)

        def _read_json(self) -> dict:
            length = int(self.headers.get("Content-Length", 0))
            return json.loads(self.rfile.read(length) or b"{}")

        def _read_body(self) -> bytes:
            length = int(self.headers.get("Content-Length", 0))
            return self.rfile.read(length)

        def _reply_blob(self, blob: bytes):
            self.send_response(200)
            self.send_header("Content-Type", "application/octet-stream")
            self.send_header("Content-Length", str(len(blob)))
            self.end_headers()
            self.wfile.write(blob)

        def _handle(self):
            path = self.path.split("?", 1)[0]
            if path.startswith("/admin/"):
                self._handle_admin(path)
                return
            # anything else is the generation API (/api canonically; the
            # pre-fleet server accepted any path, kept for compatibility)
            t0 = time.monotonic()
            status = "500"
            # the request's name, from socket to socket: the client's own
            # where it sent one, echoed on the reply, on the engine's
            # `serve_request` records and on this handler's `serve_reply`
            self._request_id = (self.headers.get(REQUEST_ID_HEADER)
                                or uuid.uuid4().hex)
            timing: dict = {}
            try:
                req = self._read_json()
                payload = service.handle(req, request_id=self._request_id,
                                         timing=timing)
                status = "200"
                self._reply(200, payload)
            except ServiceDrainingError as e:
                # SIGTERM grace or a rolling update: fast 503 the router
                # re-routes; Retry-After hints standalone clients
                status = "503"
                self._reply(503, {"message": str(e), "draining": True},
                            headers=(("Retry-After",
                                      str(RETRY_AFTER_SECONDS)),))
            except EngineOverloadedError as e:
                # bounded admission (--serve_max_queue): overload degrades
                # to fast 503s clients can back off on, not queue latency
                status = "503"
                self._reply(503, {"message": str(e)},
                            headers=(("Retry-After",
                                      str(RETRY_AFTER_SECONDS)),))
            except RequestTimeoutError as e:
                # expired deadline (deadline_s / --serve_request_timeout):
                # the client's budget is spent — the router passes 504
                # through rather than retrying on its behalf
                status = "504"
                self._reply(504, {"message": str(e), "timeout": True})
            except ValueError as e:
                status = "400"
                self._reply(400, {"message": str(e)})
            except Exception as e:  # noqa: BLE001 — server must not die
                self._reply(500, {"message": f"internal error: {e}"})
            finally:
                now = time.monotonic()
                service._m_requests.inc(status=status)
                service._m_latency.observe(now - t0)
                # after the reply's last byte: handler_s from the request's
                # first bytes to here, engine_s from the first of its
                # prompts' submits to the last of their retirements (the
                # engine's clock; absent where no engine served it). The
                # difference is the server's own: parse, tokenise, the wait
                # for the interpreter lock behind the loop, detokenise,
                # JSON, the socket
                fields = {"id": self._request_id, "status": status,
                          "handler_s": round(now - self._t_first, 6),
                          "prompts": timing.get("prompts", 0)}
                if timing.get("engine_s") is not None:
                    fields["engine_s"] = round(timing["engine_s"], 6)
                service._journal("serve_reply", **fields)
                self._request_id = None

        def _handle_admin(self, path: str):
            from megatron_tpu.inference.fleet.migration import (
                MigrationIntegrityError,
            )
            from megatron_tpu.inference.fleet.reload import (
                NoValidCheckpointError,
            )

            if path in ("/admin/import", "/admin/import_prefix"):
                # migration frames are binary (manifest + crc contract,
                # fleet/migration.py) — read raw, never through JSON
                try:
                    blob = self._read_body()
                    if path == "/admin/import":
                        self._reply(200, service.import_state(blob))
                    else:
                        self._reply(200, service.import_prefix_blob(blob))
                except MigrationIntegrityError as e:
                    # torn/corrupt transfer: the exporter walks down its
                    # degradation ladder on this status
                    self._reply(409, {"message": str(e), "torn": True})
                except ServiceDrainingError as e:
                    self._reply(503, {"message": str(e)},
                                headers=(("Retry-After",
                                          str(RETRY_AFTER_SECONDS)),))
                except RequestTimeoutError as e:
                    self._reply(504, {"message": str(e), "timeout": True})
                except ValueError as e:
                    self._reply(400, {"message": str(e)})
                except Exception as e:  # noqa: BLE001 — server must not die
                    self._reply(500, {"message": f"admin failed: {e}"})
                return
            try:
                req = self._read_json()
            except ValueError:
                self._reply(400, {"message": "admin body must be JSON"})
                return
            try:
                if path == "/admin/drain":
                    drained = service.drain(
                        float(req.get("timeout_s", 30.0)),
                        handoff_urls=req.get("handoff"))
                    self._reply(200, {"drained": drained, "draining": True})
                elif path == "/admin/readmit":
                    service.readmit()
                    self._reply(200, {"draining": False})
                elif path == "/admin/reload":
                    version = service.reload(
                        load=req.get("load"),
                        iteration=req.get("iteration"))
                    self._reply(200, {"version": version})
                elif path == "/admin/profile":
                    from urllib.parse import parse_qs, urlsplit

                    q = parse_qs(urlsplit(self.path).query)
                    steps = req.get("steps", q.get("steps", ["4"])[0])
                    timeout_s = req.get(
                        "timeout_s", q.get("timeout_s", ["30"])[0])
                    try:
                        self._reply(200, service.profile(
                            steps=int(steps), timeout_s=float(timeout_s),
                            out_dir=req.get("dir")))
                    except RuntimeError as e:
                        # another capture owns the process-global
                        # profiler session: conflict, retry later
                        self._reply(409, {"message": str(e)})
                elif path == "/admin/export_prefix":
                    blob = service.export_prefix_blob(
                        req.get("tokens") or [])
                    if blob is None:
                        self._reply(404,
                                    {"message": "prefix not cached here"})
                    else:
                        self._reply_blob(blob)
                elif path == "/admin/register_prefix":
                    self._reply(200, service.register_prefix(
                        req.get("tokens") or []))
                else:
                    self._reply(404, {"message": "POST /admin/"
                                      "{drain,readmit,reload,profile,"
                                      "import,export_prefix,"
                                      "import_prefix,register_prefix}"})
            except NoValidCheckpointError as e:
                # no verifiable committed checkpoint: an operator/ckpt
                # problem, not a server fault — 409 so the router's
                # rolling update stops and readmits the old weights
                self._reply(409, {"message": str(e)})
            except ValueError as e:
                self._reply(400, {"message": str(e)})
            except Exception as e:  # noqa: BLE001 — server must not die
                self._reply(500, {"message": f"admin failed: {e}"})

        do_PUT = _handle
        do_POST = _handle

        def do_GET(self):
            # observability endpoints (Prometheus scrape + probes); the
            # generation API stays PUT/POST /api
            path = self.path.split("?", 1)[0]
            if path == "/metrics":
                body = service.metrics.render().encode()
                self.send_response(200)
                self.send_header("Content-Type", PROMETHEUS_CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
            elif path == "/healthz":
                # liveness: "the process + step loop exist" — stays green
                # through drains/reloads so an orchestrator doesn't kill a
                # replica that's merely finishing in-flight work
                alive = (service.engine is None
                         or service.engine._thread is None
                         or service.engine._thread.is_alive())
                self._reply(200 if alive else 500,
                            {"ok": bool(alive),
                             "engine": service.engine is not None})
            elif path == "/readyz":
                ok, detail = service.ready()
                self._reply(200 if ok else 503, detail)
            elif path == "/admin/status":
                self._reply(200, service.admin_status())
            else:
                self._reply(404, {"message": "GET serves /metrics, "
                                             "/healthz, /readyz, "
                                             "/admin/status; the API is "
                                             "PUT/POST /api"})

        def log_message(self, *a):  # quiet
            pass

    return Handler


def run_server(cfg: ModelConfig, params: Any, tokenizer,
               host: str = "0.0.0.0", port: int = 5000,
               mesh=None, forward_fn=None, kv_cache_int8=False,
               engine_slots: int = 0, engine_max_seq_len=None,
               engine_max_queue: Optional[int] = None,
               page_size: int = 16, prefill_chunk: int = 32,
               num_pages: Optional[int] = None,
               request_timeout: Optional[float] = None,
               drain_timeout: float = 30.0,
               warmup: bool = False,
               port_file: Optional[str] = None,
               reload_dir: Optional[str] = None,
               weights_version: Optional[int] = None,
               stall_threshold_s: float = STALL_THRESHOLD_SECONDS,
               speculative: Optional[str] = None,
               spec_k: int = 4,
               draft_cfg=None, draft_params=None,
               profile_dir: Optional[str] = None,
               compress_collectives: str = "none",
               comm_policy: Optional[str] = None,
               cp_serving: bool = False,
               cp_collectives: str = "dense",
               cp_comm_policy: Optional[str] = None,
               cp_geometry: str = "ring",
               cp_subgroup: int = 0,
               cp_overlap: bool = True,
               cp_lanes: int = 1,
               peers: Optional[list] = None) -> None:
    """Serve until killed. SIGTERM/SIGINT triggers a graceful drain
    (mirroring DistributedSignalHandler): stop admitting (503 +
    Retry-After), finish in-flight requests up to `drain_timeout`, then
    exit cleanly; a second signal force-exits 128+signum immediately.
    With `peers` configured the drain first HANDS OFF in-flight and
    queued requests to those replicas over the KV migration fabric
    (docs/fault_tolerance.md "Serving state migration") — a preempted
    replica costs zero failed requests, not one retry per client.
    port=0 binds an ephemeral port; `port_file` (fleet subprocess
    choreography) publishes the bound port as {"port": N} once listening.
    warmup=True compiles the decode step before /readyz goes green."""
    service = GenerationService(cfg, params, tokenizer, mesh=mesh,
                                forward_fn=forward_fn,
                                kv_cache_int8=kv_cache_int8,
                                engine_slots=engine_slots,
                                engine_max_seq_len=engine_max_seq_len,
                                engine_max_queue=engine_max_queue,
                                page_size=page_size,
                                prefill_chunk=prefill_chunk,
                                num_pages=num_pages,
                                request_timeout=request_timeout,
                                reload_dir=reload_dir,
                                weights_version=weights_version,
                                stall_threshold_s=stall_threshold_s,
                                warmup=warmup,
                                speculative=speculative, spec_k=spec_k,
                                draft_cfg=draft_cfg,
                                draft_params=draft_params,
                                profile_dir=profile_dir,
                                compress_collectives=compress_collectives,
                                comm_policy=comm_policy,
                                cp_serving=cp_serving,
                                cp_collectives=cp_collectives,
                                cp_comm_policy=cp_comm_policy,
                                cp_geometry=cp_geometry,
                                cp_subgroup=cp_subgroup,
                                cp_overlap=cp_overlap,
                                cp_lanes=cp_lanes,
                                peers=peers)
    server = ThreadingHTTPServer((host, port), make_handler(service))
    bound_port = server.server_address[1]
    if port_file:
        # atomic publish: the parent polls this file — it must never read
        # a torn write
        tmp = port_file + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"port": bound_port, "pid": os.getpid()}, f)
        os.replace(tmp, port_file)

    received: list = []

    def _graceful(signum, frame):
        if received:
            # second signal: the drain is presumed wedged — die NOW,
            # unmaskably (DistributedSignalHandler semantics)
            sys.stderr.write(
                f"received {signal.Signals(signum).name} after "
                f"{signal.Signals(received[0]).name}; forcing exit "
                "without waiting for drain\n")
            sys.stderr.flush()
            os._exit(128 + signum)
        received.append(signum)

        def _shutdown():
            drained = service.drain(drain_timeout)
            print(f"drain {'complete' if drained else 'TIMED OUT'}; "
                  "shutting down", flush=True)
            server.shutdown()

        # drain off-signal-context: a handler must not block for seconds
        threading.Thread(target=_shutdown, daemon=True,
                         name="drain-on-signal").start()

    if threading.current_thread() is threading.main_thread():
        for s in (signal.SIGTERM, signal.SIGINT):
            signal.signal(s, _graceful)

    if warmup and service.engine is not None:
        # compile on a side thread so serve_forever answers probes (503,
        # not connection timeouts) during the warmup

        def _warmup():
            try:
                service.warmup()
            except Exception as e:  # noqa: BLE001 - a failed warmup keeps
                # readiness red (correct: don't route here) but the reason
                # must reach the log, not die with the thread
                sys.stderr.write(f"warmup failed: {e}\n")
                sys.stderr.flush()

        threading.Thread(target=_warmup, daemon=True,
                         name="serve-warmup").start()

    mode = (f"continuous batching, {engine_slots} slots, paged KV + "
            "prefix cache"
            + (f", context-parallel KV (cp="
               f"{getattr(service.engine, 'cp', 0)}, "
               f"{cp_geometry}"
               + (f" sub={cp_subgroup}" if cp_geometry == "2d" else "")
               + f" {'overlapped' if cp_overlap else 'serial'} "
               f"{getattr(getattr(service.engine, 'cp_comm', None), 'mode', '?')}"
               + (f", {cp_lanes} lanes" if cp_lanes > 1 else "")
               + ")"
               if cp_serving else "")
            + (f", speculative ({speculative}, k={spec_k})"
               if speculative else "")
            + (f", compressed collectives ({service.engine.tp_comm.mode}, "
               f"sites {sorted(service.engine.tp_comm.sites)})"
               if getattr(service.engine, "tp_comm", None) is not None
               else "")
            if service.engine else "one-shot")
    print(f"serving generation API on http://{host}:{bound_port}/api "
          f"({mode})", flush=True)
    try:
        server.serve_forever()
    finally:
        server.server_close()
        service.shutdown()
