"""Traffic-replay SLO harness: offered load in, latency percentiles out.

Serving claims need the discipline training claims get from the
benchmark: measured percentiles under a FIXED OFFERED LOAD, not anecdotes. An
open-loop replay (requests fire at their scheduled times whether or not
earlier ones returned — the "millions of users" arrival model) is the
honest one: a closed loop would slow its own arrival rate exactly when the
system degrades, hiding the queueing collapse the SLO exists to catch.

The trace is deterministic (seeded exponential inter-arrivals ≈ Poisson at
`offered_rps`, seeded prompt/length mix), so two runs — or two fleet
configurations — see byte-identical traffic. TTFT/TPOT percentiles come
from the engine's own Prometheus histograms (telemetry/metrics.py),
scraped before and after the window and DIFFED, so warmup compiles and
unrelated traffic fall out; client-side wall-time percentiles ride along
as the end-to-end view (router retries included).

Used by tools/slo_harness.py (CLI: attach to a live fleet or spawn one).
Pure host code — no jax.
"""

from __future__ import annotations

import json
import random
import threading
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional, Sequence

from megatron_tpu.inference.fleet import scrape

#: (quantile, label) pairs every report carries
PERCENTILES = ((0.50, "p50"), (0.95, "p95"), (0.99, "p99"))


def make_trace(num_requests: int, offered_rps: float, *, seed: int = 0,
               vocab: int = 64, prompt_len: Sequence[int] = (4, 12),
               new_tokens: int = 16) -> List[Dict[str, Any]]:
    """Deterministic open-loop trace: `num_requests` generation requests
    with exponential inter-arrival times averaging 1/offered_rps seconds,
    prompts of uniform length in [prompt_len[0], prompt_len[1]] drawn from
    a NullTokenizer-style integer vocabulary. Each item is
    {"at_s", "prompts", "tokens_to_generate", "random_seed"}."""
    if offered_rps <= 0:
        raise ValueError("offered_rps must be > 0")
    rng = random.Random(seed)
    t = 0.0
    trace = []
    for i in range(num_requests):
        t += rng.expovariate(offered_rps)
        plen = rng.randint(prompt_len[0], prompt_len[1])
        prompt = " ".join(str(rng.randrange(1, vocab - 1))
                          for _ in range(plen))
        trace.append({"at_s": round(t, 6), "prompts": [prompt],
                      "tokens_to_generate": new_tokens, "temperature": 0.0,
                      "random_seed": i})
    return trace


def _fire(api_url: str, item: Dict[str, Any], timeout: float
          ) -> Dict[str, Any]:
    body = json.dumps({k: v for k, v in item.items() if k != "at_s"})
    req = urllib.request.Request(api_url, data=body.encode(),
                                 method="POST",
                                 headers={"Content-Type":
                                          "application/json"})
    t0 = time.monotonic()
    out: Dict[str, Any] = {"at_s": item["at_s"]}
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            resp.read()
            out["status"] = resp.status
    except urllib.error.HTTPError as e:
        e.read()
        out["status"] = e.code
    except (OSError, urllib.error.URLError) as e:
        out["status"] = 0
        out["error"] = str(e)
    out["wall_s"] = round(time.monotonic() - t0, 6)
    out["ok"] = out["status"] == 200
    return out


def replay(api_url: str, trace: List[Dict[str, Any]],
           timeout: float = 120.0) -> List[Dict[str, Any]]:
    """Fire the trace open-loop at `api_url` (one thread per request,
    launched at its scheduled offset) and return per-request results in
    trace order. Failures are recorded, never raised — the report decides
    what an error rate means."""
    results: List[Optional[Dict[str, Any]]] = [None] * len(trace)
    t0 = time.monotonic()

    def worker(idx: int, item: Dict[str, Any]) -> None:
        delay = item["at_s"] - (time.monotonic() - t0)
        if delay > 0:
            time.sleep(delay)
        results[idx] = _fire(api_url, item, timeout)

    threads = [threading.Thread(target=worker, args=(i, item), daemon=True)
               for i, item in enumerate(trace)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=timeout + trace[-1]["at_s"] + 10 if trace else 10)
    # a hung worker's placeholder keeps the schema (at_s/wall_s) so the
    # report can still be assembled — the degraded-fleet scenario is
    # exactly when the harness must NOT crash
    return [r if r is not None
            else {"at_s": trace[i]["at_s"], "wall_s": timeout, "status": 0,
                  "ok": False, "error": "worker hung"}
            for i, r in enumerate(results)]


def _client_percentiles(walls: List[float]) -> Dict[str, float]:
    if not walls:
        return {label: float("nan") for _, label in PERCENTILES}
    s = sorted(walls)
    return {label: round(s[min(len(s) - 1, int(q * len(s))) ], 6)
            for q, label in PERCENTILES}


def slo_report(results: List[Dict[str, Any]],
               metrics_before: List[scrape.Samples],
               metrics_after: List[scrape.Samples],
               offered_rps: float) -> Dict[str, Any]:
    """Assemble the SLO report: engine-side TTFT/TPOT percentiles from
    the diffed histogram windows (merged across replicas), client-side
    wall percentiles, achieved throughput, and the failure ledger."""
    deltas = [scrape.diff_samples(b, a)
              for b, a in zip(metrics_before, metrics_after)]
    ttft = {label: scrape.merged_histogram_percentile(
                deltas, "engine_ttft_seconds", q)
            for q, label in PERCENTILES}
    tpot = {label: scrape.merged_histogram_percentile(
                deltas, "engine_time_per_output_token_seconds", q)
            for q, label in PERCENTILES}
    ok = [r for r in results if r.get("ok")]
    failed = [r for r in results if not r.get("ok")]
    span = (max(r["at_s"] + r["wall_s"] for r in results)
            - min(r["at_s"] for r in results)) if results else 0.0
    by_status: Dict[str, int] = {}
    for r in results:
        key = str(r.get("status", 0))
        by_status[key] = by_status.get(key, 0) + 1
    return {
        "offered_rps": offered_rps,
        "achieved_rps": round(len(ok) / span, 3) if span > 0 else 0.0,
        "requests": len(results),
        "completed": len(ok),
        "failed": len(failed),
        "status_counts": by_status,
        "ttft_s": ttft,
        "tpot_s": tpot,
        "client_wall_s": _client_percentiles(
            [r["wall_s"] for r in ok if "wall_s" in r]),
    }


def run_slo(api_url: str, metrics_urls: List[str],
            trace: List[Dict[str, Any]], offered_rps: float,
            timeout: float = 120.0) -> Dict[str, Any]:
    """Scrape → replay → scrape → report. `api_url` is the front door
    (the router, or one replica for a solo baseline); `metrics_urls` are
    the REPLICA /metrics endpoints (the router's own histogram measures
    dispatch wall, not token latency). A replica whose scrape fails
    contributes an empty window (counted in scrape_errors) instead of
    killing the run."""
    def scrape_all() -> List[scrape.Samples]:
        out = []
        for u in metrics_urls:
            try:
                out.append(scrape.scrape(u, timeout=5.0))
            except (OSError, urllib.error.URLError, ValueError):
                out.append({})
        return out

    before = scrape_all()
    results = replay(api_url, trace, timeout=timeout)
    after = scrape_all()
    report = slo_report(results, before, after, offered_rps)
    # a failed BEFORE scrape matters as much as a failed AFTER one: its
    # empty window makes diff_samples keep the replica's full cumulative
    # history (warmup included) — the report must flag that the
    # percentiles are not cleanly windowed
    report["scrape_errors"] = sum(1 for s in before + after if not s)
    return report
