#!/usr/bin/env python3
"""Where a traced serving run's ticks went, by the engine's own spans and
records: what PERF.md section 5 says of a served cell's host side.

    python3 benchmark/tools/tick_phases.py runs/benchmark/<cell>

From the trace (`<run>/trace`, the host plane's `serve-tick` line: the
program's inference/engine.py): the ticks it holds, their median, the
median and the summed share of every `tick-*` / `page-*` span inside them,
and how much of the ticks their outermost spans cover (the phases sum to
the tick). From the journal (`<run>/tele/events.jsonl`), between its first
and last `serve_ticks` snapshot after `--from` seconds: the loop thread's
time by phase (`phase_s`, own time) over the wall time there, rows a tick
beside the rate times the decode time of the same records (Little's law),
the pages evicted, the largest error of `queue_s + prefill_s - ttft_s`,
the slow ticks with their phases, and the handler's time over the
engine's. One JSON line; a part the run does not hold is left out.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import stats  # noqa: E402
from benchmark.harness.trace import named, serve_ticks, xplane  # noqa: E402
from benchmark.harness.trace.names import HOST_PLANE  # noqa: E402


def from_trace(trace_dir: str) -> dict:
    for name, buf in xplane.capture_planes(trace_dir):
        if name != HOST_PLANE:
            continue
        ticks = serve_ticks.host_ticks(xplane.decode_plane(buf))
        if not ticks:
            return {}
        whole = sum(t["tick_ps"] for t in ticks)
        names = sorted({n for t in ticks for n in t["spans"]})
        spans = {n: {
            "ms_p50": stats.median([t["spans"].get(n, 0) * 1e-9
                                    for t in ticks]),
            "share": sum(t["spans"].get(n, 0) for t in ticks) / whole}
            for n in names}
        return {"ticks": len(ticks),
                "tick_ms_p50": stats.median([t["tick_ps"] * 1e-9
                                             for t in ticks]),
                "host_ms_p50": stats.median(
                    [(t["tick_ps"] - t["read_ps"]) * 1e-9 for t in ticks]),
                "spans": spans,
                "covered": sum(t["top_ps"] for t in ticks) / whole}
    return {}


def from_journal(path: str, after_s: float) -> dict:
    records = named.journal(path)
    snaps = [r for r in records if r.get("kind") == "serve_ticks"]
    if not snaps:
        return {}
    start = snaps[0]["ts"] + after_s
    snaps = [r for r in snaps if r["ts"] >= start]
    if len(snaps) < 2:
        return {}
    first, last = snaps[0], snaps[-1]
    wall = last["ts"] - first["ts"]
    served = [r for r in records if r.get("kind") == "serve_request"
              and first["ts"] < r["ts"] <= last["ts"]]
    out = {"wall_s": wall, "requests": len(served),
           "ticks": last["ticks"] - first["ticks"]}
    if "phase_s" in last:
        phases = {k: v - first["phase_s"].get(k, 0.0)
                  for k, v in last["phase_s"].items()}
        out["phase_s"] = phases
        out["phases_over_wall"] = sum(phases.values()) / wall
    if "rows" in last and out["ticks"]:
        out["rows_per_tick"] = (last["rows"] - first["rows"]) / out["ticks"]
        # the offered rate times the mean time a request decodes, in
        # ticks: the rows the window's own requests decoded, a tick
        out["rows_per_tick_by_little"] = sum(
            r["new_tokens"] - 1 for r in served) / out["ticks"]
    if "evicted" in last:
        out["evicted"] = last["evicted"] - first["evicted"]
    split = [r for r in served if "queue_s" in r and "ttft_s" in r]
    if split:
        out["split_error_s_max"] = max(
            abs(r["queue_s"] + r["prefill_s"] - r["ttft_s"]) for r in split)
        out["queue_ms_p50"] = stats.median([r["queue_s"] * 1e3
                                            for r in split])
        out["prefill_ms_p50"] = stats.median([r["prefill_s"] * 1e3
                                              for r in split])
    out["slow_ticks"] = [
        {k: r[k] for k in ("tick", "wall_s", "phase_s", "active", "queue",
                           "pages_free", "drains", "gc_s") if k in r}
        for r in records if r.get("kind") == "serve_slow_tick"
        and first["ts"] < r["ts"] <= last["ts"]]
    over = [(r["handler_s"] - r["engine_s"]) * 1e3 for r in records
            if r.get("kind") == "serve_reply" and "engine_s" in r
            and first["ts"] < r["ts"] <= last["ts"]]
    if over:
        out["server_overhead_ms"] = {"p50": stats.median(over),
                                     "max": max(over)}
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("run_dir")
    p.add_argument("--from", dest="after_s", type=float, default=0.0,
                   help="leave out the journal's first seconds (the "
                        "warm-up requests, a mix's lead)")
    args = p.parse_args(argv)
    out = {"run_dir": os.path.basename(os.path.normpath(args.run_dir))}
    trace = from_trace(os.path.join(args.run_dir, "trace"))
    if trace:
        out["trace"] = trace
    journal = from_journal(os.path.join(args.run_dir, "tele",
                                        "events.jsonl"), args.after_s)
    if journal:
        out["journal"] = journal
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
