#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell once, on the chip: the
highest offered rate the server sustains. The cell then offers four
fifths of it, as a number in its traffic file; no run searches.

    python3 benchmark/tools/knee_sweep.py --workload serve_mistral7b_instruct \\
        --rates 6,9,12,15,18,22 --seconds 25 [--out chiprun_out/knee.json]

One server boot; at each rate the mix's own requests and arrivals for
lead_s + --seconds, then a pause until the engine is idle. A rate is
sustained when the requests due in the window's second half wait no
longer than those of its first half (no growing backlog) and none fails.
The sweep stops at the first rate that is not. One JSON line per rate on
stdout.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import common, serve_driver, spec, stats, traffic  # noqa: E402


def summarise(rate: float, out: dict) -> dict:
    records = out["records"]
    lead, end = out["window"]
    ok = [r for r in records if r["ok"]]
    half = (lead + end) / 2
    first = [r["latency_s"] for r in ok if r["due_s"] < half]
    second = [r["latency_s"] for r in ok if r["due_s"] >= half]
    ms = lambda xs: round(stats.median(xs) * 1e3, 1) if xs else None  # noqa: E731
    lat = [r["latency_s"] for r in ok]
    line = {"rate_rps": rate, "offered": len(records), "ok": len(ok),
            "request_ms_p50": ms(lat),
            "p50_first_half_ms": ms(first), "p50_second_half_ms": ms(second),
            "completed_tokens_per_s": round(sum(
                r["new_tokens"] for r in ok) / (end - lead), 1),
            "lateness_ms_max": round(max(
                (r["lateness_s"] for r in records), default=0) * 1e3, 2)}
    try:
        line["request_ms_p95"] = round(stats.percentile(lat, 95) * 1e3, 1)
    except stats.TooFewSamples:
        line["request_ms_p95"] = None
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=25.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--spec", default=os.path.join(REPO, "BENCHMARK.json"))
    p.add_argument("--out", default=None)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    cell = spec.Cell(args.spec, args.workload)
    run_dir = common.fresh_run_dir(cell.name + ".knee")
    lines = []
    with serve_driver.Server(cell, args.seed, False, args.rehearse,
                             run_dir) as server:
        serve_driver.warm_up(cell, server.port, args.seed)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            mix = dict(cell.traffic, rate_rps=rate)
            out = asyncio.run(traffic.run_open_loop(
                serve_driver.HOST, server.port, mix,
                cell.config["vocab_size"], args.seed + i, args.seconds))
            lines.append(summarise(rate, out))
            print(json.dumps(lines[-1]), flush=True)
            first, second = (lines[-1]["p50_first_half_ms"],
                             lines[-1]["p50_second_half_ms"])
            if lines[-1]["ok"] < lines[-1]["offered"] or (
                    first and second and second > 1.5 * first):
                break  # past the knee: higher rates only queue longer
            # let the dropped tail of this rate drain before the next: an
            # idle engine makes no ticks
            ticks, deadline = None, time.time() + 120
            while time.time() < deadline:
                status = json.loads(serve_driver.http_get(
                    server.base + "/admin/status")[1] or "{}")
                now = status.get("engine", {}).get("ticks")
                if now == ticks:
                    break
                ticks = now
                time.sleep(1.5)
        server.stop()
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
