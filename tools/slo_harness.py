#!/usr/bin/env python
"""Traffic-replay SLO harness for the serving fleet (docs/serving.md).

Replays a deterministic request trace at a fixed offered load against a
front door (the fleet router, or a single replica) and reports TTFT/TPOT
p50/p95/p99 from the replicas' telemetry histograms plus client-side wall
percentiles — measured SLOs under load, not anecdotes.

Attach to a live fleet:

  python tools/slo_harness.py --api http://127.0.0.1:8000 \
      --replica http://127.0.0.1:5001 --replica http://127.0.0.1:5002 \
      --requests 64 --offered_rps 4

or spawn a throwaway local fleet of tiny deterministic replicas first
(CPU-friendly; the shape the fleet tests use):

  python tools/slo_harness.py --spawn 2 --requests 64 --offered_rps 4

--churn (with --spawn >= 2) is the serving-churn drill
(docs/fault_tolerance.md "Serving state migration"): replica 0 is
spawned with the others as handoff peers, then SIGTERMed mid-window —
its graceful drain MIGRATES in-flight and queued requests to the peers
over the KV fabric, so the gate stays "failed": 0 even though a replica
died under load. Exit code 1 if any client-visible request failed.

Output is one JSON report on stdout (percentiles in seconds).
"""

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="offered-load SLO replay against a serving fleet")
    ap.add_argument("--api", default=None,
                    help="front-door URL (router or replica). Omit with "
                         "--spawn to build a local fleet")
    ap.add_argument("--replica", action="append", default=[],
                    help="replica base URL (repeatable) — /metrics is "
                         "scraped for TTFT/TPOT histograms; defaults to "
                         "--api when omitted")
    ap.add_argument("--spawn", type=int, default=0,
                    help="spawn N tiny local replicas + a router and "
                         "replay against that (ignores --api/--replica)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--offered_rps", type=float, default=4.0)
    ap.add_argument("--new_tokens", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=64,
                    help="prompt token id bound (NullTokenizer-style "
                         "integer prompts)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="per-request client timeout")
    ap.add_argument("--engine_slots", type=int, default=2,
                    help="slots per spawned replica (--spawn)")
    ap.add_argument("--churn", action="store_true",
                    help="SIGTERM replica 0 mid-window (needs --spawn "
                         ">= 2); its drain hands in-flight requests off "
                         "to the surviving peers — the zero-failures "
                         "gate still applies")
    ap.add_argument("--churn_at", type=float, default=0.5,
                    help="when to deliver the SIGTERM, as a fraction of "
                         "the trace window")
    return ap.parse_args(argv)


def run_attached(args) -> dict:
    from megatron_tpu.inference.fleet import slo

    trace = slo.make_trace(args.requests, args.offered_rps,
                           seed=args.seed, vocab=args.vocab,
                           new_tokens=args.new_tokens)
    metrics_urls = [u.rstrip("/") + "/metrics"
                    for u in (args.replica or [args.api])]
    return slo.run_slo(args.api.rstrip("/") + "/api", metrics_urls, trace,
                       args.offered_rps, timeout=args.timeout)


def run_spawned(args) -> dict:
    import threading

    from megatron_tpu.inference.fleet import slo
    from megatron_tpu.inference.fleet.replica import ReplicaProcess
    from megatron_tpu.inference.fleet.router import RouterServer

    if args.churn and args.spawn < 2:
        raise SystemExit("--churn needs --spawn >= 2 (the victim's "
                         "requests must have somewhere to migrate)")
    with tempfile.TemporaryDirectory(prefix="slo_fleet_") as tmp:
        replicas = []

        def _spawn(i, peers=None):
            spec = {"preset": "tiny",
                    "cfg": {"vocab_size": args.vocab, "seq_length": 64},
                    "seed": 0, "engine_slots": args.engine_slots,
                    "port": 0, "warmup": True,
                    "port_file": os.path.join(tmp, f"r{i}.port")}
            if peers:
                spec["peers"] = peers
            rep = ReplicaProcess(
                spec, log_path=os.path.join(tmp, f"r{i}.log")).spawn()
            replicas.append(rep)
            return rep

        try:
            # replicas 1..N-1 first: their bound URLs become replica 0's
            # handoff peers, so a churn SIGTERM on 0 migrates its live
            # requests instead of failing them
            for i in range(1, args.spawn):
                _spawn(i)
            for rep in replicas:
                rep.wait_ready(timeout=300)
            victim = _spawn(0, peers=[r.url for r in replicas]
                            if args.churn else None)
            victim.wait_ready(timeout=300)
            router = RouterServer([r.url for r in replicas]).start()
            try:
                trace = slo.make_trace(args.requests, args.offered_rps,
                                       seed=args.seed, vocab=args.vocab,
                                       new_tokens=args.new_tokens)
                churn_timer = None
                churn_at_s = None
                fire_lock = threading.Lock()
                fired = []

                def _sigterm_victim():
                    # exactly-once: a second SIGTERM takes the server's
                    # force-exit path instead of the graceful drain
                    with fire_lock:
                        if fired:
                            return
                        fired.append(True)
                    victim.terminate()

                if args.churn:
                    window_s = max(e["at_s"] for e in trace)
                    churn_at_s = round(window_s * args.churn_at, 3)
                    churn_timer = threading.Timer(churn_at_s,
                                                  _sigterm_victim)
                    churn_timer.daemon = True
                    churn_timer.start()
                report = slo.run_slo(
                    router.url + "/api",
                    [r.url + "/metrics" for r in replicas], trace,
                    args.offered_rps, timeout=args.timeout)
                report["spawned_replicas"] = args.spawn
                if args.churn:
                    churn_timer.cancel()
                    _sigterm_victim()  # window beat the timer: drill now
                    try:
                        exit_code = victim.wait(timeout=60)
                    except Exception:
                        exit_code = None
                    report["churn"] = {
                        "victim": victim.url,
                        "sigterm_at_s": churn_at_s,
                        "victim_exit": exit_code,
                    }
                return report
            finally:
                router.close()
        finally:
            for rep in replicas:
                rep.close()


def main(argv=None) -> int:
    args = parse_args(argv)
    if not args.spawn and not args.api:
        print("need --api URL (attach) or --spawn N (local fleet)",
              file=sys.stderr)
        return 2
    report = run_spawned(args) if args.spawn else run_attached(args)
    print(json.dumps(report, indent=2))
    return 0 if report.get("failed", 1) == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
