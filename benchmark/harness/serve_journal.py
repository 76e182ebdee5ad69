"""A traced serving run's journal read by kind, inside the window.

The harness keeps the window's `serve_request` records
(`run.engine_requests`: retired between the window's start and its end).
The engine and the server write other kinds beside them (the program's
docs/observability.md: `serve_ticks`, cumulative counters of the loop with
each retirement; `serve_slow_tick`, a tick that stood; `serve_reply`, the
handler's own time for a request), and this file gives a reader those of
one kind that lie inside the same window, taken as the time from the
first of `run.engine_requests` to the last (the journal's `ts`, the wall
clock). Empty for a run that kept no such records: an untraced run, a
training cell, a made-up run in a test (nothing is read from disk for it).
"""

from __future__ import annotations

from typing import List

from benchmark.harness.trace import named


def of_kind(run, kind: str) -> List[dict]:
    """The journal's records of `kind` between the first and the last of
    the window's retirements, in order."""
    stamps = [r["ts"] for r in run.engine_requests if "ts" in r]
    if not stamps:
        return []
    first, last = min(stamps), max(stamps)
    return [r for r in named.journal(named.run_files(run)[1])
            if r.get("kind") == kind and first <= r.get("ts", 0) <= last]


def replies(run) -> List[dict]:
    """The `serve_reply` records of the window's requests, joined by id
    (a request of several prompts is `<id>/<k>` in the engine's records
    and `<id>` in the handler's): a reply is written after the
    retirements it answers, so the last ones lie past the window's last
    retirement and are found by name, not by time."""
    ids = {str(r["id"]).split("/")[0] for r in run.engine_requests
           if r.get("id") is not None}
    if not ids:
        return []
    return [r for r in named.journal(named.run_files(run)[1])
            if r.get("kind") == "serve_reply" and r.get("id") in ids]
