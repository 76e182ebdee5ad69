"""Time the loop stood: the sum of `wall_s` over the `serve_slow_tick`
journal records inside the window (a tick that took both a quarter of a
second and eight times the median of the last 256; the record names the
phase it stood in). 0.0 where the engine keeps its phases' time
(`serve_ticks` records with `phase_s`) and wrote no slow tick; None where
the program writes neither."""

from benchmark.harness import serve_journal


def read(run):
    slow = serve_journal.of_kind(run, "serve_slow_tick")
    if slow:
        return 1e3 * sum(r["wall_s"] for r in slow)
    kept = any("phase_s" in r
               for r in serve_journal.of_kind(run, "serve_ticks"))
    return 0.0 if kept else None
