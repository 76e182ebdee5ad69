"""The mean decoding batch: decoding rows summed over the ticks read,
over those ticks, between the first and the last `serve_ticks` record
inside the window (cumulative counters of the engine's loop, one snapshot
a retired request). By Little's law it is the offered rate times the mean
time a request decodes; the decode step's time follows it."""

from benchmark.harness import serve_journal


def read(run):
    snaps = [r for r in serve_journal.of_kind(run, "serve_ticks")
             if "rows" in r]
    if len(snaps) < 2 or snaps[-1]["ticks"] <= snaps[0]["ticks"]:
        return None
    return ((snaps[-1]["rows"] - snaps[0]["rows"])
            / (snaps[-1]["ticks"] - snaps[0]["ticks"]))
