"""The share of the (row, choice) pairs the engine's steps routed that
went to experts held on this chip, of a served model that holds a share
of a wider router's experts: `engine_moe_held_rows_total` over
`engine_moe_rows_total` between the first and the last `serve_ticks`
record inside the window (the engine's cumulative pair `moe_rows`:
[held, all], over ticks and chunks and all expert layers). The even split
is held / router width (128 of 512: 0.25). Neither direction is better:
BENCHMARK.json has to say one and says `lower` because the held experts'
rows rise with the share; read it beside the experts' time, not as a
score. None where the records lack the pair (a model that holds every
expert or none, a parent commit)."""

from benchmark.harness import serve_journal


def read(run):
    snaps = [r["moe_rows"] for r in serve_journal.of_kind(run, "serve_ticks")
             if "moe_rows" in r]
    if len(snaps) < 2 or snaps[-1][1] <= snaps[0][1]:
        return None
    return (snaps[-1][0] - snaps[0][0]) / (snaps[-1][1] - snaps[0][1])
