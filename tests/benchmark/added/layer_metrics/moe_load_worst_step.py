"""The router's balance at its worst inside the window: the largest of the
steps' `moe_load_max_over_mean` (benchmark/layer_metrics/
moe_load_max_over_mean.py reads their median). A per-layer metric as a PR
that adds a configuration brings it: a file of its own, listed for that
PR's cell alone. None where the journal's records lack the field (a model
without experts journals none: a reader says nothing where its mechanism
does not occur)."""

from benchmark.harness.trace import named


def read(run):
    if not run.steps:
        return None
    inside = {s["iteration"] for s in run.steps}
    values = [r["moe_load_max_over_mean"]
              for r in named.journal(named.run_files(run)[1])
              if r.get("kind") == "step" and r.get("iteration") in inside
              and r.get("moe_load_max_over_mean") is not None]
    return max(values) if values else None
