"""How unevenly the router loads the experts: the largest expert's row
count over the mean (1.0 is perfect balance), the worst layer's, mean of
the step's micro-batches, as the trainer's `step` records carry it
(`moe_load_max_over_mean`, megatron_tpu/training/train_step.py); median
over the steps that finished inside the window. None where the journal's
records lack the field (a dense model, a parent commit)."""

from benchmark.harness import stats
from benchmark.harness.trace import named


def read(run):
    if not run.steps:
        return None
    inside = {s["iteration"] for s in run.steps}
    values = [r["moe_load_max_over_mean"]
              for r in named.journal(named.run_files(run)[1])
              if r.get("kind") == "step" and r.get("iteration") in inside
              and r.get("moe_load_max_over_mean") is not None]
    return stats.median(values) if values else None
