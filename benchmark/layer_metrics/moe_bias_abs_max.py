"""How far the router's selection bias has moved: the largest |bias| of
any expert of any layer after the step's update (`bias_e += u * sign(1/E
- load_e)`, megatron_tpu/training/optimizer.py update_selection_bias), as
the trainer's `step` records carry it (`moe_bias_abs_max`); median over
the steps that finished inside the window. The bias balances the load in
place of an auxiliary loss: read it beside `moe_load_max_over_mean` and
`moe_held_rows_share` (a bias that keeps growing while the load stays
uneven says the rate is too small for the logits' spread; neither
direction is better in itself, and BENCHMARK.json says `lower` because a
balanced router needs little of it). None where the journal's records
lack the field (a model without the bias's update rate, a dense model, a
parent commit)."""

from benchmark.harness import stats
from benchmark.harness.trace import named

FIELD = "moe_bias_abs_max"


def read(run):
    if not run.steps:
        return None
    inside = {s["iteration"] for s in run.steps}
    values = [r[FIELD] for r in named.journal(named.run_files(run)[1])
              if r.get("kind") == "step" and r.get("iteration") in inside
              and r.get(FIELD) is not None]
    return stats.median(values) if values else None
