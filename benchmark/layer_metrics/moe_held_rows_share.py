"""The share of a call's N x k (token, choice) rows that the router sends
to the experts held on this chip, of a model that holds a share of a wider
router's experts (megatron_tpu/ops/moe.py moe_block_dropless): mean of
the layers and of the step's micro-batches, as the trainer's `step`
records carry it (`moe_held_rows_share`); median over the steps that
finished inside the window. The even split is held / router width (16 of
64: 0.25); the buffer takes every row whatever the share, so none is left
out. Neither direction is better: BENCHMARK.json has to say one and says
`lower` because the held experts' time rises with the share (`moves`
train_tokens_per_s), but a share under the even split means only that the
router prefers experts held elsewhere; read it beside the experts' time,
not as a score. None where the journal's records lack the field (a model
that holds every expert of its router journals none, nor does a dense
model or a parent commit)."""

from benchmark.harness import stats
from benchmark.harness.trace import named

FIELD = "moe_held_rows_share"


def read(run):
    if not run.steps:
        return None
    inside = {s["iteration"] for s in run.steps}
    values = [r[FIELD] for r in named.journal(named.run_files(run)[1])
              if r.get("kind") == "step" and r.get("iteration") in inside
              and r.get(FIELD) is not None]
    return stats.median(values) if values else None
