"""What the host itself works a step: over the `train-pass` spans the
trace holds whole (one pass of TrainLoop.train's loop, on the profiler's
clock), the median of the pass less its `metrics-fetch` (the host waits
for the device there) and its `batch-generator` (it waits for data). What
remains is dispatch, bookkeeping and the journal: the time the device
would idle a step if it were infinitely fast."""

from benchmark.harness import stats
from benchmark.harness.trace import named


def read(run):
    if not run.steps:
        return None
    passes = named.read(named.run_files(run)[0])["passes"]
    if not passes:
        return None
    return stats.median([(p["pass_ps"] - p["fetch_ps"] - p["data_ps"]) * 1e-9
                         for p in passes])
