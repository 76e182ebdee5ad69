"""Median `step_ms` of the step journal (dispatch plus the lagged fetch
of the step's metrics, which in steady state is the device's step
time), over the steps that finished inside the window."""

from benchmark.harness import stats


def read(run):
    values = [s["step_ms"] for s in run.steps if s.get("step_ms")]
    return stats.median(values) if values else None
