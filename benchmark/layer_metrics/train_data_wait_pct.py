"""Share of step time the train loop spent waiting for its next batch:
sum of `data_wait_ms` over sum of `step_ms`, step journal records of the
steps that finished inside the window."""


def read(run):
    steps = [s for s in run.steps if s.get("step_ms")]
    if not steps:
        return None
    return (100.0 * sum(s.get("data_wait_ms") or 0.0 for s in steps)
            / sum(s["step_ms"] for s in steps))
