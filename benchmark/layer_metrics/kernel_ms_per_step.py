"""Device time of the Pallas (Mosaic) custom-call operations in one run
of the train step, from the device trace: their own time inside the runs
of the step program that the trace holds whole, over the number of those
runs. A time and not a share of busy time: a share rises when everything
else gets faster. One number for all five flash kernels until they carry
names (tracing issue)."""


def read(run):
    if not run.trace or run.trace.get("kernel_s_per_run") is None:
        return None
    return 1e3 * run.trace["kernel_s_per_run"]
