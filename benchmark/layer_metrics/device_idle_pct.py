"""1 minus the union of the device's operation intervals over the traced
window, averaged over the chips used."""


def read(run):
    if not run.trace or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
