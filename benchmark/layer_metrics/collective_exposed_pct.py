"""Time in collective operations during which no compute runs on that
device, over the traced window, on the device where it is largest."""


def read(run):
    if not run.trace or run.trace["devices"] < 2:
        return None
    return (100.0 * run.trace["collective_exposed_worst_s"]
            / run.trace["window_s"])
