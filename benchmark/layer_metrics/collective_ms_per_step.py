"""Device time a step in communication, whatever the operation is called:
the classes `collective` (an event named all-reduce, all-gather,
reduce-scatter, ..., its `-start` and `-done` included) and
`collective_fused` (a fusion whose `hlo_category` names a collective, or
an `async-collective` fusion) of harness/trace/classes.py, in every region
and in none; own time inside the whole runs of the step program, over
those runs, mean of devices. On the one serial `XLA Ops` line of a device
an operation's own time overlaps no other's, so this is time the device
spent communicating and doing nothing else it names: what
`collective_exposed_pct` counts for the plain ones only.
`extras.collectives` splits it by region, kind and fused or not."""

from benchmark.harness.trace import classes


def read(run):
    if not run.trace or run.trace["devices"] < 2:
        return None
    return classes.class_ms(run, classes.COLLECTIVE,
                            classes.COLLECTIVE_FUSED)
