"""Device time a step of the matmuls under the scope `mlp`: the
operations of class `matmul` (harness/trace/classes.py) in the region: a
dense FFN's gate-up and down projections, forward and both backward
products, with whatever the compiler fused onto them (the activation, and
under TP possibly a collective: `extras.collectives` and the journal's
`step_program.collectives` say). Own time inside the whole runs of the
step program, over those runs, mean of devices."""

from benchmark.harness.trace import classes


def read(run):
    return classes.region_class_ms(run, "mlp", classes.MATMUL)
