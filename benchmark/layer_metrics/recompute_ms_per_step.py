"""Device time a step spent computing again what was computed before to
save memory: own time of the operations whose name stack holds
`rematted_computation` (jax.checkpoint's replay in the backward pass: the
flash forward under selective recompute, the CE chunk's logits), inside
the whole runs of the step program, over those runs, mean over devices.
Cuts across the regions; a program with no recomputation reads 0."""

from benchmark.harness.trace import named


def read(run):
    return named.scope_ms(run, "rematted_computation")
