"""Device time a step of the operations under the scope `mlp`
(models/transformer.py block_forward: the second norm, the fused SwiGLU
matmuls or the MoE, the down projection, dropout and the residual add,
with their collectives): own time inside the whole runs of the step
program, over those runs, mean over the devices."""

from benchmark.harness.trace import named


def read(run):
    return named.region_ms(run, "mlp")
