"""Device time a step of the operations under the scope `moe_combine`
(megatron_tpu/ops/moe.py moe_block_dropless: the gate weighting and the float32 scatter-add of the k expert outputs back to their tokens),
forward, backward and recomputed, every micro-batch and layer of the
step: own time inside the whole runs of the step program, over those
runs, mean over the devices. Inside `mlp`; None on a program without the
named regions, 0.0 on one that has them and no such scope."""

from benchmark.harness.trace import named


def read(run):
    return named.scope_ms(run, "moe_combine")
