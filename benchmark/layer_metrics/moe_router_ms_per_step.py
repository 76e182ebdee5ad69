"""Device time a step of the operations under the scope `moe_router`
(megatron_tpu/ops/moe.py moe_block_dropless: the router matmul in float32, the softmax, the top-k and the auxiliary statistics (bincount, load-balance and z-loss)),
forward, backward and recomputed, every micro-batch and layer of the
step: own time inside the whole runs of the step program, over those
runs, mean over the devices. Inside `mlp`; None on a program without the
named regions, 0.0 on one that has them and no such scope."""

from benchmark.harness.trace import named


def read(run):
    return named.scope_ms(run, "moe_router")
