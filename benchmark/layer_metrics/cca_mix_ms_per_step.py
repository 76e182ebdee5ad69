"""Device time a step of what compressed convolutional attention puts
between its projections and the kernel: the operations under the scope
`cca_mix` (megatron_tpu/ops/cca.py: the value shift, the two causal
convolutions over the (q, k) latent, the q-k mean, the unit norm of q and
k with k's temperature), forward, backward and recomputed, every layer of
the step: own time inside the whole runs of the step program, over those
runs, mean over the devices. Inside `attention`, beside `attn_qkv`,
`attn_rope`, `attn_core` and `attn_out`. None where no operation carries
the scope (a model of plain attention, a CPU trace with no device plane,
a parent commit)."""

from benchmark.harness.trace import named


def read(run):
    return named.scope_ms(run, "cca_mix") or None
