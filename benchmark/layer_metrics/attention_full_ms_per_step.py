"""Device time a step of the full-attention layers of a stack that also
holds window layers: the operations under the scope `attn_full` inside
region `attention` (see attention_sliding_ms_per_step.py: the same parts
of a layer, the flash kernels over the whole causal triangle, the rotary
table its kind's, YaRN's here). None where no operation carries the
scope."""

from benchmark.harness.trace import named


def read(run):
    return named.scope_ms(run, "attn_full") or None
