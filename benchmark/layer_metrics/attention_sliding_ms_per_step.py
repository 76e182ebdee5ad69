"""Device time a step of the sliding-window attention layers: the
operations under the scope `attn_sliding`, which a stack of several
attention kinds puts around a layer inside region `attention`
(megatron_tpu/models/transformer.py block_forward: the norm, QKV, rotary,
the flash kernels under the layer's static window, the output projection
and the residual add; forward, backward and recomputed), own time inside
the whole runs of the step program, over those runs, mean over devices.
With `attention_full_ms_per_step` it splits `attention_ms_per_step` by
kind. None where no operation carries the scope (a model whose layers are
all alike names no kind; a CPU trace has no device plane)."""

from benchmark.harness.trace import named


def read(run):
    return named.scope_ms(run, "attn_sliding") or None
