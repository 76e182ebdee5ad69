"""Device time a step of what `attention` holds that is neither a kernel,
a matmul nor a collective: the classes `elementwise`, `data_movement` and
`rest` of harness/trace/classes.py in the region (norm, rotary, bias and
residual adds, the layout changes around the kernels' `shard_map`, the
broadcast of K and V to the query heads and its reduction in the backward
pass). `extras.step_classes.scopes` says which part of the region
(`attn_norm`, `attn_qkv`, `attn_rope`, `attn_core`, `attn_out`) holds it."""

from benchmark.harness.trace import classes


def read(run):
    return classes.region_class_ms(run, "attention", *classes.GLUE)
