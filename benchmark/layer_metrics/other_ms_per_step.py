"""Device time a step of everything outside the four large regions: the
scope `embed` and the operations under no scope at all (the fp32 gradient
accumulation of the microbatch scan, its zeros, copies the compiler adds
between regions). With `attention`, `mlp`, `head_loss` and `optimizer` it
sums to the busy time of a run by construction, so a refactor that loses a
scope shows here and not as a faster region."""

from benchmark.harness.trace import named


def read(run):
    return named.region_ms(run, "embed", named.OTHER)
