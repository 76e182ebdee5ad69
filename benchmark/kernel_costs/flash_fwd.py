"""What one call of the forward flash kernel needs over its [B, H, S, D]
result: two matmuls (QK^T, PV) over the causal pairs inside the
configuration's sliding window; q, k, v and o moved once."""

from benchmark.harness.trace import kernel_cost


def needed(dims, itemsize, config):
    return kernel_cost.causal_attention(
        dims, itemsize, config.get("sliding_window"), matmuls=2, tensors=4)
