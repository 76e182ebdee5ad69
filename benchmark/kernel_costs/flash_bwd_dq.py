"""What one call of the dq backward kernel is booked: three of the five
matmuls the backward needs (QK^T again, dO V^T, dS K; `flash_bwd_dkv` is
booked the other two, and only the pair's sum means anything) and six of
its eight tensors (q, k, v, o, do, dq) moved once."""

from benchmark.harness.trace import kernel_cost


def needed(dims, itemsize, config):
    return kernel_cost.causal_attention(
        dims, itemsize, config.get("sliding_window"), matmuls=3, tensors=6)
