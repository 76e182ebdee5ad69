"""What one call of the dk/dv backward kernel is booked: two of the five
matmuls the backward needs (P^T dO, dS^T Q; its own QK^T and dO V^T are
recomputation, not needed work) and two of its eight tensors (dk, dv);
see flash_bwd_dq.py for the rest of the pair."""

from benchmark.harness.trace import kernel_cost


def needed(dims, itemsize, config):
    return kernel_cost.causal_attention(
        dims, itemsize, config.get("sliding_window"), matmuls=2, tensors=2)
