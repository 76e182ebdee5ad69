"""What one call of a fused backward kernel is booked: all five matmuls
the backward needs (QK^T again, dO V^T, P^T dO, dS^T Q, dS K) and its
eight tensors (q, k, v, o, do, dq, dk, dv) moved once: exactly the sum of
what flash_bwd_dq.py and flash_bwd_dkv.py book the split pair, so the
share a fused kernel reads is comparable with the pair's.

The shape comes from the call's own HLO text, and of a call with several
results (`(dq, dk, dv) custom-call(...)`) the harness reads the FIRST
(`kernel_cost.result_shape`). The counts here are over a result of the
query's shape [B, H, S, D], which all three have while the kernel takes K
and V already broadcast to the query heads; a kernel that writes dk and
dv over the KV heads lists dq first."""

from benchmark.harness.trace import kernel_cost


def needed(dims, itemsize, config):
    return kernel_cost.causal_attention(
        dims, itemsize, config.get("sliding_window"), matmuls=5, tensors=8)
