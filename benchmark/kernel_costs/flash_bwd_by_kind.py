"""What one call of the fused backward kernel needs in a stack whose
layers are of several attention kinds: all five matmuls the backward needs
(QK^T again, dO V^T, P^T dO, dS^T Q, dS K) over the causal pairs of the
MEAN layer kind (the calls of a step have one shape and differ in their
static window, which a call's HLO text does not show) and
its eight tensors (q, k, v, o, do, dq, dk, dv) moved once: what
flash_bwd.py books a fused call, by kind. The split pair, where a program
runs it, is booked its parts of the same sum (three matmuls and six
tensors on `flash_bwd_dq`, two and two on `flash_bwd_dkv`:
layer_metrics/flash_bwd_by_kind_roofline_pct.py hands them out)."""

from benchmark.harness.trace import kernel_cost

MATMULS, TENSORS = 5, 8


def needed(dims, itemsize, config, matmuls=MATMULS, tensors=TENSORS):
    kinds = config.get("layer_types")
    if not kinds:
        return None
    costs = [kernel_cost.causal_attention(
        dims, itemsize,
        config["sliding_window"] if kind == "sliding_attention" else None,
        matmuls, tensors) for kind in kinds]
    if None in costs:
        return None
    return (sum(c[0] for c in costs) / len(costs),
            sum(c[1] for c in costs) / len(costs))
