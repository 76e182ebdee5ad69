"""What one micro-batch's pass through one layer's experts needs, forward
and backward, over its [rows, hidden] routed rows (rows = tokens x experts
a token): each row through its expert's [hidden, 2 x intermediate] gate-up
matrix and its [intermediate, hidden] down matrix, three times (the
forward product and the backward's two: gradient of the rows, gradient of
the weights); a pass moves every expert's weights once (read, or written
as their gradient) and the rows once in and once out at each of the two
matrices. The scope `moe_experts` (megatron_tpu/ops/moe.py) is what runs
it: two `lax.ragged_dot` and the activation, no Pallas kernel, so the
reader (layer_metrics/moe_experts_roofline_pct.py) gives the shape from
the cell's configuration and traffic instead of a call's HLO text."""


def needed(dims, itemsize, config):
    if len(dims) != 2:
        return None
    rows, h = dims
    f = config["intermediate_size"]
    per_row = h * 2 * f + f * h                      # multiply-adds a row
    flops = 3 * 2.0 * rows * per_row
    weights = config["num_experts"] * per_row
    moved = rows * (h + 2 * f + f + h)               # in, mid out, mid in, out
    return flops, float(3 * (weights + moved) * itemsize)
