"""What one forward call's pass through one layer's HELD experts needs,
forward and backward, over dims = (rows routed to held experts, hidden,
micro-batches a step): each row through its expert's [hidden, 2 x
`moe_intermediate_size`] gate-up matrix and its [`moe_intermediate_size`,
hidden] down matrix, three times (the forward product and the backward's
two: gradient of the rows, gradient of the weights); a pass moves the
weights of the `num_experts` experts held here once (read, or written as
their gradient) and the rows once in and once out at each of the two
matrices. Where the step accumulates over several micro-batches the
gradient pass reads and writes the float32 accumulator instead (8 bytes a
parameter: megatron_tpu/ops/pallas/grouped_matmul.py `sink`). The scope
`moe_experts` runs it (`moe_gmm` / `moe_tgmm` over the held groups and the
activation), so the reader
(layer_metrics/moe_held_experts_roofline_pct.py) gives the rows from the
trainer's own count of them, not from a call's HLO text: the buffer the
kernels' results have is larger than the rows they visit. None for a
configuration that does not state an expert's width under
`moe_intermediate_size`."""


def needed(dims, itemsize, config):
    if len(dims) != 3 or "moe_intermediate_size" not in config:
        return None
    rows, h, micro_batches = dims
    f = config["moe_intermediate_size"]
    per_row = h * 2 * f + f * h                      # multiply-adds a row
    flops = 3 * 2.0 * rows * per_row
    weights = config["num_experts"] * per_row
    moved = rows * (h + 2 * f + f + h)               # in, mid out, mid in, out
    gradient = weights * (8 if micro_batches > 1 else itemsize)
    return flops, float((2 * weights + 3 * moved) * itemsize + gradient)
