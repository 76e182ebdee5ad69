"""What a dense FFN's matmuls of one device need a step, over dims =
(tokens a data-parallel replica holds a step, tensor-parallel size): the
fused gate-up projection ([h, 2 f]) and the down projection ([f, h]), each
three times (the forward product and the backward's two), every layer,
nothing computed again; tensor parallelism divides f over its devices.
Bytes: the three products of a matmul move input, weight and output three
times each. The region's class `matmul` runs it
(layer_metrics/mlp_matmul_roofline_pct.py gives the dims from the cell's
traffic). None for a configuration with experts: its FFN is the grouped
kernels' (kernel_costs/moe_experts.py)."""


def needed(dims, itemsize, config):
    if len(dims) != 2 or config.get("num_experts"):
        return None
    tokens, tp = dims
    h, f = config["hidden_size"], config["intermediate_size"]
    layers = config["num_hidden_layers"]
    up, down = h * 2 * f, f * h                      # multiply-adds a token
    flops = 3 * 2.0 * tokens * (up + down) / tp * layers
    moved = (tokens * (h + 2 * f / tp) + up / tp
             + tokens * (f / tp + h) + down / tp)
    return flops, float(3 * moved * itemsize * layers)
