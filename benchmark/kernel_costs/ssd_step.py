"""What one call of the Mamba-2 decode step needs, over dims = (rows R, 1,
inner width d_i) of its first result, y float32: one position of the
recurrence for every row of a layer's state (megatron_tpu/ops/pallas/
ssd_step.py). The state a head's channel N is the configuration's
`ssm_state_size`, the groups G its `n_groups`.

Bytes, each operand once: the layer's state read and written ([R, N, d_i]
float32 twice: nearly all of it), each channel's decay and input read and
y written ([R, d_i] three times), B and C read ([R, G, N] twice).

Operations, a state element: the decay's product, B x (dt x), the sum,
the product with C and the sum over N: 5. All the vector unit's; against
the HBM / MXU roofline the call is bound by the state's bytes (64 rows:
0.54 GB, 0.66 ms at the HBM's peak).

A row that does not decode this tick is read and written like the others
(its factors are 1 and 0): the count is of the call's rows, as the kernel
moves them."""


def needed(dims, itemsize, config):
    if len(dims) != 3 or "ssm_state_size" not in config:
        return None
    rows, _, di = dims
    n, g = config["ssm_state_size"], config["n_groups"]
    flops = float(5 * rows * n * di)
    moved = (2 * rows * n * di + 3 * rows * di + 2 * rows * g * n) * itemsize
    return flops, float(moved)
