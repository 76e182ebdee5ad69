"""What one call of the selective scan needs, over dims = (sequences B,
positions T, inner width d_i) of its first result, y float32: the state a
channel N is the configuration's `mamba_d_state`.

Bytes, each operand once: x, delta and z read and y written ([B, T, d_i]
float32 each), B_t and C_t read ([B, T, N]: the kernel takes them with
each number repeated along a lane tile, which is its layout's cost and no
needed work), A [N, d_i] and D [d_i] read, the state read and written
([B, N, d_i] float32 twice).

Operations, a state element a position: delta x A, its exponential, the
product with h, B x (delta x), the sum (5), the product with C and the sum
over N (2): 7 N; and a channel a position: delta x, D x, the sum, and the
gate's sigmoid, product and product: 6. An exponential counts as one.

All of it is the vector unit's: the share of the HBM / MXU roofline that
layer_metrics/ssm_scan_roofline_pct.py forms from these reads low by the
nature of the work (512 positions: 0.31 GFLOP and 42 MB a call, 51 us at
the HBM's peak)."""


def needed(dims, itemsize, config):
    if len(dims) != 3 or "mamba_d_state" not in config:
        return None
    b, t, di = dims
    n = config["mamba_d_state"]
    flops = float(b * t * di * (7 * n + 6))
    moved = (4 * b * t * di + 2 * b * t * n + n * di + di
             + 2 * b * n * di) * itemsize
    return flops, float(moved)
