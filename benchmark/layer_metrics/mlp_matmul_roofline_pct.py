"""Share of the MXU's peak the dense FFN's matmuls reach: the least time
one device could take for the FLOP that gate-up and down NEED a step
(kernel_costs/mlp_matmul.py: forward and both backward products of every
layer, nothing computed again; from the cell's configuration, the mix's
tokens a step and its TP x DP; peaks from benchmark/peaks.json) over
`mlp_matmul_ms_per_step`. The record, with the compiler's own FLOP count
beside the needed, goes to the line's `extras.roofline.mlp_matmul`. None
in a rehearsal (no peaks) or on an untraced run."""

from benchmark.harness.trace import classes


def read(run):
    return classes.matmul_roofline_pct(run, "mlp_matmul", "mlp")
