"""Share of the roofline the two backward kernels reach together
(`flash_bwd_dq` + `flash_bwd_dkv`): five matmuls over the causal windowed
pairs are needed (the split kernels run seven), and each of q, k, v, o, do,
dq, dk, dv moves once (kernel_costs/flash_bwd_dq.py, flash_bwd_dkv.py), over the time the
trace shows for both. The bound that applies goes to the line's
`extras.roofline`."""

from benchmark.harness.trace import named


def read(run):
    return named.roofline_pct(run, "flash_bwd", "flash_bwd_dq",
                              "flash_bwd_dkv")
