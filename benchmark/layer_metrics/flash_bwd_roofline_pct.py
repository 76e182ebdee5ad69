"""Share of the roofline the backward kernels reach together (the split
pair `flash_bwd_dq` + `flash_bwd_dkv`, a fused `flash_bwd`, or both where
a program runs both): five matmuls over the causal windowed pairs are
needed (the split kernels run seven), and each of q, k, v, o, do, dq, dk,
dv moves once (kernel_costs/flash_bwd_dq.py, flash_bwd_dkv.py; flash_bwd.py
books the fused call the pair's sum), over the time the trace shows for
them. A name no kernel carries adds nothing (kernel_cost.roofline). The
bound that applies goes to the line's `extras.roofline`."""

from benchmark.harness.trace import named


def read(run):
    return named.roofline_pct(run, "flash_bwd", "flash_bwd_dq",
                              "flash_bwd_dkv", "flash_bwd")
