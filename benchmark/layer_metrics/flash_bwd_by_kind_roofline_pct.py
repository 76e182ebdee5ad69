"""Share of the roofline the backward flash kernels reach together in a
stack whose layers are of several attention kinds (a fused `flash_bwd`, the
split pair `flash_bwd_dq` + `flash_bwd_dkv`, or both where a program runs
both): five matmuls over the causal pairs of the mean layer kind and eight
tensors moved once a call (kernel_costs/flash_bwd_by_kind.py; of the
split pair three matmuls and six tensors are booked on dq, two and two on
dkv, as flash_bwd_dq.py and flash_bwd_dkv.py book them), over the time the
trace shows for them. The record goes to the line's
`extras.roofline.flash_bwd_by_kind`. None in a rehearsal (no peaks), on an
untraced run, where none of the kernels ran, or for a configuration
without `layer_types`."""

import functools

from benchmark.harness.trace import kernel_cost, named

LABEL = "flash_bwd_by_kind"
# (matmuls, tensors) of the backward's five and eight that a call is booked
PARTS = {"flash_bwd": (5, 8), "flash_bwd_dq": (3, 6), "flash_bwd_dkv": (2, 2)}


def read(run):
    got = named.of_run(run) if run.peaks is not None else None
    needed = run.cell.kernel_cost(LABEL)
    if got is None or needed is None:
        return None
    roof = kernel_cost.roofline(
        got["kernels"], tuple(PARTS),
        lambda kernel: functools.partial(
            needed, matmuls=PARTS[kernel][0], tensors=PARTS[kernel][1]),
        run.cell.config, run.peaks)
    if roof is None:
        return None
    run.extras.setdefault("roofline", {})[LABEL] = roof
    return roof["pct"]
