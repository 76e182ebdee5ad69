"""Share of the roofline the `ssd_step` kernel's calls reach inside the
decode step (`jit_decode_step`'s whole runs): the least time the chip
could take for what one position of the Mamba-2 recurrence needs over
every row of a layer's state (kernel_costs/ssd_step.py: the state read and
written once; peaks from benchmark/peaks.json) over the time the trace
shows for the calls. The bound that applies goes to the line's
`extras.roofline`.

The call's results are y and the whole store (aliased to the operand, in
place): the shape the cost file counts from is y's, the result of rank 3,
wherever it stands among the results (`ssm_scan_roofline_pct` says why a
call's first result may be the store). None in a rehearsal (no peaks), on
an untraced run, or where the kernel did not run (a model without
Mamba-2 layers, a CPU, a parent commit)."""

import re

from benchmark.harness.trace import by_program, kernel_cost

_RESULT = re.compile(r"([a-z0-9]+)\[([0-9,]+)\]")


def y_shape(hlo_text):
    """(dtype, (R, 1, d_i)) of the call's result of rank 3, or None."""
    head = re.split(r" (?:fusion|custom-call)\(", hlo_text, 1)[0]
    for dtype, dims in _RESULT.findall(head.split("=", 1)[-1]):
        dims = tuple(int(d) for d in dims.split(","))
        if len(dims) == 3 and dtype in kernel_cost.ITEMSIZE:
            return dtype, dims
    return None


def read(run):
    got = by_program.of_run(run, "jit_decode_step")
    needed = run.cell.kernel_cost("ssd_step")
    if got is None or run.peaks is None or needed is None:
        return None
    kernel = got["kernels"].get("ssd_step")
    if kernel is None or not kernel["s"]:
        return None
    flops = nbytes = 0.0
    for text, calls in kernel["calls"].items():
        shape = y_shape(text)
        work = shape and needed(shape[1], kernel_cost.ITEMSIZE[shape[0]],
                                run.cell.config)
        if not work:
            return None
        flops += calls * work[0]
        nbytes += calls * work[1]
    compute_s = flops / run.peaks["bf16_flops_per_s"]
    memory_s = nbytes / run.peaks["hbm_bytes_per_s"]
    roof = {"pct": 100.0 * max(compute_s, memory_s) / kernel["s"],
            "bound": "compute" if compute_s >= memory_s else "memory",
            "needed_flop": flops, "needed_bytes": nbytes,
            "needed_ms": 1e3 * max(compute_s, memory_s),
            "measured_ms": 1e3 * kernel["s"]}
    run.extras.setdefault("roofline", {})["ssd_step"] = roof
    return roof["pct"]
