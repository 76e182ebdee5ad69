"""Share of the roofline the `ssm_scan` kernel's calls reach inside the
prefill chunk (`jit_chunk_step`'s whole runs): the least time the chip
could take for what the selective scan needs over the calls' own shapes
(kernel_costs/ssm_scan.py: each operand once, the recurrence's
operations; peaks from benchmark/peaks.json) over the time the trace
shows for them. The recurrence is elementwise and sequential in time: the
vector unit's work, none for the matrix unit, so against the HBM / MXU
roofline the share says how far the kernel is from being free, not how
well it uses what it can use. The bound that applies goes to the line's
`extras.roofline`.

XLA fuses the kernel's custom call with the write of its new state into
the engine's state store (in place), so the call's event is a fusion
named after the kernel whose FIRST result is the whole store,
`(f32[26,64,16,5120], f32[1,512,5120]) fusion(...)`: the shape the cost
file counts from is y's, the result of rank 3, and `kernel_cost.roofline`,
which reads a call's first result, is not used. None in a rehearsal (no
peaks), on an untraced run, or where the kernel did not run."""

import re

from benchmark.harness.trace import by_program, kernel_cost

_RESULT = re.compile(r"([a-z0-9]+)\[([0-9,]+)\]")


def y_shape(hlo_text):
    """(dtype, (B, T, d_i)) of the call's result of rank 3, or None."""
    head = re.split(r" (?:fusion|custom-call)\(", hlo_text, 1)[0]
    for dtype, dims in _RESULT.findall(head.split("=", 1)[-1]):
        dims = tuple(int(d) for d in dims.split(","))
        if len(dims) == 3 and dtype in kernel_cost.ITEMSIZE:
            return dtype, dims
    return None


def read(run):
    got = by_program.of_run(run, "jit_chunk_step")
    needed = run.cell.kernel_cost("ssm_scan")
    if got is None or run.peaks is None or needed is None:
        return None
    kernel = got["kernels"].get("ssm_scan")
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
    run.extras.setdefault("roofline", {})["ssm_scan"] = roof
    return roof["pct"]
