"""Share of the roofline the `flash_fwd` calls reach in a stack whose
layers are of several attention kinds: the least time the chip could take
for what causal attention needs over the calls' own shapes, each call
booked the mean over the configuration's layer kinds (window layers their
band, full layers the whole triangle: kernel_costs/flash_fwd_by_kind.py;
`flash_fwd_roofline_pct` would clip every call to the window), over the
time the trace shows for them. The record goes to the line's
`extras.roofline.flash_fwd_by_kind`. None in a rehearsal (no peaks), on an
untraced run, where the kernel did not run, or for a configuration
without `layer_types`."""

from benchmark.harness.trace import kernel_cost, named

LABEL = "flash_fwd_by_kind"


def read(run):
    got = named.of_run(run) if run.peaks is not None else None
    needed = run.cell.kernel_cost(LABEL)
    if got is None or needed is None:
        return None
    roof = kernel_cost.roofline(got["kernels"], ("flash_fwd",),
                                lambda kernel: needed, run.cell.config,
                                run.peaks)
    if roof is None:
        return None
    run.extras.setdefault("roofline", {})[LABEL] = roof
    return roof["pct"]
