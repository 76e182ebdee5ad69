"""Share of the roofline the `flash_fwd` kernel's calls reach: the least
time the chip could take for what causal windowed attention needs over the
calls' own shapes (kernel_costs/flash_fwd.py; peaks from
benchmark/peaks.json; the window from the cell's configuration) over the
time the trace shows for them. The bound that applies, compute or memory,
goes to the line's `extras.roofline`."""

from benchmark.harness.trace import named


def read(run):
    return named.roofline_pct(run, "flash_fwd", "flash_fwd")
