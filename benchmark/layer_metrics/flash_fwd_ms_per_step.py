"""Device time a step of the `flash_fwd` Pallas kernel
(ops/pallas/flash_template.py), found by its name in the operation's name
stack: the forward's calls and the calls that recompute it in the
backward pass, inside the whole runs of the step program, over those
runs, mean over devices."""

from benchmark.harness.trace import named


def read(run):
    return named.kernel_ms(run, "flash_fwd")
