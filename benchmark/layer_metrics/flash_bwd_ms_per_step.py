"""Device time a step of the two backward Pallas kernels, `flash_bwd_dq`
and `flash_bwd_dkv` (ops/pallas/flash_template.py), found by name: inside
the whole runs of the step program, over those runs, mean over devices."""

from benchmark.harness.trace import named


def read(run):
    return named.kernel_ms(run, "flash_bwd_dq", "flash_bwd_dkv")
