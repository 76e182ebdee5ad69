"""Device time a step of the backward Pallas kernels, found by name: the
split pair `flash_bwd_dq` and `flash_bwd_dkv` (ops/pallas/
flash_template.py) and a fused `flash_bwd` that forms dq, dk and dv in one
call, whichever of them the program runs; inside the whole runs of the
step program, over those runs, mean over devices."""

from benchmark.harness.trace import named


def read(run):
    return named.kernel_ms(run, "flash_bwd_dq", "flash_bwd_dkv", "flash_bwd")
