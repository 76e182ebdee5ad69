"""Device time a step of the operations under the scope
`grad_accumulate` (megatron_tpu/training/train_step.py one_micro: each
micro-batch's gradients raised to float32 and added to the float32
accumulator), over all micro-batches of the step: own time inside the
whole runs of the step program, over those runs, mean over the devices.
Part of `other` (no region holds it)."""

from benchmark.harness.trace import named


def read(run):
    return named.scope_ms(run, "grad_accumulate")
