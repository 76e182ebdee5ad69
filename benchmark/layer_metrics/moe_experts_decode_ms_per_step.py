"""Own device time of the held experts' two grouped products and the
activation between them (the scope `moe_experts`) a decode step, inside
the whole runs of `jit_decode_step`: every tick streams the held experts'
matrices for the few rows each expert was sent. None where no operation
carries the scope (a model without expert layers, a parent commit)."""

from benchmark.harness.trace import by_program


def read(run):
    return by_program.scope_ms(run, "jit_decode_step", "moe_experts")
