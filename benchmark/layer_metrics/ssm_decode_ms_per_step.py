"""Own device time of the state-space mixers (the scope `ssm_mixer`: in
and out projections, convolution, dt/B/C, the recurrence's one step) a
decode step, inside the whole runs of `jit_decode_step`. None where no
operation carries the scope (a model without such layers)."""

from benchmark.harness.trace import by_program


def read(run):
    return by_program.scope_ms(run, "jit_decode_step", "ssm_mixer")
