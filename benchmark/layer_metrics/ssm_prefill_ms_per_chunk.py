"""Own device time of the state-space mixers (the scope `ssm_mixer`, the
kernel `ssm_scan` inside it) a prefill chunk, inside the whole runs of
`jit_chunk_step`. None where no operation carries the scope."""

from benchmark.harness.trace import by_program


def read(run):
    return by_program.scope_ms(run, "jit_chunk_step", "ssm_mixer")
