"""Median duration of the engine's decode step on the device: the whole
runs of `jit_decode_step` on the trace's module line (one token for every
slot that decodes: all the weights once, the state-space layers' state
read and written, the attention layers' pages read)."""

from benchmark.harness.trace import by_program


def read(run):
    return by_program.run_ms_p50(run, "jit_decode_step")
