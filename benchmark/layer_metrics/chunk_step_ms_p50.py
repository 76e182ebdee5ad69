"""Median duration of the engine's prefill chunk on the device: the whole
runs of `jit_chunk_step` on the trace's module line (one chunk of one
prompt; `named.per_run` reads the decode step, which takes most of a
served cell's trace: harness/trace/by_program.py selects this one by
name)."""

from benchmark.harness.trace import by_program


def read(run):
    return by_program.run_ms_p50(run, "jit_chunk_step")
