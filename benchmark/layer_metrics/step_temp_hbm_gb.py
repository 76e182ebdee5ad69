"""Temporaries of the compiled train step on one chip, by the compiler's
account of the program that ran: `temp_bytes` of the journal's
`step_program` record (see step_hbm_gb.py). The part of step_hbm_gb that
is activations and workspace and not state, so the part that recompute,
chunking and kernel changes move."""

from benchmark.harness.trace import named


def read(run):
    program = named.step_program(run)
    return None if program is None else program["temp_bytes"] / 1e9
