"""What the compiled train step needs on one chip, by the compiler's own
account of the program that ran: arguments (state and batch) plus
temporaries plus the outputs that do not reuse an argument's room, from
the `step_program` record the trainer writes to its journal after a run
that opened a trace window (training/pretrain.py `_journal_step_program`).
`memory_stats()["peak_bytes_in_use"]` (the result line's
`device.memory_peak_bytes`) leaves the temporaries out, so this is the
number that says how full the chip is."""

from benchmark.harness.trace import named


def read(run):
    program = named.step_program(run)
    if program is None:
        return None
    return (program["argument_bytes"] + program["temp_bytes"]
            + program["output_bytes"] - program["alias_bytes"]) / 1e9
