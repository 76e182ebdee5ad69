"""What the compiled train step needs on one chip, by the compiler's own
account of the program that ran: arguments (state and batch) plus
temporaries plus the outputs that do not reuse an argument's room.
`memory_stats()["peak_bytes_in_use"]` (the result line's
`device.memory_peak_bytes`) leaves the temporaries out, so this is the
number that says how full the chip is."""


def read(run):
    if not run.step_memory_bytes:
        return None
    return sum(run.step_memory_bytes.values()) / 1e9
