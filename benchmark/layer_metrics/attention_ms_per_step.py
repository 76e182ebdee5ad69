"""Device time a step of the operations under the scope `attention`
(models/transformer.py block_forward: the norm, QKV, rotary, the flash
kernels, the output projection, dropout and the residual add, forward,
backward and recomputed, with the TP/SP collectives GSPMD hangs on them):
their own time inside the runs of the step program that the trace holds
whole, over those runs, mean over the devices."""

from benchmark.harness.trace import named


def read(run):
    return named.region_ms(run, "attention")
