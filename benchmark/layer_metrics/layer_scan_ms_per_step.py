"""Device time a step of the layers' scan itself: the operations under
the scope `layer_stack` (megatron_tpu/models/language_model.py
scan_with_remat) and under no region: slicing a layer's weights out of
the stacked parameters, stacking what the backward pass saved, the loop's
own bookkeeping, and what GSPMD hangs on them. Part of
`other_ms_per_step`. None on a program that does not name the scan."""

from benchmark.harness.trace import classes


def read(run):
    return classes.outside_ms(run, "layer_stack")
