"""Device time a step of the operations under the scope `optimizer`
(training/train_step.py: gradient norm and clipping, Adam, the fp32
master's cast back to bf16, the ZeRO-1 gather): own time inside the whole
runs of the step program, over those runs, mean over the devices."""

from benchmark.harness.trace import named


def read(run):
    return named.region_ms(run, "optimizer")
