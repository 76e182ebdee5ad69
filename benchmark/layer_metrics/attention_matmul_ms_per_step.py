"""Device time a step of the matmuls under the scope `attention`: the
operations of class `matmul` (harness/trace/classes.py: the profiler's
`hlo_category` "convolution fusion", a bare convolution or dot) in the
region: the Q, K, V and output projections, forward and both backward
products, with whatever the compiler fused onto them; own time inside the
whole runs of the step program, over those runs, mean of devices. The
flash kernels are class `kernel`, not this."""

from benchmark.harness.trace import classes


def read(run):
    return classes.region_class_ms(run, "attention", classes.MATMUL)
