"""Device time a step of the operations under the scope `head_loss`
(models/language_model.py: the final norm, the head matmul and the
(chunked) cross-entropy, with the chunk's recomputed logits): own time
inside the whole runs of the step program, over those runs, mean over the
devices."""

from benchmark.harness.trace import named


def read(run):
    return named.region_ms(run, "head_loss")
