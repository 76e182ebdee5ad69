"""Device time a step of the expert matmuls and the activation between
them (megatron_tpu/ops/moe.py moe_block_dropless, scope `moe_experts`),
forward and backward, every micro-batch and layer of the step: own time
inside the whole runs of the step program, over those runs, mean over the
devices.

Two names are read. The activation and the glue sit under the program's
scope `moe_experts`. The grouped matmuls themselves do not: XLA's TPU
compiler turns each `lax.ragged_dot` into a Mosaic kernel of its own,
whose instruction carries the compiler's name `ragged-dot-none` (and
`ragged-dot-metadata` for the group offsets) in place of the program's
name stack, so the trace books it under no region of the program
(`other`, not `mlp`). This program calls `ragged_dot` nowhere else. None
on a program without the named regions."""

from benchmark.harness.trace import named

NAMES = ("moe_experts", "ragged-dot-none", "ragged-dot-metadata")


def read(run):
    parts = [named.scope_ms(run, name) for name in NAMES]
    return None if None in parts else sum(parts)
