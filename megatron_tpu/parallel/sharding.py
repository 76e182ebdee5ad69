"""Sharding rules and helpers.

This module is where the reference's explicit tensor-parallel machinery
(megatron/core/tensor_parallel/layers.py ColumnParallelLinear /
RowParallelLinear / VocabParallelEmbedding and the autograd collective
mappings in mappings.py:253-278) collapses to data: a PartitionSpec per
parameter plus sharding constraints on activations. XLA's SPMD partitioner
inserts the all-reduces / all-gathers / reduce-scatters those 980 LoC
hand-write, and its latency-hiding scheduler overlaps them with the GEMMs
(replacing LinearWithGradAccumulationAndAsyncCommunication, layers.py:213-317,
and the CUDA_DEVICE_MAX_CONNECTIONS=1 ordering hack).

Conventions:
  * "column parallel" (output-dim split)  -> last axis "tensor"
  * "row parallel" (input-dim split)      -> contracting axis "tensor"
  * vocab-parallel embedding / lm head    -> vocab axis "tensor"
  * stacked layer params have a leading layer axis sharded over "pipe"
  * sequence parallelism: residual-stream seq axis over ("context","tensor")
    (ref: layers.py:225-236,285-296,691-692 scatter/gather at TP block edges)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_tpu.parallel.mesh import (
    AXIS_CONTEXT,
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_PIPE,
    AXIS_TENSOR,
    MeshRuntime,
)

# the batch dimension shards over data AND expert (EP is a sub-axis of DP
# for everything outside MoE blocks — see mesh.py BATCH_SPEC)
BATCH_AXES = (AXIS_DATA, AXIS_EXPERT)


def batch_spec() -> P:
    """[batch, seq] integer token arrays."""
    return P(BATCH_AXES, AXIS_CONTEXT)


def activation_spec(sequence_parallel: bool) -> P:
    """Residual-stream activations [batch, seq, hidden].

    With sequence_parallel the sequence axis is split over context AND
    tensor outside the matmul blocks — the TPU expression of Korthikanti
    SP: XLA materializes the all-gather entering a column-parallel matmul
    and the reduce-scatter leaving a row-parallel one.
    """
    if sequence_parallel:
        return P(BATCH_AXES, (AXIS_CONTEXT, AXIS_TENSOR), None)
    return P(BATCH_AXES, AXIS_CONTEXT, None)


def _bound_axis_names():
    """Axis names currently bound by an enclosing shard_map/*map body —
    i.e. the MANUAL axes at this trace point. Private-API probe (no public
    accessor on jax 0.4.37); fail-soft to 'none bound'."""
    try:
        # jaxlint: disable=internal-api - no public accessor on jax
        # 0.4.37; any drift lands in the except => 'none bound'
        from jax._src import core as _core

        return set(_core.unsafe_get_axis_names())
    except Exception:  # noqa: BLE001 - jax-internals drift => assume auto
        return set()


def constrain(x: jax.Array, spec: P) -> jax.Array:
    """Apply a sharding constraint inside jit (requires mesh context).

    Inside a shard_map body a constraint over a MANUAL axis is meaningless
    — nothing is left for GSPMD to place on it — and jax rejects it at
    lowering (too late for a try/except here). Axes outside the
    shard_map's axis_names stay automatic and the constraint matters
    there, so it is skipped ONLY when one of its axes is actually bound
    manual at this trace point."""
    spec_axes = {a for part in spec if part is not None
                 for a in ((part,) if isinstance(part, str) else part)}
    if spec_axes & _bound_axis_names():
        return x
    return jax.lax.with_sharding_constraint(x, spec)


@dataclasses.dataclass(frozen=True)
class ActivationSharder:
    """The trainer's `sharder(x, role)` (models/transformer.py Sharder):
    the residual stream is held to activation_spec at every block edge;
    every other role passes through. The head and loss read
    `sequence_parallel` off it to know which rows of the hidden state a
    rank of "tensor" owns (ops/cross_entropy.py head_loss_plan)."""

    sequence_parallel: bool = False

    def __call__(self, x: jax.Array, role: str) -> jax.Array:
        if role == "residual":
            return constrain(x, activation_spec(self.sequence_parallel))
        return x


def tree_shardings(runtime: MeshRuntime, spec_tree: Any) -> Any:
    """PartitionSpec pytree -> NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(runtime.mesh, s),
        spec_tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def shard_tree(runtime: MeshRuntime, tree: Any, spec_tree: Any) -> Any:
    """Device_put a pytree according to a PartitionSpec tree."""
    shardings = tree_shardings(runtime, spec_tree)
    return jax.tree.map(jax.device_put, tree, shardings)


# ---------------------------------------------------------------------------
# ZeRO-1 distributed optimizer sharding
# ---------------------------------------------------------------------------


def zero1_spec(spec: P, shape: tuple, dp: int, ep: int = 1) -> P:
    """Extend a parameter spec so optimizer state also shards over "data".

    TPU-native ZeRO-1 (ref: megatron/optimizer/distrib_optimizer.py, 700 LoC
    of manual grad-buffer shard bookkeeping + reduce-scatter/all-gather):
    here it is only a *placement* decision — optimizer moments and fp32
    master params take the param's spec with the data axis added onto the
    first dimension that is unsharded and divisible by dp. XLA then emits
    reduce-scattered gradients into the shard and all-gathers updated params,
    which is exactly the reference's comm pattern
    (distrib_optimizer.py:522-612) derived instead of hand-written.
    """
    if dp <= 1:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))

    def has(axis):
        return any(e == axis or (isinstance(e, tuple) and axis in e)
                   for e in entries)

    if has(AXIS_DATA):
        # already data-sharded: the state is distributed over dp as-is;
        # adding the axis again would be invalid
        return spec
    # `dp` is the TOTAL batch degree (data x expert). Expert-parallel MoE
    # weights already consume the expert axis on their expert dim, so
    # their state shards over bare "data" (degree dp/ep); everything else
    # shards over the combined (data, expert) pair.
    if has(AXIS_EXPERT):
        add, degree = AXIS_DATA, dp // ep
    else:
        add, degree = BATCH_AXES, dp
    if degree <= 1:
        return spec
    for i, (axes, dim) in enumerate(zip(entries, shape)):
        if axes is None and dim % degree == 0:
            entries[i] = add
            return P(*entries)
    return spec  # nothing divisible — leave replicated over data


def zero1_spec_tree(spec_tree: Any, params: Any, dp: int, ep: int = 1) -> Any:
    """`params` may be a pytree of arrays or ShapeDtypeStructs (same
    structure as spec_tree)."""
    return jax.tree.map(
        lambda s, p: zero1_spec(s, tuple(p.shape), dp, ep),
        spec_tree,
        params,
        is_leaf=lambda s: isinstance(s, P),
    )
