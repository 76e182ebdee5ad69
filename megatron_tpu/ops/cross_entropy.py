"""Cross-entropy over (possibly vocab-sharded) logits.

Replaces megatron/core/tensor_parallel/cross_entropy.py (175 LoC): the
reference computes vocab-parallel CE with three hand-placed all-reduces
(max, predicted-logit, sum-exp) plus a custom backward. Here the loss is a
plain fp32 log-softmax expression; when logits carry a vocab-sharded
PartitionSpec, the SPMD partitioner emits those same reductions — one jitted
function covers both the sharded and unsharded cases, label smoothing
included. The distributed argmax used by validation metrics
(cross_entropy.py:146-175) is jnp.argmax under the same sharding.

The training loss under a mesh with "tensor" > 1 is the exception: there
the head and the chunked cross-entropy state their own communication
(vocab_parallel_chunked_loss below), because the partitioner's choice cost
sixteen gathers of the whole hidden state a step on the chip.
"""

from __future__ import annotations

import functools
import math
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, get_abstract_mesh

from megatron_tpu.parallel.mesh import AXIS_CONTEXT, AXIS_TENSOR
from megatron_tpu.parallel.sharding import BATCH_AXES


def cross_entropy_loss(
    logits: jnp.ndarray,          # [B, S, V] (any float dtype; computed fp32)
    targets: jnp.ndarray,         # [B, S] int32
    loss_mask: Optional[jnp.ndarray] = None,  # [B, S] float weights
    label_smoothing: float = 0.0,
    z_loss: float = 0.0,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (mean_loss, per_token_loss).

    per_token_loss matches the reference's contract of returning the
    unreduced [B, S] loss tensor (gpt_model.py:18-42) so callers can apply
    instruction-tuning loss masks (finetune.py:153-166).

    z_loss regularizes the log-partition toward 0 (PaLM-style) — not in the
    reference; off by default.
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)                     # [B, S]
    # one-hot contraction instead of take_along_axis: gather-free, so the
    # SPMD partitioner handles a vocab-sharded logits axis as a plain
    # masked reduction (and XLA fuses the one-hot away)
    vocab_iota = jnp.arange(logits.shape[-1], dtype=jnp.int32)
    onehot = (targets[..., None].astype(jnp.int32) == vocab_iota)
    target_logit = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    loss = lse - target_logit
    if label_smoothing > 0.0:
        # smoothed CE: (1-eps)*nll + eps * mean over vocab of nll_v
        # == lse - [(1-eps)*target_logit + eps*mean(logits)]
        vocab = logits.shape[-1]
        eps = label_smoothing
        mean_logit = jnp.mean(logits, axis=-1)
        loss = lse - (1.0 - eps) * target_logit - eps * mean_logit
    if z_loss > 0.0:
        loss = loss + z_loss * jnp.square(lse)

    if loss_mask is not None:
        mask = loss_mask.astype(jnp.float32)
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        mean = jnp.sum(loss * mask) / denom
    else:
        mean = jnp.mean(loss)
    return mean, loss


def vocab_argmax(logits: jnp.ndarray) -> jnp.ndarray:
    """Predicted token ids; sharded-vocab-safe under GSPMD
    (ref: vocab_parallel_max_indices, cross_entropy.py:146-175)."""
    return jnp.argmax(logits, axis=-1)


# ---------------------------------------------------------------------------
# The head and the chunked cross-entropy under a mesh with "tensor" > 1
# ---------------------------------------------------------------------------


class HeadLossPlan(NamedTuple):
    """How vocab_parallel_chunked_loss maps onto the ambient mesh."""

    axes: Tuple[str, ...]        # every mesh axis (the shard_map is manual
    #                              over all of them: ops/attention.py)
    batch_axes: Tuple[str, ...]  # the batch dimension's axes
    context: Optional[str]       # the sequence dimension's outer axis
    tp: int                      # size of "tensor"
    gather: bool                 # sequence parallel: rows are gathered
    rows: int                    # rows of one slice before the gather


def head_loss_plan(batch: int, seq: int, vocab: int, chunk: int,
                   sequence_parallel: bool) -> Optional[HeadLossPlan]:
    """The plan for a [batch, seq, hidden] state and a head of `vocab`
    columns in chunks of `chunk` tokens, or None where the plain
    expression serves: no mesh, "tensor" of size 1, or a trace point
    inside somebody else's shard_map (the pipeline schedule is manual over
    "pipe" and owns the mapping there). Shapes that do not divide over the
    mesh also give None, said out loud at trace time."""
    mesh = get_abstract_mesh()
    if mesh is None or not mesh.shape or mesh.manual_axes:
        return None
    sizes = dict(mesh.shape)
    tp = sizes.get(AXIS_TENSOR, 1)
    if tp == 1:
        return None
    batch_axes = tuple(a for a in BATCH_AXES if a in sizes)
    nb = math.prod(sizes[a] for a in batch_axes)
    cp = sizes.get(AXIS_CONTEXT, 1)
    # a context shard's tokens go through in chunks of `chunk`; with
    # sequence parallelism each rank brings chunk / tp rows to a chunk
    per_ctx = seq // cp
    chunk = min(chunk, per_ctx)
    rows = chunk // tp if sequence_parallel else chunk
    why = (f"batch {batch} does not divide over {batch_axes}={nb}"
           if batch % nb else
           f"vocab {vocab} does not divide over {AXIS_TENSOR}={tp}"
           if vocab % tp else
           f"seq {seq} does not divide over {AXIS_CONTEXT}={cp} into "
           f"chunks of {chunk}" if seq % cp or per_ctx % chunk else
           f"chunk {chunk} does not divide over {AXIS_TENSOR}={tp}"
           if chunk % tp else None)
    if why:
        warnings.warn(
            f"head and loss under tensor parallelism: {why}; the "
            "partitioner places this call's collectives", stacklevel=3)
        return None
    return HeadLossPlan(tuple(mesh.axis_names), batch_axes,
                        AXIS_CONTEXT if AXIS_CONTEXT in sizes else None, tp,
                        sequence_parallel, rows)


def _local_logits(h, w, tied):
    return jnp.einsum("bsh,vh->bsv" if tied else "bsh,hv->bsv", h, w)


def _label_onehot(y, width):
    """[.., width] True where the label is this rank's column: a label in
    another rank's part of the vocabulary matches no column here."""
    first = jax.lax.axis_index(AXIS_TENSOR) * width
    return (y[..., None].astype(jnp.int32) - first
            == jnp.arange(width, dtype=jnp.int32))


def _slice_rows(h, j, plan):
    """Slice j of this rank's rows [b, s, H], and under sequence
    parallelism the same slice of every other rank's: [b, chunk, H]."""
    h_c = jax.lax.dynamic_slice_in_dim(h, j * plan.rows, plan.rows, axis=1)
    if plan.gather:
        h_c = jax.lax.all_gather(h_c, AXIS_TENSOR, axis=1, tiled=True)
    return h_c


def _slices(x, n):
    """[b, n * r, ...] -> [n, b, r, ...]"""
    b = x.shape[0]
    return jnp.moveaxis(x.reshape(b, n, -1, *x.shape[2:]), 1, 0)


def _gathered_slices(x, tp, n):
    """[b, tp * n * r] in sequence order -> [n, b, tp * r]: slice j as the
    gather over "tensor" of every rank's j-th slice assembles it."""
    b = x.shape[0]
    return x.reshape(b, tp, n, -1).transpose(2, 0, 1, 3).reshape(n, b, -1)


def _sequence_order(x, tp):
    """The inverse of _gathered_slices: [n, b, tp * r] -> [b, tp * n * r]."""
    n, b = x.shape[:2]
    return x.reshape(n, b, tp, -1).transpose(1, 2, 0, 3).reshape(b, -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _per_device_loss(h, w, y, tied, plan):
    return _per_device_fwd(h, w, y, tied, plan)[0]


def _per_device_fwd(h, w, y, tied, plan):
    """One device's part: h [b, s, H] its own rows (all of the context
    shard's without sequence parallelism), w its columns of the head, y
    [b, S / cp] the labels of the context shard. Returns the per-token
    loss of this rank's S / (cp * tp) rows, float32."""
    n = h.shape[1] // plan.rows
    y_sl = (_gathered_slices(y, plan.tp, n) if plan.gather
            else _slices(y, n))

    def one(_, xs):
        j, y_c = xs
        logits = _local_logits(_slice_rows(h, j, plan), w, tied
                               ).astype(jnp.float32)
        top = jax.lax.pmax(jnp.max(logits, axis=-1), AXIS_TENSOR)
        sumexp = jax.lax.psum(
            jnp.sum(jnp.exp(logits - top[..., None]), axis=-1), AXIS_TENSOR)
        picked = jax.lax.psum(
            jnp.sum(jnp.where(_label_onehot(y_c, logits.shape[-1]), logits,
                              0.0), axis=-1), AXIS_TENSOR)
        lse = top + jnp.log(sumexp)
        return None, (lse - picked, lse)

    _, (loss, lse) = jax.lax.scan(one, None, (jnp.arange(n), y_sl))
    # of the context shard's tokens this rank keeps its own S / tp rows
    # (every output names "tensor": the cotangent arrives whole)
    loss = (_sequence_order(loss, plan.tp) if plan.gather
            else jnp.moveaxis(loss, 0, 1).reshape(y.shape))
    own = y.shape[1] // plan.tp
    loss = jax.lax.dynamic_slice_in_dim(
        loss, jax.lax.axis_index(AXIS_TENSOR) * own, own, axis=1)
    return loss, (h, w, y_sl, lse)


def _per_device_bwd(tied, plan, res, g):
    """The chunk's logits once more from the saved hidden state and the
    saved log-sum-exp (no reduction is repeated); d hidden returns to the
    rank that owns the rows; the head's gradient is summed over the chunks
    here, in float32, and leaves as this device's partial sum: the
    shard_map's transpose adds it up over the axes the head is replicated
    on (the data-parallel ones), once."""
    h, w, y_sl, lse = res
    n = h.shape[1] // plan.rows
    g = jax.lax.all_gather(g, AXIS_TENSOR, axis=1, tiled=True)
    g_sl = _gathered_slices(g, plan.tp, n) if plan.gather else _slices(g, n)

    def one(carry, xs):
        dh_all, dw = carry
        j, y_c, lse_c, g_c = xs
        h_c = _slice_rows(h, j, plan)
        # the name jax.checkpoint gives what it computes again: a trace
        # reads the step's recomputation by it
        with jax.named_scope("rematted_computation"):
            logits = _local_logits(h_c, w, tied).astype(jnp.float32)
            p = jnp.exp(logits - lse_c[..., None])
        dlogits = ((p - _label_onehot(y_c, logits.shape[-1]))
                   * g_c[..., None]).astype(h.dtype)
        dh = jnp.einsum("bsv,vh->bsh" if tied else "bsv,hv->bsh", dlogits, w)
        if plan.gather:
            dh = jax.lax.psum_scatter(dh, AXIS_TENSOR, scatter_dimension=1,
                                      tiled=True)
        dh_all = jax.lax.dynamic_update_slice_in_dim(
            dh_all, dh, j * plan.rows, axis=1)
        dw = dw + jnp.einsum("bsv,bsh->vh" if tied else "bsh,bsv->hv",
                             *((dlogits, h_c) if tied else (h_c, dlogits)),
                             preferred_element_type=jnp.float32)
        return (dh_all, dw), None

    (dh, dw), _ = jax.lax.scan(
        one, (jnp.zeros_like(h), jnp.zeros(w.shape, jnp.float32)),
        (jnp.arange(n), y_sl, lse, g_sl))
    # the float32 sum is rounded to the head's dtype before d hidden goes
    # on into the layers: left to itself the chip's scheduler put the
    # rounding beside the head gradient's reduction, late in the layers'
    # backward pass, and the 4 H V / tp bytes stayed alive through the
    # step's peak (0.52 GB at the four-chip cell's size)
    dh, dw = jax.lax.optimization_barrier((dh, dw.astype(w.dtype)))
    return dh, dw, None


_per_device_loss.defvjp(_per_device_fwd, _per_device_bwd)


def vocab_parallel_chunked_loss(hidden: jnp.ndarray, w: jnp.ndarray,
                                labels: jnp.ndarray, tied: bool,
                                plan: HeadLossPlan) -> jnp.ndarray:
    """Per-token cross-entropy [B, S] (float32) of the head over `hidden`
    [B, S, H], as the vocabulary-parallel loss that it is: every rank of
    "tensor" holds V / tp columns of the head (w: [V, H] tied, [H, V]
    untied) and, under sequence parallelism, S / tp rows of `hidden`.

    One shard_map over every mesh axis, so that each collective stands
    where it is written (ref: vocab_parallel_cross_entropy,
    cross_entropy.py:14-127, and the sequence-parallel gather in front of
    parallel_lm_logits). The chunk loop runs over this rank's own rows.
    For each slice: all_gather over "tensor" of that one slice, the local
    logits, and the three reductions of the cross-entropy (row maximum,
    sum of exponentials, the label's logit) over "tensor" on [B, C]
    float32. Backward (custom_vjp): the logits recomputed per slice as in
    the plain chunked loss, d hidden back to its rows by one psum_scatter
    a slice, the head's gradient accumulated over the slices in float32
    and reduced across the data-parallel replicas once, after the loop.
    Without sequence parallelism the gather is an identity and d hidden
    is all-reduced by the shard_map's transpose."""
    batch = plan.batch_axes or None
    own = (plan.context, AXIS_TENSOR) if plan.context else AXIS_TENSOR
    return jax.shard_map(
        lambda h, w_, y: _per_device_loss(h, w_, y, tied, plan),
        in_specs=(P(batch, own if plan.gather else plan.context, None),
                  P(AXIS_TENSOR, None) if tied else P(None, AXIS_TENSOR),
                  P(batch, plan.context)),
        out_specs=P(batch, own), axis_names=set(plan.axes), check_vma=False,
    )(hidden, w, labels)
