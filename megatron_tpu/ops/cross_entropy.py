"""Cross-entropy over (possibly vocab-sharded) logits.

Replaces megatron/core/tensor_parallel/cross_entropy.py (175 LoC): the
reference computes vocab-parallel CE with three hand-placed all-reduces
(max, predicted-logit, sum-exp) plus a custom backward. Here the loss is a
plain fp32 log-softmax expression; when logits carry a vocab-sharded
PartitionSpec, the SPMD partitioner emits those same reductions — one jitted
function covers both the sharded and unsharded cases, label smoothing
included. The distributed argmax used by validation metrics
(cross_entropy.py:146-175) is jnp.argmax under the same sharding.

The chunked training loss is the exception (chunked_head_loss below): the
head and the cross-entropy over chunks of the sequence as one function
with a gradient rule of its own, which forms each chunk's gradient while
the chunk's logits are there, and which under a mesh states its own
communication, because the partitioner's choice cost sixteen gathers of
the whole hidden state a step on the chip.
"""

from __future__ import annotations

import functools
import math
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.custom_derivatives import SymbolicZero
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
# The head and the chunked cross-entropy: one loop, for every mesh
# ---------------------------------------------------------------------------


class HeadLossPlan(NamedTuple):
    """How chunked_head_loss maps onto the ambient mesh."""

    axes: Tuple[str, ...]        # every mesh axis (the shard_map is manual
    #                              over all of them: ops/attention.py);
    #                              empty: no shard_map, the body runs as it is
    batch_axes: Tuple[str, ...]  # the batch dimension's axes
    context: Optional[str]       # the sequence dimension's outer axis
    tp: int                      # size of "tensor"
    gather: bool                 # sequence parallel: rows are gathered
    rows: int                    # rows of one slice before the gather
    everyone: Tuple[str, ...]    # every axis of more than one device; all
    #                              but "tensor" hold copies of the head


def head_loss_plan(batch: int, seq: int, vocab: int, chunk: int,
                   sequence_parallel: bool) -> HeadLossPlan:
    """The plan for a [batch, seq, hidden] state and a head of `vocab`
    columns in chunks of `chunk` tokens. With no mesh, or at a trace point
    inside somebody else's shard_map (the pipeline schedule is manual over
    "pipe" and owns the mapping there), the plan has no axes: the loop
    runs on the arrays as they are and names no collective. Shapes that do
    not divide over the mesh give that plan too, said out loud at trace
    time: the partitioner then places the call's collectives."""
    direct = HeadLossPlan((), (), None, 1, False, min(chunk, seq), ())
    mesh = get_abstract_mesh()
    if mesh is None or not mesh.shape or mesh.manual_axes:
        return direct
    sizes = dict(mesh.shape)
    tp = sizes.get(AXIS_TENSOR, 1)
    batch_axes = tuple(a for a in BATCH_AXES if a in sizes)
    nb = math.prod(sizes[a] for a in batch_axes)
    cp = sizes.get(AXIS_CONTEXT, 1)
    # a context shard's tokens go through in chunks of `chunk`; with
    # sequence parallelism each rank brings chunk / tp rows to a chunk
    per_ctx = seq // cp
    chunk = min(chunk, per_ctx)
    gather = sequence_parallel and tp > 1
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
            f"head and loss under a mesh: {why}; the partitioner places "
            "this call's collectives", stacklevel=3)
        return direct
    return HeadLossPlan(tuple(mesh.axis_names), batch_axes,
                        AXIS_CONTEXT if AXIS_CONTEXT in sizes else None, tp,
                        gather, chunk // tp if gather else chunk,
                        tuple(a for a in mesh.axis_names if sizes[a] > 1))


def _over_tensor(reduce, x, plan):
    """A reduction over "tensor" where the vocabulary is cut over it."""
    return reduce(x, AXIS_TENSOR) if plan.tp > 1 else x


def _tensor_rank(plan):
    return jax.lax.axis_index(AXIS_TENSOR) if plan.tp > 1 else 0


def _label_onehot(y, width, plan):
    """[.., width] True where the label is this rank's column: a label in
    another rank's part of the vocabulary matches no column here."""
    return (y[..., None].astype(jnp.int32) - _tensor_rank(plan) * width
            == jnp.arange(width, dtype=jnp.int32))


def _slices(x, n, plan):
    """[b, S] of a context shard in sequence order -> [n, b, chunk]: slice
    j as the loop meets it. Under sequence parallelism that is the gather
    over "tensor" of every rank's j-th slice of its own rows."""
    b = x.shape[0]
    if plan.gather:
        return (x.reshape(b, plan.tp, n, -1).transpose(2, 0, 1, 3)
                .reshape(n, b, -1))
    return jnp.moveaxis(x.reshape(b, n, -1), 1, 0)


def _sequence_order(x, plan):
    """The inverse of _slices: [n, b, chunk] -> [b, S]."""
    n, b = x.shape[:2]
    if plan.gather:
        return (x.reshape(n, b, plan.tp, -1).transpose(1, 2, 0, 3)
                .reshape(b, -1))
    return jnp.moveaxis(x, 0, 1).reshape(b, -1)


def _chunk_loop(h, w, y, wt, tied, plan, with_grads):
    """One device's part of the head and the loss, chunk by chunk: h
    [b, s, H] its own rows (all of the context shard's without sequence
    parallelism), w its columns of the head, y and wt [b, S / cp] the
    labels and the weights of the context shard's tokens.

    A chunk: the rows (gathered over "tensor" under sequence parallelism),
    the local logits, and in float32 the row maximum, the sum of
    exponentials and the label's logit, each reduced over "tensor" on
    [b, chunk] where the vocabulary is cut. With `with_grads` the same
    iteration, which still holds the logits, goes on to the chunk's
    gradient: d logits = (softmax - onehot) * weight rounded to the head's
    dtype, d hidden = d logits x head^T written to its rows (back to the
    rank that owns them by one psum_scatter), and the head's gradient
    h^T x d logits added into the sum that the loop carries (float32 and
    rounded once after the loop under tensor parallelism and for a
    float16 head, see below). The logits are computed once and never
    stored.

    Returns the weighted sum over every device's tokens, this rank's own
    S / (cp * tp) per-token losses (float32), and with `with_grads` the
    gradients of the weighted sum in d hidden and in the head (float32
    for a float16 head), summed over the devices that hold copies of
    it."""
    n = h.shape[1] // plan.rows
    width = w.shape[0] if tied else w.shape[1]

    def one(carry, xs):
        j, h_c, y_c, wt_c = xs
        if plan.gather:
            h_c = jax.lax.all_gather(h_c, AXIS_TENSOR, axis=1, tiled=True)
        logits = jnp.einsum("bsh,vh->bsv" if tied else "bsh,hv->bsv", h_c, w
                            ).astype(jnp.float32)
        mine = _label_onehot(y_c, width, plan)
        top = _over_tensor(jax.lax.pmax, jnp.max(logits, axis=-1), plan)
        sumexp = _over_tensor(
            jax.lax.psum,
            jnp.sum(jnp.exp(logits - top[..., None]), axis=-1), plan)
        picked = _over_tensor(
            jax.lax.psum, jnp.sum(jnp.where(mine, logits, 0.0), axis=-1),
            plan)
        lse = top + jnp.log(sumexp)
        if not with_grads:
            return carry, lse - picked
        dh_all, dw = carry
        dlogits = ((jnp.exp(logits - lse[..., None]) - mine)
                   * wt_c[..., None]).astype(h.dtype)
        dh = jnp.einsum("bsv,vh->bsh" if tied else "bsv,hv->bsh", dlogits, w)
        if plan.gather:
            dh = jax.lax.psum_scatter(dh, AXIS_TENSOR, scatter_dimension=1,
                                      tiled=True)
        dh_all = jax.lax.dynamic_update_slice_in_dim(
            dh_all, dh, j * plan.rows, axis=1)
        dw = dw + jnp.einsum("bsv,bsh->vh" if tied else "bsh,bsv->hv",
                             *((dlogits, h_c) if tied else (h_c, dlogits)),
                             preferred_element_type=jnp.float32
                             ).astype(dw.dtype)
        return (dh_all, dw), lse - picked

    # the head gradient's sum over the chunks. Under tensor parallelism
    # it is float32 and rounded once after the loop. Without, it is
    # carried in the head's dtype, each chunk's product rounded as it is
    # added: what autodiff of a scan over the chunks does, and what this
    # path did before it had a rule of its own. The product reads and
    # writes its whole sum in every iteration, 8 H V bytes in float32
    # against 2 T H V operations for a chunk's T tokens, and one
    # sequence's chunk of 512 is bound by those bytes (measured on a v5e:
    # 12.7 ms a pass where bf16 takes 7.2, and 6 H V bytes more at the
    # step's peak).
    # A float16 head's sum is float32 on every mesh and stays so, through
    # the sum over the replicas and as a residual, until the backward
    # rule has multiplied it by the cotangent: it is a sum over all of a
    # device's tokens at the magnitude of their weights, which passes
    # float16's 65,504 at a few ten thousand tokens whatever the loss
    # scale is, where the gradient itself (times scale / sum of the
    # weights) does not
    keep = w.dtype == jnp.float16
    sum_dtype = jnp.float32 if plan.tp > 1 or keep else w.dtype
    carry = ((jnp.zeros_like(h), jnp.zeros(w.shape, sum_dtype))
             if with_grads else None)
    # this rank's rows reach the loop already cut, as the scan's own
    # slices: cut out of `h` inside the loop, the layout that the head
    # gradient's product likes best went back up into the layer in front
    # (eight copies a micro-batch in the MoE block's combine, OLMoE cell)
    rows = jnp.moveaxis(h.reshape(h.shape[0], n, plan.rows, -1), 1, 0)
    carry, loss = jax.lax.scan(
        one, carry,
        (jnp.arange(n), rows, _slices(y, n, plan), _slices(wt, n, plan)))
    # of the context shard's tokens this rank keeps its own S / tp rows
    own = y.shape[1] // plan.tp
    first = _tensor_rank(plan) * own
    loss = jax.lax.dynamic_slice_in_dim(_sequence_order(loss, plan), first,
                                        own, axis=1)
    total = jnp.sum(
        loss * jax.lax.dynamic_slice_in_dim(wt, first, own, axis=1))
    if plan.everyone:
        total = jax.lax.psum(total, plan.everyone)
    if not with_grads:
        return total, loss
    dh, dw = carry
    if plan.tp > 1 and not plan.gather:
        # every rank multiplied its own columns into all of the rows
        dh = jax.lax.psum(dh, AXIS_TENSOR)
    if not keep:
        # a float32 sum is rounded to the head's dtype before d hidden
        # goes on into the layers: left to itself the chip's scheduler
        # put the rounding beside the head gradient's reduction, late in
        # the layers' backward pass, and the 4 H V / tp bytes stayed alive
        # through the step's peak (0.52 GB at the four-chip cell's size)
        dh, dw = jax.lax.optimization_barrier((dh, dw.astype(w.dtype)))
    replicas = tuple(a for a in plan.everyone if a != AXIS_TENSOR)
    if replicas:
        # every device that holds a copy of the head brings its tokens'
        # part: one sum, after the loop
        dw = jax.lax.psum(dw, replicas)
    return total, loss, dh, dw


def _run_chunk_loop(hidden, w, labels, weights, tied, plan, with_grads):
    """_chunk_loop on the arrays as they are, or under a mesh inside one
    shard_map over every mesh axis, so that each collective stands where
    it is written (ref: vocab_parallel_cross_entropy, cross_entropy.py:
    14-127, and the sequence-parallel gather in front of
    parallel_lm_logits). Nothing differentiates through it (the gradient
    rule below calls it), so nothing is left to the shard_map's
    transpose."""
    loop = functools.partial(_chunk_loop, tied=tied, plan=plan,
                             with_grads=with_grads)
    if not plan.axes:
        return loop(hidden, w, labels, weights)
    batch = plan.batch_axes or None
    own = (plan.context, AXIS_TENSOR) if plan.context else AXIS_TENSOR
    rows = P(batch, own if plan.gather else plan.context, None)
    head = P(AXIS_TENSOR, None) if tied else P(None, AXIS_TENSOR)
    tokens = P(batch, plan.context)
    return jax.shard_map(
        loop, in_specs=(rows, head, tokens, tokens),
        out_specs=(P(), P(batch, own)) + ((rows, head) if with_grads else ()),
        axis_names=set(plan.axes), check_vma=False,
    )(hidden, w, labels, weights)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _head_loss(hidden, w, labels, weights, tied, plan):
    return _run_chunk_loop(hidden, w, labels, weights, tied, plan, False)


def _head_loss_fwd(hidden, w, labels, weights, tied, plan):
    total, loss, dh, dw = _run_chunk_loop(
        hidden.value, w.value, labels.value, weights.value, tied, plan, True)
    return (total, loss), (dh, dw, loss)


def _head_loss_bwd(tied, plan, res, cts):
    """Both gradients were formed beside the logits; what is left is the
    one scalar they are the gradients of."""
    dh, dw, loss = res
    g, g_tokens = cts
    if not isinstance(g_tokens, SymbolicZero):
        raise TypeError(
            "chunked_head_loss: the per-token losses are for reporting and "
            "carry no gradient; differentiate the weighted sum, with the "
            "weights passed in")
    if isinstance(g, SymbolicZero):
        return None, None, None, None
    g = g.astype(jnp.float32)
    # the head's dtype is the hidden state's; its sum may still be float32
    return ((g * dh).astype(dh.dtype), (g * dw).astype(dh.dtype), None,
            g * loss)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd, symbolic_zeros=True)


def chunked_head_loss(hidden: jnp.ndarray, w: jnp.ndarray,
                      labels: jnp.ndarray, weights: Optional[jnp.ndarray],
                      tied: bool, chunk: int, sequence_parallel: bool = False,
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum over tokens of weight x cross-entropy, per-token cross-entropy
    [B, S] float32) of the head over `hidden` [B, S, H], in chunks of
    `chunk` tokens: the [B, S, V] logits never exist, and a chunk's logits
    are computed once a step.

    w is the head as the model keeps it, [V, H] tied, [H, V] untied, in
    the dtype of `hidden`. `weights` [B, S] (None: every token 1) are the
    caller's mask; a normaliser such as 1 / sum(mask) stays with the
    caller, on the scalar. A float16 run with a loss scale needs that:
    the scale is not known yet when the gradients are formed, so d logits
    is rounded at the magnitude of the mask, no smaller than with the
    scale over the mask's sum, and the head gradient's sum over the
    tokens, which at that magnitude passes float16's range, is kept in
    float32 until the cotangent has multiplied it.

    Evaluated, the loop computes the losses alone. Differentiated, its
    forward rule forms d logits, d hidden and the head's gradient in the
    iteration that holds the chunk's logits (_chunk_loop), and the
    backward rule multiplies the two gradients by the cotangent of the
    weighted sum. The per-token losses are for reporting: differentiating
    them raises. Where memory a call matters more than a pass over the
    head (the pipeline schedule's last stage, a tick), wrap the call in
    jax.checkpoint: it then keeps `hidden` alone and runs the loop twice.

    Under a mesh it is the vocabulary-parallel loss that it is: every rank
    of "tensor" holds V / tp columns of the head and, with
    `sequence_parallel`, S / tp rows of `hidden`; head_loss_plan says how
    the arrays lie, and which tokens share a chunk changes no number."""
    b, s, _ = hidden.shape
    vocab = w.shape[0] if tied else w.shape[1]
    plan = head_loss_plan(b, s, vocab, chunk, sequence_parallel)
    weights = (jnp.ones((b, s), jnp.float32) if weights is None
               else weights.astype(jnp.float32))
    return _head_loss(hidden, w, labels, weights, tied, plan)
