"""Rotary position embeddings (RoPE).

Equivalent of megatron/model/positional_embeddings.py (51 LoC): frequency
precompute with linear position-interpolation scaling (--rope_scaling_factor)
and configurable theta (CodeLlama), applied to q/k with arbitrary —
possibly non-monotonic — position ids (packed instruction data,
positional_embeddings.py apply_rotary_emb position_ids gather).

Convention: rotate-half (HF style) rather than the reference's interleaved
complex-pair layout. The reference must permute HF QKV weights into its
interleaved layout on import (weights_conversion/utils/permute_qkv.py); using
rotate-half natively makes HF weights load without permutation — one less
lossy transform, same math.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

SAVED_ROTATED = "rotary_rotated"


def precompute_rope(
    head_dim: int,
    max_positions: int,
    theta: float = 10000.0,
    scaling_factor: float = 1.0,
    dtype=jnp.float32,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (cos, sin), each [max_positions, head_dim].

    scaling_factor > 1 linearly compresses positions (position
    interpolation), matching --rope_scaling_factor semantics
    (ref: positional_embeddings.py:10-12 divides t by the factor).
    """
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_positions, dtype=jnp.float32) / scaling_factor
    freqs = jnp.outer(t, inv_freq)  # [P, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [P, D]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max_positions: int, beta_fast: float,
                  beta_slow: float) -> jnp.ndarray:
    """[head_dim / 2] YaRN frequencies (arXiv:2309.00071 as Hugging Face's
    `_compute_yarn_parameters` computes them, its `truncate` default): pair
    i of a head turns original_max_positions * theta^(-2i/d) / 2 pi times
    over the original context. Pairs up to the one that turns beta_fast
    times (rounded down) keep their frequency, pairs from the one that
    turns beta_slow times (rounded up) have it divided by `factor`
    (interpolated positions), and a linear ramp over the pairs between
    blends the two."""
    half = head_dim // 2

    def pair_that_turns(times: float) -> float:
        return (head_dim * math.log(original_max_positions
                                    / (times * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_that_turns(beta_fast)), 0)
    high = min(math.ceil(pair_that_turns(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    extrapolated = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                               dtype=jnp.float32) / head_dim))
    return extrapolated / factor * ramp + extrapolated * (1.0 - ramp)


def _pass_the_rest(cos, sin, head_dim: int):
    """Tables of the first channels of a head widened to all head_dim: the
    channels behind them are turned by no angle (cos 1, sin 0)."""
    rest = head_dim - cos.shape[-1]
    if not rest:
        return cos, sin
    return (jnp.pad(cos, ((0, 0), (0, rest)), constant_values=1.0),
            jnp.pad(sin, ((0, 0), (0, rest))))


def rope_table(kind, head_dim: int, max_positions: int,
               dtype=jnp.float32, rotary_dim: Optional[int] = None,
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin), each [max_positions, head_dim], of one kind of attention
    layer (config.AttentionKind): the plain or linearly interpolated table,
    or YaRN's, whose cos and sin carry its attention factor. rotary_dim
    (ModelConfig.rotary_dim; None: head_dim): the table is of a head of
    that many channels, the first of the head's, and the rest pass
    (`apply_rotary_emb` is told the same number)."""
    if rotary_dim is not None and rotary_dim != head_dim:
        return _pass_the_rest(
            *rope_table(kind, rotary_dim, max_positions, dtype), head_dim)
    if kind.rope_type != "yarn":
        return precompute_rope(head_dim, max_positions, kind.rope_theta,
                               kind.rope_scaling_factor, dtype)
    inv_freq = yarn_inv_freq(
        head_dim, kind.rope_theta, kind.rope_scaling_factor,
        kind.yarn_original_max_positions, kind.yarn_beta_fast,
        kind.yarn_beta_slow)
    scale = kind.yarn_attention_factor
    if scale is None:
        scale = 0.1 * math.log(kind.rope_scaling_factor) + 1.0
    freqs = jnp.outer(jnp.arange(max_positions, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return ((jnp.cos(emb) * scale).astype(dtype),
            (jnp.sin(emb) * scale).astype(dtype))


def _half_turn(x: jnp.ndarray, signed: bool = True,
               rot: Optional[int] = None) -> jnp.ndarray:
    """rotate_half(x) == concatenate([-x[..., D/2:], x[..., :D/2]]) as the
    product x @ R, R the [D, D] signed permutation (unsigned: the halves
    swapped). Every element of the product is one element of x times +-1
    plus zeros, so it is exact in x's own dtype, and the lane axis is never
    split: a slice and concatenate of a head's halves is not fused on the
    TPU (the compiler writes both halves out, lane-padded). rot: the first
    `rot` channels are the head that turns (None: all D), and the product
    is zero behind them, where the tables' sine is."""
    d = x.shape[-1]
    rot = d if rot is None else rot
    j = np.arange(rot)
    r = np.zeros((d, d), np.float32)
    r[(j + rot // 2) % rot, j] = np.where(j < rot // 2, -1, 1) if signed else 1
    one_pass = x.dtype == jnp.bfloat16  # bf16 products are exact on the MXU
    return lax.dot_general(
        x, jnp.asarray(r, x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        precision=None if one_pass else lax.Precision.HIGHEST,
        preferred_element_type=x.dtype)


def _turn(x, cos, sin, rot=None):
    """x * cos + rotate_half(x) * sin in float32, rounded once to x's
    dtype: one pass that reads x once and writes it once."""
    return (x.astype(jnp.float32) * cos
            + _half_turn(x, rot=rot).astype(jnp.float32) * sin
            ).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rotate(x, cos, sin, rot=None):
    return _turn(x, cos, sin, rot)


def _rotate_fwd(x, cos, sin, rot):
    return _turn(x, cos, sin, rot), (cos, sin)


def _rotate_bwd(rot, tables, dy):
    # Linear in x, so the cotangent takes the transposed pass and needs no
    # activation: dx = dy * cos + (dy * sin) @ R^T. With R^T = -R the
    # permutation moves to the cotangent itself, which is exact where
    # autodiff turns the float32 dy * sin: dx = dy * cos - (dy @ R) * sin',
    # sin' the sine with its halves swapped (a table, not an activation).
    # The tables are constants of the model (stop_gradient below).
    cos, sin = tables
    return (_turn(dy, cos, -_half_turn(sin, signed=False, rot=rot), rot),
            jnp.zeros_like(cos), jnp.zeros_like(sin))


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


def apply_rotary_emb(
    q: jnp.ndarray,
    k: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    rotary_dim: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rotate q,k ([batch, seq, heads, head_dim]) by position.

    rotary_dim: the first channels of a head that turn, where not all do
    (`rope_table` was told the same number; None: all).

    positions: [batch, seq] int ids; None => 0..seq-1. Non-monotonic ids
    (packed sequences) are supported via gather, as in the reference.

    The results carry the name SAVED_ROTATED: a layer's checkpoint that
    keeps them (models/language_model.py, `selective`) applies rotary
    twice a layer, forward and backward, and not again in between.
    """
    if positions is None:
        seq = q.shape[1]
        tables = (cos[None, :seq], sin[None, :seq])
    else:
        tables = (cos[positions], sin[positions])
    # [B, S, D] -> [B, S, 1, D] to broadcast over heads
    tables = [lax.stop_gradient(t[:, :, None, :].astype(jnp.float32))
              for t in tables]
    if rotary_dim == q.shape[-1]:
        rotary_dim = None
    return (checkpoint_name(_rotate(q, *tables, rotary_dim), SAVED_ROTATED),
            checkpoint_name(_rotate(k, *tables, rotary_dim), SAVED_ROTATED))
