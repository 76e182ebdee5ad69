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

import math
from typing import Optional, Tuple

import jax.numpy as jnp


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


def rope_table(kind, head_dim: int, max_positions: int,
               dtype=jnp.float32) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(cos, sin), each [max_positions, head_dim], of one kind of attention
    layer (config.AttentionKind): the plain or linearly interpolated table,
    or YaRN's, whose cos and sin carry its attention factor."""
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


def _rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rotary_emb(
    q: jnp.ndarray,
    k: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Rotate q,k ([batch, seq, heads, head_dim]) by position.

    positions: [batch, seq] int ids; None => 0..seq-1. Non-monotonic ids
    (packed sequences) are supported via gather, as in the reference.
    """
    if positions is None:
        seq = q.shape[1]
        cos_g, sin_g = cos[None, :seq], sin[None, :seq]
    else:
        cos_g, sin_g = cos[positions], sin[positions]
    # [B, S, D] -> [B, S, 1, D] to broadcast over heads
    cos_g = cos_g[:, :, None, :].astype(jnp.float32)
    sin_g = sin_g[:, :, None, :].astype(jnp.float32)

    def rot(x):
        xf = x.astype(jnp.float32)
        return (xf * cos_g + _rotate_half(xf) * sin_g).astype(x.dtype)

    return rot(q), rot(k)
