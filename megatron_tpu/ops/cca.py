"""Compressed convolutional attention (CCA, arXiv:2510.04476; ZAYA1's
attention, arXiv:2511.17127): q, k and v of a layer's normed input.

Attention runs in a compressed latent: the query is Hq x D wide, key and
value Hkv x D, and nothing is projected back up in front of the kernel.
What stands between the projections and the rotary, on u [B, S, h]:

    qt = u Wq  [Hq, D]       kt = u Wk  [Hkv, D]
    v  = heads(u Wv)         with the second half of its channels read from
                             the position before (the value shift): `wv`'s
                             columns [0, Hkv D / 2) see u_t, the rest u_t-1
    c  = concat(qt, kt) over heads
    c1_t    = sum_j conv1[j] * c_{t-K0+1+j}         depthwise, a tap a channel
    c2_t[g] = sum_j c1_{t-K1+1+j}[g] @ conv2[g, j]  grouped by head
    q = c2[:Hq] + (qt + kt[group]) / 2
    k = c2[Hq:] + (mean of the group's qt + kt) / 2  the q-k mean, of the
                                                     PRE-convolution values
    q = sqrt(D) q / |q|      k = tau sqrt(D) k / |k|  a head over D,
                             float32; tau [Hkv] the learned temperature

Both convolutions and the shift are causal, and positions before the
sequence's first read zeros; a packed batch's documents are not told apart
(as attention's mask is not in training). The shift commutes with the
projection, so v is ONE product and its second half is moved a position.
Everything but the two projections carries the scope `cca_mix` in a
device trace (docs/observability.md "Runtime traces").

Training alone: incremental decoding would need the last K - 1 positions
of c, c1 and u a sequence beside its keys and values
(models/transformer.py attention_block refuses a cache).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig


def shift(x: jnp.ndarray, by: int = 1) -> jnp.ndarray:
    """x [B, S, ...] -> x_{t - by} along S, zeros before the first
    position."""
    if by == 0:
        return x
    pad = [(0, 0), (by, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x[:, :-by], pad)


def causal_taps(x: jnp.ndarray, kernel: int):
    """The `kernel` inputs of a causal convolution's output at t, oldest
    first: x_{t-kernel+1} .. x_t."""
    return [shift(x, kernel - 1 - j) for j in range(kernel)]


def qk_mean(qt: jnp.ndarray, kt: jnp.ndarray
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(mq like qt [B, S, Hq, D], mk like kt [B, S, Hkv, D]): query head i
    shares KV head i // G with the G heads of its group."""
    b, s, nq, d = qt.shape
    nkv = kt.shape[2]
    g = nq // nkv
    mq = (qt + jnp.repeat(kt, g, axis=2)) * 0.5
    mk = (jnp.mean(qt.reshape(b, s, nkv, g, d), axis=3) + kt) * 0.5
    return mq, mk


def unit_norm(x: jnp.ndarray) -> jnp.ndarray:
    """sqrt(D) x / |x|_2 over the last axis (float32 in, float32 out)."""
    d = x.shape[-1]
    return x * (jnp.sqrt(jnp.float32(d)) * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12))


def cca_mix(cfg: ModelConfig, p: Dict[str, Any], qt: jnp.ndarray,
            kt: jnp.ndarray, v: jnp.ndarray):
    """(q, k, v) of the latents qt [B, S, Hq, D], kt [B, S, Hkv, D] and the
    unshifted v [B, S, Hkv * D]: what the module docstring puts between
    the projections and the rotary. float32 inside; results in qt's dtype."""
    b, s, nq, d = qt.shape
    nkv = kt.shape[2]
    k0, k1 = cfg.cca_conv_kernels
    dtype = qt.dtype
    f32 = jnp.float32
    half = v.shape[-1] // 2
    v = jnp.concatenate([v[..., :half], shift(v[..., half:])], axis=-1)
    qt, kt = qt.astype(f32), kt.astype(f32)
    c = jnp.concatenate([qt, kt], axis=2)                 # [B, S, H, D]
    conv1 = p["conv1"].astype(f32)                        # [K0, H, D]
    c1 = sum(conv1[j] * tap for j, tap in enumerate(causal_taps(c, k0)))
    # the taps side by side are one product a head: [B, S, H, K1 * D]
    # against conv2's [H, K1 * D, D]
    taps = jnp.concatenate(causal_taps(c1, k1), axis=-1)
    conv2 = p["conv2"].astype(f32).reshape(nq + nkv, k1 * d, d)
    c2 = jnp.einsum("bshk,hkd->bshd", taps, conv2)
    mq, mk = qk_mean(qt, kt)
    q = unit_norm(c2[:, :, :nq] + mq)
    k = unit_norm(c2[:, :, nq:] + mk) * p["k_temp_scale"].astype(
        f32)[:, None]
    return q.astype(dtype), k.astype(dtype), v.reshape(b, s, nkv, d)
