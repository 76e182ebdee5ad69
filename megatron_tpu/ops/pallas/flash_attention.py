"""Blockwise flash attention (forward + backward) — the prefill/training
instantiation of the one kernel family in flash_template.py.

TPU-native replacement for the reference's FlashAttention-2 dependency
(megatron/model/transformer.py:524-553, incl. Mistral's sliding window
:528-536) and, transitively, its fused scaled-masked-softmax CUDA kernels
(megatron/fused_kernels/scaled_*_softmax*): O(S) memory exact attention
with causal + sliding-window masking and GQA, and an FA-2 recompute
backward via jax.custom_vjp so jax.grad through it never builds the XLA
O(S^2) gradient.

The kernels (fwd, dq, dk/dv), the custom_vjp wiring, the block-skip and
the mask arithmetic all live in flash_template.py / masks.py; this module
is the stable import point plus the splash-attention comparison baseline
(jax's bundled block-sparse kernel, used as an A/B reference on real
hardware via MEGATRON_TPU_SPLASH_ATTENTION=1 — the template is primary so
training and prefill share one custom gradient path).
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from megatron_tpu.ops.pallas.flash_template import (  # noqa: F401
    _NEG_INF,
    _bwd,
    _delta_arr,
    _dkv_kernel,
    _dq_kernel,
    _flash_bhsd,
    _fwd,
    _fwd_kernel,
    _interpret,
    _pick_block,
    flash_mha,
    supported,
)


def _use_splash() -> bool:
    """Opt-in A/B baseline: route full-sequence attention through jax's
    bundled splash kernel instead of the in-tree template (hardware
    only — splash is the pre-template TPU path, kept for comparison
    runs, not a supported training path: it bypasses the template's
    custom_vjp)."""
    return (os.environ.get("MEGATRON_TPU_SPLASH_ATTENTION", "")
            not in ("", "0") and not _interpret())


def _splash_attention(q, k, v, causal: bool, window: Optional[int]):
    """jax's bundled splash (block-sparse flash) kernel in MQA form:
    q [B,Hq,S,D] grouped as [B,Hkv,G,S,D] so GQA shares K/V per group with
    NO kv-head replication; masked-out blocks (beyond the causal frontier /
    outside the sliding window) are skipped entirely, not just masked."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    b, hq, s, d = q.shape
    hkv = k.shape[1]
    groups = hq // hkv
    blk = _pick_block(s)
    if blk is None:
        raise ValueError(f"splash kernel needs seq % 128 == 0 ({s=})")

    if window is not None:
        # Mistral semantics: attend to at most the last `window` positions
        # (self + window-1 back); LocalMask((left, right)) keeps
        # q-left <= k <= q+right
        head_mask = sm.LocalMask((s, s), (window - 1, 0), 0)
    elif causal:
        head_mask = sm.CausalMask((s, s))
    else:
        head_mask = sm.FullMask((s, s))
    mask = sm.MultiHeadMask([head_mask] * groups)
    bs = sk.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk)
    kern = sk.make_splash_mqa_single_device(mask, block_sizes=bs,
                                            interpret=_interpret())
    scale = 1.0 / (d ** 0.5)
    qg = (q * jnp.asarray(scale, q.dtype)).reshape(b, hkv, groups, s, d)
    out = jax.vmap(jax.vmap(kern))(qg, k, v)         # [B,Hkv,G,S,D]
    return out.reshape(b, hq, s, d)


def flash_attention(
    q: jnp.ndarray,  # [B, Sq, Hq, D]
    k: jnp.ndarray,  # [B, Skv, Hkv, D]
    v: jnp.ndarray,
    sliding_window: Optional[int] = None,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jnp.ndarray:
    """Public entry in framework layout: the template's fused fwd +
    custom-vjp bwd (flash_template.flash_mha) on every backend —
    interpreter mode on CPU hosts, compiled on TPU. Set
    MEGATRON_TPU_SPLASH_ATTENTION=1 on hardware to A/B against jax's
    bundled splash kernel instead."""
    if _use_splash():
        b, sq, hq, d = q.shape
        skv = k.shape[1]
        if sq != skv or _pick_block(sq) is None:
            raise ValueError(
                f"splash kernel needs equal seq lens divisible by 128 "
                f"({sq=}, {skv=})")
        qt = jnp.transpose(q, (0, 2, 1, 3))          # [B,Hq,S,D]
        kt = jnp.transpose(k, (0, 2, 1, 3))          # [B,Hkv,S,D]
        vt = jnp.transpose(v, (0, 2, 1, 3))
        o = _splash_attention(qt, kt, vt, causal, sliding_window)
        return jnp.transpose(o, (0, 2, 1, 3))
    return flash_mha(q, k, v, sliding_window=sliding_window, causal=causal,
                     block_q=block_q, block_k=block_k)
