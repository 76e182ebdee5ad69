"""fp8 training matmuls (TransformerEngine parity row, TPU form).

The reference wraps its transformer in TransformerEngine fp8 autocast
(megatron/model/transformer.py:962-1043): Format.E4M3 or Format.HYBRID
(e4m3 forward / e5m2 grads) with a DelayedScaling recipe — per-tensor
scales from a rolling amax history, refreshed every `interval` steps.

This module implements the same quantized-GEMM structure with CURRENT
scaling, a deliberate TPU-first substitution for the delayed-scaling
machinery:

  * Delayed scaling exists because on GPUs the amax reduction is a
    separate kernel whose result must round-trip through a CUDA-graph-
    unfriendly sync before the quantize kernel can run — so TE amortizes
    it across steps and keeps history state. Under XLA the amax reduction
    fuses into the producing op and the scale feeds the quantize in the
    same program: the latency motivation is gone, and with it the state
    (amax_history / interval / amax_compute_algo knobs) and the one-step-
    stale-scale overflow hazard delayed scaling must margin against.
  * What remains is what the hardware sees: e4m3 operands into the MXU
    for the forward GEMM, e5m2 gradients into the two backward GEMMs
    (hybrid), per-tensor software scales applied as an fp32 epilogue.

fp8_matmul is a custom_vjp:

  forward   out = (x8 @ w8) / (sx * sw)            x8, w8: e4m3
  backward  dx  = (g8 @ w8^T) / (sg * sw)          g8: e5m2 (hybrid) / e4m3
            dw  = (x8^T @ g8) / (sx * sg)          [or x8^T @ g fp32 when
                                                    fp8_wgrad is off — the
                                                    reference's
                                                    override_linear_precision]

The residuals saved for backward are the fp8 operands themselves — half
the bytes of the bf16 activations a plain matmul would save.

On hardware without native f8 MXU lanes XLA upcasts the operands and the
GEMM runs at bf16 speed with fp8 *numerics* (exactly how CI exercises
this path on CPU); on f8-capable TPUs the same HLO hits the fp8 MXU
path. Which of the two a given chip takes is what tools/fp8_probe.py
reports (run it through the chip tool; not measured yet on the v5e).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

E4M3 = jnp.float8_e4m3fn
E5M2 = jnp.float8_e5m2


def _scale(t: jnp.ndarray, fmax: float, margin: int) -> jnp.ndarray:
    """Per-tensor quantization scale: fmax * 2^-margin / amax, fp32.
    A non-finite amax (inf/nan in the tensor) degrades to scale 1 — the
    f8 cast then saturates/propagates only the offending elements, like
    TE's scale-reset — instead of poisoning the whole GEMM. (The guard
    must test amax, not the scale: fmax/inf == 0.0 IS finite, and a zero
    scale would NaN every element through the 1/(sx*sw) epilogue.)"""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)))
    s = (fmax * (2.0 ** -margin)) / jnp.maximum(amax, 1e-12)
    return jnp.where(jnp.isfinite(amax), s, 1.0)


def _q(t: jnp.ndarray, s: jnp.ndarray, dt) -> jnp.ndarray:
    return (t.astype(jnp.float32) * s).astype(dt)


def fp8_matmul(x: jnp.ndarray, w: jnp.ndarray, fmt: str = "hybrid",
               margin: int = 0, fp8_wgrad: bool = True) -> jnp.ndarray:
    """x [..., K] @ w [K, N] -> [..., N] with fp8 GEMMs (see module doc).

    fmt: "hybrid" (e4m3 fwd / e5m2 grads, TE Format.HYBRID) or "e4m3"
    (everything e4m3, TE Format.E4M3).
    """
    if fmt not in ("hybrid", "e4m3"):
        raise ValueError(f"fp8 format {fmt!r}: expected 'hybrid' or 'e4m3'")
    gdt = E5M2 if fmt == "hybrid" else E4M3
    gmax = float(jnp.finfo(gdt).max)
    out_dtype = x.dtype

    @jax.custom_vjp
    def mm(x, w):
        out, _ = fwd(x, w)
        return out

    def fwd(x, w):
        sx = _scale(x, float(jnp.finfo(E4M3).max), margin)
        sw = _scale(w, float(jnp.finfo(E4M3).max), margin)
        x8 = _q(x, sx, E4M3)
        w8 = _q(w, sw, E4M3)
        out = jax.lax.dot_general(
            x8, w8, (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = (out / (sx * sw)).astype(out_dtype)
        return out, (x8, w8, sx, sw)

    def bwd(res, g):
        x8, w8, sx, sw = res
        sg = _scale(g, gmax, margin)
        g8 = _q(g, sg, gdt)
        # dx = g @ w^T : contract N
        dx = jax.lax.dot_general(
            g8, w8, (((g.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dx = (dx / (sg * sw)).astype(out_dtype)
        # dw = x^T @ g : contract all leading (batch) dims
        m = math.prod(x8.shape[:-1])
        x2 = x8.reshape(m, x8.shape[-1])
        if fp8_wgrad:
            g2 = g8.reshape(m, g8.shape[-1])
            dw = jax.lax.dot_general(
                x2, g2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) / (sx * sg)
        else:
            # reference --no_fp8_wgrad: the wgrad GEMM runs in higher
            # precision (on the stored casted activations, like TE's
            # override_linear_precision)
            g2 = g.reshape(m, g.shape[-1]).astype(jnp.float32)
            dw = jax.lax.dot_general(
                x2.astype(jnp.float32), g2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) / sx
        return dx, dw.astype(w.dtype)

    mm.defvjp(fwd, bwd)
    return mm(x, w)


def maybe_fp8_matmul(cfg, x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """The projection primitive for transformer matmuls: fp8 GEMM when
    cfg.fp8_format is set, plain (XLA-fused) matmul otherwise."""
    if cfg.fp8_format is None:
        return jnp.einsum("...k,kn->...n", x, w)
    return fp8_matmul(x, w, fmt=cfg.fp8_format, margin=cfg.fp8_margin,
                      fp8_wgrad=cfg.fp8_wgrad)
