"""Token sampling: greedy / temperature / top-k / top-p.

Equivalent of megatron/text_generation/sampling.py (93 LoC), as one jittable
function. Filtering works on sorted logits so top-k and top-p compose, and
everything stays fixed-shape for XLA.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def sample_logits(
    logits: jnp.ndarray,          # [B, V] float
    key: Optional[jax.Array],
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 0.0,
    vocab_size: Optional[int] = None,
) -> jnp.ndarray:
    """Returns sampled token ids [B]. top_k=0/top_p=0 disable the filters;
    temperature 0 (or key None) is greedy (ref: sampling.py sample())."""
    logits = logits.astype(jnp.float32)
    if vocab_size is not None and vocab_size < logits.shape[-1]:
        # clamp padded vocab columns (ref: vocab boundary clamp)
        neg = jnp.finfo(jnp.float32).min
        mask = jnp.arange(logits.shape[-1]) < vocab_size
        logits = jnp.where(mask, logits, neg)

    greedy = key is None or temperature == 0.0
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    logits = logits / temperature

    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, jnp.finfo(jnp.float32).min, logits)

    if top_p > 0.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p (always
        # keep the top token)
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)  # [B]
        cutoff_logit = jnp.take_along_axis(
            sorted_logits, cutoff_idx[:, None], axis=-1)
        logits = jnp.where(logits < cutoff_logit,
                           jnp.finfo(jnp.float32).min, logits)

    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def filter_top_k_top_p(
    scaled: jnp.ndarray,          # [B, V] temperature-scaled logits
    top_k: jnp.ndarray,           # [B] int; 0 disables per row
    top_p: jnp.ndarray,           # [B] float; 0 disables per row
) -> jnp.ndarray:
    """Per-row top-k then top-p composition on sorted logits — THE one
    implementation of the filter semantics, shared by the batched
    sampler below and the speculative verify's accept/reject
    (inference/speculative.py, which flattens its [N, k+1, V] positions
    into the batch axis): the speculative exactness contract is that
    both paths draw from the IDENTICAL filtered distribution, so the
    composition must never fork. Rows with top_k<=0 / top_p<=0 keep all
    mass for that filter; each row's top token always survives. Masking
    only values BELOW the kth keeps the descending sort valid for the
    top-p pass, so one sort serves both filters."""
    neg = jnp.finfo(jnp.float32).min
    V = scaled.shape[-1]
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        desc, jnp.clip(top_k[:, None] - 1, 0, V - 1), axis=-1)
    cond_tk = (top_k[:, None] > 0) & (scaled < kth)
    scaled = jnp.where(cond_tk, neg, scaled)
    desc = jnp.where((top_k[:, None] > 0) & (desc < kth), neg, desc)
    cum = jnp.cumsum(jax.nn.softmax(desc, axis=-1), axis=-1)
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(desc, cutoff_idx, axis=-1)
    return jnp.where((top_p[:, None] > 0) & (scaled < cutoff),
                     neg, scaled)


def sample_logits_batched(
    logits: jnp.ndarray,          # [B, V] float
    keys: jnp.ndarray,            # [B, 2] per-row PRNG keys
    temperature: jnp.ndarray,     # [B] float; 0 = greedy for that row
    top_k: jnp.ndarray,           # [B] int; 0 disables
    top_p: jnp.ndarray,           # [B] float; 0 disables
    vocab_size: Optional[int] = None,
) -> jnp.ndarray:
    """Per-row sampling for the continuous-batching engine: every knob is
    a traced [B] array so heterogeneous requests (different temperatures,
    top-k/top-p) share ONE compiled decode step — the scalar sampler's
    static args would force a recompile per sampling config. Row semantics
    match sample_logits exactly: greedy rows ignore the filters, top-k and
    top-p compose on sorted logits, padded vocab columns are clamped.

    The expensive pieces run under lax.cond on what the batch actually
    needs: all-greedy traffic pays one argmax (no sort, no categorical),
    and the [B, V] filter sort only runs when some row has top-k/top-p.
    XLA:CPU's sort is scalar, so a sort on every tick dominated the
    decode step there; on the chip the sort of f32[64, 65536] is 3.2 ms
    of a 19.9 ms decode step (PERF.md, PR 47).

    A row with top_k 1 is a greedy row whatever its temperature (one
    candidate is the argmax; the reference's sampler calls top_k 1
    greedy): it takes the argmax and keeps neither branch live."""
    logits = logits.astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min
    V = logits.shape[-1]
    if vocab_size is not None and vocab_size < V:
        logits = jnp.where(jnp.arange(V) < vocab_size, logits, neg)

    # greedy rows bypass temperature/filters entirely (scalar fast path)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    drawn = (temperature > 0) & (top_k != 1)

    def _sample(logits):
        t = temperature[:, None]
        scaled = logits / jnp.where(t > 0, t, 1.0)
        # filter semantics live in filter_top_k_top_p (shared with the
        # speculative verify step); same composition order as the
        # scalar sampler, one sort serves both filters
        scaled = jax.lax.cond(
            jnp.any(drawn & ((top_k > 0) | (top_p > 0))),
            lambda s: filter_top_k_top_p(s, top_k, top_p),
            lambda s: s, scaled)
        return jax.vmap(jax.random.categorical)(keys, scaled).astype(
            jnp.int32)

    sampled = jax.lax.cond(jnp.any(drawn), _sample,
                           lambda _: greedy_tok, logits)
    return jnp.where(drawn, sampled, greedy_tok)
