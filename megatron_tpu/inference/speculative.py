"""Speculative decoding inside the engine's one jitted decode step.

Classic speculative sampling (Leviathan et al., arXiv 2211.17192): a
cheap drafter proposes ``k`` tokens per slot, ONE batched multi-token
target forward scores all ``k+1`` query positions, and an in-step exact
accept/reject keeps or replaces each draft so the emitted tokens follow
the target model's distribution exactly. A tick that accepts ``a``
drafts emits ``a+1`` tokens for one target forward — the throughput win
— and a tick that rejects everything still emits 1 token (never slower
in tokens per forward than plain decode).

Two pluggable drafters:

  * ``"ngram"`` — zero-weight prompt-lookup (PLD / arXiv 2304.04487
    family): propose the ``k`` tokens that followed the most recent
    earlier occurrence of the sequence's trailing n-gram. Proposal runs
    on the host (numpy over the request's own token history) and rides
    into the jitted step as a traced ``[N, k]`` array; great on
    repetitive / copy-heavy traffic, free everywhere else.
  * ``"model"`` — a small draft model sharing the engine's slot/page KV
    machinery through a SECOND cache tree: the draft proposes greedily
    via a ``lax.scan`` of k single-token forwards inside the same
    jitted step (plus one extra write-only forward so the draft cache
    covers the all-accepted case), then the target verifies. The
    engine's chunked prefill writes the draft cache through the same
    page tables and write fences as the target cache, so prefix-cache
    aliasing and preempt-resume recompute work identically for both
    trees.

Exactness contract (pinned by tests/test_speculative.py):

  * greedy (temperature 0): a draft is accepted iff it equals the
    target argmax given the accepted prefix, and the emitted token at
    every position IS that argmax — token-identical to non-speculative
    decode, bit for bit, for any drafter and any acceptance rate.
  * sampled (temperature > 0): both drafters propose deterministically
    (point-mass q), so standard speculative sampling reduces to: accept
    draft d with probability p(d) under the (temperature / top-k /
    top-p filtered) target distribution, else sample from the residual
    p with d removed and renormalized — the emitted token is an exact
    sample from p either way. Randomness is keyed by the request's PRNG
    chain AND the absolute token position (``fold_in(chain, position)``,
    not a per-tick split), so sampled output is chain-DETERMINISTIC:
    identical runs (same seed, same tick schedule) agree exactly. It is
    NOT schedule-independent — which drafts exist at a position depends
    on the tick alignment, and a preemption resume re-draws its
    boundary token through the prefill sampler's split-based chain — so
    only greedy output is invariant under preemption/scheduling
    (docs/serving.md pins this asymmetry).

Rollback: rejected drafts' K/V entries (written at positions past the
accepted length by the same multi-token forward) are invalidated purely
by the per-slot length roll-back — attention masks every row to its own
valid prefix, and the next tick overwrites those positions. The
engine's page table is untouched: speculative writes only ever land in
the slot's private tail pages (shared prefix pages hold only FULL pages
of the original prompt, strictly below the decode positions), so no
page is freed or re-mapped on rejection.

``k`` is static in the compiled step (drafts ride as a padded ``[N, k]``
dimension), so the engine still compiles exactly once at warmup — the
live ``decode_recompiles`` counter stays 0.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import ModelConfig

#: drafter registry — "ngram" is host-side prompt lookup, "model" a
#: draft network sharing the engine's cache machinery
DRAFTERS = ("ngram", "model")


@dataclasses.dataclass
class SpecConfig:
    """Engine-level speculative decoding configuration.

    k: drafted tokens per slot per tick (the verify forward takes
       [N, k+1] query rows). Emitted tokens per tick per slot range
       from 1 (all rejected) to k+1 (all accepted).
    drafter: "ngram" (zero-weight prompt lookup) or "model" (a small
       draft model with its own cache tree).
    ngram: trailing n-gram length the lookup drafter matches (it falls
       back to shorter suffixes down to 1 before giving up).
    draft_cfg/draft_params: the draft model ("model" drafter only).
       Must share the target's vocab; everything else (layers, heads,
       head_dim) is free — the draft keeps its own cache tree.
    """

    k: int = 4
    drafter: str = "ngram"
    ngram: int = 2
    draft_cfg: Optional[ModelConfig] = None
    draft_params: Any = None


def validate_spec(cfg: ModelConfig, spec: SpecConfig) -> None:
    if spec.k < 1:
        raise ValueError(f"spec k must be >= 1, got {spec.k}")
    if spec.drafter not in DRAFTERS:
        raise ValueError(
            f"unknown drafter {spec.drafter!r} (choose from {DRAFTERS})")
    if spec.drafter == "ngram" and spec.ngram < 1:
        raise ValueError(f"ngram must be >= 1, got {spec.ngram}")
    if spec.drafter == "model":
        if spec.draft_cfg is None or spec.draft_params is None:
            raise ValueError(
                "drafter='model' needs draft_cfg and draft_params "
                "(use drafter='ngram' for the zero-weight drafter)")
        if spec.draft_cfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                f"draft vocab {spec.draft_cfg.vocab_size} != target vocab "
                f"{cfg.vocab_size} — verify compares token ids directly")


# ---------------------------------------------------------------------------
# n-gram / prompt-lookup drafter (host side)
# ---------------------------------------------------------------------------


def ngram_propose(history: np.ndarray, k: int, n: int) -> np.ndarray:
    """Propose k continuation tokens by prompt lookup: find the most
    recent EARLIER occurrence of the trailing n-gram of ``history`` and
    return the k tokens that followed it (falling back to shorter
    suffixes down to 1). When nothing matches, repeat the last token —
    a cheap guess the verifier will usually reject at cost 0 (the tick
    still emits its guaranteed token).

    Host-side vectorized numpy over one request's own token history
    (the per-tick proposal sits on the decode hot path serialized
    before the device step, so no Python-level window loop); the result
    rides into the jitted step as data, so the compiled step never
    changes shape."""
    history = np.asarray(history, np.int32)
    ln = len(history)
    out = np.full(k, history[-1] if ln else 0, np.int32)
    for nn in range(min(n, ln - 1), 0, -1):
        suffix = history[ln - nn:]
        # all windows history[i:i+nn] for i < ln-nn at once: match[i]
        # is True when the window equals the trailing n-gram
        windows = np.lib.stride_tricks.sliding_window_view(
            history[:ln - 1], nn)                     # [ln-nn, nn]
        match = (windows == suffix).all(axis=1)
        if not match.any():
            continue
        i = int(len(match) - 1 - np.argmax(match[::-1]))  # newest match
        cont = history[i + nn:i + nn + k]
        out[:len(cont)] = cont
        if 0 < len(cont) < k:
            out[len(cont):] = cont[-1]
        return out
    return out


# ---------------------------------------------------------------------------
# exact accept/reject (inside the jitted step)
# ---------------------------------------------------------------------------


def speculative_accept(
    logits: jnp.ndarray,      # [N, k+1, V] target logits per query row
    drafts: jnp.ndarray,      # [N, k] proposed tokens
    lengths: jnp.ndarray,     # [N] cache length (absolute position base)
    keys: jnp.ndarray,        # [N, 2] per-slot PRNG chain state
    temps: jnp.ndarray,       # [N] 0 = greedy
    top_ks: jnp.ndarray,      # [N]
    top_ps: jnp.ndarray,      # [N]
    vocab_size: Optional[int] = None,
    spec_rows: Optional[jnp.ndarray] = None,  # [N] bool; False = no spec
    want_logprobs: bool = True,
):
    """The exact accept/reject core. Returns
    ``(toks [N, k+1], lps [N, k+1], accepts [N])``.

    Row semantics (position j is the query fed token j: j=0 the slot's
    last sampled token, j>=1 draft j):

      * greedy rows: toks[:, j] is the target argmax at position j;
        draft j is accepted iff it equals toks[:, j-1] — so the emitted
        prefix toks[:, :accepts+1] is EXACTLY what non-speculative
        greedy decode would produce.
      * sampled rows: draft j is accepted with probability p_j(draft)
        under the filtered/scaled target distribution (point-mass
        proposal acceptance); a rejected position emits a sample from
        the residual (p with the draft removed, renormalized), and the
        bonus position k emits a full sample. Either way the emitted
        token is an exact draw from p_j.
      * rows with spec_rows=False accept nothing and emit ONE token
        sampled from the full distribution — greedy rows stay
        bit-identical to non-speculative decode.

    Randomness is keyed by absolute position: ``fold_in(chain, pos)``
    with pos = lengths + j, never a per-tick split — the chain state in
    ``keys`` is NOT consumed, so acceptance scheduling (and
    preempt/resume) cannot shift later draws.

    The caller emits ``toks[:, :accepts+1]``; positions past the first
    rejection are garbage by construction and must not be read.

    The heavy branches keep the engine's all-greedy fast path: the
    whole sampling machinery (softmax/uniform/categorical over
    [N, k+1, V]) runs under ``lax.cond(any(temps > 0))`` and the
    [N, k+1, V] filter sort under a nested cond on the top-k/top-p
    knobs — an all-greedy tick pays one argmax, exactly like
    sample_logits_batched."""
    raw32 = logits.astype(jnp.float32)
    N, K1, V = raw32.shape
    k = K1 - 1
    neg = jnp.finfo(jnp.float32).min
    clamped = raw32
    if vocab_size is not None and vocab_size < V:
        clamped = jnp.where(jnp.arange(V) < vocab_size, raw32, neg)
    greedy_t = jnp.argmax(clamped, axis=-1).astype(jnp.int32)   # [N, K1]
    greedy_match = drafts == greedy_t[:, :k]                    # [N, k]
    srow = (jnp.ones((N,), bool) if spec_rows is None
            else spec_rows.astype(bool))

    # positional PRNG: one subkey per (slot, absolute position), two
    # tagged draws per subkey (uniform accept test, categorical sample)
    pos = lengths[:, None] + jnp.arange(K1)[None, :]            # [N, K1]

    def _sampled(operand):
        clamped, pos = operand
        t = temps[:, None, None]
        scaled = clamped / jnp.where(t > 0, t, 1.0)

        def _filter(scaled):
            # THE batched sampler's filter (sampling.filter_top_k_top_p
            # — the exactness contract requires the identical filtered
            # distribution), with the k+1 positions flattened into the
            # batch axis and each row's knobs repeated per position
            from megatron_tpu.inference.sampling import filter_top_k_top_p

            flat = filter_top_k_top_p(
                scaled.reshape(N * K1, V),
                jnp.repeat(top_ks, K1), jnp.repeat(top_ps, K1))
            return flat.reshape(N, K1, V)

        fl = jax.lax.cond(jnp.any((top_ks > 0) | (top_ps > 0)),
                          _filter, lambda s: s, scaled)
        subs = jax.vmap(jax.vmap(jax.random.fold_in, (None, 0)),
                        (0, 0))(keys, pos)                      # [N, K1, 2]
        u = jax.vmap(jax.vmap(
            lambda s: jax.random.uniform(jax.random.fold_in(s, 0))
        ))(subs[:, :k])                                         # [N, k]
        p = jax.nn.softmax(fl, axis=-1)
        p_draft = jnp.take_along_axis(
            p[:, :k], drafts[..., None], axis=-1)[..., 0]       # [N, k]
        # spec-off rows must ignore the accept test entirely: emitting
        # the draft on a passed test AND sampling the full distribution
        # on a failed one would overweight the draft token
        accept = (u < p_draft) & srow[:, None]
        # residual = p minus the point-mass proposal, renormalized =
        # categorical over fl with the draft column removed. Spec-off
        # rows never ran the accept test, so they sample the FULL
        # distribution (no column removed).
        mask_d = jax.nn.one_hot(drafts, V, dtype=bool)
        resid = jnp.where(mask_d & srow[:, None, None], neg, fl[:, :k])
        ckeys = jax.vmap(jax.vmap(lambda s: jax.random.fold_in(s, 1))
                         )(subs)                                # [N, K1, 2]
        rej = jax.vmap(jax.vmap(jax.random.categorical)
                       )(ckeys[:, :k], resid).astype(jnp.int32)
        bonus = jax.vmap(jax.random.categorical)(
            ckeys[:, k], fl[:, k]).astype(jnp.int32)
        out = jnp.concatenate(
            [jnp.where(accept, drafts, rej), bonus[:, None]], axis=1)
        return out, accept

    out_s, accept_s = jax.lax.cond(
        jnp.any(temps > 0), _sampled,
        lambda op: (greedy_t, greedy_match), (clamped, pos))
    is_sampled = temps[:, None] > 0
    accept = jnp.where(is_sampled, accept_s, greedy_match) & srow[:, None]
    toks = jnp.where(is_sampled, out_s, greedy_t)
    # accepted prefix length: drafts accepted until the first rejection
    accepts = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                      axis=1).astype(jnp.int32)
    if want_logprobs:
        # same convention as the non-speculative step: fp32 log-softmax
        # of the RAW logits at the emitted token
        lps = jnp.take_along_axis(
            jax.nn.log_softmax(raw32, axis=-1),
            toks[..., None], axis=-1)[..., 0]
    else:
        lps = jnp.zeros(toks.shape, jnp.float32)
    return toks, lps, accepts


# ---------------------------------------------------------------------------
# jitted step builders (ngram + model drafter)
# ---------------------------------------------------------------------------


def build_spec_decode_step(
    cfg: ModelConfig,
    spec: SpecConfig,
    vocab_size: Optional[int],
    want_logprobs: bool,
    donate_argnums: tuple,
):
    """One jitted speculative decode step for the engine.

    Signature (positional, matching the engine's splice convention —
    the page table between the cache trees and the carry):

      ngram:  (params, caches, table, last_tok, lengths, keys, temps,
               top_ks, top_ps, spec_rows, drafts)
      model:  (params, caches, dparams, dcaches, table, last_tok,
               lengths, keys, temps, top_ks, top_ps, spec_rows)

    Returns (toks [N, k+1], lps, accepts, caches, [dcaches], new_keys,
    new_lengths, new_last_tok). new_keys is the untouched chain state
    (randomness is positional — see speculative_accept) returned so the
    device carry layout matches the non-speculative step's.

    Every forward is told which slots decode, off the table, as the plain
    decode step's is (paging/engine.py `rows_decoding`): a slot that does
    not costs the decode kernels no trip. The verify pass counts all of a
    decoding row's k + 1 positions as real.
    """
    from megatron_tpu.inference.paging.engine import rows_decoding
    from megatron_tpu.models.language_model import lm_forward

    k = spec.k
    dcfg = spec.draft_cfg
    neg = jnp.finfo(jnp.float32).min

    def _verify(params, caches, table, last, lens, keys, temps, tks, tps,
                spec_rows, drafts):
        toks_in = jnp.concatenate([last[:, None], drafts], axis=1)
        logits, caches = lm_forward(
            cfg, params, toks_in, kv_caches=caches, cache_index=lens,
            page_table=table, state_valid=rows_decoding(table) * (k + 1))
        toks, lps, accepts = speculative_accept(
            logits, drafts, lens, keys, temps, tks, tps,
            vocab_size=vocab_size, spec_rows=spec_rows,
            want_logprobs=want_logprobs)
        last_new = jnp.take_along_axis(toks, accepts[:, None], axis=1)[:, 0]
        return toks, lps, accepts, caches, keys, lens + accepts + 1, last_new

    if spec.drafter == "ngram":
        @partial(jax.jit, donate_argnums=donate_argnums)
        def spec_step(params, caches, table, last, lens, keys, temps,
                      tks, tps, spec_rows, drafts):
            return _verify(params, caches, table, last, lens, keys,
                           temps, tks, tps, spec_rows, drafts)
        return spec_step

    V = cfg.vocab_size

    @partial(jax.jit, donate_argnums=donate_argnums)
    def spec_step(params, caches, dparams, dcaches, table, last, lens,
                  keys, temps, tks, tps, spec_rows):
        decoding = rows_decoding(table)

        def body(carry, _):
            dc, tok, ln = carry
            lg, dc = lm_forward(dcfg, dparams, tok[:, None], kv_caches=dc,
                                cache_index=ln, page_table=table,
                                state_valid=decoding)
            lg = lg[:, 0].astype(jnp.float32)
            if vocab_size is not None and vocab_size < V:
                lg = jnp.where(jnp.arange(V) < vocab_size, lg, neg)
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
            return (dc, nxt, ln + 1), nxt

        (dcaches, d_last, d_len), drafts = jax.lax.scan(
            body, (dcaches, last, lens), None, length=k)
        drafts = jnp.transpose(drafts)                   # [k, N] -> [N, k]
        # one extra write-only draft forward: position lengths+k holds
        # draft k's K/V so a fully-accepted tick leaves the draft cache
        # complete for the next tick's proposal
        _, dcaches = lm_forward(dcfg, dparams, d_last[:, None],
                                kv_caches=dcaches, cache_index=d_len,
                                page_table=table, state_valid=decoding)
        toks, lps, accepts, caches, keys, lens_new, last_new = _verify(
            params, caches, table, last, lens, keys, temps, tks, tps,
            spec_rows, drafts)
        return toks, lps, accepts, caches, dcaches, keys, lens_new, last_new

    return spec_step
