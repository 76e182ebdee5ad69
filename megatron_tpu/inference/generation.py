"""Autoregressive generation with functional KV caches.

Equivalent of megatron/text_generation/generation.py (429 LoC) +
forward_step.py (204): the reference's InferenceParams KV-cache dict and
token-at-a-time pipeline become a jitted lax.while_loop whose carry holds
the stacked per-layer caches; prompts of different lengths are handled the
reference's way — decode starts at the shortest prompt and forced prompt
tokens override samples until each row's prompt is exhausted
(generation.py:89-287 generate_tokens_probs_and_return_on_first_stage).

Early termination on EOD ends the while_loop when every row is done, so
short generations don't pay for max_new_tokens steps.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import ModelConfig
from megatron_tpu.inference.sampling import sample_logits
from megatron_tpu.models.language_model import lm_forward
from megatron_tpu.ops import kv_store


@dataclasses.dataclass
class GenerationOutput:
    tokens: np.ndarray       # [B, total_len] int32 (prompt + generated)
    lengths: np.ndarray      # [B] generated sequence end (index past last)
    logprobs: np.ndarray     # [B, total_len-1] logprob of each emitted token
    # the serving engine's alone: seconds from the first row's submit to
    # the last row's retirement, on the engine's clock
    engine_s: Optional[float] = None


def _refuse_state_space(cfg: ModelConfig, what: str) -> None:
    """The one-shot loops carry keys and values from step to step and
    nothing else: a model with state-space layers would decode every token
    after the first from a zeroed recurrent state."""
    if cfg.has_ssm:
        raise NotImplementedError(
            f"{what} carries no recurrent state: serve a model with "
            "state-space layers through the engine (engine_slots > 0)")


def _default_fwd(cfg):
    """forward_fn contract: (params, tokens, positions, caches,
    cache_index) -> (logits, caches). Default = the single-stage cached
    lm_forward; pipelined.make_pipelined_lm_forward provides the pp>1
    version (ref forward_step.py:45-204)."""
    def fwd(params, toks, positions, caches, cache_index):
        return lm_forward(cfg, params, toks, positions=positions,
                          kv_caches=caches, cache_index=cache_index)
    return fwd


@partial(jax.jit, static_argnames=("cfg", "total_len", "prefill_len",
                                   "temperature", "top_k",
                                   "top_p", "vocab_size", "eod",
                                   "want_logprobs", "forward_fn",
                                   "kv_cache_int8"))
def _generate_jit(
    cfg: ModelConfig,
    params: Any,
    tokens: jnp.ndarray,    # [B, total_len], prompt tokens then pad
    lengths: jnp.ndarray,   # [B] prompt lengths
    key: jax.Array,
    total_len: int,
    prefill_len: int,
    temperature: float,
    top_k: int,
    top_p: float,
    vocab_size: Optional[int],
    eod: Optional[int],
    want_logprobs: bool = True,
    forward_fn=None,
    kv_cache_int8: bool = False,
):
    fwd = forward_fn or _default_fwd(cfg)
    B = tokens.shape[0]
    min_len = jnp.min(lengths)
    caches = kv_store.create(cfg, B, total_len, int8=kv_cache_int8)

    # Prefill the prompt region in one pass — the reference likewise batches
    # the common prompt prefix. min_len is dynamic, so the prefill runs a
    # *static* bucketed length covering every prompt (>= max prompt length,
    # rounded up by the caller so a 5-token prompt with 2000 new tokens does
    # not pay a 2000-position prefill); decode overwrites cache entries for
    # positions it re-runs, with identical forced-token values.
    positions = jnp.arange(total_len)[None, :]
    logits_all, caches = fwd(params, tokens[:, :prefill_len],
                             positions[:, :prefill_len], caches, 0)

    # the full-prefill fp32 log_softmax ([B, S, V]) is only paid when the
    # caller wants per-token logprobs
    logprobs_all = (jax.nn.log_softmax(logits_all.astype(jnp.float32), axis=-1)
                    if want_logprobs else None)

    # carry: (t, tokens, caches, done, key, logprobs, last_logits)
    def body2(carry):
        t, tokens, caches, done, key, lp, last_logits = carry
        key, sub = jax.random.split(key)
        prev_logits = last_logits[:, 0]
        sampled = sample_logits(prev_logits, sub, temperature, top_k, top_p,
                                vocab_size)
        in_prompt = t < lengths
        forced = tokens[:, t]
        nxt = jnp.where(in_prompt | done, forced, sampled)
        if eod is not None:
            nxt = jnp.where(done, eod, nxt)
        tokens = tokens.at[:, t].set(nxt)
        step_lp = jnp.take_along_axis(
            jax.nn.log_softmax(prev_logits.astype(jnp.float32), axis=-1),
            nxt[:, None], axis=-1)[:, 0]
        lp = lp.at[:, t - 1].set(jnp.where(done, 0.0, step_lp))
        if eod is not None:
            done = done | ((nxt == eod) & ~in_prompt)
        step_pos = jax.lax.dynamic_slice_in_dim(positions, t, 1, axis=1)
        logits_step, caches = fwd(params, nxt[:, None], step_pos, caches, t)
        return (t + 1, tokens, caches, done, key, lp, logits_step)

    def cond2(carry):
        t, tokens, caches, done, key, lp, last = carry
        return (t < total_len) & ~jnp.all(done)

    # seed the loop at t = min_len with the prefill logits at min_len-1
    gather_idx = jnp.maximum(min_len - 1, 0)
    first_logits = jnp.take_along_axis(
        logits_all, jnp.full((B, 1, 1), gather_idx), axis=1)

    # teacher-forced logprobs for the prompt region
    lp0 = jnp.zeros((B, total_len - 1), jnp.float32)
    if want_logprobs:
        prompt_lp = jnp.take_along_axis(
            logprobs_all, tokens[:, 1:prefill_len + 1][..., None],
            axis=-1)[..., 0]
        valid = (jnp.arange(1, prefill_len + 1)[None, :] < lengths[:, None])
        lp0 = lp0.at[:, :prefill_len].set(jnp.where(valid, prompt_lp, 0.0))

    done0 = jnp.zeros((B,), bool)
    carry = (min_len, tokens, caches, done0, key, lp0, first_logits)
    t, tokens, caches, done, key, lp, _ = jax.lax.while_loop(cond2, body2, carry)

    if eod is not None:
        has_eod = jnp.any(
            (tokens == eod)
            & (jnp.arange(total_len)[None, :] >= lengths[:, None]), axis=1)
        first_eod = jnp.argmax(
            (tokens == eod)
            & (jnp.arange(total_len)[None, :] >= lengths[:, None]), axis=1)
        ends = jnp.where(has_eod, first_eod + 1, total_len)
    else:
        ends = jnp.full((B,), total_len)
    return tokens, ends, lp


def generate_tokens(
    cfg: ModelConfig,
    params: Any,
    prompts: np.ndarray,     # [B, max_prompt_len] int32, right-padded
    lengths: np.ndarray,     # [B]
    max_new_tokens: int,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 0.0,
    vocab_size: Optional[int] = None,
    eod: Optional[int] = None,
    seed: int = 0,
    want_logprobs: bool = True,
    forward_fn=None,
    kv_cache_int8: bool = False,
) -> GenerationOutput:
    _refuse_state_space(cfg, "one-shot generation")
    if kv_cache_int8 and forward_fn is not None:
        raise ValueError(
            "kv_cache_int8 is supported on the single-stage forward only "
            "(the pipelined pp>1 forward threads bf16 cache pairs)")
    B, max_prompt = prompts.shape
    total_len = max_prompt + max_new_tokens
    if (cfg.position_embedding_type == "absolute"
            and total_len > (cfg.max_position_embeddings or 0)):
        raise ValueError(
            f"prompt + tokens_to_generate = {total_len} exceeds "
            f"max_position_embeddings {cfg.max_position_embeddings} — "
            "absolute position embeddings would silently clamp")
    tokens = np.zeros((B, total_len), np.int32)
    tokens[:, :max_prompt] = prompts
    # bucketed static prefill length: covers the longest prompt, rounded up
    # to 64 so nearby prompt lengths share a compile
    prefill_len = min(total_len - 1, max(1, -(-max_prompt // 64) * 64))
    toks, ends, lp = _generate_jit(
        cfg, params, jnp.asarray(tokens), jnp.asarray(lengths, jnp.int32),
        jax.random.PRNGKey(seed), total_len, prefill_len, float(temperature),
        int(top_k), float(top_p), vocab_size, eod, want_logprobs,
        forward_fn, bool(kv_cache_int8))
    return GenerationOutput(tokens=np.asarray(toks), lengths=np.asarray(ends),
                            logprobs=np.asarray(lp))


def score_tokens(cfg: ModelConfig, params: Any, tokens: np.ndarray) -> np.ndarray:
    """Teacher-forced per-token logprobs [B, S-1]
    (ref: score_and_return_on_first_stage)."""
    t = jnp.asarray(tokens, jnp.int32)
    logits = lm_forward(cfg, params, t[:, :-1])
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    out = jnp.take_along_axis(lp, t[:, 1:][..., None], axis=-1)[..., 0]
    return np.asarray(out)


def beam_search_tokens(
    cfg: ModelConfig,
    params: Any,
    prompt: np.ndarray,       # [prompt_len] single prompt (ref: batch=1 only)
    max_new_tokens: int,
    beam_size: int,
    eod: int,
    length_penalty: float = 1.0,
    kv_cache_int8: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Beam search for one prompt (the reference's beam path also requires
    batch 1, text_generation/api.py:147). Host-side loop over a jitted
    scoring step; returns (beams [beam_size, total], scores [beam_size]).
    The per-beam cache gathers are kv_store's, so the int8 store flows
    through unchanged."""
    _refuse_state_space(cfg, "beam search")
    prompt = np.asarray(prompt, np.int32)
    plen = len(prompt)
    total = plen + max_new_tokens

    # Incremental decode on the same cached path as sampling (ref beam
    # search shares the cached ForwardStep, text_generation/generation.py:288):
    # prefill the prompt once at batch 1, tile the caches across beams, then
    # one single-token forward per emitted token with per-beam cache
    # reordering (gather over the batch axis) at each step.
    caches = kv_store.create(cfg, 1, total, int8=kv_cache_int8)
    prefill_logits, caches = lm_forward(
        cfg, params, jnp.asarray(prompt)[None, :],
        positions=jnp.arange(plen)[None, :], kv_caches=caches, cache_index=0)
    caches = kv_store.repeat_rows(caches, beam_size)
    step_logits_dev = jnp.repeat(prefill_logits[:, -1], beam_size, axis=0)

    @jax.jit
    def decode_step(caches, parents, toks, t):
        caches = kv_store.take_rows(caches, parents)
        pos = jnp.full((beam_size, 1), t, jnp.int32)
        logits, caches = lm_forward(cfg, params, toks[:, None], positions=pos,
                                    kv_caches=caches, cache_index=t)
        return logits[:, 0], caches

    beams = np.tile(prompt[None, :], (beam_size, 1))
    scores = np.full((beam_size,), -1e9, np.float64)
    scores[0] = 0.0
    finished = []  # (score_with_penalty, tokens) — BeamHypotheses equivalent

    for t in range(plen, total):
        logits = np.asarray(step_logits_dev, np.float64)
        logprobs = logits - np.log(np.exp(logits - logits.max(-1, keepdims=True))
                                   .sum(-1, keepdims=True)) - logits.max(-1, keepdims=True)
        cand = scores[:, None] + logprobs  # [beams, V]
        flat = cand.reshape(-1)
        top = np.argpartition(-flat, 2 * beam_size)[: 2 * beam_size]
        top = top[np.argsort(-flat[top])]
        new_beams, new_scores, parents, new_toks = [], [], [], []
        for idx in top:
            b, v = divmod(int(idx), logits.shape[-1])
            seq = np.concatenate([beams[b], [v]])
            if v == eod:
                penalty = ((len(seq) - plen) ** length_penalty)
                finished.append((flat[idx] / penalty, seq))
            else:
                new_beams.append(seq)
                new_scores.append(flat[idx])
                parents.append(b)
                new_toks.append(v)
            if len(new_beams) == beam_size:
                break
        beams = np.stack([np.pad(s, (0, total - len(s))) for s in new_beams])[:, :t + 1]
        scores = np.asarray(new_scores)
        if len(finished) >= beam_size:
            best_possible = scores.max() / (max(1, t + 1 - plen) ** length_penalty)
            worst_kept = sorted(finished, key=lambda x: -x[0])[beam_size - 1][0]
            if worst_kept >= best_possible:
                break
        if t + 1 < total:
            step_logits_dev, caches = decode_step(
                caches, jnp.asarray(parents, jnp.int32),
                jnp.asarray(new_toks, jnp.int32), jnp.int32(t))

    for s, b in zip(scores, beams):
        penalty = (max(1, beams.shape[1] - plen) ** length_penalty)
        finished.append((s / penalty, np.concatenate([b, [eod]])))
    finished.sort(key=lambda x: -x[0])
    finished = finished[:beam_size]
    out_tokens = np.stack([np.pad(f[1], (0, total + 1 - len(f[1])),
                                  constant_values=eod) for f in finished])
    out_scores = np.asarray([f[0] for f in finished])
    return out_tokens, out_scores
