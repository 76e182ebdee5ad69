"""Text-level generation API.

Equivalent of megatron/text_generation/api.py (201 LoC) +
tokenization.py (118): tokenize+pad prompt batches, run generation, and
detokenize with segment boundaries. The reference's rank-0
broadcast-params-to-all-ranks choreography (api.py:93-115) has no
equivalent — a single-controller program has no ranks to convince.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from megatron_tpu.config import ModelConfig
from megatron_tpu.inference.generation import (
    beam_search_tokens, generate_tokens, score_tokens,
)


def tokenize_prompts(
    tokenizer, prompts: Sequence[str], max_prompt_len: Optional[int] = None,
    add_bos: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """Right-padded prompt batch + lengths (ref: tokenization.py:47)."""
    ids = []
    for p in prompts:
        t = list(tokenizer.tokenize(p))
        if add_bos and tokenizer.bos is not None:
            t = [tokenizer.bos] + t
        if max_prompt_len:
            t = t[:max_prompt_len]
        if not t:
            raise ValueError("empty prompt after tokenization")
        ids.append(t)
    lengths = np.asarray([len(t) for t in ids], np.int32)
    width = int(lengths.max())
    batch = np.full((len(ids), width), tokenizer.pad, np.int32)
    for i, t in enumerate(ids):
        batch[i, :len(t)] = t
    return batch, lengths


def generate_and_post_process(
    cfg: ModelConfig,
    params: Any,
    tokenizer,
    prompts: Sequence[str],
    tokens_to_generate: int = 64,
    temperature: float = 1.0,
    top_k_sampling: int = 0,
    top_p_sampling: float = 0.0,
    add_BOS: bool = False,
    return_output_log_probs: bool = False,
    random_seed: int = 0,
    forward_fn=None,
    kv_cache_int8: bool = False,
    engine=None,
    deadline_s=None,
    spec: bool = True,
    request_id=None,
    timing=None,
):
    """(texts, segments, logprobs, tokens) like the reference's
    generate_and_post_process (api.py:19-90). forward_fn plugs in the
    pipelined pp>1 forward (inference/pipelined.py); engine routes the
    request through a continuous-batching InferenceEngine
    (inference/engine.py) instead of the one-shot generate_tokens — its
    slot scheduler lets concurrent callers share decode steps.
    deadline_s (engine path only) bounds each request's total wall time:
    past it the engine fails the request with RequestTimeoutError
    (HTTP 504) instead of leaving the caller waiting. spec=False pins
    the request to plain one-token-per-tick decode on a speculating
    engine (no-op otherwise); greedy output is identical either way.
    request_id (engine path) names the prompts' requests in the journal;
    timing, a dict, takes the engine's `engine_s` for them (engine.py
    `generate`): what the server's `serve_reply` record sets against its
    own time."""
    if tokens_to_generate < 0:
        raise ValueError("tokens_to_generate must be >= 0")
    prompt_tokens, lengths = tokenize_prompts(tokenizer, prompts,
                                              add_bos=add_BOS)
    if tokens_to_generate == 0:
        # scoring mode (ref: tokens_to_generate==0 -> teacher-forced)
        lp = score_tokens(cfg, params, prompt_tokens)
        texts = [tokenizer.detokenize(t[:l]) for t, l in zip(prompt_tokens, lengths)]
        return texts, None, lp, prompt_tokens

    if engine is not None:
        # the engine owns its own forward and cache configuration — a
        # conflicting request must fail loudly, not be silently dropped
        if forward_fn is not None:
            raise ValueError(
                "engine= and forward_fn= are mutually exclusive (the "
                "continuous-batching engine runs the single-stage forward)")
        if bool(kv_cache_int8) != bool(engine.kv_cache_int8):
            raise ValueError(
                f"kv_cache_int8={kv_cache_int8} conflicts with the "
                f"engine's kv_cache_int8={engine.kv_cache_int8} — the "
                "cache mode is fixed when the engine is built")
        out = engine.generate(
            prompt_tokens, lengths, max_new_tokens=tokens_to_generate,
            temperature=temperature, top_k=top_k_sampling,
            top_p=top_p_sampling, eod=tokenizer.eod, seed=random_seed,
            deadline_s=deadline_s, spec=spec, request_id=request_id)
        if timing is not None:
            timing["engine_s"] = out.engine_s
    else:
        out = generate_tokens(
            cfg, params, prompt_tokens, lengths,
            max_new_tokens=tokens_to_generate,
            temperature=temperature, top_k=top_k_sampling, top_p=top_p_sampling,
            vocab_size=tokenizer.vocab_size, eod=tokenizer.eod, seed=random_seed,
            want_logprobs=return_output_log_probs, forward_fn=forward_fn,
            kv_cache_int8=kv_cache_int8)

    texts, segments = [], []
    for row, end in zip(out.tokens, out.lengths):
        toks = row[: int(end)]
        texts.append(tokenizer.detokenize(toks))
        segments.append([tokenizer.detokenize([t]) for t in toks])
    logprobs = out.logprobs if return_output_log_probs else None
    return texts, segments, logprobs, out.tokens


def beam_search_and_post_process(
    cfg: ModelConfig,
    params: Any,
    tokenizer,
    prompts: Sequence[str],
    tokens_to_generate: int = 64,
    beam_size: int = 4,
    add_BOS: bool = False,
    length_penalty: float = 1.0,
    kv_cache_int8: bool = False,
):
    """(texts, segments, scores) — ref api.py:147-201 (batch of 1 only)."""
    if len(prompts) != 1:
        raise ValueError("beam search supports a single prompt (as in the reference)")
    prompt_tokens, lengths = tokenize_prompts(tokenizer, prompts,
                                              add_bos=add_BOS)
    beams, scores = beam_search_tokens(
        cfg, params, prompt_tokens[0, :int(lengths[0])],
        max_new_tokens=tokens_to_generate, beam_size=beam_size,
        eod=tokenizer.eod, length_penalty=length_penalty,
        kv_cache_int8=kv_cache_int8)
    texts = [tokenizer.detokenize(b) for b in beams]
    segments = [[tokenizer.detokenize([t]) for t in b] for b in beams]
    return texts, segments, scores
