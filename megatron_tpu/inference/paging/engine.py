"""The serving engine's device programs over the page pool.

`InferenceEngine` (inference/engine.py) owns the requests, the queue, the
page tables and the loop; what it dispatches is built here, once an
engine, as jitted functions that close over plain values alone (never
over the engine: a bound method in a step's closure would hold the
engine in a reference cycle):

  * `build_decode_step`: one token for every slot. K/V writes and reads
    route through the `[N, max_pages]` device page table
    (ops/attention.py picks the paged flash-decode kernel on TPU, the
    gather elsewhere); sampling knobs are traced `[N]` arrays, so
    heterogeneous traffic shares the one compile.
  * `build_chunk_step`: `prefill_chunk` tokens of one prompt through one
    row of the table, with the write fences that park a shared prefix's
    overlap and the padded tail on the scratch page.
  * `build_draft_chunk_step`: the same chunk into the draft model's pools
    (speculative decoding, drafter "model"), write-only.

Every step that samples resolves `sample_logits_batched` as THIS module's
global when it is traced.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from megatron_tpu.inference.paging.pool import SCRATCH_PAGE
from megatron_tpu.inference.sampling import sample_logits_batched
from megatron_tpu.models.language_model import lm_forward


def rows_decoding(table):
    """[slots] int32, 1 where the slot decodes, from the decode step's
    table. A decoding slot's row holds a page, the one it writes at the
    least; an idle slot's and a prefilling slot's (its pages wait in the
    engine's `_pending_rows`) are all scratch. Not the row's first entry:
    the window's release parks that one on scratch while the slot decodes
    on (`_release_window_pages`).

    Three readers, through the forward's `state_valid`: a state-space
    mixer advances those rows' state alone, the experts route those rows
    alone, and an attention layer hands the paged decode kernel, for
    every other row, the length its loop reads as nothing to visit
    (ops/pallas/masks.py `decode_idle_length`: 0 for one query a row,
    1 - sq for the speculative verify's sq)."""
    return jnp.any(table != SCRATCH_PAGE, axis=1).astype(jnp.int32)


def make_forward(cfg, tp_comm, cp_comm):
    """lm_forward over the page pool and the state store beside it (None
    without state-space layers) -> (logits, pool, state, *counts): what
    the decode and the chunk step run. With a `cp_comm` a 3-D device
    table routes the forward through the ring-attention island
    (models/transformer.py)."""

    def forward(params, caches, state, tokens, *counts, tick=False,
                **where):
        out = lm_forward(cfg, params, tokens, kv_caches=caches,
                         ssm_state=state, tp_comm=tp_comm,
                         cp_comm=cp_comm, return_moe_aux=bool(counts),
                         **where)
        if counts:
            # the layers' shares of held rows, summed, times the pairs
            # a layer is handed: whole numbers, exact in float32;
            # behind them the held experts a read row reached, which
            # only a decode tick counts
            *out, moe_aux = out
            layers, k = cfg.expert_layers, cfg.moe_top_k
            read = jnp.sum(jnp.minimum(where["state_valid"],
                                       tokens.shape[1]))
            new = [jnp.round(moe_aux[2] * (tokens.size * k)),
                   read * (k * layers),
                   jnp.round(moe_aux[3]) if tick else 0,
                   cfg.moe_experts_held * layers if tick else 0]
            counts = (counts[0] + jnp.stack(
                [jnp.asarray(n).astype(jnp.uint32) for n in new]),)
        return (*out, *((None,) if state is None else ()), *counts)

    return forward


def _counts_out(cfg):
    """The steps' last result where they count (the engine's
    `_step_counts`: a model that holds a share of its router's experts)."""
    return ("rep",) if cfg.holds_expert_share else ()


def build_decode_step(cfg, forward, which_rows, *, vocab_size,
                      want_logprobs, donate_argnums, shard_outputs):
    """(params, caches, state, table, last_tok, lengths, keys, temps,
    top_ks, top_ps, *counts) -> (toks, lps, caches, state, keys,
    lengths + 1, *counts). `which_rows` reads the decoding slots off
    the table (`rows_decoding`, or the CP engine's over its ranks' local
    tables), for every model: the attention layers read it as the mixers
    and the router do (`rows_decoding`), and the kernel takes no trip
    for a slot it leaves out. `shard_outputs` maps a template of "kv" /
    "rep" tags to the jit's `out_shardings` keywords (the engine's
    `_jit_sharding_kwargs`); it is called here and not kept."""
    vocab, wlp = vocab_size, want_logprobs

    @partial(jax.jit, donate_argnums=donate_argnums,
             **shard_outputs(("rep", "rep", "kv", "rep", "rep", "rep")
                             + _counts_out(cfg)))
    def decode_step(params, caches, state, table, last_tok, lengths,
                    keys, temps, top_ks, top_ps, *counts):
        # one batched token for every slot: write K/V at each slot's own
        # position, attend each slot's own valid prefix through its row
        # of the table. state (None without state-space layers): this
        # tick advances the rows of the slots that decode (which_rows:
        # an idle slot's and a prefilling slot's state stays). The expert
        # layers route those rows alone, and the decode kernel visits
        # those rows' pages alone: an idle row still writes its K/V (on
        # the scratch page) and its length still grows by 1 a tick, but
        # it attends nothing, and nobody reads what it samples.
        decoding = which_rows(table)
        # counts (of a model that holds a share of its experts, else
        # absent): _step_counts, which this step adds its rows to and
        # returns behind everything else
        logits, caches, state, *counts = forward(
            params, caches, state, last_tok[:, None], *counts, tick=True,
            cache_index=lengths, page_table=table, state_valid=decoding)
        logits = logits[:, 0]
        split = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
        new_keys, subs = split[:, 0], split[:, 1]
        toks = sample_logits_batched(logits, subs, temps, top_ks,
                                     top_ps, vocab)
        if wlp:
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1),
                toks[:, None], axis=-1)[:, 0]
        else:
            lp = jnp.zeros(toks.shape, jnp.float32)
        # toks/lengths+1 re-enter the next tick as the carry
        return toks, lp, caches, state, new_keys, lengths + 1, *counts

    return decode_step


def build_chunk_step(cfg, forward, chunk, *, vocab_size, want_logprobs,
                     donate_argnums, shard_outputs):
    """The prefill step at the static chunk length `chunk`; arguments as
    `build_decode_step`'s."""
    vocab, wlp, C = vocab_size, want_logprobs, chunk
    experts = cfg.num_experts is not None

    @partial(jax.jit, donate_argnums=donate_argnums,
             **shard_outputs(("rep", "rep", "rep", "kv", "rep", "rep")
                             + _counts_out(cfg)))
    def chunk_step(params, caches, state, table_row, tokens_ext, off,
                   write_start, write_end, sample_pos, key, temp,
                   top_k, top_p, slot=None, *counts):
        """One prefill chunk of one prompt.

        tokens_ext [1, C+1]: the chunk's tokens at absolute positions
        off..off+C-1 plus the NEXT prompt token, so the chunk scores
        its last position's teacher-forced logprob without waiting
        for the next chunk. Writes outside [write_start, write_end)
        land on the scratch page (shared-prefix overlap + padded
        tail). Every call also samples from the logits at absolute
        position sample_pos (= prompt_len - 1); the host uses that
        token and the advanced key only on the final chunk, so
        non-final chunks never consume the request's PRNG chain.

        state, slot (None without state-space layers): the state
        store and the row of it the prompt belongs to. The chunk takes
        the state up where the prompt's last chunk left it and leaves
        it after the last real position (write_end - off of C: the
        padded tail moves neither the state nor the convolution's
        tail, and reaches no expert). counts: as the decode step's."""
        real = (None if state is None and not experts else
                jnp.clip(write_end - off, 0, C)[None])
        logits, caches, state, *counts = forward(
            params, caches, state, tokens_ext[:, :C], *counts,
            cache_index=off, page_table=table_row,
            page_write_start=write_start, page_write_end=write_end,
            state_row=slot, state_valid=real)
        if wlp:
            lsm = jax.nn.log_softmax(logits[0].astype(jnp.float32),
                                     axis=-1)
            plp = jnp.take_along_axis(
                lsm, tokens_ext[0, 1:, None], axis=-1)[:, 0]
        else:
            plp = jnp.zeros((C,), jnp.float32)
        # non-final chunks pass a sample_pos outside this chunk; the
        # clamp keeps the (discarded) gather in bounds
        idx = jnp.clip(sample_pos - off, 0, C - 1)
        last = jnp.take_along_axis(
            logits, jnp.full((1, 1, 1), idx), axis=1)[:, 0]
        key, sub = jax.random.split(key)
        tok = sample_logits_batched(last, sub[None], temp[None],
                                    top_k[None], top_p[None], vocab)[0]
        if wlp:
            lp = jnp.take_along_axis(
                jax.nn.log_softmax(last.astype(jnp.float32), axis=-1),
                tok[None, None], axis=-1)[0, 0]
        else:
            lp = jnp.zeros((), jnp.float32)
        return tok, lp, plp, caches, state, key, *counts

    return chunk_step


def build_draft_chunk_step(draft_cfg, donate_argnums):
    """One prefill chunk of one prompt into the DRAFT page pools
    (speculative model drafter): same table row and scratch-page
    write fences as the target chunk, so shared-prefix aliasing and
    padded-tail parking behave identically for both trees. Write-
    only — the draft never scores prompt tokens."""

    @partial(jax.jit, donate_argnums=donate_argnums)
    def draft_chunk(dparams, dcaches, table_row, tokens_c, off,
                    write_start, write_end):
        _, dcaches = lm_forward(draft_cfg, dparams, tokens_c,
                                kv_caches=dcaches, cache_index=off,
                                page_table=table_row,
                                page_write_start=write_start,
                                page_write_end=write_end)
        return dcaches

    return draft_chunk
