"""PagedInferenceEngine: the serving engine over a shared page pool.

Drop-in paged mode of the slot engine (inference/engine.py,
``--serve_kv_paging``). The per-slot ``[N, max_seq_len, ...]`` cache rows
become one pool of fixed-size pages shared by every slot:

  * admission allocates pages for the PROMPT span only (a young sequence
    holds the pages it has, not its worst case); decode grows a slot one
    page at a time as its length crosses page boundaries;
  * requests sharing a prompt prefix alias the same refcounted pages via
    the radix tree (radix.py) and skip prefill for the shared span;
  * prompts enter the cache ``prefill_chunk`` tokens per tick, one chunk
    before each batched decode (scheduler.py), so one long prompt can
    never stall the whole batch;
  * under memory pressure the engine first evicts cache-only prefix
    pages (LRU), then preempts the lowest-priority slot — the most
    recently admitted request (LIFO, so later arrivals yield to earlier
    ones). A preempted request keeps its sampled tokens and PRNG chain
    (Request.resume_key) and resumes by teacher-forced recompute of
    prompt + generated, which is exact: it finishes with the tokens it
    would have produced without the preemption.

Parity gates (tests/test_serving_engine.py): token-identical to the slot
engine on the serving matrix — greedy, sampled, int8, ragged, preempted
— and zero decode recompiles after warmup (the decode step's shapes,
including the ``[N, max_pages]`` device page table, never change).
"""

from __future__ import annotations

import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import ModelConfig
from megatron_tpu.inference.engine import (
    ADMIT, APPLY, EVICT, PAGES, PRE, PREEMPT, PREFILL, InferenceEngine,
    Request, _InFlight,
)
from megatron_tpu.inference.paging.pool import SCRATCH_PAGE, PagePool
from megatron_tpu.inference.paging.radix import RadixPrefixCache
from megatron_tpu.inference.paging.scheduler import (
    ChunkedPrefillQueue, PrefillTask,
)
from megatron_tpu.inference.sampling import sample_logits_batched
from megatron_tpu.ops import kv_store, ssm

# what the steps of a model that holds a share of its router's experts
# count on the device, in `_step_counts`' order: the key in `stats`, the
# counter on /metrics and its help
_MOE_COUNTS = (
    ("moe_held_rows", "engine_moe_held_rows_total",
     "those of engine_moe_rows_total sent to experts held on this chip"),
    ("moe_rows", "engine_moe_rows_total",
     "(row, choice) pairs the steps routed, over the expert layers (rows "
     "somebody reads x experts a token x expert layers; a model that holds "
     "a share of its router's experts)"),
    ("moe_experts_read", "engine_moe_experts_read_total",
     "held experts a decoding row reached, over the decode ticks and the "
     "expert layers (the router's count: what the experts' kernels may "
     "leave unread)"),
    ("moe_experts_offered", "engine_moe_experts_offered_total",
     "held experts there were for them (held x expert layers a tick)"),
)


class PagedInferenceEngine(InferenceEngine):
    """Slot scheduler + paged KV pool + radix prefix cache.

    Same threading contract as the base engine: submit() from any
    thread, step()/run_until_idle() from one driver thread.
    """

    def __init__(self, cfg: ModelConfig, params: Any, num_slots: int = 8,
                 max_seq_len: Optional[int] = None,
                 kv_cache_int8: bool = False,
                 page_size: int = 16, prefill_chunk: int = 32,
                 num_pages: Optional[int] = None,
                 vocab_size: Optional[int] = None, mesh=None,
                 want_logprobs: bool = True, metrics=None,
                 flight_recorder=None,
                 force_donate: Optional[bool] = None,
                 max_queue: Optional[int] = None,
                 speculative=None,
                 compress_collectives: str = "none",
                 comm_policy=None,
                 comm_chunk: int = 32):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if num_pages is not None and num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is scratch), got {num_pages}")
        self.page_size = int(page_size)
        self.prefill_chunk = int(prefill_chunk)  # validated by the queue
        self.num_pages = num_pages
        self.max_pages = 0          # set by _fresh_caches (needs max_seq_len)
        self.prefix_cache: Optional[RadixPrefixCache] = None
        super().__init__(
            cfg, params, num_slots=num_slots, max_seq_len=max_seq_len,
            kv_cache_int8=kv_cache_int8, vocab_size=vocab_size, mesh=mesh,
            want_logprobs=want_logprobs, metrics=metrics,
            flight_recorder=flight_recorder, force_donate=force_donate,
            max_queue=max_queue, speculative=speculative,
            compress_collectives=compress_collectives,
            comm_policy=comm_policy, comm_chunk=comm_chunk)
        if self.num_pages - 1 < self.max_pages:
            raise ValueError(
                f"num_pages={self.num_pages} cannot hold even one full "
                f"sequence ({self.max_pages} pages of {self.page_size} for "
                f"max_seq_len {self.max_seq_len}, + the scratch page)")

        N = num_slots
        self.pool = PagePool(self.num_pages)
        self.prefix_cache = RadixPrefixCache(
            self.pool, self.page_size, evict_span=self.timers(EVICT))
        # host page tables: tables[i] is slot i's logical->physical map.
        # Mid-prefill slots keep their REAL row in _pending_rows and a
        # scratch row here, so the shared decode table can never route an
        # idle-drift write into a half-filled (possibly shared) page.
        self.tables = np.zeros((N, self.max_pages), np.int32)
        self._pending_rows = {}
        self._device_table = None
        self._table_dirty = True
        self.prefill_queue = ChunkedPrefillQueue(self.prefill_chunk)
        self._chunk_step = self._build_chunk_step()
        self._carry_row_writer = None  # once-jitted (_write_carry_row)
        # static per-chunk wire price for the compressed-collective
        # counters (one [1, C] forward; quant/collectives.py)
        from megatron_tpu.quant.collectives import forward_comm_bytes

        self._comm_chunk_bytes = forward_comm_bytes(
            cfg, self.tp_comm, 1, self.prefill_chunk)
        self._draft_chunk_step = (self._build_draft_chunk_step()
                                  if self._has_draft_model() else None)
        # admission order for the preemption policy (higher = younger)
        self._admit_seq = [0] * N
        self._admit_counter = 0
        # sliding-window release cursor: first page index of each slot
        # NOT yet released (lengths never shrink below the committed
        # value, so release progress is monotone — the per-tick scan
        # starts here instead of at page 0)
        self._window_cursor = [0] * N

        self.stats.update({
            "prefix_hits": 0, "prefix_misses": 0,
            "prefix_tokens_saved": 0, "prefill_tokens": 0,
            "prefill_chunks": 0, "preemptions": 0,
            "window_pages_released": 0, "pages_evicted": 0,
            # prefill_live_block_share joins them at the first chunk
            "prefill_blocks_visited": 0, "prefill_blocks_held": 0,
        })
        m = self.metrics
        self._m_pages_total = m.gauge("engine_pages_total",
                                      "KV pool pages (minus scratch)")
        self._m_pages_free = m.gauge("engine_pages_free",
                                     "KV pool pages on the free list")
        self._m_prefix_hits = m.counter(
            "engine_prefix_cache_hits_total",
            "admissions that aliased cached prefix pages")
        self._m_prefix_misses = m.counter(
            "engine_prefix_cache_misses_total",
            "admissions with no cached prefix")
        self._m_prefix_saved = m.counter(
            "engine_prefix_tokens_saved_total",
            "prefill positions skipped via the prefix cache")
        self._m_preempted = m.counter(
            "engine_preemptions_total",
            "slots preempted under page-pool pressure")
        self._m_chunks = m.counter("engine_prefill_chunks_total",
                                   "chunked-prefill steps executed")
        self._m_prefill_blocks = m.gauge(
            "engine_prefill_live_block_share",
            "KV blocks the last prefill chunk's attention kernel visited "
            "over the blocks its page table holds a query tile")
        self._m_window_released = m.counter(
            "engine_window_pages_released_total",
            "pages freed from behind the sliding attention window")
        self._m_evicted = m.counter(
            "engine_pages_evicted_total",
            "cache-only prefix pages the radix tree gave back to the pool")
        self._m_pages_total.set(self.num_pages - 1)
        self._m_pages_free.set(self.pool.free_pages)
        # a model with state-space layers: self.state (_fresh_caches) is
        # its state store, a row a slot, beside the KV pool of its
        # attention layers; None for every other model. The row is zeroed
        # at admission, carried from chunk to chunk of its slot's prompt,
        # advanced by the decode ticks the slot takes part in (those whose
        # row of the decode table holds a page), and dropped with the slot.
        self._m_state_bytes = m.gauge(
            "engine_state_bytes",
            "recurrent state held beside the KV pages (state-space layers)")
        self._m_state_resets = m.counter(
            "engine_state_resets_total",
            "slot states zeroed at admission (state-space layers)")
        if self.state is not None:
            self.stats["state_resets"] = 0
            self._m_state_bytes.set(ssm.state_bytes(self.state))
            self._zero_state_row = jax.jit(
                ssm.zero_row, donate_argnums=(0,) if self._donate() else ())
        # a model that holds a share of its router's experts: how many of
        # the (row, choice) pairs its steps computed FOR A ROW SOMEBODY
        # READS (a decoding slot's, a chunk's real positions: the others
        # are not routed, ops/moe.py moe_block `rows_read`) went to experts
        # held here, summed over the expert layers; and, of the decode
        # ticks alone, how many of the held experts such a row reached
        # (the matrices the tick's expert kernels had to move) of those
        # there are. Both steps add to one vector of counters on the device
        # (_step_counts: [held, all, experts read, experts offered],
        # uint32, which wraps), read with the steps' tokens
        # (_apply_counts).
        self._m_moe = [m.counter(name, text) for _, name, text in _MOE_COUNTS]
        if self.cfg.holds_expert_share:
            for key, _, _ in _MOE_COUNTS:
                self.stats[key] = 0
            self._step_counts = self._commit_small(
                np.zeros(len(_MOE_COUNTS), np.uint32))
            self._counts_seen = np.zeros(len(_MOE_COUNTS), np.uint32)

    # ----- cache + shape policy -------------------------------------------

    def _kernel_seq_multiple(self) -> int:
        # logical capacity is whole pages; the paged kernel's grid is
        # per-page, so the dense kernel's 128 constraint doesn't apply
        return self.page_size

    def _refuse_unless_it_carries_state(self, mesh, speculative) -> None:
        if speculative is not None:
            raise NotImplementedError(
                "speculative decoding over a model with state-space layers: "
                "a rejected draft rolls the length back, and the recurrent "
                "state has no rollback")
        if mesh is not None or getattr(self, "cp_comm", None) is not None:
            raise NotImplementedError(
                "sharded serving (a tensor- or context-parallel mesh) of a "
                "model with state-space layers: the state store and the "
                "mixer are not sharded; serve it on one chip")

    def _fresh_caches(self):
        """Paged pools: num_pages rows of page_size positions
        (ops/kv_store.py; int8 with per-position scales), of the attention
        layers; with them self.state, the state-space layers' state store
        (ops/ssm.py: a zeroed row a slot; None for a model without). On the
        failed-step rebuild path every cached prefix dies with the pool
        bytes, and mid-prefill slots lose their computed chunks — fail
        them like the active ones the caller already failed."""
        if self.prefix_cache is not None:
            for i in sorted(self.prefill_queue.slots):
                req = self.slots[i]
                if req is not None:
                    self._clear_slot(i)
                    req._finish("engine cache rebuilt after a failed step")
            self.prefix_cache.clear()
            self._m_pages_free.set(self.pool.free_pages)
        if self.num_pages is None:
            # default pool = full slot-engine capacity (every slot can
            # grow to max_seq_len); shrink it to oversubscribe
            self.max_pages = -(-self.max_seq_len // self.page_size)
            self.num_pages = self.num_slots * self.max_pages + 1
        else:
            self.max_pages = -(-self.max_seq_len // self.page_size)
        self.state = (self._commit(ssm.create_state(self.cfg, self.num_slots))
                      if self.cfg.has_ssm else None)
        return kv_store.create(self.cfg, self.num_pages, self.page_size,
                               int8=self.kv_cache_int8)

    def _fresh_draft_caches(self):
        """Draft-model page pools (speculative decoding): the draft
        config's own layer/head geometry over the SAME page count and
        page size as the target pools, addressed through the SAME per-
        slot page tables — one allocation/refcount/prefix-aliasing
        story covers both trees (a page shared via the radix cache is
        shared in both pools, since both were written through the same
        table by the original prefill). Always bf16/f32."""
        return kv_store.create(self.spec.draft_cfg, self.num_pages,
                               self.page_size)

    def _spec_paged(self) -> bool:
        return True

    # ----- jitted device steps --------------------------------------------

    def _donate_with_state(self):
        """Both steps write the pool and the state store in place."""
        return (1, 2) if self._donate() else ()

    def _forward(self):
        """lm_forward over the page pool and the state store beside it
        (None without state-space layers) -> (logits, pool, state): what
        both jitted steps run."""
        cfg, tp_comm = self.cfg, self.tp_comm
        # the CP engine sets cp_comm before super().__init__ so the same
        # builders serve it — a 3-D device table then routes the forward
        # through the ring-attention island (models/transformer.py)
        cp_comm = getattr(self, "cp_comm", None)
        from megatron_tpu.models.language_model import lm_forward

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

    def _counts_template(self):
        """The steps' last result where they count (`_step_counts`)."""
        return ("rep",) if self.cfg.holds_expert_share else ()

    def _counts_arg(self):
        return () if self._step_counts is None else (self._step_counts,)

    def _rows_decoding(self):
        """The function the decode step reads its table with: [slots]
        int32, 1 where the slot decodes. A decoding slot's row of the
        table holds a page, the one it writes at the least; an idle
        slot's and a prefilling slot's (its pages wait in `_pending_rows`)
        are all scratch. Not the row's first entry: the window's release
        parks that one on scratch while the slot decodes on
        (_release_window_pages). It holds no reference to the engine, as
        nothing the steps close over does."""
        return lambda table: jnp.any(table != SCRATCH_PAGE,
                                     axis=1).astype(jnp.int32)

    def _build_decode_step(self):
        vocab, wlp = self.vocab_size, self.want_logprobs
        experts = self.cfg.num_experts is not None
        forward, rows_decoding = self._forward(), self._rows_decoding()
        from functools import partial

        @partial(jax.jit, donate_argnums=self._donate_with_state(),
                 **self._jit_sharding_kwargs(
                     ("rep", "rep", "kv", "rep", "rep", "rep")
                     + self._counts_template()))
        def decode_step(params, caches, state, table, last_tok, lengths,
                        keys, temps, top_ks, top_ps, *counts):
            # identical to the slot decode step except K/V writes and
            # reads route through the page table (ops/attention.py picks
            # the paged flash-decode kernel on TPU, the gather elsewhere).
            # state (None without state-space layers): this tick advances
            # the rows of the slots that decode (_rows_decoding: an idle
            # slot's and a prefilling slot's state stays). The expert
            # layers route those rows alone. A model with neither is not
            # told.
            decoding = (None if state is None and not experts else
                        rows_decoding(table))
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
            return toks, lp, caches, state, new_keys, lengths + 1, *counts

        return decode_step

    def _build_chunk_step(self):
        vocab, wlp = self.vocab_size, self.want_logprobs
        C = self.prefill_chunk
        experts = self.cfg.num_experts is not None
        forward = self._forward()
        from functools import partial

        @partial(jax.jit, donate_argnums=self._donate_with_state(),
                 **self._jit_sharding_kwargs(
                     ("rep", "rep", "rep", "kv", "rep", "rep")
                     + self._counts_template()))
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

    def _build_draft_chunk_step(self):
        """One prefill chunk of one prompt into the DRAFT page pools
        (speculative model drafter): same table row and scratch-page
        write fences as the target chunk, so shared-prefix aliasing and
        padded-tail parking behave identically for both trees. Write-
        only — the draft never scores prompt tokens."""
        dcfg = self.spec.draft_cfg
        from functools import partial

        from megatron_tpu.models.language_model import lm_forward

        @partial(jax.jit, donate_argnums=self._donate())
        def draft_chunk(dparams, dcaches, table_row, tokens_c, off,
                        write_start, write_end):
            _, dcaches = lm_forward(dcfg, dparams, tokens_c,
                                    kv_caches=dcaches, cache_index=off,
                                    page_table=table_row,
                                    page_write_start=write_start,
                                    page_write_end=write_end)
            return dcaches

        return draft_chunk

    # ----- page accounting -------------------------------------------------

    def _alloc_pages(self, n: int,
                     logical_start: int = 0) -> Optional[List[int]]:
        """n fresh pages, evicting LRU cache-only prefix pages if the
        free list can't cover it. None = still dry (caller defers or
        preempts). logical_start is the logical page index the run
        starts at within its row — ignored here, but the CP engine's
        striped pool draws each page from the rank owning that logical
        slot (inference/context_parallel/pool.py)."""
        pages = self.pool.alloc(n)
        if pages is None:
            self._note_evicted(
                self.prefix_cache.evict(n - self.pool.free_pages))
            pages = self.pool.alloc(n)
        if pages is not None:
            self._m_pages_free.set(self.pool.free_pages)
        return pages

    def _note_evicted(self, freed: int) -> int:
        if freed:
            self.stats["pages_evicted"] += freed
            self._m_evicted.inc(freed)
        return freed

    def _serve_ticks_fields(self) -> dict:
        fields = {"evicted": self.stats["pages_evicted"],
                  "prefill_blocks": [self.stats["prefill_blocks_visited"],
                                     self.stats["prefill_blocks_held"]]}
        if self.cfg.holds_expert_share:
            fields["moe_rows"] = [self.stats["moe_held_rows"],
                                  self.stats["moe_rows"]]
            fields["moe_experts"] = [self.stats["moe_experts_read"],
                                     self.stats["moe_experts_offered"]]
        return fields

    def _slow_tick_fields(self) -> dict:
        return {"pages_free": self.pool.free_pages}

    def _release_slot_pages(self, i: int) -> None:
        row = self._pending_rows.pop(i, self.tables[i])
        live = [int(p) for p in row if p != SCRATCH_PAGE]
        if live:
            self.pool.release(live)
        self.tables[i] = SCRATCH_PAGE
        self._table_dirty = True
        self._m_pages_free.set(self.pool.free_pages)

    def _clear_slot(self, i: int):
        self._release_slot_pages(i)
        self.prefill_queue.drop_slot(i)
        self._window_cursor[i] = 0
        super()._clear_slot(i)

    # ----- admission -------------------------------------------------------

    def _admit(self) -> int:
        n = 0
        for i in range(self.num_slots):
            if self.slots[i] is not None:
                continue
            with self._cv:
                req = self._queue.popleft() if self._queue else None
                if req is not None:
                    # visible to wait_idle(): popped but not yet in a slot
                    self._admitting += 1
            if req is None:
                break
            try:
                if not self._try_assign(i, req):
                    # pool can't cover the prompt right now: keep arrival
                    # order (front of the queue) and stop admitting —
                    # active slots retiring will free pages
                    with self._cv:
                        self._queue.appendleft(req)
                        self._m_queue.set(len(self._queue))
                    break
                n += 1
                with self._cv:
                    self._m_queue.set(len(self._queue))
            finally:
                with self._cv:
                    self._admitting -= 1
                self.last_progress_time = time.monotonic()
        return n

    def _try_assign(self, i: int, req: Request) -> bool:
        """Give req slot i: alias cached prefix pages, allocate the rest
        of the prompt span, queue the chunked prefill. False = defer
        (req untouched); a request no idle engine could EVER fit is
        failed loudly instead (returns True: req was consumed)."""
        resumed = req.resume_key is not None or bool(req.generated)
        toks = (np.concatenate([np.asarray(req.prompt, np.int32),
                                np.asarray(req.generated, np.int32)])
                if resumed else np.asarray(req.prompt, np.int32))
        p_ext = len(toks)
        ps = self.page_size
        # the prefix cache gives a model with state-space layers no hit:
        # a hit needs the recurrent state at the prefix's end beside its
        # pages, and no snapshot holds it yet (the tree is never asked,
        # and _finish_prefill enters nothing into it)
        hit_pages, hit_lps = (([], []) if self.cfg.has_ssm
                              else self.prefix_cache.lookup(toks))
        span = len(hit_pages) * ps
        n_prompt_pages = -(-p_ext // ps)
        # retain the hits BEFORE allocating: _alloc_pages may evict
        # cache-only pages, and un-pinned hit pages are exactly that —
        # an eviction here would free a hit page and hand it back as
        # "fresh", mapping one physical page at two logical blocks
        self.pool.retain(hit_pages)
        fresh = self._alloc_pages(n_prompt_pages - len(hit_pages),
                                  logical_start=len(hit_pages))
        if fresh is None:
            self.pool.release(hit_pages)
            if self.num_active == 0:
                req._finish(
                    f"prompt needs {n_prompt_pages} pages but the pool has "
                    f"{self.pool.free_pages} free with no active slots to "
                    f"wait for (num_pages={self.num_pages})")
                self.stats["rejected"] += 1
                self._m_rejected.inc()
                return True
            return False
        self._m_pages_free.set(self.pool.free_pages)

        row = np.zeros(self.max_pages, np.int32)
        row[:len(hit_pages)] = hit_pages
        row[len(hit_pages):n_prompt_pages] = fresh
        self._pending_rows[i] = row
        if self.state is not None:
            # a sequence starts (a preempted one again, from position 0).
            # The tick in flight may still advance the old occupant's row
            # (one that ended by eod runs one tick more): this write is
            # dispatched on the same chain of donated `state` buffers, so
            # it orders after that tick by data dependence
            self.state = self._zero_state_row(self.state, np.int32(i))
            self.stats["state_resets"] += 1
            self._m_state_resets.inc()
        self.slots[i] = req
        if req.first_token_time is None:
            req.slot_time = time.monotonic()
        self._admit_counter += 1
        self._admit_seq[i] = self._admit_counter

        # recompute starts one position INSIDE the shared span so the
        # boundary token's teacher-forced logprob comes from real logits;
        # its K/V write is fenced onto scratch (write_start = span)
        start = max(span - 1, 0)
        task = PrefillTask(
            slot=i, tokens=toks, start=start, off=start,
            write_start=span,
            # a fresh chain stays a device array: reading it back would
            # wait for the tick in flight
            key=(np.asarray(req.resume_key) if req.resume_key is not None
                 else jax.random.PRNGKey(req.seed)),
            resumed=resumed, t_start=time.monotonic())
        if not resumed and span > 0:
            # cached teacher-forced logprobs for tokens 1..span-1; the
            # recomputed chunks continue seamlessly from token `span`
            task.plp_parts.extend(hit_lps)
        self.prefill_queue.add(task)

        if span > 0:
            req.prefix_tokens += start
            self.stats["prefix_hits"] += 1
            self.stats["prefix_tokens_saved"] += start
            self._m_prefix_hits.inc()
            self._m_prefix_saved.inc(start)
        else:
            self.stats["prefix_misses"] += 1
            self._m_prefix_misses.inc()
        self.stats["admitted"] += 1
        self._m_admitted.inc()
        self._m_active.set(self.num_active)
        return True

    # ----- chunked prefill -------------------------------------------------

    def _prefill_tick(self) -> int:
        """Dispatch at most ONE chunk of the oldest incomplete prefill.
        Returns 1 when a chunk ran (progress signal for run_until_idle).
        Nothing of it is read here: the chunk queues behind the tick in
        flight, its scalars go up with the call (numpy values, no device
        array made one by one), its prompt logprobs stay device arrays
        until the prompt's last chunk, and that chunk's first token is
        read with the ticks (_finish_prefill)."""
        task = self.prefill_queue.peek()
        if task is None:
            return 0
        i = task.slot
        req = self.slots[i]
        C = self.prefill_chunk
        off = task.off
        toks_ext = np.zeros((1, C + 1), np.int32)
        avail = task.tokens[off:off + C + 1]
        toks_ext[0, :len(avail)] = avail
        row = self._pending_rows[i]
        self._note_prefill_blocks(off, task.total)
        try:
            tok, lp, plp, self.caches, self.state, key, *counts = (
                self._chunk_step(
                    self.params, self.caches, self.state,
                    self._chunk_table_arg(row),
                    toks_ext, np.int32(off),
                    np.int32(task.write_start), np.int32(task.total),
                    np.int32(task.total - 1), task.key,
                    np.float32(req.temperature), np.int32(req.top_k),
                    np.float32(req.top_p),
                    None if self.state is None else np.int32(i),
                    *self._counts_arg()))
            self._step_counts, = counts or (None,)
            if self._has_draft_model():
                # mirror the chunk into the draft pools through the same
                # table row and write fences
                self.draft_caches = self._draft_chunk_step(
                    self.draft_params, self.draft_caches,
                    self._chunk_table_arg(row),
                    toks_ext[:, :C], np.int32(off),
                    np.int32(task.write_start), np.int32(task.total))
        except Exception as e:  # noqa: BLE001 - a failing chunk must fail
            # THIS request, not strand it un-signalled and kill the loop
            # (same contract as the slot engine's prefill failure)
            self._clear_slot(i)
            req._finish(f"prefill failed: {e}")
            self.stats["rejected"] += 1
            self._m_rejected.inc()
            if self._donate():
                # the failed call may have consumed the donated pools
                # (target AND draft trees), and what is in flight with them
                self._drop_inflight()
                for j, other in enumerate(self.slots):
                    if other is not None:
                        self._clear_slot(j)
                        other._finish(f"prefill failed: {e}")
                self._rebuild_caches()
            self._m_active.set(self.num_active)
            return 1
        n = min(C, task.total - off)
        if self.want_logprobs:
            task.plp_parts.append(plp)  # the device's, until the last chunk
        req.chunks += 1
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_tokens"] += n
        self._count_comm(self._comm_chunk_bytes)
        self._m_chunks.inc()
        if self.flight_recorder is not None:
            self.flight_recorder.heartbeat(
                f"prefill chunk slot {i} ({off}+{n}/{task.total})")
        if self.prefill_queue.advance(task, n):
            self._finish_prefill(i, task, tok, lp, key)
        return 1

    def _note_prefill_blocks(self, off: int, total: int) -> None:
        """Set `engine_prefill_live_block_share` for the chunk about to
        run: the trips the chunk kernel's loops take (a query tile's, over
        the blocks its queries see below the prompt's end) over the blocks
        the row's table holds a query tile, from the host's offset and
        length through the kernel's own loop bounds. The twin of
        `engine_decode_live_block_share`; the journal's `serve_ticks`
        carries both counts summed over every chunk."""
        from megatron_tpu.ops.pallas.flash_template import (
            chunk_blocks_visited)

        cfg = self.cfg
        visited, held = chunk_blocks_visited(
            off, self.prefill_chunk, total,
            cfg.num_attention_heads // cfg.n_kv_heads, self.max_pages,
            self.page_size, self._kernel_kv_heads(),
            window=cfg.attention_kind.sliding_window_size)
        self.stats["prefill_blocks_visited"] += visited
        self.stats["prefill_blocks_held"] += held
        self.stats["prefill_live_block_share"] = visited / held
        self._m_prefill_blocks.set(visited / held)

    def _finish_prefill(self, i: int, task: PrefillTask, tok, lp, key):
        """The prompt's last chunk is dispatched: publish the slot's table
        row to the shared decode table, arm the decode mirrors, and write
        the first sampled token and the chain into the slot's row of the
        device carry, so the slot decodes in this step's tick. `tok`, `lp`
        and `key` are device values nobody has read: what the host owes
        the request for them (the token, the logprobs, the radix tree's
        entry) waits in flight and is paid at its read (_read_first)."""
        req = self.slots[i]
        row = self._pending_rows.pop(i)
        self.tables[i] = row
        self._table_dirty = True
        self.lengths[i] = task.total
        self.temps[i] = req.temperature
        self.top_ks[i] = req.top_k
        self.top_ps[i] = req.top_p
        self._carry_dirty = True
        self._write_carry_row(i, tok, key)
        if self.spec is not None:
            self.spec_on[i] = bool(req.spec)
            self._spec_rows_dev = None
        self._owed[i] += 1
        pinned: tuple = ()
        p0 = len(req.prompt)
        if p0 >= self.page_size and not self.cfg.has_ssm:
            # the FULL pages of the ORIGINAL prompt, for the radix tree.
            # They enter it at the read, with their logprobs; held until
            # then, so that neither the window's release nor a retirement
            # hands one back to the pool in between
            pinned = tuple(int(p) for p in row[:p0 // self.page_size])
            self.pool.retain(pinned)
        plps = (list(task.plp_parts)
                if self.want_logprobs and not task.resumed else [])
        rec = _InFlight(rows=[(i, req)],
                        out=self._start_fetch((tok, lp, plps,
                                               self._step_counts)),
                        step=self._step_no, t0=time.monotonic(),
                        task=task, pinned=pinned)
        if self.spec is not None:
            # the speculative tick is synchronous: it proposes from the
            # tokens, so the mirrors must be true before it runs
            self._read(rec)
        else:
            self._inflight.append(rec)

    def _write_carry_row(self, i: int, tok, key) -> None:
        """One row of the device carry takes a finished prompt's first
        token and PRNG chain, both device values: a device-side write
        (as `zero_row` is for the state), so that one prompt's end stalls
        no other row. Lengths and knobs go up from the mirrors
        (_init_carry)."""
        if self._carry_row_writer is None:
            def write_carry_row(last, keys, row, tok, key):
                return last.at[row].set(tok), keys.at[row].set(key)

            # nothing donated: `last` is also the tick in flight's tokens
            self._carry_row_writer = jax.jit(
                write_carry_row,
                **self._jit_sharding_kwargs(("rep", "rep")))
        last, lens, keys, temps, top_ks, top_ps = self._init_carry()
        last, keys = self._carry_row_writer(last, keys, np.int32(i), tok,
                                            key)
        self._carry = (last, lens, keys, temps, top_ks, top_ps)

    def _read(self, rec: _InFlight) -> None:
        if rec.task is None:
            return super()._read(rec)
        self._read_first(rec)

    def _read_first(self, rec: _InFlight) -> None:
        """Read a finished prompt's first token: record it and the
        prompt's logprobs, and register the prompt's full pages in the
        radix tree."""
        tok, lp, plps, counts = self._fetch(rec)
        with self.timers(APPLY):
            self._apply_first(rec, tok, lp, plps)
            self._apply_counts(counts)

    def _apply_first(self, rec: _InFlight, tok, lp, plps) -> None:
        (i, req), = rec.rows
        task = rec.task
        rec.out = None  # the device's copies go here, not between phases
        if self.slots[i] is not req:   # the rule of every row in flight
            self.pool.release(rec.pinned)
            return
        self._owed[i] -= 1
        self.last_tok[i] = int(tok)
        req.generated.append(int(tok))
        req.logprobs.append(float(lp))
        if not task.resumed and self.want_logprobs:
            req.prompt_logprobs = [
                float(x) for x in np.concatenate(plps)[:task.total - 1]
            ] if plps else []
        if rec.pinned:
            # only FULL pages of the ORIGINAL prompt enter the tree (the
            # partially-filled tail page stays private — decode writes
            # into it); resumes re-register recomputed pages, and insert
            # skips paths already cached. The tree holds its own
            # references now: the pin goes
            self.prefix_cache.insert(req.prompt, rec.pinned,
                                     req.prompt_logprobs)
            self.pool.release(rec.pinned)
            self._m_pages_free.set(self.pool.free_pages)
        now = time.monotonic()
        self._m_prefill.observe(now - task.t_start)
        if not task.resumed:
            req.first_token_time = now
            if req.submit_time is not None:
                self._m_ttft.observe(now - req.submit_time)
        self._m_tokens.inc()
        self.last_progress_time = now
        if self._req_finished(req):
            self._retire(i)

    def _drop_inflight(self) -> None:
        for rec in self._inflight:
            if rec.pinned:
                self.pool.release(rec.pinned)
        super()._drop_inflight()

    # ----- preemption ------------------------------------------------------

    def _preempt_one(self) -> bool:
        """Preempt the youngest active slot (LIFO — later arrivals yield
        pages to earlier ones). Its request re-enters the queue FRONT and
        resumes by exact teacher-forced recompute."""
        with self.timers(PREEMPT):
            return self._preempt_youngest()

    def _preempt_youngest(self) -> bool:
        # the chain to keep is the device's, and what is in flight may
        # end a request: read it before choosing
        self._sync_carry("pages")
        cands = [i for i in range(self.num_slots) if self.slots[i] is not None]
        if not cands:
            return False
        i = max(cands, key=lambda j: self._admit_seq[j])
        req = self.slots[i]
        req.preemptions += 1
        if i not in self.prefill_queue.slots:
            # mid-decode: preserve the PRNG chain so the resumed request
            # samples exactly the tokens it would have sampled
            req.resume_key = self.keys[i].copy()
        self._clear_slot(i)
        with self._cv:
            self._queue.appendleft(req)
            self._m_queue.set(len(self._queue))
        self.stats["preemptions"] += 1
        self._m_preempted.inc()
        self._m_active.set(self.num_active)
        return True

    def _ensure_decode_pages(self) -> None:
        """Before a decode tick, every decodable slot needs real pages
        under its write span (lengths[i] .. lengths[i] + span - 1; span
        is 1 plain, k+1 speculative — rejected drafts roll back the
        length but the pages stay mapped for future growth, and shared
        prefix pages are never in the span). Allocate across page
        boundaries, preempting the youngest slot when the pool is dry.
        Each preemption frees that slot's pages, so this terminates.
        Lengths are those of the last dispatch (a decoding row grows by
        exactly 1 a tick), so this needs no token of the tick in flight;
        only a dry pool reads it, since it may end a request and hand
        its pages back."""
        span = self._decode_write_span()
        ps = self.page_size
        while True:
            rows = self._decode_rows()
            dry = False
            for i in rows:
                first = int(self.lengths[i]) // ps
                last_pg = (int(self.lengths[i]) + span - 1) // ps
                for pg in range(first, last_pg + 1):
                    if self.tables[i, pg] != SCRATCH_PAGE:
                        continue
                    pages = self._alloc_pages(1, logical_start=pg)
                    if pages is None:
                        if (not self._drain("pages")
                                and not self._preempt_one()):
                            # unreachable: slot i itself is preemptible
                            return
                        dry = True
                        break  # re-derive rows (the victim may be gone)
                    self.tables[i, pg] = pages[0]
                    self._table_dirty = True
                if dry:
                    break
            if not dry:
                return

    # ----- stepping --------------------------------------------------------

    def _decode_rows(self):
        busy = self.prefill_queue.slots
        return [i for i in super()._decode_rows() if i not in busy]

    def _decode_table_geometry(self):
        return self.max_pages, self.page_size

    def _decode_extra_args(self):
        if self._table_dirty or self._device_table is None:
            # a copy goes up: the host edits the table while the tick it
            # went into may still be in flight
            self._device_table = self._commit_small(self.tables.copy())
            self._table_dirty = False
        return (self._device_table,)

    def _call_decode_step(self, *carry):
        toks, lps, self.caches, self.state, keys, lens, *counts = (
            self._decode_step(
                self.params, self.caches, self.state,
                *self._decode_extra_args(), *carry, *self._counts_arg()))
        self._step_counts, = counts or (None,)
        return toks, lps, keys, lens

    def _apply_counts(self, counts) -> None:
        """The device's counts so far (_MOE_COUNTS), as a read step's
        fetch brought them: the counters move by what is new since the
        last read (the device's numbers wrap at 2**32; the difference
        does not care)."""
        if counts is None:
            return
        new = counts - self._counts_seen
        self._counts_seen = counts
        for (key, _, _), metric, n in zip(_MOE_COUNTS, self._m_moe, new):
            metric.inc(int(n))
            self.stats[key] += int(n)

    def _chunk_table_arg(self, row):
        """Device form of one pending table row for the chunk step
        ([1, max_pages] here; the CP engine rebuilds it as per-rank
        local tables sharded over the context axis)."""
        return row[None, :]

    def _release_window_pages(self) -> None:
        """Sliding-window page release (Mistral; ROADMAP item 1): pages
        every position of which sits fully behind a slot's attention
        window can never be attended again — the decode mask only allows
        k_pos >= length + 1 - window and lengths never shrink below the
        committed value (speculative rollback rolls back only
        UNcommitted draft positions) — so the slot's reference goes back
        to the pool and the table entry parks on scratch (reads of it
        are exactly masked; scratch contents are finite activations, so
        the masked scores stay well-defined). Pages the radix prefix
        cache also holds keep their cache reference: a later request
        sharing the prompt still hits them."""
        window = self.cfg.attention_kind.sliding_window_size
        if window is None:
            return
        ps = self.page_size
        freed = 0
        for i in self._decode_rows():
            limit = int(self.lengths[i]) - int(window)
            if limit < ps:
                continue
            # O(1) amortized: at most one page per slot newly crosses
            # the window per tick, and the cursor never rewinds (a
            # cleared/preempted slot resets it in _clear_slot)
            for pg in range(self._window_cursor[i], limit // ps):
                if self.tables[i, pg] != SCRATCH_PAGE:
                    self.pool.release([int(self.tables[i, pg])])
                    self.tables[i, pg] = SCRATCH_PAGE
                    self._table_dirty = True
                    freed += 1
            self._window_cursor[i] = max(self._window_cursor[i],
                                         limit // ps)
        if freed:
            self.stats["window_pages_released"] += freed
            self._m_window_released.inc(freed)
            self._m_pages_free.set(self.pool.free_pages)

    def _tick(self) -> int:
        """One engine tick: admit, dispatch one prefill chunk and one
        batched decode for every slot whose prompt is fully cached, then
        read the tick before (the loop runs one tick ahead of the device:
        everything before the read works from lengths the host has, and
        happens while the device runs the last tick). Returns slots
        served + chunks run, or what a step with nothing to dispatch
        read (0 = idle, and nothing in flight)."""
        with self.timers(PRE):
            self._pre_tick()  # faults, staged weight swaps, deadlines
        with self.timers(ADMIT):
            self._admit()
        with self.timers(PREFILL):
            chunked = self._prefill_tick()
            if chunked:
                # chunked prefill with no decodable slots is still progress
                # — without this a long multi-chunk prompt would trip the
                # stalled() readiness check while prefilling normally
                self.last_progress_time = time.monotonic()
        with self.timers(PAGES):
            self._release_window_pages()
            self._ensure_decode_pages()
        return self._read_behind(self._decode_phase() + chunked)

    def _retire(self, i: int):
        # base _retire -> _clear_slot releases this slot's page refs;
        # pages also held by the radix tree stay cached for future hits
        super()._retire(i)
        self._m_pages_free.set(self.pool.free_pages)

    # ----- state migration (fleet/migration.py) ----------------------------

    def _export_slot_kv(self, i: int):
        """Gather slot i's pages into the canonical [L, T, H, D] wire
        layout. None when any page of the span is gone (sliding-window
        release parked it on scratch) — there is no exact KV to ship, so
        the importer recompute-resumes from the migrated tokens (exact
        under the deterministic position-based window mask)."""
        length = int(self.lengths[i])
        ps = self.page_size
        if length <= 0:
            return None
        n_pages = -(-length // ps)
        row = self._pending_rows.get(i, self.tables[i])
        pages = [int(p) for p in row[:n_pages]]
        if any(p == SCRATCH_PAGE for p in pages):
            return None
        host = kv_store.export_span(jax.device_get(self.caches), pages,
                                    length)
        return self._pack_kv_sections(host, length)

    def _install_request_kv(self, req: Request, kv: dict,
                            sections) -> bool:
        """Paged install: allocate the span's pages, write each through
        the once-jitted page writer, publish the table row, and re-enter
        the prompt's full pages into the radix tree — the migrated
        request's prefix lineage survives the hop, so followers sharing
        its prompt hit on THIS replica too."""
        i = self._free_slot_for_import()
        if i is None:
            return False
        length = int(kv["length"])
        ps = self.page_size
        n_pages = -(-length // ps)
        pages = self._alloc_pages(n_pages)
        if pages is None:
            return False
        leaves = self._decode_kv_sections(kv, sections)
        writer = self._kv_install_writer()
        self._sync_carry("migration")
        for j, pg in enumerate(pages):
            self.caches = writer(self.caches,
                                 kv_store.span_block(leaves, j, ps),
                                 jnp.int32(pg))
        row = np.zeros(self.max_pages, np.int32)
        row[:n_pages] = pages
        self.tables[i] = row
        self._table_dirty = True
        self._admit_counter += 1
        self._admit_seq[i] = self._admit_counter
        self._arm_imported_slot(i, req, length)
        p0 = len(req.prompt)
        if p0 >= ps and req.prompt_logprobs:
            # radix-prefix lineage: same full-pages-only rule as
            # _finish_prefill (the tail page is private — decode writes it)
            self.prefix_cache.insert(
                req.prompt, [int(p) for p in row[:p0 // ps]],
                req.prompt_logprobs)
        self._m_pages_free.set(self.pool.free_pages)
        return True

    # ----- fleet prefix directory (cross-replica radix sharing) ------------

    def export_prefix_state(self, tokens):
        """Package the radix-cached whole-page prefix of `tokens` for
        replication to a peer: (meta, sections) in the migration wire
        vocabulary (kind="prefix"), or None when nothing is cached."""
        self._refuse_state_transfer("the fleet's prefix directory")
        toks = [int(t) for t in tokens]
        with self.paused():
            self._drain("migration")
            pages, lps = self.prefix_cache.lookup(toks)
            if not pages:
                return None
            ps = self.page_size
            span = len(pages) * ps
            host = kv_store.export_span(jax.device_get(self.caches),
                                        [int(p) for p in pages], span)
            kv_meta, sections = self._pack_kv_sections(host, span)
        meta = {"kind": "prefix", "tokens": toks[:span], "kv": kv_meta}
        # per-node logprob slices concatenate back into the engine's
        # (position-1)-indexed prompt_logprobs layout for tokens[1:span]
        sections["prefix_logprobs"] = (
            np.concatenate([np.asarray(x, np.float32) for x in lps])
            if lps else np.zeros(0, np.float32))
        return meta, sections

    def import_prefix_state(self, meta: dict, sections) -> int:
        """Install replicated prefix pages into this pool + radix tree.
        Returns pages added (0 = incompatible, lossy, or already
        cached). Only EXACT codecs enter the tree — a lossy prefix would
        silently poison every future request that hits it."""
        self._refuse_state_transfer("the fleet's prefix directory")
        kv = meta.get("kv") or {}
        ok, _ = self._kv_import_compatible(kv)
        if not ok or not kv.get("exact"):
            return 0
        toks = [int(t) for t in meta.get("tokens", [])]
        span = int(kv.get("length", 0))
        ps = self.page_size
        if span <= 0 or span % ps != 0 or span > len(toks):
            return 0
        n_pages = span // ps
        with self.paused():
            self._drain("migration")
            have, _ = self.prefix_cache.lookup(toks)
            if len(have) >= n_pages:
                return 0  # the local copy stays authoritative
            pages = self._alloc_pages(n_pages)
            if pages is None:
                return 0
            leaves = self._decode_kv_sections(kv, sections)
            writer = self._kv_install_writer()
            for j, pg in enumerate(pages):
                self.caches = writer(self.caches,
                                     kv_store.span_block(leaves, j, ps),
                                     jnp.int32(pg))
            lp = np.asarray(sections.get("prefix_logprobs",
                                         np.zeros(0)), np.float32)
            added = self.prefix_cache.insert(toks[:span], pages, lp)
            # insert() retained the refs the tree owns; drop the
            # allocation refs so the pages become cache-only (evictable
            # under pressure), and so pages skipped as already-cached
            # free immediately
            self.pool.release(pages)
            self._m_pages_free.set(self.pool.free_pages)
        return added
