"""ContextParallelEngine: paged serving with sequence-sharded KV.

Subclass of InferenceEngine that keeps EVERY host-side policy
unchanged — one global radix prefix tree, one chunked-prefill queue,
LIFO preemption, sliding-window release, the global [N, max_pages]
table rows — and restructures only the device side:

  * the KV page pools are sharded over the "context" mesh axis on the
    pages dimension, and allocation is striped so logical page l of any
    row lives on CP rank ``l % cp`` (pool.StripedPagePool);
  * the decode/chunk steps receive PER-RANK local tables
    ([cp, rows, pages_per_rank], sharded on dim 0) instead of the flat
    global row, which routes the per-layer attention through the
    ring-attention island (ring_kv.paged_ring_attention): each rank
    attends its own sequence stripe and the normalized partials merge
    under the selected geometry — the flat overlapped ring (cp-1
    ``ppermute`` hops, hop l+1 issued before hop l's merge) or the 2d
    cp_seq x cp_head factorization (head all-to-all inside a
    `cp_subgroup`-sized group, cp_seq-1 ring hops across groups);
  * the hop transport is quant/collectives.CpComm — dense fp32 or
    policy-gated int8/fp8 (site "cp_ring"), composable with the
    existing TP compressed collectives on a TP x CP mesh.

Because the host bookkeeping is inherited verbatim, radix hits,
mid-prefill preempt/resume and ragged prompt tails are exact by the
same arguments as the single-host engine; the parity gates in
tests/test_context_parallel.py pin greedy token identity against
one-shot generation. int8 KV pools and speculative decoding are out of scope
(both rejected at build).
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import ModelConfig
from megatron_tpu.inference.context_parallel.pool import StripedPagePool
from megatron_tpu.inference.engine import EVICT, InferenceEngine
from megatron_tpu.inference.paging.pool import SCRATCH_PAGE
from megatron_tpu.inference.paging.radix import RadixPrefixCache
from megatron_tpu.parallel.mesh import AXIS_CONTEXT
from megatron_tpu.quant.collectives import cp_ring_comm_bytes, make_cp_comm


class ContextParallelEngine(InferenceEngine):
    """Paged serving engine over a TP x CP mesh (tp >= 1, cp >= 2)."""

    def __init__(self, cfg: ModelConfig, params: Any, num_slots: int = 8,
                 max_seq_len: Optional[int] = None,
                 page_size: int = 16, prefill_chunk: int = 32,
                 num_pages: Optional[int] = None,
                 vocab_size: Optional[int] = None, mesh=None,
                 want_logprobs: bool = True, metrics=None,
                 flight_recorder=None,
                 force_donate: Optional[bool] = None,
                 max_queue: Optional[int] = None,
                 compress_collectives: str = "none",
                 comm_policy=None,
                 comm_chunk: int = 32,
                 cp_collectives: str = "dense",
                 cp_comm_policy=None,
                 cp_geometry: str = "ring",
                 cp_subgroup: int = 0,
                 cp_overlap: bool = True):
        if mesh is None:
            raise ValueError(
                "ContextParallelEngine requires a mesh with a non-trivial "
                f"'{AXIS_CONTEXT}' axis")
        cp = dict(mesh.shape).get(AXIS_CONTEXT, 1)
        if cp <= 1:
            raise ValueError(
                f"ContextParallelEngine needs {AXIS_CONTEXT} >= 2 on the "
                f"mesh (got {cp}); use InferenceEngine for cp == 1")
        self.cp = cp
        # set BEFORE super().__init__: the inherited step builders close
        # over cp_comm, and _fresh_caches rounds the pool to cp shards
        self.cp_comm = make_cp_comm(mesh, cp_collectives, cfg=cfg,
                                    policy=cp_comm_policy, chunk=comm_chunk,
                                    geometry=cp_geometry,
                                    subgroup=cp_subgroup,
                                    overlap=cp_overlap)
        if self.cp_comm is None:
            raise ValueError(
                f"cp_collectives={cp_collectives!r} disables the ring "
                "transport the CP engine is built on (use 'dense', 'int8' "
                "or 'fp8')")
        self._cp_bytes_for = {}
        super().__init__(
            cfg, params, num_slots=num_slots, max_seq_len=max_seq_len,
            kv_cache_int8=False, page_size=page_size,
            prefill_chunk=prefill_chunk, num_pages=num_pages,
            vocab_size=vocab_size, mesh=mesh,
            want_logprobs=want_logprobs, metrics=metrics,
            flight_recorder=flight_recorder, force_donate=force_donate,
            max_queue=max_queue, speculative=None,
            compress_collectives=compress_collectives,
            comm_policy=comm_policy, comm_chunk=comm_chunk)
        self._npl = self.num_pages // cp          # pool pages per rank
        self._mpl = -(-self.max_pages // cp)      # table slots per rank
        if self._npl - 1 < self._mpl:
            raise ValueError(
                f"num_pages={self.num_pages} over cp={cp} leaves "
                f"{self._npl} pages per rank — rank 0 (minus scratch) "
                f"cannot hold one full sequence ({self._mpl} pages)")
        # re-home the allocator: striped per-rank free lists under the
        # SAME refcount/scratch contract (nothing is allocated yet — the
        # base constructor only sized the pool)
        self.pool = StripedPagePool(self.num_pages, cp)
        self.prefix_cache = RadixPrefixCache(
            self.pool, self.page_size, evict_span=self.timers(EVICT))
        self._m_pages_free.set(self.pool.free_pages)

        self._cp_bytes_for = {
            id(self._comm_tick_bytes): cp_ring_comm_bytes(
                cfg, self.cp_comm, num_slots, 1),
            id(self._comm_chunk_bytes): cp_ring_comm_bytes(
                cfg, self.cp_comm, 1, self.prefill_chunk),
        }
        self.stats.update({"cp_ring_steps": 0, "cp_comm_dense_bytes": 0,
                           "cp_comm_compressed_bytes": 0,
                           "cp_comm_a2a_dense_bytes": 0,
                           "cp_comm_a2a_compressed_bytes": 0,
                           "cp_admission_blocked": 0})
        self._cp_dry_shards: tuple = ()
        m = self.metrics
        self._m_cp_ring = m.counter(
            "engine_cp_ring_steps_total",
            "context-parallel ring hops executed (per layer per forward)")
        self._m_cp_dense = m.counter(
            "engine_cp_comm_dense_bytes_total",
            "wire bytes the CP ring hops would move dense")
        self._m_cp_comp = m.counter(
            "engine_cp_comm_compressed_bytes_total",
            "wire bytes the CP ring hops move at the configured mode")
        self._m_cp_a2a_dense = m.counter(
            "engine_cp_a2a_dense_bytes_total",
            "wire bytes the 2d geometry's head a2a legs would move dense")
        self._m_cp_a2a_comp = m.counter(
            "engine_cp_a2a_compressed_bytes_total",
            "wire bytes the 2d geometry's head a2a legs move at the "
            "configured mode")
        self._m_cp_shard_free = m.gauge(
            "engine_cp_shard_pages_free",
            "free pages in each CP rank's pool shard",
            label_names=("shard",))
        self._m_cp_blocked = m.counter(
            "engine_cp_admission_blocked_total",
            "page allocations blocked by an exhausted CP pool shard "
            "(striped-pool pressure, distinct from queue depth)",
            label_names=("shard",))
        self._set_shard_gauges()

    # ----- cache + shape policy -------------------------------------------

    def _fresh_caches(self):
        """Same pools as the base engine, with the page count rounded up
        to a multiple of cp so every rank holds an equal shard (the
        striping arithmetic and the P(None, context, ...) placement both
        need exact divisibility)."""
        if self.num_pages is None:
            self.num_pages = self.num_slots * self.max_pages + 1
        self.num_pages += (-self.num_pages) % self.cp
        return super()._fresh_caches()

    def _kv_sharding(self):
        """Pool placement: pages sharded over "context" — each rank holds
        its sequence stripe's pages. Heads stay replicated over "tensor":
        the ring island was written full-manual over every mesh axis (all
        that jax 0.4.37 offered), where a tensor-sharded heads dim would
        just be force-gathered at the island boundary each step — not
        re-tried on jax 0.9 (ROADMAP D9)."""
        from jax.sharding import NamedSharding

        from megatron_tpu.ops import kv_store

        return NamedSharding(self.mesh,
                             kv_store.partition_spec(rows=AXIS_CONTEXT))

    # ----- page accounting -------------------------------------------------

    def _alloc_pages(self, n: int,
                     logical_start: int = 0) -> Optional[List[int]]:
        """Striped allocation with per-rank-aware eviction: a failed
        alloc means SOME rank's shard is dry, so evict LRU cache-only
        pages (whatever ranks hold them) and retry until the striped
        grab fits or eviction runs dry. A final failure is attributed
        to the dry shard(s): counter + journal + the distinct 503
        detail (_overload_detail), so operators can tell striped-pool
        pressure from ordinary queue depth."""
        pages = self.pool.alloc(n, logical_start)
        while pages is None and self._note_evicted(
                self.prefix_cache.evict(max(n, 1))) > 0:
            pages = self.pool.alloc(n, logical_start)
        if pages is not None:
            self._m_pages_free.set(self.pool.free_pages)
            self._cp_dry_shards = ()
            return pages
        need = [0] * self.cp
        for j in range(n):
            need[(logical_start + j) % self.cp] += 1
        free = self.pool.free_pages_by_rank()
        dry = tuple(r for r in range(self.cp) if need[r] > free[r])
        self.stats["cp_admission_blocked"] += 1
        for r in dry:
            self._m_cp_blocked.inc(shard=str(r))
        if dry != self._cp_dry_shards:
            # once per episode, not per retried tick
            from megatron_tpu.telemetry import journal as _journal

            j = _journal.get_global_journal()
            if j is not None:
                j.emit("cp_admission_blocked", shards=list(dry),
                       need=need, free_by_rank=list(free), pages=n)
        self._cp_dry_shards = dry
        return None

    def _overload_detail(self) -> str:
        """Queue-full rejections name the dry shard(s) when striped-pool
        exhaustion — not decode throughput — is what's stalling
        admission (the 503 detail fleet operators key on)."""
        if self._cp_dry_shards:
            shards = ",".join(str(r) for r in self._cp_dry_shards)
            return (f"cp shard(s) {shards} exhausted (striped KV pool "
                    "pressure); ")
        return ""

    # ----- device tables ---------------------------------------------------

    def _loc_tables(self, rows: np.ndarray) -> np.ndarray:
        """Global table rows [M, max_pages] -> per-rank local tables
        [cp, M, mpl]: entry [r, i, j] is rank r's LOCAL pool index of
        logical page ``j*cp + r`` of row i. Unallocated entries (global
        SCRATCH_PAGE) map to local scratch on rank 0 (same masked-write
        semantics as the flat engine) and to the out-of-range sentinel
        ``npl`` elsewhere (writes drop, reads are masked)."""
        rows = np.asarray(rows, np.int32)
        cp, npl, mpl = self.cp, self._npl, self._mpl
        loc = np.full((cp, rows.shape[0], mpl), npl, np.int32)
        for r in range(cp):
            cols = rows[:, r::cp]
            if ((cols != SCRATCH_PAGE) & (cols // npl != r)).any():
                raise AssertionError(
                    f"page-striping invariant violated on rank {r}: a "
                    "logical page maps outside its owner's pool shard")
            loc[r, :, :cols.shape[1]] = np.where(
                cols == SCRATCH_PAGE, 0 if r == 0 else npl, cols - r * npl)
        return loc

    def _rows_decoding(self):
        """As the flat engine's, over the ranks' local tables [cp, M, mpl]:
        an entry without a page is rank 0's scratch there and the
        sentinel `npl` on the other ranks (_loc_tables; the pool is sized
        when the steps are built)."""
        cp, npl = self.cp, self.num_pages // self.cp

        def rows_decoding(table):
            empty = jnp.where(jnp.arange(cp) == 0, SCRATCH_PAGE, npl)
            return jnp.any(table != empty[:, None, None],
                           axis=(0, 2)).astype(jnp.int32)

        return rows_decoding

    def _cp_table_device(self, loc: np.ndarray):
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P(AXIS_CONTEXT))
        return jax.device_put(jnp.asarray(loc), sh)

    def _decode_extra_args(self):
        if self._table_dirty or self._device_table is None:
            self._device_table = self._cp_table_device(
                self._loc_tables(self.tables))
            self._table_dirty = False
        return (self._device_table,)

    def _chunk_table_arg(self, row):
        return self._cp_table_device(
            self._loc_tables(np.asarray(row)[None, :]))

    # ----- telemetry -------------------------------------------------------

    def _count_comm(self, bytes_pair) -> None:
        super()._count_comm(bytes_pair)
        cp_pair = self._cp_bytes_for.get(id(bytes_pair))
        if cp_pair is None:
            return
        hops = self.cp_comm.ring_hops() * self.cfg.num_layers
        self.stats["cp_ring_steps"] += hops
        self.stats["cp_comm_dense_bytes"] += cp_pair["dense"]
        self.stats["cp_comm_compressed_bytes"] += cp_pair["compressed"]
        self._m_cp_ring.inc(hops)
        self._m_cp_dense.inc(cp_pair["dense"])
        self._m_cp_comp.inc(cp_pair["compressed"])
        if cp_pair.get("a2a_dense"):
            self.stats["cp_comm_a2a_dense_bytes"] += cp_pair["a2a_dense"]
            self.stats["cp_comm_a2a_compressed_bytes"] += (
                cp_pair["a2a_compressed"])
            self._m_cp_a2a_dense.inc(cp_pair["a2a_dense"])
            self._m_cp_a2a_comp.inc(cp_pair["a2a_compressed"])

    def _set_shard_gauges(self) -> None:
        for r, free in enumerate(self.pool.free_pages_by_rank()):
            self._m_cp_shard_free.set(free, shard=str(r))

    def _tick(self) -> int:
        served = super()._tick()
        self._set_shard_gauges()
        return served
