"""Paged KV-cache serving: block pool + radix prefix cache + chunked
prefill.

A cache that reserves `max_seq_len` rows per slot up front wastes almost
all of them on a young sequence, and two requests sharing a system prompt
each recompute and store it. The serving engine (inference/engine.py)
keeps its KV in a shared pool of fixed-size pages instead; this package
holds the parts:

  * :mod:`pool` — the free-list page allocator with refcounts. KV
    storage becomes ``[layers, num_pages, page_size, kv_heads, head_dim]``
    and each slot holds an int32 page table mapping logical blocks to
    physical pages.
  * :mod:`radix` — a radix tree over token IDs at page granularity:
    requests sharing a prompt prefix map their tables onto the same
    refcounted pages and skip prefill for the shared span (copy-on-write
    when a partially-shared page is about to be written).
  * :mod:`scheduler` — the chunked-prefill queue: long prompts enter the
    cache `prefill_chunk` tokens per engine tick, interleaved with the
    batched decode, so one long prompt can never stall the batch.
  * :mod:`engine` — the builders of the engine's jitted device programs
    (the decode step, the chunk step, the draft model's chunk step): zero
    decode recompiles after warmup.

The decode attention path reads through the table: the paged
flash-decode kernel (ops/pallas/paged_flash_decode.py) resolves pages
inside the Pallas grid on TPU; everywhere else ops/attention.py gathers
the pages into a dense view and the masked einsum computes identical
values.
"""

from megatron_tpu.inference.paging.pool import PagePool
from megatron_tpu.inference.paging.radix import RadixPrefixCache
from megatron_tpu.inference.paging.scheduler import ChunkedPrefillQueue

__all__ = [
    "PagePool",
    "RadixPrefixCache",
    "ChunkedPrefillQueue",
]
