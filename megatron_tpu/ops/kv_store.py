"""The KV cache: its format, and the one way it travels.

A store is what `lm_forward(kv_caches=...)` takes and hands back: a tuple
of stacked leaves, every leaf

    [layers, rows, row_len, kv_heads, head_dim]

  * slots — `rows` is the batch (one row a sequence), `row_len` the
    longest sequence a row holds (the one-shot loops of generation.py);
  * pages — `rows` is the pool of pages every sequence shares, `row_len`
    the page size; a `page_table` [B, n] names each sequence's pages in
    order (the serving engine, inference/engine.py), page 0 is scratch.

bf16/f32: `(k, v)`. int8 (ops/kv_quant.py): `(k_q, v_q, k_scale,
v_scale)`, the scales one float32 a vector (`head_dim` 1).

This module is the only place that knows that axis order and that arity.
The layer stack carries the store through its scan (`lm_forward`), each
layer writes its new rows in place at `[layer, ...]` (`write`) and hands
attention a view of the store (`read`): nothing the size of a layer's
share is copied on the way (int8 stores dequantize a layer for
attention: that is arithmetic, kept token-identical between the loops).
The engine and the one-shot loops create, export and install through the
functions at the end. The wire format of exported spans is the canonical
[layers, positions, kv_heads, head_dim] of fleet/migration.py.

To attention a cache is always paged: `read` presents slots as a pool
whose pages are whole rows (row b of layer l is page l * rows + b of the
stacked store seen flat), so one gather and one decode kernel serve both.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from megatron_tpu.ops.kv_quant import dequantize_kv, quantize_kv

Store = Tuple[jnp.ndarray, ...]


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------


def create(cfg, rows: int, row_len: int, int8: bool = False) -> Store:
    """A zeroed store for `cfg`'s attention layers (every layer, but in a
    stack with state-space layers, whose state is ops/ssm.py's store; a
    layer indexes the store by its ordinal among the attention layers):
    `rows` slots of `row_len` positions, or a pool of `rows` pages of
    `row_len` positions."""
    shape = (cfg.layers_of("attention"), rows, row_len, cfg.n_kv_heads,
             cfg.head_dim)
    if int8:
        scales = shape[:-1] + (1,)
        return (jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                jnp.zeros(scales, jnp.float32),
                jnp.zeros(scales, jnp.float32))
    return (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


def is_int8(store: Store) -> bool:
    return len(store) == 4


def rows_and_row_len(leaf) -> Tuple[int, int]:
    """(rows, row_len) of a stacked leaf — of the local shard, inside a
    `shard_map` that splits the rows."""
    return leaf.shape[1], leaf.shape[2]


def logical_length(store: Store, page_table=None) -> int:
    """Positions a sequence can hold (the rotary table's length): a slot
    row, or the table's width in pages. A context-parallel table
    [cp, B, pages_per_rank] covers cp x pages_per_rank pages a row."""
    _, row_len = rows_and_row_len(store[0])
    if page_table is None:
        return row_len
    if page_table.ndim == 3:
        return page_table.shape[0] * page_table.shape[2] * row_len
    return page_table.shape[1] * row_len


def partition_spec(layers=None, rows=None, heads=None) -> PartitionSpec:
    """Placement of every leaf with the named axes sharded over the given
    mesh axes (scales shard like their vectors)."""
    return PartitionSpec(layers, rows, None, heads, None)


def pool_dims(pool) -> Tuple[int, int, int, int]:
    """(pages, page_size, kv_heads, head_dim) of one layer's pool as
    attention and the decode kernels take it, [pages, page, Hkv, D]."""
    pages, page_size, kv_heads, head_dim = pool.shape
    return pages, page_size, kv_heads, head_dim


def gather_pages(pool, page_table):
    """Each row's logical context out of a pool [pages, page, Hkv, D]:
    [B, n * page, Hkv, D] for a table [B, n]. Exact: pages hold the bits
    a dense cache would."""
    _, _, kv_heads, head_dim = pool_dims(pool)
    return pool[page_table].reshape(page_table.shape[0], -1, kv_heads,
                                    head_dim)


# ---------------------------------------------------------------------------
# a layer's write and read, inside the layer scan
# ---------------------------------------------------------------------------


def scatter_rows(leaf, layer, rows, offsets, new, drop: bool = False):
    """leaf[layer, rows[i, j], offsets[i, j]] = new[i, j]: the one
    in-place write of scattered positions. drop: an out-of-range row is
    a write that does not happen (the context-parallel stripes)."""
    at = leaf.at[layer, rows, offsets]
    new = new.astype(leaf.dtype)
    return at.set(new, mode="drop") if drop else at.set(new)


def write(store: Store, layer, k, v, cache_index, page_table=None,
          write_start=None, write_end=None) -> Store:
    """The store with this layer's new keys and values [B, s, Hkv, D]
    written at positions cache_index .. cache_index + s - 1, in place
    (the caller donates the store and carries it through its scan).

    cache_index is a vector [B] (every row at its own depth: one token a
    row, or the s tokens of a speculative verify) or a scalar (every row
    at the same depth: a prefill, a chunk, one-shot generation).
    page_table [B, n] routes positions to pages; there a scalar
    cache_index is one chunk of one row, and positions outside
    [write_start, write_end) are parked on scratch page 0: below the
    start fence they would rewrite a page shared through the prefix
    cache (shared pages are copy-on-write: never written through a
    sharer's table), past the end fence they are the chunk's padded
    tail, which an index-clipped write could scribble on a live page."""
    b, s = k.shape[0], k.shape[1]
    if is_int8(store):
        (k, k_scale), (v, v_scale) = quantize_kv(k), quantize_kv(v)
        new = (k, v, k_scale, v_scale)
    else:
        new = (k, v)
    per_row = getattr(cache_index, "ndim", 0) == 1
    if page_table is None and not per_row:
        at = (layer, 0, cache_index, 0, 0)
        return tuple(
            jax.lax.dynamic_update_slice(leaf, n[None].astype(leaf.dtype), at)
            for leaf, n in zip(store, new))
    if per_row:
        positions = cache_index[:, None] + jnp.arange(s)        # [B, s]
    else:
        if b != 1:
            raise ValueError(
                f"paged chunked prefill is single-row (batch {b})")
        positions = (cache_index + jnp.arange(s))[None, :]      # [1, s]
    if page_table is None:
        rows, offsets = jnp.arange(b)[:, None], positions
    else:
        _, page_size = rows_and_row_len(store[0])
        rows = jnp.take_along_axis(page_table, positions // page_size,
                                   axis=1, mode="clip")
        offsets = positions % page_size
        if not per_row and write_start is not None:
            rows = jnp.where(positions >= write_start, rows, 0)
        if not per_row and write_end is not None:
            rows = jnp.where(positions < write_end, rows, 0)
    return tuple(scatter_rows(leaf, layer, rows, offsets, n)
                 for leaf, n in zip(store, new))


def read(store: Store, layer, page_table, dtype):
    """(k_pool, v_pool, table): the layer's keys and values as
    `ops.attention.attention(q, k, v, page_table=table)` takes a paged
    cache. bf16/f32: the whole stacked store seen flat (a view, no copy)
    and a table that points into the layer's share of it. int8: the
    layer dequantized to `dtype`."""
    rows, _ = rows_and_row_len(store[0])
    if is_int8(store):
        k_q, v_q, k_scale, v_scale = (
            jax.lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
            for leaf in store)
        k = dequantize_kv(k_q, k_scale, dtype)
        v = dequantize_kv(v_q, v_scale, dtype)
        base = 0
    else:
        k, v = (leaf.reshape((-1,) + leaf.shape[2:]) for leaf in store)
        base = layer * rows
    if page_table is None:
        page_table = jnp.arange(rows, dtype=jnp.int32)[:, None]
    return k, v, base + page_table


def gather_rows(leaf, layer, table):
    """leaf[layer, table]: rows [B, n] of one layer, [B, n, row_len, ...]
    (the context-parallel stripes read their local pages with it)."""
    return leaf[layer, table]


# ---------------------------------------------------------------------------
# whole rows: what the engine and the one-shot loops do between steps
# ---------------------------------------------------------------------------


def install(store: Store, blocks: Sequence[jnp.ndarray], at) -> Store:
    """Row `at` (a page; traced) of every layer overwritten from
    position 0 by `blocks`, one [layers, positions, ...] array a leaf:
    a migrated span into its page."""
    return tuple(
        jax.lax.dynamic_update_slice(
            leaf, block[:, None].astype(leaf.dtype), (0, at, 0, 0, 0))
        for leaf, block in zip(store, blocks))


def repeat_rows(store: Store, n: int) -> Store:
    """Every row n times over (beam search fans one prompt out)."""
    return tuple(jnp.repeat(leaf, n, axis=1) for leaf in store)


def take_rows(store: Store, rows) -> Store:
    """The store re-ordered to `rows` (beam search follows parents)."""
    return tuple(jnp.take(leaf, rows, axis=1) for leaf in store)


def export_span(host_store: Sequence[np.ndarray], rows: Sequence[int],
                length: int) -> List[np.ndarray]:
    """The first `length` positions of the sequence stored in `rows` (a
    sequence's pages in order) of a store fetched to the
    host, in the canonical wire layout [layers, positions, Hkv, D]."""
    out = []
    for leaf in host_store:
        span = np.asarray(leaf)[:, list(rows)]
        layers, n, row_len = span.shape[:3]
        out.append(span.reshape(layers, n * row_len,
                                *span.shape[3:])[:, :length])
    return out


def span_block(leaves: Sequence[np.ndarray], j: int, row_len: int
               ) -> Tuple[jnp.ndarray, ...]:
    """Block j of `row_len` positions of canonical leaves [layers,
    positions, ...], zero-padded past their end: what `install` takes."""
    blocks = []
    for leaf in leaves:
        block = np.zeros((leaf.shape[0], row_len) + leaf.shape[2:],
                         leaf.dtype)
        end = max(0, min(leaf.shape[1] - j * row_len, row_len))
        block[:, :end] = leaf[:, j * row_len:j * row_len + end]
        blocks.append(jnp.asarray(block))
    return tuple(blocks)
