"""Block-visibility predicates shared by every flash-kernel instantiation.

Every attention variant in ops/pallas/ answers the same two questions per
(query tile, kv tile) pair, and before this module each kernel answered
them with its own copy of the arithmetic:

  1. element mask — which (q, k) pairs inside the tile are visible?
  2. block skip  — can the whole kv tile be skipped without loading it?

Both reduce to ONE position model. Assign every query row a global
position ``q_pos`` and every key column a global position ``k_pos``; then

  * causal visibility is ``k_pos <= q_pos``;
  * a Mistral sliding window of width W is ``k_pos > q_pos - W``
    (the newest W positions, self included);

and the per-variant differences are only in how positions are assigned:

  * prefill/training tiles: ``q_pos = qi*BQ + row (+ delta)``,
    ``k_pos = ki*BK + col`` — ``delta`` is the q-vs-k global offset the
    ring-attention stripes thread through SMEM;
  * decode (the Sq-small specialization): query row r of a slot with
    valid prefix ``kv_len`` is speculative query ``j = r // G`` (G =
    grouped heads per kv head) sitting at ``q_pos = kv_len - 1 + j``;
    ``k_pos`` indexes the cache. The "kv_lengths mask"
    ``k_pos < kv_len + j`` IS the causal rule at those positions — not a
    separate mask family.

The block-skip predicates are the interval form of the same rule: a kv
tile is live iff it intersects the union of visible bands of the tile's
queries, ``(q_lo - W, q_hi]``. All functions accept traced values (SMEM
scalars inside kernels) and Python ints / numpy arrays (the dense
reference the unit tests check against) alike.

Everything is kept 2-D in-kernel: 1-D iota lowers to scalar code on TPU,
so the iota helpers emit [rows, cols] grids directly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

#: Finite -inf stand-in: subtracting it from itself must stay finite in
#: the online-softmax update (a true -inf would produce NaN via inf-inf),
#: and downstream consumers (ring merge) treat <= NEG_INF/2 as "row saw
#: nothing".
NEG_INF = float(-1e30)


# ---------------------------------------------------------------------------
# element-level visibility (the one mask rule)
# ---------------------------------------------------------------------------


def visible(q_pos, k_pos, *, causal: bool = True,
            window: Optional[int] = None):
    """Element visibility of key position(s) to query position(s).

    Works on traced 2-D iota grids inside kernels and on numpy/int
    arguments in tests — this function IS the dense reference the unit
    tests prove the block predicates against."""
    m = (k_pos <= q_pos) if causal else (k_pos == k_pos)
    if window is not None:
        m = m & (k_pos > q_pos - window)
    return m


def prefill_positions(qi, ki, block_q: int, block_k: int, delta=0):
    """(q_pos, k_pos) [BQ, BK] grids for a prefill/training tile pair.

    delta (may be a traced SMEM scalar): global offset q_global -
    k_global of the two tiles' origins. Ring attention uses it so ONE
    kernel covers every stripe pair — aligned diagonal (delta 0),
    fully-past (delta >= stripe) and shifted sliding-window bands."""
    return tile_positions(qi * block_q + delta, ki * block_k, block_q,
                          block_k)


def tile_positions(q_lo, k_lo, rows: int, cols: int):
    """(q_pos, k_pos) [rows, cols] grids of the pairs whose first query
    sits at q_lo and first key at k_lo (traced scalars or Python ints: a
    piece of a tile whose place in the band is static has both static,
    `prefill_tile_pieces`)."""
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    return q_pos, k_pos


def decode_positions(ki, block_k: int, kv_len, groups: int, rows: int,
                     kv_heads: int = 1):
    """(q_pos, k_pos) [kv_heads * rows, BK * kv_heads] grids for a decode
    tile that holds every kv head of BK cache positions.

    rows = Sq * groups: row r of a kv head is speculative query
    j = r // groups of this slot, at global position kv_len - 1 + j (the
    verify pass — each query one position deeper than the last; Sq == 1
    is plain single-token decode). The q tile stacks the heads' rows
    (head-major); the kv tile is [BK, kv_heads] flattened, position-major,
    the order a cache row lies in memory. kv_len may be a traced SMEM
    scalar."""
    shape = (kv_heads * rows, block_k * kv_heads)
    q_idx = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) % rows) // groups
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, shape, 1) // kv_heads
    return kv_len - 1 + q_idx, k_pos


def decode_same_head(block_k: int, rows: int, kv_heads: int):
    """[kv_heads * rows, BK * kv_heads] grid: the key of that column
    belongs to the kv head of that row's queries (the layout of
    `decode_positions`)."""
    shape = (kv_heads * rows, block_k * kv_heads)
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0) // rows
            == jax.lax.broadcasted_iota(jnp.int32, shape, 1) % kv_heads)


def chunk_positions(q_lo, k_lo, tq: int, groups: int, block_k: int):
    """(q_pos, k_pos) [groups * tq, BK] grids for a prefill chunk's query
    tile against ONE kv head's keys: the tile stacks the head's grouped
    query heads (head-major: row g * tq + i is query i, at q_lo + i), the
    columns are the block's positions from k_lo. Traced scalars or ints."""
    shape = (groups * tq, block_k)
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 0) % tq
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return q_pos, k_pos


def chunk_block_interior(q_lo, tq: int, k_lo, block_k: int, row_end, *,
                         window: Optional[int] = None):
    """True iff EVERY key of the block [k_lo, k_lo + BK) is visible to
    every query of the tile [q_lo, q_lo + tq) of a row that holds row_end
    positions: at or before the shallowest query, inside the deepest
    one's window, below the row's end. The chunk kernel runs such a block
    without mask arithmetic."""
    k_hi = k_lo + block_k - 1
    interior = (k_hi <= q_lo) & (k_hi < row_end)
    if window is not None:
        interior = interior & (k_lo > q_lo + tq - 1 - window)
    return interior


# ---------------------------------------------------------------------------
# block-level skip predicates (the interval form)
# ---------------------------------------------------------------------------


def block_live(ki, block_k: int, q_lo, q_hi, *, causal: bool = True,
               window: Optional[int] = None):
    """True iff kv tile ki ([ki*BK, (ki+1)*BK)) contains ANY position
    visible to queries spanning global positions [q_lo, q_hi].

    The union of the queries' visible bands is (q_lo - W, q_hi] (causal
    upper edge from the deepest query, window lower edge from the
    shallowest), so the tile is live iff it intersects that interval:

      causal edge: ki*BK <= q_hi
      window edge: (ki+1)*BK - 1 > q_lo - W

    Equality with the dense reference (ANY over `visible` on the tile's
    columns) is unit-tested for every edge, including the decode
    ``kv_len + Sq - 1`` boundary and the window lower edge."""
    live = (ki * block_k <= q_hi) if causal else (ki == ki)
    if window is not None:
        live = live & ((ki + 1) * block_k - 1 > q_lo - window)
    return live


def decode_block_live(ki, block_k: int, kv_len, sq: int, *,
                      window: Optional[int] = None):
    """Block-skip predicate for the decode specialization: queries span
    [kv_len - 1, kv_len + sq - 2], so the causal edge is
    ``ki*BK < kv_len + sq - 1`` (the historical mq boundary) and the
    window edge is ``(ki+1)*BK > kv_len - W``. Blocks past a young
    slot's prefix (or scratch-mapped unallocated pages) never
    load/compute."""
    return block_live(ki, block_k, kv_len - 1, kv_len + sq - 2,
                      causal=True, window=window)


def decode_position_live(k_pos, kv_len, sq: int, *,
                         window: Optional[int] = None):
    """True where SOME query of a decode row sees cache position k_pos:
    the union (kv_len - 1 - W, kv_len + sq - 2] of the queries' visible
    bands, element by element. `decode_block_live` is its interval form;
    the decode kernel zeroes the value rows outside it, which its copies
    may not have filled."""
    live = k_pos <= kv_len + sq - 2
    if window is not None:
        live = live & (k_pos > kv_len - 1 - window)
    return live


def decode_live_blocks(block_k: int, kv_len, sq: int, *,
                       window: Optional[int] = None):
    """(first, last) kv block that `decode_block_live` admits for a row:
    the interval form solved for ki, the bounds of the decode kernel's
    loop. Unclipped, as `prefill_live_kv_tiles`: `first` may be negative
    and `last` beyond the table, or below `first` (kv_len 0 at sq 1: no
    live block), so callers clip into their table."""
    first = (kv_len - window) // block_k if window is not None else 0
    last = (kv_len + sq - 2) // block_k
    return first, last


def decode_idle_length(sq: int) -> int:
    """The kv_len a decode row carries to be visited by nobody: the
    largest at which `decode_live_blocks` gives `last < 0` at any block
    size and window (kv_len + sq - 2 < 0: not even the row's last query
    sees position 0), so a loop clipped into its table is empty. 0 for
    one query; query j of the speculative verify sees k_pos < kv_len + j,
    so its sq queries want 1 - sq. What the serving layer hands the
    decode kernels for a slot that does not decode
    (models/transformer.py attention_block), and what the engine's count
    of the kernel's trips gives such a slot."""
    return 1 - sq


def prefill_block_live(qi, ki, block_q: int, block_k: int, *,
                       causal: bool = True, window: Optional[int] = None,
                       delta=0):
    """Block-skip predicate for a prefill/training tile pair: queries
    span [qi*BQ + delta, qi*BQ + BQ - 1 + delta]."""
    return block_live(ki, block_k, qi * block_q + delta,
                      qi * block_q + block_q - 1 + delta,
                      causal=causal, window=window)


def prefill_tile_pieces(dist: int, block: int, *, causal: bool = True,
                        window: Optional[int] = None):
    """What its mask leaves of the aligned square tile pair `dist` tiles
    under the diagonal (dist = qi - ki; block_q == block_k == block, no
    offset, the window a multiple of the half tile): the interval form
    once more, at the half tile. A quarter of the tile, [h, h] pairs at
    `dh` half tiles under the diagonal, is dead (beyond the causal
    frontier, dh < 0, or before the window's lower edge, dh > W / h), cut
    by an edge (dh == 0, dh == W / h) or wholly visible. Returns the
    pieces that cover the live quarters, (first row, rows, first column,
    columns, masked) each, a row half at a time so that a query row's
    statistics see all of its visible columns in one pass, the smaller
    piece first (on a v5e the forward kernel reads 0.7 to 1.1 us a tile
    faster in that order than in the other: PERF.md section 6, PR 53):

      every quarter live   one piece, the whole tile (masked if an edge
                           cuts any quarter; unmasked: an interior tile)
      causal edge          rows [0, h) x columns [0, h), rows [h, 2h) x
      (the diagonal tile)  columns [0, 2h), both masked: the upper right
                           quarter is never computed
      window edge          rows [h, 2h) x columns [h, 2h), rows [0, h) x
      (W a multiple of     columns [0, 2h), both masked: the lower left
      the tile)            quarter is never computed
      no quarter live      () — a tile `prefill_block_live` refuses

    `masked` is per piece: a masked piece applies `visible` over all of
    its pairs (a mask over the cut quarter alone read no faster)."""
    half = block // 2
    reach = None if window is None else window // half

    def dead(dh):
        return (causal and dh < 0) or (reach is not None and dh > reach)

    def cut(dh):
        return (causal and dh == 0) or dh == reach

    # quarter (row half, column half) lies 2 * dist + row - column half
    # tiles under the diagonal
    live = {(r, c): 2 * dist + r - c for r in (0, 1) for c in (0, 1)
            if not dead(2 * dist + r - c)}
    pieces = []
    for rows in ((0, 1),) if len(live) == 4 else ((0,), (1,)):
        cols = [c for c in (0, 1) if (rows[0], c) in live]
        if cols:
            pieces.append((
                rows[0] * half, len(rows) * half, cols[0] * half,
                len(cols) * half,
                any(cut(dh) for (r, _), dh in live.items() if r in rows)))
    return tuple(sorted(pieces, key=lambda piece: piece[1] * piece[3]))


def prefill_live_kv_tiles(qi, block_q: int, block_k: int, *,
                          causal: bool = True, window: Optional[int] = None,
                          delta=0):
    """(first, last) kv tile that `prefill_block_live` admits for q tile
    qi: the interval form solved for ki. Unclipped: `first` may be
    negative and `last` beyond the grid (or below `first`: no live tile),
    so callers clip into their grid."""
    q_lo = qi * block_q + delta
    first = (q_lo - window + 1) // block_k if window is not None else 0
    last = (q_lo + block_q - 1) // block_k if causal else 2 ** 30
    return first, last


def prefill_live_q_tiles(ki, block_q: int, block_k: int, *,
                         causal: bool = True, window: Optional[int] = None,
                         delta=0):
    """(first, last) q tile that `prefill_block_live` admits for kv tile
    ki: the same interval solved for qi (the inner axis of the fused
    backward kernel and of the split dk/dv kernel)."""
    first = (ki * block_k - delta) // block_q if causal else 0
    last = (((ki + 1) * block_k + window - delta - 2) // block_q
            if window is not None else 2 ** 30)
    return first, last
