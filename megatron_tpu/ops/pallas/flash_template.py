"""One FlashAttention-2 Pallas kernel family.

Every attention path in the repo — training/prefill forward AND backward,
single-token decode, speculative multi-query decode, paged decode — is an
instantiation of the one template in this module, with four knobs:

  knob          | values                  | what it changes
  --------------|-------------------------|------------------------------------
  work shape    | prefill / decode        | prefill: grid (B, Hq, Sq/BQ, Skv/BK),
                |                         | q tile [BQ, D] (FA-2 partitioning:
                |                         | parallel over Sq blocks and heads, kv
                |                         | axis innermost+sequential); decode:
                |                         | grid (B,), the kv loop INSIDE the
                |                         | kernel, from the row's first live
                |                         | block to its last; kv block
                |                         | [BK*Hkv, D] (BK cache positions with
                |                         | every kv head, as the cache lies in
                |                         | memory: several pages, copied by
                |                         | the kernel itself into two
                |                         | buffers), q tile [Hkv*Sq*G, D] (the
                |                         | Sq-small specialization — every kv
                |                         | head's grouped queries ride in one
                |                         | MXU tile, K/V never replicated)
  mask          | causal / bidirectional, | ops/pallas/masks.py: ONE position
                | sliding window,         | model supplies the element mask and
                | kv_lengths (decode)     | the block-skip predicate for every
                |                         | instantiation. A decode row's
                |                         | kv_lengths is its context, or, for
                |                         | a slot that does not decode, the
                |                         | length no query sees a position at
                |                         | (masks.decode_idle_length: 0, or
                |                         | 1 - Sq for Sq queries): the serving
                |                         | layer's word for "visit nothing"
                |                         | (models/transformer.py
                |                         | attention_block)
  paging        | dense / page table      | the page table rides in as a
                |                         | scalar-prefetch operand; the pools
                |                         | stay in HBM and the kernel's loop
                |                         | dereferences the table as it starts
                |                         | each page's copy (no dense gather);
                |                         | a dense cache is a pool whose pages
                |                         | are whole rows
  gradient      | fwd-only / custom_vjp   | the FA-2 recompute backward: fwd
                |                         | saves o and lse (compact, one float
                |                         | a row; named SAVED_RESIDUAL so a
                |                         | layer's checkpoint keeps them and
                |                         | the forward runs once), bwd
                |                         | recomputes p from (q, k, lse): ONE
                |                         | kernel visits each live tile pair
                |                         | once and sums dk/dv over q blocks
                |                         | in a tile's scratch and dq over kv
                |                         | blocks in a scratch of the head's
                |                         | whole sequence (five matmuls a
                |                         | pair). Under GQA dk/dv sum over the
                |                         | KV head's query heads too, in two
                |                         | such scratches of the KV head's
                |                         | whole sequence, float32, rounded
                |                         | once. A sequence whose sums do
                |                         | not fit VMEM (`fused_bwd_fits`:
                |                         | beyond 64k rows of 128 bf16, 16k
                |                         | under GQA) runs the split pair
                |                         | instead: one kernel for dq, one
                |                         | for dk/dv (seven).
                |                         | Before either, `flash_bwd_stats`
                |                         | spreads lse and delta = rowsum(do
                |                         | * o) over the lanes of the one
                |                         | operand the kernels read both from

Online softmax (running max m, running sum l, unnormalized acc in VMEM
scratch persisting across the sequential kv steps) is shared by every
instantiation, as is the block-skip, in two forms. The training kernels
walk a grid: a step whose kv tile lies outside the visible band of the
tile's queries (masks.block_live) skips its compute, so causal prefill
computes ~half the tiles. The step itself is still taken, and the
BlockSpec pipeline would still issue its DMA, so their index maps clamp
a skipped step to the nearest live tile of its row (`_inner_tile_map`):
the pipeline sees the block it holds and a dead step moves nothing. It
still costs its ~0.1 us, which is nothing beside a 1024 x 1024 tile and
was everything in decode: a grid step for every entry of every row's
page table (64 rows x 528 entries) took 3.4 ms a call where 2 % of the
entries were live, at the same time a step whether it held 32 KB or
4 KB (ledger, PR 47). So the decode kernel takes no dead step at all:
its grid is the rows, and each row's loop runs over the interval form
of the same predicate (masks.decode_live_blocks), a young slot in a
long cache over the context it has and an idle slot over nothing: the
serving layer hands it the length at which the interval is empty
(masks.decode_idle_length), and its grid step copies no page and
computes no block.

A live tile of the training kernels does what its mask leaves and no
more (`_visit_tile`): its place in the band is static wherever the
blocks are square, the call carries no traced offset and the window is
a multiple of the half tile (`_tile_classes`), so each kernel holds a
body a class. An interior tile, every pair visible, runs without
positions, mask or selects. A tile an edge of the band crosses (the
diagonal's, the window's lower edge's) runs as the two pieces that cover
its three live quarters, a row half at a time under a mask of static
positions, and never computes the dead quarter
(masks.prefill_tile_pieces). Any other shape, the ring's stripes among
them, runs the one masked body over whole tiles. `tile_counts` says what
a shape computes over what it needs.

Precision: the training kernels (fwd; the fused bwd and the split dq,
dk/dv, which share one tile function) hand the MXU their
operands in the dtype they arrive in — q, k, v, do as given, the
probabilities and ds cast to that dtype right before their matmuls — and
every matmul accumulates in float32 (`_dot`). The softmax statistics (m,
l, lse, delta), exp, the 1/sqrt(d) scale and all accumulators are
float32. bf16 in: bf16 MXU passes. float32 in (the CPU suite, a float32
reference): float32 operands, at the backend's default matmul precision.

Layouts: public entries take the framework-native [B, S, H, D]; the
training kernels run on [B, H, S, D] so the (S, D) tile is MXU-facing
(their transposes are of activations). K and V stay at their own heads,
[B, Hkv, S, D]: the kernels' index maps send query head h to KV head
h // groups (`_kv_head`; groups = Hq // Hkv, read off the operands'
shapes), so a KV head's tiles are read where they lie by each query head
of its group and nothing is broadcast to the query heads, and dk and dv
come back at that shape. The decode kernels read a KV cache
where it lies, [rows, positions, Hkv, D] (ops/kv_store.py): no cache is
transposed or copied for them. Kernels run in interpreter mode on CPU
hosts (tests/CI) and compile for real on TPU.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_tpu.ops import kv_store
from megatron_tpu.ops.pallas import masks

_NEG_INF = masks.NEG_INF
# the `checkpoint_name` of what the training forward hands its backward
# beside q, k, v: the output and the log-sum-exp. Selective recomputation
# saves them by this name (models/language_model.py _remat_policy): a
# Pallas call's result is no dot, and unnamed it is thrown away at the
# layer's checkpoint and the forward kernel runs a second time
SAVED_RESIDUAL = "flash_fwd_residual"
# Mosaic's scoped-VMEM default on a v5e, and what a kernel may ask for of
# the core's 128 MiB
_DEFAULT_SCOPED_VMEM = 16 << 20
_MAX_SCOPED_VMEM = 96 << 20

# contraction patterns of the 2-D tile matmuls: A·Bᵀ, A·B, Aᵀ·B
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_TN = (((0,), (0,)), ((), ()))


def _dot(a, b, dims):
    """Tile matmul of the training kernels: operands in the dtype they
    were given, float32 result (the precision contract above)."""
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _interpret() -> bool:
    # Pallas TPU kernels run in interpreter mode on CPU hosts (tests/CI)
    return jax.default_backend() == "cpu"


def interpret_forced() -> bool:
    """True when the dispatcher should use the kernels EVEN on a CPU host
    (interpreter mode — orders of magnitude slower than fused XLA, so
    only tests and the benchmark's rehearsals set this; see
    ops/attention.py)."""
    return os.environ.get("MEGATRON_TPU_FLASH_INTERPRET", "") not in ("", "0")


def _pick_block(s: int, cap: int = 512) -> Optional[int]:
    for b in (cap, 256, 128):
        if b <= s and s % b == 0:
            return b
    return s if s % 128 == 0 else None


def _fit_block(block: int, s: int) -> int:
    """The asked block, halved down to the 128 tile until it divides the
    sequence (384, 640, ...: serving prefill buckets run at 128). Where
    none does, a sequence shorter than the block is one block
    (interpreter-size inputs) and a longer one keeps the asked block,
    which `supported` then refuses."""
    b = block
    while b >= 128:
        if b <= s and s % b == 0:
            return b
        if b % 256:
            break
        b //= 2
    return min(block, s)


def supported(q_len: int, kv_len: int, block_q: int, block_k: int) -> bool:
    return (q_len == kv_len and q_len % block_q == 0
            and kv_len % block_k == 0)


# Tiles of the training kernels, from sweeps on the v5e (PR 24, and PR 40
# for the fused backward; bf16 [1,32,S,128], window 4096, ms a call at
# block_q x block_k with dead steps re-naming the held block and every
# live tile run whole under one masked body; PERF.md section 6 has all of
# it):
#
#   S 4096         256x256  512x512  512x1024  1024x512  1024x1024
#   flash_fwd        5.62     2.50     2.03      2.67      1.79
#   flash_bwd_dq     4.20     2.28     2.10      2.08      1.91
#   flash_bwd_dkv    6.75     2.60     2.16      2.22      2.11
#   flash_bwd                 2.82     2.69      2.69      2.56
#   (dq + dkv, PR 40)         4.47     4.20      4.21      3.97
#
# The fused `flash_bwd` does the work of the two rows above it in one
# visit of each tile pair: five matmuls and one vector pass where the pair
# run seven and two (the smaller tiles were read with dv's product first,
# which reads 2.62 at 1024x1024).
# 1024x1024 is also the fastest of the nine at S 2048, 8192 and 16384 for
# every kernel (16384: 13.3 / 13.7 / 17.1 ms against 52.5 / 42.6 / 68.2 at
# 256x256) and at [8,16,4096,128]; at S 1024 every tile of 512 or more
# reads the same. Why: the body is straight-line code that issues one
# bundle a cycle, and per 1024 scores it is 23 bundles at 256x256, 11 at
# 512x512 and 8 at 1024x1024 (the per-row statistics, their lane
# reductions and the accumulator's rescale spread over more columns). A
# 2048 tile's [BQ, BK] float32 temporaries do not fit.
#
# What a 1024 tile wastes on the band's edges it no longer computes (PR
# 53: each live tile runs the body of its class, `_visit_tile`). ms a call
# at 1024x1024 on the v5e, `tools/flash_kernel_bench.py`, every live tile
# whole under the one masked body -> a body a class, by class of layer:
#
#   layer, [B,H,S,128], window        tiles a head   flash_fwd       flash_bwd
#   window [2,32,8192] 1024      15 edge, 0 interior  6.29 -> 4.52    9.02 -> 7.15
#   full   [2,32,8192] none       8 edge, 28 interior 11.15 -> 9.83  17.67 -> 16.57
#   causal [1,32,4096] 4096       4 edge, 6 interior  1.85 -> 1.41    2.58 -> 2.28
#          [8,16,4096] 4096       (the four-chip shard) 7.54 -> 5.77 10.18 -> 8.99
#          [1,16,4096] none                           0.83 -> 0.71    1.29 -> 1.16
#
# Since PR 70 the kernels read K and V by KV head (`_kv_head`) and the
# fused backward sums dk and dv over a KV head's query heads in its own
# scratch. Same tool, ms a call, K and V broadcast to the query heads
# outside the kernels -> read where they lie; then the layer's forward and
# backward with XLA's broadcast and group sum around the kernels:
#
#   q [B,H,S,128] over KV heads      flash_fwd       flash_bwd      fwd layer       bwd layer
#   window [2,32,8192] over 4      4.53 -> 4.55   7.15 -> 6.65   4.97 -> 4.55    8.03 -> 6.66
#   full   [2,32,8192] over 4      9.83 -> 9.90  16.57 -> 16.60 10.27 -> 9.90   17.46 -> 16.62
#   causal [1,32,4096] over 8      1.41 -> 1.41   2.28 -> 2.27   1.42 -> 1.41    2.43 -> 2.27
#          [8,16,4096] over 4      5.77 -> 5.77   8.99 -> 8.94   6.26 -> 5.77    9.97 -> 8.95
#
# By tile (us; read off the same calls and their variants): a forward tile
# whole under the mask of its traced positions 6.5, under a mask of static
# positions 5.3, interior 4.0 (its two matmuls need 2.7), an edge tile's
# two pieces 4.7; a backward tile 9.4 masked, 7.1 interior (its five
# matmuls need 6.8), 7.4 as two pieces. The forward's pieces save less
# than their area because a row's statistics cost the same whatever it
# sees (an edge tile cut by column halves, a second pass over 512 of its
# rows, reads 1.65 us slower), and the smaller piece runs first because
# the other order reads 0.7 to 1.1 us a tile slower; masking only the
# quarter an edge cuts, or dropping the second select where every row
# sees a key, read the same to 0.1 %.
_SWEPT_BLOCK = 1024


def pick_blocks(s: int, d: int, dtype) -> Tuple[int, int]:
    """(block_q, block_k) of the training kernels for sequences of s rows
    of d elements of dtype: the swept tile fitted to the sequence
    (`_fit_block`), halved while the backward kernels' VMEM footprint at
    this row width is more than a kernel may ask for."""
    block = _SWEPT_BLOCK
    while (block > 128 and _bwd_vmem_bytes(
            block, block, d, jnp.dtype(dtype).itemsize) > _MAX_SCOPED_VMEM):
        block //= 2
    block = _fit_block(block, s)
    return block, block


# ---------------------------------------------------------------------------
# prefill/training forward
# ---------------------------------------------------------------------------


def _tile_classes(nq: int, nk: int, block_q: int, block_k: int,
                  causal: bool, window: Optional[int], offset):
    """{dist: pieces}: the tile pairs of a grid of nq x nk tiles that are
    NOT whole and wholly visible, by their distance under the diagonal
    (dist = qi - ki), each with the pieces of it that its mask leaves
    (`masks.prefill_tile_pieces`). A live tile at any other distance is
    an interior tile. A causal sequence has one such distance, the
    diagonal's; a window adds the tile its lower edge crosses (two, where
    the window is an odd number of half tiles). None where a tile's place
    in the band is not static or not on the half-tile grid, and every
    live tile runs the one masked body: a traced position offset (ring
    and Ulysses stripes), unequal blocks, a window that is no multiple of
    the half tile, a half tile that hardware cannot slice (no multiple of
    128; the interpreter takes any even block)."""
    half = block_q // 2
    if (offset is not None or block_q != block_k or block_q % 2
            or (half % 128 and not _interpret())
            or (window is not None and window % half)):
        return None
    whole = ((0, block_q, 0, block_k, False),)
    classes = {}
    for dist in range(1 - nk, nq):
        pieces = masks.prefill_tile_pieces(dist, block_q, causal=causal,
                                           window=window)
        if pieces and pieces != whole:
            classes[dist] = pieces
    return classes


def tile_counts(s: int, block: int, causal: bool,
                window: Optional[int]) -> dict:
    """How far the tile classes engage for a head of s rows at square
    tiles of `block`, aligned: the live tile pairs (`tiles`) by class —
    `interior` (every pair visible), `causal_edge` (the causal frontier
    crosses it), `window_edge` (the window's lower edge does), `both` —
    whether each runs the body of its class, an edge tile its live
    pieces and no more (`by_class`;
    false where `_tile_classes` gives none and every live tile is
    computed whole), and the score elements the kernels compute, as
    tiles (`tiles_computed`) and over the visible pairs
    (`computed_over_visible`; 1.0 would be a kernel that computes no
    masked pair). A count from the shape alone: the trainer journals it
    for each kind of attention layer (`step_program.attention_tiles`)."""
    n = s // block
    classes = _tile_classes(n, n, block, block, causal, window, None)
    counts = {"interior": 0, "causal_edge": 0, "window_edge": 0, "both": 0}
    computed = 0
    # aligned square tiles: a pair's class follows from its distance
    # under the diagonal alone, and n - |dist| pairs lie at each
    for dist in range(1 - n, n):
        qi, ki = max(dist, 0), max(-dist, 0)
        if not masks.prefill_block_live(qi, ki, block, block, causal=causal,
                                        window=window):
            continue
        lo, hi = qi * block, qi * block + block - 1
        on_causal = causal and (ki + 1) * block - 1 > lo
        on_window = window is not None and ki * block <= hi - window
        pieces = (classes or {}).get(dist, ((0, block, 0, block, None),))
        counts[("interior", "causal_edge", "window_edge", "both")[
            on_causal + 2 * on_window]] += n - abs(dist)
        computed += (n - abs(dist)) * sum(nr * nc
                                          for _, nr, _, nc, _ in pieces)
    q_pos = np.arange(s, dtype=np.int64)
    last = q_pos if causal else np.full_like(q_pos, s - 1)
    first = (np.zeros_like(q_pos) if window is None
             else np.maximum(q_pos - window + 1, 0))
    visible = int((last - first + 1).sum())
    return {"tiles": sum(counts.values()), **counts,
            "by_class": classes is not None,
            "tiles_computed": computed / block ** 2,
            "computed_over_visible": computed / visible}


def _visit_tile(qi, ki, off, classes, piece, *, causal: bool,
                window: Optional[int], block_q: int, block_k: int):
    """Runs `piece(rows, cols, mask)` over what its mask leaves of tile
    pair (qi, ki): the slices of the tile's q rows and kv columns a piece
    holds, and its mask, None where every pair of the piece is visible.
    The FA-2 block-skip: a tile outside the
    visible band (beyond the causal frontier / before the window's lower
    edge) runs nothing, and its index map has re-named the held block, so
    it loads nothing either. A live tile runs the body of its class
    (`_tile_classes`): an interior tile the whole tile with no mask
    arithmetic at all, an edge tile its live pieces under a mask of
    static positions. Without classes, one body: the whole tile under
    the mask of its traced positions."""
    live = masks.prefill_block_live(qi, ki, block_q, block_k, causal=causal,
                                    window=window, delta=off)
    whole_q, whole_k = pl.ds(0, block_q), pl.ds(0, block_k)
    if classes is None:
        @pl.when(live)
        def _masked():
            q_pos, k_pos = masks.prefill_positions(qi, ki, block_q, block_k,
                                                   off)
            piece(whole_q, whole_k,
                  masks.visible(q_pos, k_pos, causal=causal, window=window))
        return

    dist = qi - ki
    interior = live
    for at, pieces in classes.items():
        interior = interior & (dist != at)

        @pl.when(dist == at)
        def _edge(at=at, pieces=pieces):
            for r0, nr, c0, nc, masked in pieces:
                mask = None
                if masked:
                    # the tile's first query sits at * block_q positions
                    # past its first key, wherever the tile is
                    q_pos, k_pos = masks.tile_positions(at * block_q + r0,
                                                        c0, nr, nc)
                    mask = masks.visible(q_pos, k_pos, causal=causal,
                                         window=window)
                piece(pl.ds(r0, nr), pl.ds(c0, nc), mask)

    @pl.when(interior)
    def _interior():
        piece(whole_q, whole_k, None)


def _fwd_kernel(delta_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale: float, causal: bool, window: Optional[int],
                block_q: int, block_k: int, classes):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def piece(rows, cols, mask):
        """The online-softmax step of those q rows over those kv columns
        of the tile: rows are independent in every statistic, so a piece
        updates its rows' and no others."""
        q = q_ref[0, 0, rows, :]                         # [nr, D]
        k = k_ref[0, 0, cols, :]                         # [nc, D]
        s = _dot(q, k, _NT) * scale                      # [nr, nc] f32
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[rows, :]                          # [nr, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if mask is not None:
            # a row the mask leaves nothing of has m_new at _NEG_INF
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[rows, :] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0, cols, :]                         # [nc, D]
        pv = _dot(p.astype(v.dtype), v, _NN)             # [nr, D] f32
        acc_scr[rows, :] = acc_scr[rows, :] * alpha + pv
        m_scr[rows, :] = m_new
        l_scr[rows, :] = l_new

    _visit_tile(qi, ki, delta_ref[0], classes, piece, causal=causal,
                window=window, block_q=block_q, block_k=block_k)

    @pl.when(ki == nk - 1)
    def _emit():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # lane-padded to 128: [..., 1]-shaped outputs get tiled to 128 lanes
        # anyway, and the narrow layout trips XLA's scoped-vmem stack
        # allocation for custom-call outputs (observed on v5e)
        lse_ref[0, 0] = jnp.broadcast_to(m_scr[:] + jnp.log(l),
                                         lse_ref.shape[2:])


def _named_pallas_call(name: str, kernel, **kwargs):
    """`pl.pallas_call(kernel, name=name, ...)`, bound under
    `jax.named_scope(name)`. `name=` names the Mosaic module and the HLO
    instruction (`%flash_fwd.13`); the scope puts the same word into the
    operation's name stack (".../attention/flash_fwd/pallas_call"), which
    is where a device trace's readers look for it and which holds
    whatever the instruction ends up being called
    (docs/observability.md "Runtime traces")."""
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def bound(*args):
        with jax.named_scope(name):
            return call(*args)

    return bound


def _delta_arr(delta):
    """Scalar global-position offset -> [1] int32 SMEM operand."""
    if delta is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(delta, jnp.int32).reshape(1)


def _kv_head(h, groups: int):
    """The KV head that query head h reads: a KV head's `groups` query
    heads lie side by side. One query head a KV head (MHA, and the ring's
    stripes, which arrive broadcast) names its own, with no division."""
    return h if groups == 1 else jax.lax.div(h, groups)


def _outer_tile_map(groups: int = 1):
    """Index map of the tile a grid (b, h, outer, inner) holds across its
    inner, sequential axis (the scalar-prefetch operand rides along).
    `groups`: the tile is a KV head's, addressed by the query head of the
    grid (`_kv_head`); 1 for a tile of the query head's own."""
    def index(b, h, outer, inner, off_ref):
        return (b, _kv_head(h, groups), outer, 0)
    return index


def _inner_tile_map(live_tiles, n: int, groups: int = 1):
    """Index map of the tile that walks the inner axis of a grid
    (b, h, outer, inner) whose scalar-prefetch operand is the position
    offset. `live_tiles(outer, delta=)` is the (first, last) inner tile
    the block-skip admits (masks.prefill_live_kv_tiles for the forward
    and dq grids, prefill_live_q_tiles for the fused backward's and
    dk/dv's): a step outside it
    names the nearest live tile instead of its own, so the pipeline sees
    the block it already holds and issues no DMA. The second clip keeps a
    row with no live tile at all inside the grid of n tiles. `groups`:
    the walked tiles are a KV head's, read where they lie by each query
    head of its group (`_kv_head`): nothing is copied to the query heads."""
    def index(b, h, outer, inner, off_ref):
        lo, hi = live_tiles(outer, delta=off_ref[0])
        return (b, _kv_head(h, groups),
                jnp.clip(jnp.clip(inner, lo, hi), 0, n - 1), 0)
    return index


def _group_walk_map(live_tiles, nq: int, groups: int):
    """Index map of the q-side tile of the split dk/dv grid over a KV
    head, (b, hkv, kv tile, j): its inner axis walks the q tiles of the
    head's first query head, then the second's, ... (j = g * nq + qi), so
    a kv tile's sums over its whole group stand in one tile's scratch.
    The block-skip's clamp is `_inner_tile_map`'s."""
    def index(b, hkv, outer, j, off_ref):
        lo, hi = live_tiles(outer, delta=off_ref[0])
        return (b, hkv * groups + jax.lax.div(j, nq),
                jnp.clip(jnp.clip(jax.lax.rem(j, nq), lo, hi), 0, nq - 1), 0)
    return index


def _compiler_params(vmem_bytes: int, outer: str = "parallel",
                     heads: str = "parallel"):
    """Batch and heads parallel, the inner sequence axis the sequential
    reduction; the outer sequence axis parallel too unless a kernel sums
    over it as well (`outer="arbitrary"`: the fused backward's dq; on a
    megacore part only B x H is then left to split across the two cores,
    which the single-core v5e this was measured on cannot show), and the
    heads unless a kernel sums over a KV head's query heads
    (`heads="arbitrary"`: the fused backward's dk and dv under GQA). The
    scoped VMEM limit is raised only when the footprint needs it."""
    limit = None
    if vmem_bytes > _DEFAULT_SCOPED_VMEM:
        limit = min(vmem_bytes, _MAX_SCOPED_VMEM)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", heads, outer, "arbitrary"),
        vmem_limit_bytes=limit)


def _fwd_vmem_bytes(block_q, block_k, D, item):
    """q, o and the k, v tiles double-buffered, lse lane-padded, the
    float32 statistics and accumulator, and ~4 live [BQ, BK] float32
    temporaries (s, mask, p and p in v's dtype)."""
    return (2 * (2 * block_q + 2 * block_k) * D * item
            + 2 * block_q * 128 * 4 + block_q * (D + 256) * 4
            + 4 * block_q * block_k * 4)


def _fwd(q, k, v, scale, causal, window, block_q, block_k, delta=None):
    """q [B,Hq,Sq,D], k/v [B,Hkv,Skv,D]: a KV head's keys and values as
    they lie, read by each query head of its group through the index maps
    (`_kv_head`), broadcast nowhere. Returns (o [B,Hq,Sq,D],
    lse [B,Hq,Sq]). delta: traced q-vs-k global position offset (ring
    stripes); None = aligned."""
    B, H, Sq, D = q.shape
    Skv = k.shape[2]
    groups = H // k.shape[1]
    nk = Skv // block_k

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
        classes=_tile_classes(Sq // block_q, nk, block_q, block_k, causal,
                              window, delta))
    q_map = _outer_tile_map()
    kv_map = _inner_tile_map(functools.partial(
        masks.prefill_live_kv_tiles, block_q=block_q, block_k=block_k,
        causal=causal, window=window), nk, groups)
    o, lse = _named_pallas_call(
        "flash_fwd", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, Sq // block_q, nk),
            in_specs=[
                pl.BlockSpec((1, 1, block_q, D), q_map),
                pl.BlockSpec((1, 1, block_k, D), kv_map),
                pl.BlockSpec((1, 1, block_k, D), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, block_q, D), q_map),
                pl.BlockSpec((1, 1, block_q, 128), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, D), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 128), jnp.float32),
        ],
        compiler_params=_compiler_params(
            _fwd_vmem_bytes(block_q, block_k, D, q.dtype.itemsize)),
        interpret=_interpret(),
    )(_delta_arr(delta), q, k, v)
    # every lane of the kernel's lane-padded result holds the row's number
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# prefill/training backward (FA-2 recompute scheme)
# ---------------------------------------------------------------------------


def _bwd_tile(q, k, v, do, stats, mask, scale: float):
    """(p, ds), [rows, columns] float32 each, of one piece of a live
    tile pair: the probabilities recomputed from the log-sum-exp and the
    gradient of the scores (before the 1/sqrt(d), which the kernels put
    once on their float32 sums). Two matmuls and the whole of the piece's
    vector work; every backward kernel forms them by this one function.
    mask None: every pair of the piece is visible."""
    lse, delta = stats                                   # [rows, 1] each
    s = _dot(q, k, _NT) * scale
    p = jnp.exp(s - lse)                                 # softmax probs
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    dp = _dot(do, v, _NT)                                # [rows, columns]
    return p, p * (dp - delta)


def _bwd_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, stats_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
                *, scale: float, causal: bool, window: Optional[int],
                block_q: int, block_k: int, classes, groups: int):
    """The fused backward: grid (b, h, ki, qi), q innermost. Every live
    tile pair is visited once and gives all three gradients from one p
    and one ds (five matmuls a piece of it: `_visit_tile`). dq sums over
    the OUTER axis, so its float32 accumulator holds the head's whole
    sequence, [Sq/BQ, BQ, D], and the dq output block is the head's whole
    [Sq, D]: q tile qi's rows are zeroed on the first kv tile's pass and
    scaled, cast and written on the last one's. dk and dv of the kv tile
    sum over the inner axis: with one query head a KV head in [BK, D]
    scratch, written as the tile's last q tile has passed. With `groups`
    of them they are the KV head's, summed over its query heads too, which
    the grid visits one after another (h = hkv * groups + g): their
    float32 accumulators then hold the KV head's whole sequence,
    [Skv/BK, BK, D], as dq's holds the query head's, zeroed under the
    group's first head and scaled, rounded ONCE and written under its
    last, through an output block of the KV head's whole [Skv, D]. Each
    sum takes its terms in the order the split pair takes them (a kv
    tile's: query heads ascending, tiles ascending, and a tile's pieces
    in theirs)."""
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nk = pl.num_programs(2)
    nq = pl.num_programs(3)

    def of_head(at_tile, g: int):
        """at_tile, under the g-th query head of the KV head."""
        if groups == 1:
            return at_tile
        return at_tile & (jax.lax.rem(pl.program_id(1), groups) == g)

    def kv_rows(cols):
        """Where those columns of kv tile ki stand in dk's and dv's
        accumulators."""
        return (ki, cols, slice(None)) if groups > 1 else (cols, slice(None))

    @pl.when(of_head(qi == 0, 0))
    def _init_kv():
        tile = (block_k, dk_scr.shape[-1])
        dk_scr[kv_rows(slice(None))] = jnp.zeros(tile, dk_scr.dtype)
        dv_scr[kv_rows(slice(None))] = jnp.zeros(tile, dv_scr.dtype)

    @pl.when(ki == 0)
    def _init_q():
        dq_scr[qi] = jnp.zeros(dq_scr.shape[1:], dq_scr.dtype)

    def piece(rows, cols, mask):
        q = q_ref[0, 0, rows, :]
        k = k_ref[0, 0, cols, :]
        do = do_ref[0, 0, rows, :]
        p, ds = _bwd_tile(q, k, v_ref[0, 0, cols, :], do,
                          _row_stats(stats_ref, rows), mask, scale)
        # dq's product first: it takes ds as it lies, while the other two
        # wait for a transpose of ds and of p (2.56 ms a call against 2.62
        # with dv's first and 2.65 with dq's last, at the swept shape)
        ds = ds.astype(q.dtype)
        dq_scr[qi, rows, :] += _dot(ds, k, _NN)
        dk_scr[kv_rows(cols)] += _dot(ds, q, _TN)
        dv_scr[kv_rows(cols)] += _dot(p.astype(do.dtype), do, _TN)

    _visit_tile(qi, ki, off_ref[0], classes, piece, causal=causal,
                window=window, block_q=block_q, block_k=block_k)

    @pl.when(of_head(qi == nq - 1, groups - 1))
    def _emit_kv():
        out = ((0, 0, pl.ds(pl.multiple_of(ki * block_k, block_k), block_k),
                slice(None)) if groups > 1 else (0, 0))
        # the 1/sqrt(d) of the scores, once on the float32 sum
        dk_ref[out] = (dk_scr[kv_rows(slice(None))]
                       * scale).astype(dk_ref.dtype)
        dv_ref[out] = dv_scr[kv_rows(slice(None))].astype(dv_ref.dtype)

    @pl.when(ki == nk - 1)
    def _emit_q():
        rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
        dq_ref[0, 0, rows, :] = (dq_scr[qi] * scale).astype(dq_ref.dtype)


def _dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, stats_ref,
               dq_ref, dq_scr,
               *, scale: float, causal: bool, window: Optional[int],
               block_q: int, block_k: int, classes):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def piece(rows, cols, mask):
        k = k_ref[0, 0, cols, :]
        _, ds = _bwd_tile(q_ref[0, 0, rows, :], k, v_ref[0, 0, cols, :],
                          do_ref[0, 0, rows, :],
                          _row_stats(stats_ref, rows), mask, scale)
        dq_scr[rows, :] += _dot(ds.astype(k.dtype), k, _NN)

    _visit_tile(qi, ki, off_ref[0], classes, piece, causal=causal,
                window=window, block_q=block_q, block_k=block_k)

    @pl.when(ki == nk - 1)
    def _emit():
        # the 1/sqrt(d) of the scores, once on the float32 sum
        dq_ref[0, 0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, stats_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale: float, causal: bool, window: Optional[int],
                block_q: int, block_k: int, classes, groups: int):
    """Grid (b, hkv, ki, j): the inner axis walks the q tiles of each
    query head of the KV head in turn (`_group_walk_map`; one query head
    a KV head: j is the q tile), and the kv tile's two sums run over all
    of it."""
    ki = pl.program_id(2)
    j = pl.program_id(3)
    qi = (j if groups == 1
          else jax.lax.rem(j, jax.lax.div(pl.num_programs(3), groups)))

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def piece(rows, cols, mask):
        q = q_ref[0, 0, rows, :]
        do = do_ref[0, 0, rows, :]
        p, ds = _bwd_tile(q, k_ref[0, 0, cols, :], v_ref[0, 0, cols, :], do,
                          _row_stats(stats_ref, rows), mask, scale)
        dv_scr[cols, :] += _dot(p.astype(do.dtype), do, _TN)
        dk_scr[cols, :] += _dot(ds.astype(q.dtype), q, _TN)

    _visit_tile(qi, ki, off_ref[0], classes, piece, causal=causal,
                window=window, block_q=block_q, block_k=block_k)

    @pl.when(j == pl.num_programs(3) - 1)
    def _emit():
        # the 1/sqrt(d) of the scores, once on the float32 sum
        dk_ref[0, 0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_vmem_bytes(block_q, block_k, D, item):
    """q, do, k, v tiles and the output tile(s) double-buffered, the row
    statistics lane-padded, the float32 accumulators, and ~6 live
    [BQ, BK] float32 temporaries (s, mask, p, dp, ds and a cast of p or
    ds)."""
    return (2 * (3 * block_q + 4 * block_k) * D * item
            + 2 * block_q * 128 * 4
            + 2 * max(block_q, block_k) * D * 4
            + 6 * block_q * block_k * 4)


def _fused_bwd_vmem_bytes(sq, block_q, block_k, D, item, groups=1):
    """The tiles above (dk's and dv's output tiles and accumulators
    among them) and dq of a head's whole sequence: the float32
    accumulator and the double-buffered output block. Under GQA
    (`groups` query heads a KV head) dk and dv of the KV head's whole
    sequence as well, the same two a gradient."""
    whole = sq * D * 4 + 2 * sq * D * item
    return (_bwd_vmem_bytes(block_q, block_k, D, item)
            + whole * (3 if groups > 1 else 1))


def fused_bwd_fits(sq: int, d: int, dtype, block_q: int, block_k: int,
                   groups: int = 1) -> bool:
    """Which backward a shape takes. One algorithm, two footprints: the
    fused kernel keeps dq of a head's whole sequence in VMEM, which fits
    beside the tiles up to 64k rows of 128 bf16, and under GQA (`groups`
    > 1: from the operands' shapes) dk and dv of the KV head's as well,
    which fits up to 16k rows; a longer sequence runs the split pair,
    whose footprint does not grow with the sequence."""
    return _fused_bwd_vmem_bytes(
        sq, block_q, block_k, d, jnp.dtype(dtype).itemsize,
        groups) <= _MAX_SCOPED_VMEM


# The two per-row statistics of the backward kernels, lse and
# delta = rowsum(do * o), ride in ONE float32 operand [B,H,Sq,128]: a
# [..., 1]-shaped operand is tiled to 128 lanes anyway, so lanes
# [0, _DELTA_LANE) hold lse and the rest hold delta. Spreading a row's
# number over the lanes is a pass of its own around the kernels (PERF.md
# section 6, PR 36); packed, the two statistics cost one such pass: the
# Pallas call `flash_bwd_stats` (0.43 ms for [8,16,4096,128] on a v5e,
# three quarters of the HBM's rate and what XLA's broadcast-and-select
# took for the same bits: PERF.md section 6, PR 40). delta itself stays
# with XLA, which sums it in the epilogue of the matmul that makes `do`.
_DELTA_LANE = 64


def _stats_kernel(lse_ref, delta_ref, stats_ref):
    """Grid (b, q tile, h): the q tile's two statistics of every head,
    [H, BQ] each with the rows in lanes (fetched once a q tile), spread
    into head h's [BQ, 128]: the two rows are laid over the sublanes,
    lse on the first _DELTA_LANE and delta on the rest, and transposed."""
    h = pl.program_id(2)
    block_q = stats_ref.shape[2]
    sublane = jax.lax.broadcasted_iota(jnp.int32, (128, block_q), 0)
    rows = jnp.where(
        sublane < _DELTA_LANE,
        jnp.broadcast_to(lse_ref[0, pl.ds(h, 1), :], (128, block_q)),
        jnp.broadcast_to(delta_ref[0, pl.ds(h, 1), :], (128, block_q)))
    stats_ref[0, 0] = rows.T


def _bwd_stats(lse, o, do, block_q):
    """lse [B,H,Sq] and rowsum(do * o), float32, as the backward kernels
    read them (`_row_stats`): [B,H,Sq,128]."""
    B, H, Sq = lse.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    rows = pl.BlockSpec((1, H, block_q), lambda b, qi, h: (b, 0, qi))
    return _named_pallas_call(
        "flash_bwd_stats", _stats_kernel,
        grid=(B, Sq // block_q, H),
        in_specs=[rows, rows],
        out_specs=pl.BlockSpec((1, 1, block_q, 128),
                               lambda b, qi, h: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=_interpret(),
    )(lse, delta)


def _row_stats(stats_ref, rows):
    """(lse, delta), [rows, 1] each, of those rows of a backward
    kernel's q tile."""
    stats = stats_ref[0, 0, rows, :]
    return stats[:, 0:1], stats[:, _DELTA_LANE:_DELTA_LANE + 1]


def _bwd_in_specs(block_q, block_k, D, q_map, kv_map):
    """q, k, v, do and the row statistics, as every backward kernel
    takes them behind the scalar-prefetch offset."""
    return [
        pl.BlockSpec((1, 1, block_q, D), q_map),
        pl.BlockSpec((1, 1, block_k, D), kv_map),
        pl.BlockSpec((1, 1, block_k, D), kv_map),
        pl.BlockSpec((1, 1, block_q, D), q_map),
        pl.BlockSpec((1, 1, block_q, 128), q_map),
    ]


def _bwd_dq(q, k, v, do, stats, scale, causal, window, block_q,
            block_k, offset=None):
    """dq [B,H,Sq,D]: grid (b, h, qi, ki), kv innermost, one float32
    accumulator per q tile; k, v [B,Hkv,Skv,D], read by KV head."""
    B, H, Sq, D = q.shape
    nk = k.shape[2] // block_k
    kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k,
        classes=_tile_classes(Sq // block_q, nk, block_q, block_k, causal, window,
                              offset))
    q_map = _outer_tile_map()
    kv_map = _inner_tile_map(functools.partial(
        masks.prefill_live_kv_tiles, block_q=block_q, block_k=block_k,
        causal=causal, window=window), nk, H // k.shape[1])
    return _named_pallas_call(
        "flash_bwd_dq", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, Sq // block_q, nk),
            in_specs=_bwd_in_specs(block_q, block_k, D, q_map, kv_map),
            out_specs=pl.BlockSpec((1, 1, block_q, D), q_map),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        compiler_params=_compiler_params(
            _bwd_vmem_bytes(block_q, block_k, D, q.dtype.itemsize)),
        interpret=_interpret(),
    )(_delta_arr(offset), q, k, v, do, stats)


def _bwd_dkv(q, k, v, do, stats, scale, causal, window, block_q,
             block_k, offset=None):
    """(dk, dv) [B,Hkv,Skv,D]: grid (b, hkv, ki, j), the q tiles of the
    KV head's query heads innermost, two float32 accumulators per kv
    tile: the sum over the group stands in them, rounded once."""
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1:3]
    groups = H // Hkv
    nq = Sq // block_q
    kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, groups=groups,
        classes=_tile_classes(nq, Skv // block_k, block_q, block_k, causal, window,
                              offset))
    live_q = functools.partial(
        masks.prefill_live_q_tiles, block_q=block_q, block_k=block_k,
        causal=causal, window=window)
    q_map = (_inner_tile_map(live_q, nq) if groups == 1
             else _group_walk_map(live_q, nq, groups))
    kv_map = _outer_tile_map()
    return _named_pallas_call(
        "flash_bwd_dkv", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, Skv // block_k, groups * nq),
            in_specs=_bwd_in_specs(block_q, block_k, D, q_map, kv_map),
            out_specs=[
                pl.BlockSpec((1, 1, block_k, D), kv_map),
                pl.BlockSpec((1, 1, block_k, D), kv_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Skv, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Skv, D), q.dtype),
        ],
        compiler_params=_compiler_params(
            _bwd_vmem_bytes(block_q, block_k, D, q.dtype.itemsize)),
        interpret=_interpret(),
    )(_delta_arr(offset), q, k, v, do, stats)


def _bwd_fused(q, k, v, do, stats, scale, causal, window, block_q,
               block_k, offset=None):
    """(dq [B,H,Sq,D], dk, dv [B,Hkv,Skv,D]) in one call: the dk/dv grid
    (b, h, ki, qi) with dq summed across its outer axis in a float32
    scratch of the head's whole sequence and, under GQA, dk and dv across
    the KV head's query heads in two of the KV head's (`_bwd_kernel`).
    dq is the FIRST result: the benchmark's cost file counts over it."""
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1:3]
    groups = H // Hkv
    nq = Sq // block_q
    nk = Skv // block_k
    kernel = functools.partial(
        _bwd_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, groups=groups,
        classes=_tile_classes(nq, nk, block_q, block_k, causal, window,
                              offset))
    q_map = _inner_tile_map(functools.partial(
        masks.prefill_live_q_tiles, block_q=block_q, block_k=block_k,
        causal=causal, window=window), nq)
    kv_map = _outer_tile_map(groups)
    if groups == 1:
        dkv_spec = pl.BlockSpec((1, 1, block_k, D), kv_map)
        dkv_scr = pltpu.VMEM((block_k, D), jnp.float32)
    else:
        # held for all of a KV head's steps, written back once
        dkv_spec = pl.BlockSpec(
            (1, 1, Skv, D),
            lambda b, h, ki, qi, off_ref: (b, _kv_head(h, groups), 0, 0))
        dkv_scr = pltpu.VMEM((nk, block_k, D), jnp.float32)
    return _named_pallas_call(
        "flash_bwd", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, nk, nq),
            in_specs=_bwd_in_specs(block_q, block_k, D, q_map, kv_map),
            out_specs=[
                # held for all of a head's steps, written back once
                pl.BlockSpec((1, 1, Sq, D),
                             lambda b, h, ki, qi, off_ref: (b, h, 0, 0)),
                dkv_spec, dkv_spec,
            ],
            scratch_shapes=[
                pltpu.VMEM((nq, block_q, D), jnp.float32),
                dkv_scr, dkv_scr,
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Skv, D), q.dtype),
            jax.ShapeDtypeStruct((B, Hkv, Skv, D), q.dtype),
        ],
        compiler_params=_compiler_params(
            _fused_bwd_vmem_bytes(Sq, block_q, block_k, D,
                                  q.dtype.itemsize, groups),
            outer="arbitrary",
            heads="parallel" if groups == 1 else "arbitrary"),
        interpret=_interpret(),
    )(_delta_arr(offset), q, k, v, do, stats)


def _bwd_split(q, k, v, do, stats, *tile_args):
    """The same gradients by two calls, each with one tile's accumulators
    (seven matmuls and the vector work twice a tile pair)."""
    dq = _bwd_dq(q, k, v, do, stats, *tile_args)
    dk, dv = _bwd_dkv(q, k, v, do, stats, *tile_args)
    return dq, dk, dv


def _bwd(q, k, v, o, lse, do, scale, causal, window, block_q, block_k,
         offset=None):
    """(dq [B,Hq,Sq,D], dk, dv [B,Hkv,Skv,D]) given the forward's output
    and its log-sum-exp [B,H,Sq] (compact: one float a row): the fused
    kernel wherever its footprint fits (`fused_bwd_fits`), else the
    split pair."""
    stats = _bwd_stats(lse, o, do, block_q)
    fused = fused_bwd_fits(q.shape[2], q.shape[3], q.dtype, block_q, block_k,
                           q.shape[1] // k.shape[1])
    return (_bwd_fused if fused else _bwd_split)(
        q, k, v, do, stats, scale, causal, window, block_q, block_k, offset)


# ---------------------------------------------------------------------------
# custom_vjp over [B,H,S,D]: the training fwd+bwd instantiation
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, scale, causal, window, block_q, block_k):
    o, _ = _fwd(q, k, v, scale, causal, window, block_q, block_k)
    return o


def _flash_fwd_rule(q, k, v, scale, causal, window, block_q, block_k):
    o, lse = _fwd(q, k, v, scale, causal, window, block_q, block_k)
    # The residuals a layer's checkpoint may keep (SAVED_RESIDUAL). The
    # primal output is the named `o` too: what reads it downstream (the
    # out projection's weight gradient) then reads the kept one. The
    # log-sum-exp is kept compact, float32 [B, H, S]: the kernel's
    # lane-padded [B, H, S, 128] is twice `o`, and every lane holds the
    # same number
    o = checkpoint_name(o, SAVED_RESIDUAL)
    lse = checkpoint_name(lse, SAVED_RESIDUAL)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(scale, causal, window, block_q, block_k, res, do):
    q, k, v, o, lse = res
    return _bwd(q, k, v, o, lse, do, scale, causal, window, block_q,
                block_k)


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ---------------------------------------------------------------------------
# one stripe pair of a ring schedule (ops/ring_attention.py): the forward
# and backward above without the custom_vjp, which the ring owns
# ---------------------------------------------------------------------------


def stripe_fwd(q, k, v, delta, window, scale, block, causal=True):
    """(o float32, lse [B, H, c]) for one stripe pair, [B, H, c, D] layout
    (the ring hands k/v group-broadcast, one KV head a query head, and
    takes dk/dv back at that shape: ops/ring_attention.py). ONE kernel
    covers every stripe relation: `delta` (traced, an SMEM scalar inside the kernel) is the
    q-vs-k global-position offset, so the causal mask k <= q + delta
    renders the aligned diagonal (delta 0), fully-visible past blocks
    (delta >= c) and shifted sliding-window bands alike. causal=False =
    fully-visible blocks (bidirectional contiguous ring). A fully-masked
    row reports lse at masks.NEG_INF depth, finite."""
    o, lse = _fwd(q, k, v, scale, causal, window, block, block, delta=delta)
    return o.astype(jnp.float32), lse


def stripe_bwd(q, k, v, o, lse, do, delta, window, scale, block,
               causal=True):
    """(dq, dk, dv) for one stripe pair given the GLOBAL lse [B, H, c]
    (the FA-2 recompute scheme: p = exp(s - lse_global), so per-stripe
    gradients sum to the exact dense gradient)."""
    return _bwd(q, k, v, o, lse, do, scale, causal, window, block, block,
                offset=delta)


def flash_mha(
    q: jnp.ndarray,  # [B, Sq, Hq, D]
    k: jnp.ndarray,  # [B, Skv, Hkv, D]
    v: jnp.ndarray,
    sliding_window: Optional[int] = None,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
) -> jnp.ndarray:
    """The training/prefill instantiation in framework layout: fused
    forward + the FA-2 recompute backward via custom_vjp — jax.grad
    through this never builds the XLA O(S^2) gradient. GQA (Hq a
    multiple of Hkv, read off the operands' shapes) broadcasts nothing:
    the kernels read a KV head's tiles where they lie for each query
    head of its group, and the backward sums dk and dv over the group in
    float32 and rounds once. Tiles come from `pick_blocks` unless
    block_q / block_k are given. Raises ValueError for geometries the
    template doesn't cover."""
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"{hq} query heads over {hkv} kv heads")
    picked_q, picked_k = pick_blocks(sq, d, q.dtype)
    block_q = _fit_block(block_q, sq) if block_q else picked_q
    block_k = _fit_block(block_k, skv) if block_k else picked_k
    if not supported(sq, skv, block_q, block_k):
        raise ValueError(
            f"flash kernel needs equal seq lens divisible by the block "
            f"({sq=}, {skv=}, {block_q=}, {block_k=})")
    if not _interpret() and (block_q % 128 or block_k % 128):
        # hardware tiles want lane-aligned blocks; the interpreter (CPU
        # tests) accepts any divisor so small geometries stay testable
        raise ValueError(
            f"flash kernel needs blocks divisible by 128 on hardware "
            f"({block_q=}, {block_k=})")

    qt = jnp.transpose(q, (0, 2, 1, 3))              # [B,Hq,S,D]
    kt = jnp.transpose(k, (0, 2, 1, 3))              # [B,Hkv,S,D]
    vt = jnp.transpose(v, (0, 2, 1, 3))
    scale = float(1.0 / (d ** 0.5))
    o = _flash_bhsd(qt, kt, vt, scale, causal, sliding_window,
                    block_q, block_k)
    return jnp.transpose(o, (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# decode: the Sq-small specialization
# ---------------------------------------------------------------------------


def _decode_kernel(lens_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
                   *, scale: float, window: Optional[int], unit: int,
                   units: int, parts: int, n_blocks: int, kv_heads: int,
                   groups: int, sq: int):
    """ONE body for all four decode instantiations (single/multi-query x
    dense/paged), one grid step a row. The pools stay in HBM; the row's
    kv loop runs here, from its first live block to its last
    (`decode_trips`), so a row costs what its live context costs and an
    idle one (kv_len `masks.decode_idle_length(sq)` or under: 0 at one
    query) nothing but its grid step: the loop is empty, `l` is clamped
    and `o` comes back zero. A block is `units` copies of `unit`
    cache positions with EVERY kv head, [unit * Hkv, D] each, exactly as
    a page lies in memory (ops/kv_store.py: no caller transposes a cache
    for this kernel): several whole pages, or, of a page longer than a
    block (a slot cache's row), one of its `parts` parts. The loop copies
    them itself through the page table, into two buffers: block j + 1 is
    on its way while block j computes.

    Only the units that hold a position some query sees are copied, so
    what a block's other units hold in VMEM is arbitrary. The scores
    there are selected away; the values are zeroed before p.v, because a
    probability of 0 times a NaN is a NaN. So no byte outside the row's
    live pages reaches the result, the scratch page's included.

    The q tile stacks, per kv head, the Sq speculative query rows x G
    grouped heads: [Hkv * Sq * G, D] (sq == 1 is plain decode). One
    matmul forms the scores of every query against every key of the
    block; masks.py says which pairs share a kv head and a visible
    position."""
    b = pl.program_id(0)
    kv_len = lens_ref[b]
    rows = sq * groups
    blk = unit * units
    first, end = decode_trips(kv_len, sq, window, blk, n_blocks)
    # the same interval a unit at a time: the copies a block makes
    first_unit, end_unit = decode_trips(kv_len, sq, window, unit,
                                        n_blocks * units)

    def copies(j, slot, act):
        """"start" or "wait" (act) the copies of block j's live units
        into buffer `slot`; a wait names what its start named."""
        def one(e, _):
            page = table_ref[b, e // parts] * parts + e % parts
            at = e - j * units
            for c, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                getattr(pltpu.make_async_copy(
                    hbm.at[page], buf.at[slot, at], sems.at[c, slot]), act)()
            return _
        jax.lax.fori_loop(jnp.maximum(j * units, first_unit),
                          jnp.minimum((j + 1) * units, end_unit), one, None)

    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(first < end)
    def _first():
        copies(first, 0, "start")

    def block(j, _):
        slot = (j - first) % 2

        @pl.when(j + 1 < end)
        def _next():
            copies(j + 1, 1 - slot, "start")

        copies(j, slot, "wait")

        q = q_ref[0].astype(jnp.float32) * scale         # [Hkv*rows, D]
        k = k_buf[slot].reshape(blk * kv_heads, -1).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))

        q_pos, k_pos = masks.decode_positions(j, blk, kv_len, groups, rows,
                                              kv_heads)
        allowed = (masks.decode_same_head(blk, rows, kv_heads)
                   & masks.visible(q_pos, k_pos, causal=True,
                                   window=window))
        s = jnp.where(allowed, s, _NEG_INF)

        m_prev = m_scr[:]                                # [Hkv*rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(allowed, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        v = v_buf[slot].reshape(blk * kv_heads, -1).astype(jnp.float32)
        v_pos = j * blk + jax.lax.broadcasted_iota(
            jnp.int32, v.shape, 0) // kv_heads
        v = jnp.where(masks.decode_position_live(v_pos, kv_len, sq,
                                                 window=window), v, 0.0)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())))
        acc_scr[:] = acc_scr[:] * alpha + pv
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_new
        return _

    jax.lax.fori_loop(first, end, block, None)
    l = jnp.maximum(l_scr[:], 1e-30)
    o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)


# the most rows of a kv block ([positions * Hkv, D]): 2048 rows of 128
# bf16 are 0.5 MB, so the two buffers each of k and v, their float32
# copies and the [q rows, 2048] scores stay inside the default scoped
# VMEM (`_decode_vmem_bytes`)
_DECODE_TILE_ROWS = 2048


def _decode_block(page_size: int, kv_heads: int,
                  cap: Optional[int] = None) -> Tuple[int, int]:
    """(unit, units): a block of the decode kernel is `units` contiguous
    copies of `unit` cache positions each. As many whole pages as reach
    _DECODE_TILE_ROWS rows (and `cap` positions, where a caller gives
    one); of a page longer than that (a slot cache's row is one page of
    the whole sequence), the largest power of two under both that
    divides it."""
    most = max(8, _DECODE_TILE_ROWS // kv_heads)
    if cap is not None:
        most = min(most, cap)
    if page_size <= most:
        return page_size, most // page_size
    unit = 1 << (most.bit_length() - 1)
    while unit > 8 and page_size % unit:
        unit //= 2
    return (unit, 1) if page_size % unit == 0 else (page_size, 1)


def _decode_geometry(table_width: int, page_size: int, kv_heads: int,
                     cap: Optional[int] = None) -> Tuple[int, int, int, int]:
    """(unit, units, parts, n_blocks) of a decode call over tables of
    `table_width` entries: `_decode_block`'s block, the parts a page is
    addressed in (1 unless it is longer than a block), and the blocks a
    row's table holds, the last one cut short where they do not divide
    it."""
    unit, units = _decode_block(page_size, kv_heads, cap)
    parts = page_size // unit
    return unit, units, parts, -(-table_width * parts // units)


def decode_trips(kv_len, sq: int, window: Optional[int], block: int,
                 n_blocks: int, xp=jnp):
    """(first, end) of a row's kv loop over blocks of `block` positions
    in a table of n_blocks: `masks.decode_live_blocks` clipped into the
    table, `end` one past the last. kv_len is the kernel's SMEM scalar,
    or an array of rows on the host (xp=numpy)."""
    first, last = masks.decode_live_blocks(block, kv_len, sq, window=window)
    return xp.maximum(first, 0), xp.minimum(last + 1, n_blocks)


def decode_blocks_visited(kv_lengths, table_width: int, page_size: int,
                          kv_heads: int, sq: int = 1,
                          window: Optional[int] = None) -> Tuple[int, int]:
    """(blocks a decode call visits, blocks its table holds) for rows of
    these lengths, on the host: the sum of the kernel's own loop bounds.
    The engines' `engine_decode_live_block_share` is the first over the
    second."""
    unit, units, _, n_blocks = _decode_geometry(table_width, page_size,
                                                kv_heads)
    lens = np.asarray(kv_lengths, np.int64)
    first, end = decode_trips(lens, sq, window, unit * units, n_blocks,
                              xp=np)
    return int(np.maximum(end - first, 0).sum()), n_blocks * lens.size


def _decode_vmem_bytes(blk_rows: int, q_rows: int, D: int, item: int) -> int:
    """The two buffers each of k and v, the block's float32 copies of
    both, q and o double-buffered, and ~5 live [q rows, block rows]
    float32 temporaries (scores, the two masks, p, the positions)."""
    return (4 * blk_rows * D * item + 2 * blk_rows * D * 4
            + 4 * q_rows * D * item + 5 * q_rows * blk_rows * 4)


def _decode_call(q, k_pages, v_pages, page_table, kv_lengths, *,
                 window: Optional[int], name: str,
                 block_k: Optional[int] = None):
    """Shared launch for the decode specialization: k/v are page pools
    [P, ps, Hkv, D] in the cache's own layout, left in HBM, and the grid
    is the rows; each row's loop over its table [B, n] is the kernel's.
    Nothing here copies a pool: the views are reshapes of contiguous
    memory."""
    b, sq, hq, d = q.shape
    _, ps, hkv, _ = kv_store.pool_dims(k_pages)
    groups = hq // hkv
    rows = sq * groups
    unit, units, parts, n_blocks = _decode_geometry(page_table.shape[1],
                                                    ps, hkv, block_k)

    # [B, Sq, Hkv, G, D] -> [B, Hkv*Sq*G, D]: per kv head, all Sq
    # queries' grouped heads
    qt = q.reshape(b, sq, hkv, groups, d).transpose(0, 2, 1, 3, 4)
    qt = qt.reshape(b, hkv * rows, d)
    kt = k_pages.reshape(-1, unit * hkv, d)
    vt = v_pages.reshape(-1, unit * hkv, d)
    lens = jnp.asarray(kv_lengths, jnp.int32)
    table = jnp.asarray(page_table, jnp.int32)

    kernel = functools.partial(
        _decode_kernel, scale=float(1.0 / (d ** 0.5)), window=window,
        unit=unit, units=units, parts=parts, n_blocks=n_blocks,
        kv_heads=hkv, groups=groups, sq=sq)
    vmem = _decode_vmem_bytes(unit * units * hkv, hkv * rows, d,
                              k_pages.dtype.itemsize)
    q_map = lambda bi, lens, pt: (bi, 0, 0)  # noqa: E731
    o = _named_pallas_call(
        name, kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hkv * rows, d), q_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hkv * rows, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((2, units, unit * hkv, d), k_pages.dtype),
                pltpu.VMEM((2, units, unit * hkv, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv * rows, 1), jnp.float32),
                pltpu.VMEM((hkv * rows, 1), jnp.float32),
                pltpu.VMEM((hkv * rows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv * rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=(min(vmem, _MAX_SCOPED_VMEM)
                              if vmem > _DEFAULT_SCOPED_VMEM else None)),
        interpret=_interpret(),
    )(lens, table, qt, kt, vt)
    return o.reshape(b, hkv, sq, groups, d).transpose(0, 2, 1, 3, 4
                                                      ).reshape(b, sq, hq, d)


def _check_heads(hq: int, hkv: int) -> None:
    if hq % hkv:
        raise ValueError(f"query heads {hq} not a multiple of kv heads {hkv}")


def _dense_decode(q, k, v, kv_lengths, window, block_k: int):
    """A dense cache [B, S, Hkv, D] through the one launch: row b is
    page b of a pool whose pages are whole rows."""
    b, skv, hkv, _ = kv_store.pool_dims(k)
    _check_heads(q.shape[2], hkv)
    if skv % 128:
        raise ValueError(
            f"flash_decode needs cache length divisible by 128 ({skv=})")
    return _decode_call(q, k, v, jnp.arange(b, dtype=jnp.int32)[:, None],
                        kv_lengths, window=window, name="flash_decode",
                        block_k=block_k)


def flash_decode_mq(
    q: jnp.ndarray,            # [B, Sq, Hq, D] (Sq = spec k+1 query rows)
    k: jnp.ndarray,            # [B, S, Hkv, D]
    v: jnp.ndarray,            # [B, S, Hkv, D]
    kv_lengths: jnp.ndarray,   # [B] int32, FIRST query's visible prefix
    sliding_window: Optional[int] = None,
    block_k: int = 256,
) -> jnp.ndarray:
    """Multi-query decode attention with per-row valid-prefix masking
    (the speculative verify pass: query j sees k_pos < kv_lengths + j).
    Returns [B, Sq, Hq, D]. Raises ValueError for unsupported shapes."""
    return _dense_decode(q, k, v, kv_lengths, sliding_window, block_k)


def flash_decode(
    q: jnp.ndarray,            # [B, 1, Hq, D]
    k: jnp.ndarray,            # [B, S, Hkv, D]
    v: jnp.ndarray,            # [B, S, Hkv, D]
    kv_lengths: jnp.ndarray,   # [B] int32, valid prefix per row
    sliding_window: Optional[int] = None,
    block_k: int = 256,
) -> jnp.ndarray:
    """Single-token decode attention with per-row valid-prefix masking:
    the sq == 1 point of the decode specialization. Returns
    [B, 1, Hq, D]. Raises ValueError for unsupported shapes (a dense
    cache whose length no block divides; the serving engine's pool goes
    through the paged kernels below)."""
    if q.shape[1] != 1:
        raise ValueError(
            f"flash_decode is single-token only (q_len={q.shape[1]})")
    return _dense_decode(q, k, v, kv_lengths, sliding_window, block_k)


def _check_paged(q, k_pages, page_table) -> None:
    b = q.shape[0]
    _, ps, hkv, _ = kv_store.pool_dims(k_pages)
    _check_heads(q.shape[2], hkv)
    if ps % 8:
        # TPU sublane alignment for the [ps * Hkv, D] kv tile
        raise ValueError(f"page_size {ps} must be a multiple of 8")
    if page_table.shape[0] != b:
        raise ValueError(
            f"page_table rows {page_table.shape[0]} != batch {b}")


def paged_flash_decode_mq(
    q: jnp.ndarray,            # [B, Sq, Hq, D] (Sq = spec k+1 query rows)
    k_pages: jnp.ndarray,      # [P, ps, Hkv, D] shared page pool
    v_pages: jnp.ndarray,      # [P, ps, Hkv, D]
    page_table: jnp.ndarray,   # [B, max_pages] int32
    kv_lengths: jnp.ndarray,   # [B] int32, FIRST query's visible prefix
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """Multi-query decode attention over paged KV (the speculative
    verify pass) — the paged knob of the decode specialization. Returns
    [B, Sq, Hq, D]; ValueError for unsupported shapes."""
    _check_paged(q, k_pages, page_table)
    return _decode_call(q, k_pages, v_pages, page_table, kv_lengths,
                        window=sliding_window, name="paged_flash_decode")


def paged_flash_decode(
    q: jnp.ndarray,            # [B, 1, Hq, D]
    k_pages: jnp.ndarray,      # [P, ps, Hkv, D] shared page pool
    v_pages: jnp.ndarray,      # [P, ps, Hkv, D]
    page_table: jnp.ndarray,   # [B, max_pages] int32 physical page per block
    kv_lengths: jnp.ndarray,   # [B] int32, valid prefix per row
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """Single-token decode attention over paged KV with per-row prefix
    masking. Returns [B, 1, Hq, D]. Raises ValueError for unsupported
    shapes."""
    if q.shape[1] != 1:
        raise ValueError(
            f"paged_flash_decode is single-token only (q_len={q.shape[1]})")
    _check_paged(q, k_pages, page_table)
    return _decode_call(q, k_pages, v_pages, page_table, kv_lengths,
                        window=sliding_window, name="paged_flash_decode")


# ---------------------------------------------------------------------------
# chunk: the decode loop at a prefill chunk's query count
# ---------------------------------------------------------------------------


def _head_rows(buf, slot, h: int, blk: int, kv_heads: int):
    """Head h's keys (or values) [blk, D] out of buffer `slot` of
    [2, blk * kv_heads, D], where a block lies as its pages do: position-
    major, row p * kv_heads + h. A strided read of the buffer, so nothing
    re-lays a block for the heads' sake. Mosaic's strided load moves
    32-bit rows: a 16-bit block is read as the words that pair its rows
    (2i low, 2i + 1 high: heads h and h + 1 of one position, kv_heads
    even) and the head's half is widened in place, which for bfloat16 is
    its float32 value's upper half and exact both ways (`chunk_supported`
    keeps every other 16-bit type off the kernel)."""
    if kv_heads == 1:
        return buf[slot]
    if buf.dtype.itemsize == 4:
        return buf[slot, pl.ds(h, blk, stride=kv_heads), :]
    words = buf.bitcast(jnp.uint32)[
        slot, pl.ds(h // 2, blk, stride=kv_heads // 2), :]
    bits = (words & jnp.uint32(0xFFFF0000)) if h % 2 else (words << 16)
    return jax.lax.bitcast_convert_type(bits, jnp.float32).astype(buf.dtype)


def chunk_supported(dtype, kv_heads: int, page_size: int) -> bool:
    """Whether the chunk kernel takes pools of this dtype, this many
    (local) kv heads and this page size: pages the copies can address
    (`_check_paged`: a slot cache's row is a page, and one-shot generation
    sizes its rows to the request) that `_head_rows` can read a head out
    of, float32 always, bfloat16 at one head or an even number."""
    dtype = jnp.dtype(dtype)
    return page_size % 8 == 0 and (dtype.itemsize == 4 or (
        dtype == jnp.bfloat16 and (kv_heads == 1 or kv_heads % 2 == 0)))


def _chunk_kernel(offs_ref, ends_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
                  k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
                  *, scale: float, window: Optional[int], unit: int,
                  units: int, parts: int, n_blocks: int, kv_heads: int,
                  groups: int, tq: int):
    """The decode kernel's loop at a prefill chunk's query count: grid
    (rows, query tiles), one tile of `tq` query positions x every head a
    step. The tile's queries sit at offs[b] + qi * tq ..; they see keys
    up to themselves, from the window's lower edge, and none at or past
    ends[b] (what the row holds once this chunk is written: behind it lie
    a short prompt's padded tail and the scratch page). A tile wholly
    behind ends[b] makes no trip and writes zeros.

    The loop walks the tile's live blocks through the page table as
    `_decode_kernel` does, a page copied once a tile into one of two
    buffers, only the units that hold a visible position. What does not
    carry over is the body. Here each kv head's queries, [groups * tq, D]
    (head-major: row g * tq + i is head g's query i), meet that head's
    keys alone, read out of the block where its pages put them
    (`_head_rows`); operands in the dtype they arrive in, products, the
    softmax's statistics and the accumulator in float32 (`_dot`: the
    training kernels' contract). A block every pair of which is visible
    runs without positions, mask or selects; any other under the mask,
    its values zeroed where no copy filled them (a probability of 0 times
    a NaN is a NaN), so no byte outside the row's live pages reaches the
    result."""
    b, qi = pl.program_id(0), pl.program_id(1)
    blk = unit * units
    rows = groups * tq
    q_lo = offs_ref[b] + qi * tq
    row_end = ends_ref[b]
    first, end = chunk_trips(q_lo, tq, row_end, window, blk, n_blocks)
    first_unit, end_unit = chunk_trips(q_lo, tq, row_end, window, unit,
                                       n_blocks * units)

    def copies(j, slot, act):
        def one(e, _):
            page = table_ref[b, e // parts] * parts + e % parts
            at = (e - j * units) * unit * kv_heads
            for c, (hbm, buf) in enumerate(((k_hbm, k_buf), (v_hbm, v_buf))):
                getattr(pltpu.make_async_copy(
                    hbm.at[page],
                    buf.at[slot, pl.ds(at, unit * kv_heads)],
                    sems.at[c, slot]), act)()
            return _
        jax.lax.fori_loop(jnp.maximum(j * units, first_unit),
                          jnp.minimum((j + 1) * units, end_unit), one, None)

    m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(first < end)
    def _first():
        copies(first, 0, "start")

    def visit(slot, allowed, v_live):
        """The online-softmax step of every head over the block in
        buffer `slot`; allowed None: every pair visible."""
        for h in range(kv_heads):
            q = q_ref[0, h * groups:(h + 1) * groups].reshape(rows, -1)
            k = _head_rows(k_buf, slot, h, blk, kv_heads)
            s = _dot(q, k, _NT) * scale                  # [rows, blk] f32
            if allowed is not None:
                s = jnp.where(allowed, s, _NEG_INF)
            m_prev = m_scr[h]                            # [rows, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            v = _head_rows(v_buf, slot, h, blk, kv_heads)
            if allowed is not None:
                # a row the mask leaves nothing of has m_new at _NEG_INF
                p = jnp.where(allowed, p, 0.0)
                v = jnp.where(v_live, v, jnp.zeros_like(v))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + _dot(p.astype(v.dtype), v, _NN)
            m_scr[h] = m_new

    def block(j, _):
        slot = (j - first) % 2

        @pl.when(j + 1 < end)
        def _next():
            copies(j + 1, 1 - slot, "start")

        copies(j, slot, "wait")
        k_lo = j * blk
        interior = masks.chunk_block_interior(q_lo, tq, k_lo, blk, row_end,
                                              window=window)

        @pl.when(interior)
        def _interior():
            visit(slot, None, None)

        @pl.when(jnp.logical_not(interior))
        def _edge():
            q_pos, k_pos = masks.chunk_positions(q_lo, k_lo, tq, groups, blk)
            allowed = (masks.visible(q_pos, k_pos, causal=True, window=window)
                       & (k_pos < row_end))
            v_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (blk, 1), 0)
            v_live = (masks.decode_position_live(v_pos, q_lo + 1, tq,
                                                 window=window)
                      & (v_pos < row_end))
            visit(slot, allowed, v_live)
        return _

    jax.lax.fori_loop(first, end, block, None)
    for h in range(kv_heads):
        l = jnp.maximum(l_scr[h], 1e-30)
        o_ref[0, h * groups:(h + 1) * groups] = (acc_scr[h] / l).reshape(
            groups, tq, -1).astype(o_ref.dtype)


# a chunk's query tile against one head's block: about this many query
# rows ([groups * tq, D], what a head's matmuls see), and at most this
# many scores ([groups * tq, block] float32: 2 MB a temporary). On the
# v5e (tools/chunk_kernel_bench.py): Mistral's 4 groups run tiles of 128
# positions against blocks of 256, Jamba2's 20 heads over one tiles of 32
# against blocks of 512: 0.08 / 0.07 ms a call for a whole first chunk of
# 512, 0.61 / 0.69 at the window's depth and at offset 3584 of 4000.
_CHUNK_TILE_ROWS = 512
_CHUNK_TILE_SCORES = 1 << 19


def _chunk_geometry(s: int, groups: int, table_width: int, page_size: int,
                    kv_heads: int) -> Tuple[int, int, int, int, int]:
    """(tq, unit, units, parts, n_blocks) of a chunk call: query tiles of
    the power of two of positions, 16 to 128, that reaches
    _CHUNK_TILE_ROWS rows over a head's groups (a short prompt in a long
    chunk then leaves whole tiles behind its end; one tile where that
    does not divide the chunk, which the interpreter takes), and
    `_decode_geometry`'s blocks, of the power of two of positions that
    keeps a tile's scores against one head's block under
    _CHUNK_TILE_SCORES."""
    tq = -(-_CHUNK_TILE_ROWS // groups)
    tq = min(max(1 << (tq - 1).bit_length(), 16), 128)
    if s % tq:
        tq = s
    cap = max(128, _CHUNK_TILE_SCORES // (groups * tq))
    cap = 1 << (cap.bit_length() - 1)
    return (tq,) + _decode_geometry(table_width, page_size, kv_heads, cap)


def chunk_trips(q_lo, tq: int, row_end, window: Optional[int], block: int,
                n_blocks: int, xp=jnp):
    """(first, end) of the kv loop of a query tile whose tq queries sit
    at q_lo .. q_lo + tq - 1 in a row that holds row_end positions:
    `decode_trips` for those queries, ended where the row ends; no trip
    for a tile wholly behind it."""
    first, end = decode_trips(q_lo + 1, tq, window, block, n_blocks, xp)
    end = xp.minimum(end, (row_end + block - 1) // block)
    return first, xp.where(q_lo < row_end, end, first)


def chunk_blocks_visited(off: int, s: int, row_end: int, groups: int,
                         table_width: int, page_size: int, kv_heads: int,
                         window: Optional[int] = None) -> Tuple[int, int]:
    """(blocks a chunk call visits, blocks its table holds a query tile
    times its tiles) for one chunk of s queries at offset `off` in a row
    that holds row_end positions, on the host: the sum of the kernel's own
    loop bounds. The serving engine's `engine_prefill_live_block_share` is
    the first over the second."""
    tq, unit, units, _, n_blocks = _chunk_geometry(s, groups, table_width,
                                                   page_size, kv_heads)
    q_lo = off + np.arange(s // tq, dtype=np.int64) * tq
    first, end = chunk_trips(q_lo, tq, row_end, window, unit * units,
                             n_blocks, xp=np)
    return int(np.maximum(end - first, 0).sum()), n_blocks * q_lo.size


def _chunk_vmem_bytes(blk_rows: int, heads: int, rows: int, blk: int,
                      tq: int, D: int, item: int) -> int:
    """The two buffers each of k and v, q and o double-buffered, the
    float32 statistics (lane-padded) and accumulator of every head, and
    ~6 live [rows, block] float32 temporaries of the head in hand
    (scores, the mask, p twice, the positions)."""
    return (4 * blk_rows * D * item + 4 * heads * tq * D * item
            + heads * tq * (D + 256) * 4 + 6 * rows * blk * 4)


def paged_flash_chunk(
    q: jnp.ndarray,            # [B, S, Hq, D]: one chunk a row
    k_pages: jnp.ndarray,      # [P, ps, Hkv, D] shared page pool
    v_pages: jnp.ndarray,      # [P, ps, Hkv, D]
    page_table: jnp.ndarray,   # [B, max_pages] int32
    q_offsets: jnp.ndarray,    # [B] int32, position of each row's q[0]
    kv_ends: jnp.ndarray,      # [B] int32, positions each row holds
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """Causal attention of a prefill chunk over paged KV: the chunk
    instantiation of the decode specialization. Row b's S queries sit at
    q_offsets[b] .. and see the keys its table holds up to themselves,
    inside the window, below kv_ends[b]. Returns [B, S, Hq, D]; a query
    tile wholly at or past kv_ends[b] comes back zero. Raises ValueError
    for unsupported shapes."""
    _check_paged(q, k_pages, page_table)
    b, s, hq, d = q.shape
    _, ps, hkv, _ = kv_store.pool_dims(k_pages)
    groups = hq // hkv
    if not chunk_supported(k_pages.dtype, hkv, ps):
        raise ValueError(
            f"paged_flash_chunk cannot read a head out of {k_pages.dtype} "
            f"pages of {ps} positions x {hkv} kv heads")
    tq, unit, units, parts, n_blocks = _chunk_geometry(
        s, groups, page_table.shape[1], ps, hkv)
    blk = unit * units
    rows = groups * tq

    qt = jnp.transpose(q, (0, 2, 1, 3))              # [B, Hq, S, D]
    kt = k_pages.reshape(-1, unit * hkv, d)
    vt = v_pages.reshape(-1, unit * hkv, d)
    kernel = functools.partial(
        _chunk_kernel, scale=float(1.0 / (d ** 0.5)), window=sliding_window,
        unit=unit, units=units, parts=parts, n_blocks=n_blocks,
        kv_heads=hkv, groups=groups, tq=tq)
    vmem = _chunk_vmem_bytes(blk * hkv, hq, rows, blk, tq, d,
                             k_pages.dtype.itemsize)
    q_map = lambda bi, qi, offs, ends, pt: (bi, 0, qi, 0)  # noqa: E731
    o = _named_pallas_call(
        "paged_flash_chunk", kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, s // tq),
            in_specs=[
                pl.BlockSpec((1, hq, tq, d), q_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, hq, tq, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((2, blk * hkv, d), k_pages.dtype),
                pltpu.VMEM((2, blk * hkv, d), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv, rows, 1), jnp.float32),
                pltpu.VMEM((hkv, rows, 1), jnp.float32),
                pltpu.VMEM((hkv, rows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=(min(vmem, _MAX_SCOPED_VMEM)
                              if vmem > _DEFAULT_SCOPED_VMEM else None)),
        interpret=_interpret(),
    )(jnp.asarray(q_offsets, jnp.int32), jnp.asarray(kv_ends, jnp.int32),
      jnp.asarray(page_table, jnp.int32), qt, kt, vt)
    return jnp.transpose(o, (0, 2, 1, 3))
