"""The one flash kernel family (ops/pallas/flash_template.py) vs dense
references, in interpret mode on the CPU suite.

Three layers of proof:

  1. masks.py predicate unit tests — every block-skip predicate proven
     against a dense boolean reference (ANY of `visible` over the tile),
     exhaustively over the edges: the causal frontier, the decode
     ``kv_len + Sq - 1`` mq boundary, and the window LOWER edge (the new
     windowed block skip).
  2. parity matrix — each template instantiation (prefill fwd, the
     custom-vjp bwd, decode, paged decode, both mq variants) vs the
     dense einsum path over causal x kv_lengths x window x paged x mq;
     bwd grads vs jax.grad of the dense reference.
  3. dispatch gates — attention(impl="pallas") routes the gradient
     through the template (jaxpr contains the pallas calls), stays dense
     on a CPU host that does not force interpret mode, and RAISES for a
     geometry the chosen kernel cannot tile.
  4. what a layer's checkpoint keeps of the forward kernel under
     `selective` recomputation: its output and its compact log-sum-exp,
     so the backward pass runs no second forward and computes the same
     bits as a step that recomputes nothing.

The same kernels compile for a described v5e in tests/test_chip_compile.py
and run on the chip in every benchmark cell."""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.ops.attention import attention
from megatron_tpu.ops.pallas import masks

RNG = np.random.default_rng(11)


# ---------------------------------------------------------------------------
# 1. mask predicates vs the dense boolean reference
# ---------------------------------------------------------------------------


def _dense_block_live(ki, blk, q_positions, causal, window):
    """Reference: the tile is live iff ANY (q, k) element in it is
    visible — computed from the element rule, no interval shortcuts."""
    k_positions = np.arange(ki * blk, (ki + 1) * blk)
    vis = masks.visible(q_positions[:, None], k_positions[None, :],
                        causal=causal, window=window)
    return bool(np.any(vis))


@pytest.mark.parametrize("window", [None, 1, 3, 8, 64])
@pytest.mark.parametrize("causal", [True, False])
def test_prefill_block_live_matches_dense(causal, window):
    blk_q, blk_k = 8, 8
    for delta in (0, 5, 64):
        for qi in range(6):
            q_pos = np.arange(qi * blk_q, (qi + 1) * blk_q) + delta
            for ki in range(8):
                want = _dense_block_live(ki, blk_k, q_pos, causal, window)
                got = masks.prefill_block_live(
                    qi, ki, blk_q, blk_k, causal=causal, window=window,
                    delta=delta)
                assert bool(got) == want, (qi, ki, delta)


@pytest.mark.parametrize("window", [None, 1, 3, 8, 64])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blk_q,blk_k", [(8, 8), (8, 16), (16, 8)])
def test_prefill_live_tile_ranges_are_the_block_predicate(blk_q, blk_k,
                                                          causal, window):
    """The (first, last) live tile the index maps clamp a skipped step to
    is `prefill_block_live` solved for the inner grid axis: inside the
    grid a tile is in the range iff the predicate admits it — for the kv
    axis of the forward and dq kernels and the q axis of the fused
    backward and dk/dv."""
    n = 12
    for delta in (0, 5, 64, -16):
        for outer in range(n):
            lo, hi = masks.prefill_live_kv_tiles(
                outer, blk_q, blk_k, causal=causal, window=window,
                delta=delta)
            assert [lo <= ki <= hi for ki in range(n)] == [
                bool(masks.prefill_block_live(
                    outer, ki, blk_q, blk_k, causal=causal, window=window,
                    delta=delta)) for ki in range(n)], (outer, delta)
            lo, hi = masks.prefill_live_q_tiles(
                outer, blk_q, blk_k, causal=causal, window=window,
                delta=delta)
            assert [lo <= qi <= hi for qi in range(n)] == [
                bool(masks.prefill_block_live(
                    qi, outer, blk_q, blk_k, causal=causal, window=window,
                    delta=delta)) for qi in range(n)], (outer, delta)


@pytest.mark.parametrize("window", [None, 1, 4, 16])
@pytest.mark.parametrize("sq", [1, 4])
def test_decode_block_live_matches_dense(sq, window):
    """Including the mq boundary: the deepest query sits at
    kv_len + sq - 2, so the last live causal block is the one containing
    it — checked for every kv_len around every block edge."""
    blk = 8
    nk = 6
    for kv_len in range(1, blk * nk + 1):
        q_pos = kv_len - 1 + np.arange(sq)
        for ki in range(nk):
            want = _dense_block_live(ki, blk, q_pos, True, window)
            got = masks.decode_block_live(ki, blk, kv_len, sq, window=window)
            assert bool(got) == want, (kv_len, ki)


@pytest.mark.parametrize("window", [None, 1, 4, 8, 16, 17])
@pytest.mark.parametrize("sq", [1, 4, 5])
def test_decode_live_blocks_are_the_block_predicate(sq, window):
    """The decode kernel's loop bounds: `decode_live_blocks` names
    exactly the blocks `decode_block_live` admits, for every kv_len
    around every block edge (0 too: an idle slot), and
    `decode_position_live` exactly the positions some query sees. The
    host's form (arrays of rows, `flash_template.decode_trips`) is the
    same interval clipped into the table."""
    from megatron_tpu.ops.pallas.flash_template import decode_trips

    blk, nk = 8, 6
    lens = np.arange(0, blk * nk + 2)
    firsts, ends = np.broadcast_arrays(
        *decode_trips(lens, sq, window, blk, nk, xp=np))
    for kv_len in lens:
        first, last = masks.decode_live_blocks(blk, int(kv_len), sq,
                                               window=window)
        live = [bool(masks.decode_block_live(ki, blk, int(kv_len), sq,
                                             window=window))
                for ki in range(nk)]
        assert live == [first <= ki <= last for ki in range(nk)], kv_len
        assert live == [firsts[kv_len] <= ki < ends[kv_len]
                        for ki in range(nk)], kv_len
        q_pos = kv_len - 1 + np.arange(sq)
        k_pos = np.arange(blk * nk)
        seen = masks.visible(q_pos[:, None], k_pos[None, :], causal=True,
                             window=window).any(axis=0)
        np.testing.assert_array_equal(
            masks.decode_position_live(k_pos, int(kv_len), sq,
                                       window=window), seen)


@pytest.mark.parametrize("window", [None, 1, 4, 8, 16, 17])
@pytest.mark.parametrize("sq", [1, 2, 4, 5])
def test_the_idle_length_is_the_largest_without_a_live_block(sq, window):
    """`decode_idle_length(sq)`: at it and below, `decode_block_live`
    admits no block, at any block size, `decode_live_blocks` ends under
    0, the loop clipped into the table (`decode_trips`, the kernel's own
    and the host's) is empty, and no query sees any position; one above
    it the row's last query sees position 0 and block 0 is live."""
    from megatron_tpu.ops.pallas.flash_template import decode_trips

    idle = masks.decode_idle_length(sq)
    assert idle == 1 - sq and masks.decode_idle_length(1) == 0
    nk = 6
    for blk in (8, 16, 256):
        for kv_len in (idle, idle - 1, idle - 7):
            assert not any(bool(masks.decode_block_live(
                ki, blk, kv_len, sq, window=window)) for ki in range(nk))
            first, last = masks.decode_live_blocks(blk, kv_len, sq,
                                                   window=window)
            assert last < 0
            first, end = decode_trips(np.asarray([kv_len]), sq, window, blk,
                                      nk, xp=np)
            assert (np.maximum(end - first, 0) == 0).all()
            assert not masks.decode_position_live(
                np.arange(blk * nk), kv_len, sq, window=window).any()
        assert bool(masks.decode_block_live(0, blk, idle + 1, sq,
                                            window=window))
        first, end = decode_trips(np.asarray([idle + 1]), sq, window, blk,
                                  nk, xp=np)
        assert (first, end) == (0, 1)


def test_window_lower_edge_is_tight():
    """The windowed skip keeps exactly the tiles intersecting
    (q_lo - W, q_hi]: the tile just below the window's lower edge is
    dead, the one containing the edge is live."""
    blk = 8
    # queries at [32, 39]; W=4: the shallowest query sees (28, 32], so
    # tile 3 (cols 24..31) is live only through its top columns 29..31
    assert masks.block_live(3, blk, 32, 39, window=4)
    assert not masks.block_live(2, blk, 32, 39, window=4)   # cols 16..23
    # W=1: the band is (31, 39] — tile 3's last column (31) is exactly
    # NOT in it, tile 4 is
    assert not masks.block_live(3, blk, 32, 39, window=1)
    assert masks.block_live(3, blk, 32, 39, window=2)       # 31 > 30
    assert masks.block_live(4, blk, 32, 39, window=1)
    assert not masks.block_live(5, blk, 32, 39, window=None)  # causal edge
    assert masks.block_live(5, blk, 32, 47, window=None)


def test_decode_positions_are_the_causal_rule():
    """The historical decode mask k_pos < kv_len + q_idx IS `visible`
    at q_pos = kv_len - 1 + q_idx — the unification the template rests
    on."""
    kv_len, groups, sq, blk = 13, 2, 3, 8
    rows = sq * groups
    q_pos, k_pos = masks.decode_positions(1, blk, kv_len, groups, rows)
    got = masks.visible(q_pos, k_pos, causal=True)
    q_idx = np.arange(rows)[:, None] // groups
    legacy = (np.arange(blk)[None, :] + blk) < kv_len + q_idx
    np.testing.assert_array_equal(np.asarray(got), legacy)


# ---------------------------------------------------------------------------
# 2. parity matrix (interpret mode)
# ---------------------------------------------------------------------------


def _qkv(b=1, s=128, hq=4, hkv=2, d=32, skv=None):
    skv = s if skv is None else skv
    q = jnp.asarray(RNG.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, skv, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, skv, hkv, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("causal", [True, False])
def test_template_forward_parity(causal, window):
    from megatron_tpu.ops.pallas.flash_template import flash_mha

    q, k, v = _qkv()
    got = flash_mha(q, k, v, sliding_window=window, causal=causal,
                    block_q=64, block_k=64)
    want = attention(q, k, v, sliding_window=window,
                     mask_type="causal" if causal else "bidirectional")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2)])
def test_template_bwd_grads_vs_dense_jax_grad(hq, hkv, window):
    """The recompute backward (the fused kernel behind custom_vjp) vs
    jax.grad of the dense einsum, causal x window x GQA."""
    from megatron_tpu.ops.pallas.flash_template import flash_mha

    q, k, v = _qkv(hq=hq, hkv=hkv)

    def f_flash(q, k, v):
        return jnp.sum(jnp.square(flash_mha(q, k, v, sliding_window=window,
                                            block_q=64, block_k=64)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.square(attention(q, k, v, sliding_window=window)))

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=2e-2, atol=2e-3, err_msg=f"d{name}")


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("sq", [1, 3])
def test_decode_window_parity(sq, window):
    """Decode instantiations (sq=1 plain, sq>1 speculative mq) with the
    sliding-window knob vs the masked einsum."""
    from megatron_tpu.ops.pallas.flash_template import (flash_decode,
                                                      flash_decode_mq)

    q, k, v = _qkv(b=3, s=sq, skv=256, hq=4, hkv=2, d=32)
    lens = jnp.asarray([1, 100, 256 - sq + 1], jnp.int32)
    fn = flash_decode if sq == 1 else flash_decode_mq
    got = fn(q, k, v, lens, sliding_window=window, block_k=128)
    want = attention(q, k, v, kv_lengths=lens, sliding_window=window,
                     impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def _paged(k, v, ps):
    """Chop a dense [B, S, hkv, d] cache into a shared page pool with
    page 0 reserved as scratch; returns (k_pages, v_pages, table)."""
    b, s, hkv, d = k.shape
    npages = s // ps
    kp = [jnp.zeros((ps, hkv, d), k.dtype)]
    vp = [jnp.zeros((ps, hkv, d), v.dtype)]
    table = np.zeros((b, npages), np.int32)
    for bi in range(b):
        for p in range(npages):
            table[bi, p] = len(kp)
            kp.append(k[bi, p * ps:(p + 1) * ps])
            vp.append(v[bi, p * ps:(p + 1) * ps])
    return jnp.stack(kp), jnp.stack(vp), jnp.asarray(table)


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("sq", [1, 3])
def test_paged_decode_window_parity(sq, window):
    """The paged knob: same body, page-table index maps — vs the dense
    gather reference, including sliding window."""
    from megatron_tpu.ops.pallas.flash_template import (
        paged_flash_decode, paged_flash_decode_mq)

    ps = 64
    q, k, v = _qkv(b=3, s=sq, skv=256, hq=4, hkv=2, d=32)
    kp, vp, table = _paged(k, v, ps)
    lens = jnp.asarray([1, 100, 256 - sq + 1], jnp.int32)
    fn = paged_flash_decode if sq == 1 else paged_flash_decode_mq
    got = fn(q, kp, vp, table, lens, sliding_window=window)
    want = attention(q, k, v, kv_lengths=lens, sliding_window=window,
                     impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [None, 40])
@pytest.mark.parametrize("sq", [1, 3])
def test_rows_with_the_idle_length_cost_no_trip_and_move_no_other_row(
        sq, window):
    """A batch in which some rows carry `decode_idle_length` (slots that
    do not decode: the serving layer's word for them, attention_block):
    the decoding rows come back, bit for bit, as from the call over those
    rows alone; the idle rows come back zero though their table is all
    scratch and the scratch page holds NaN (no page of theirs is copied,
    no block computed); `decode_blocks_visited` counts nothing for them.
    The dense path gives the same rows a finite mean, and the decoding
    ones what the kernel gives them."""
    from megatron_tpu.ops.pallas import flash_template as ft

    ps = 64
    q, k, v = _qkv(b=5, s=sq, skv=256, hq=4, hkv=2, d=32)
    kp, vp, table = _paged(k, v, ps)
    kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
    idle = masks.decode_idle_length(sq)
    lens = np.asarray([idle, 100, idle, 256 - sq + 1, 1], np.int32)
    decoding = np.asarray([1, 3, 4])
    resting = np.asarray([0, 2])
    table = jnp.asarray(np.asarray(table) * (lens > idle)[:, None])
    fn = ft.paged_flash_decode if sq == 1 else ft.paged_flash_decode_mq
    got = np.asarray(fn(q, kp, vp, table, jnp.asarray(lens),
                        sliding_window=window))
    alone = np.asarray(fn(q[decoding], kp, vp, table[decoding],
                          jnp.asarray(lens[decoding]),
                          sliding_window=window))
    np.testing.assert_array_equal(got[decoding], alone)
    assert (got[resting] == 0).all()
    dense = np.asarray(attention(q, k, v, kv_lengths=jnp.asarray(lens),
                                 sliding_window=window, impl="xla"))
    assert np.isfinite(dense).all()
    np.testing.assert_allclose(got[decoding], dense[decoding], rtol=2e-3,
                               atol=2e-3)

    def visited(rows):
        return ft.decode_blocks_visited(rows, table.shape[1], ps, 2, sq,
                                        window)

    assert visited(lens[resting]) == (0, 2 * visited(lens[:1])[1])
    assert visited(lens)[0] == visited(lens[decoding])[0] > 0
    # the same two rows at the carry's drift past 0, as the layer handed
    # them over before: a block each
    assert visited(np.asarray([1, 1]))[0] == 2


# (layout: a page size, or "row" for a dense cache; kv heads; groups; sq)
_POISON_CASES = [(8, 8, 4, 1), (16, 8, 4, 5), (64, 8, 4, 1),
                 ("row", 8, 4, 5), (8, 1, 20, 5), (16, 1, 20, 1),
                 (64, 1, 20, 5), ("row", 1, 20, 1), (16, 8, 20, 1),
                 (16, 1, 4, 5)]
_POISON_FNS = {}


def _poison_case(layout, hkv, groups, sq, edge):
    """One decode call over rows at every edge, and what it must give.

    The rows' lengths: 0, 1, a block's edge - 1 / edge / edge + 1 for the
    deepest query (kv_len + sq - 1) and for the shallowest, and the
    table's full width. `edge` places the window's lower edge for the
    full row: None (no window), "inside" a block, "at" a block's first
    position, "tight" (one position before it) or "before" the cache's
    start. Returns (fn, clean k, v, q, lens, window, block)."""
    from megatron_tpu.ops.pallas import flash_template as ft

    d, dense = 16, layout == "row"
    if dense:
        blk = ft._decode_block(3 * 256, hkv, 256)[0]
        seq = 3 * 256
    else:
        unit, units = ft._decode_block(layout, hkv)
        blk = unit * units
        seq = 2 * blk + blk // 2       # a last block the table cuts short
    full = seq - sq + 1
    lens = sorted({0, 1, blk - 1, blk, blk + 1, blk - sq, blk - sq + 1,
                   blk - sq + 2, 2 * blk + 1, full})
    window = {None: None, "inside": full - 3 * blk // 2, "at": full - blk,
              "tight": full - blk + 1, "before": seq + 7}[edge]
    b = len(lens)
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((b, sq, hkv * groups, d)),
                    jnp.float32)
    k = rng.standard_normal((b, seq, hkv, d)).astype(np.float32)
    v = rng.standard_normal((b, seq, hkv, d)).astype(np.float32)
    key = (layout, hkv, groups, sq, window)
    if key not in _POISON_FNS:
        if dense:
            fn = ft.flash_decode if sq == 1 else ft.flash_decode_mq
            _POISON_FNS[key] = jax.jit(
                lambda q, k, v, t, n: fn(q, k, v, n, sliding_window=window))
        else:
            fn = (ft.paged_flash_decode if sq == 1
                  else ft.paged_flash_decode_mq)
            _POISON_FNS[key] = jax.jit(
                lambda q, k, v, t, n: fn(q, k, v, t, n,
                                         sliding_window=window))
    return _POISON_FNS[key], k, v, q, np.asarray(lens, np.int32), window, blk


@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
@pytest.mark.parametrize("edge", [None, "inside", "at", "tight", "before"])
@pytest.mark.parametrize("layout,hkv,groups,sq", _POISON_CASES)
def test_decode_reads_nothing_but_the_rows_live_positions(
        layout, hkv, groups, sq, edge, poison):
    """The four decode entries against the masked einsum over a CLEAN
    cache, with every position no query of the row sees poisoned: the
    tail of the row's own last page, the pages behind its window (whose
    table entries park on scratch, as the engine's do), every page it
    does not own and the scratch page. The kernel copies only the units
    that hold a visible position and zeroes the value rows outside the
    band, so no such byte may reach the result. The table is scrambled."""
    fn, k, v, q, lens, window, blk = _poison_case(layout, hkv, groups, sq,
                                                  edge)
    b, seq = k.shape[:2]
    pos = np.arange(seq)[None, :]
    seen = np.asarray(masks.decode_position_live(
        pos, lens[:, None], sq, window=window))
    kp = np.where(seen[:, :, None, None], k, poison).astype(np.float32)
    vp = np.where(seen[:, :, None, None], v, poison).astype(np.float32)
    if layout == "row":
        pool_k, pool_v, table = kp, vp, np.zeros((b, 1), np.int32)
    else:
        n = seq // layout
        rng = np.random.default_rng(6)
        ids = rng.permutation(np.arange(1, 2 * b * n))[:b * n].reshape(b, n)
        owned = seen.reshape(b, n, layout).any(axis=2)
        table = np.where(owned, ids, 0).astype(np.int32)
        pool_k = np.full((2 * b * n, layout) + k.shape[2:], poison,
                         np.float32)
        pool_v = pool_k.copy()
        pool_k[ids[owned]] = kp.reshape(b, n, layout, *k.shape[2:])[owned]
        pool_v[ids[owned]] = vp.reshape(b, n, layout, *k.shape[2:])[owned]
    got = np.asarray(fn(q, jnp.asarray(pool_k), jnp.asarray(pool_v),
                        jnp.asarray(table), jnp.asarray(lens)))
    want = np.asarray(attention(q, jnp.asarray(k), jnp.asarray(v),
                                kv_lengths=jnp.asarray(lens),
                                sliding_window=window, impl="xla"))
    assert np.isfinite(got).all()
    # a query that sees nothing (query 0 of a row of length 0) reads 0;
    # the reference's softmax over no position is not a number to match
    sees = (lens[:, None] + np.arange(sq)[None, :]) > 0
    np.testing.assert_array_equal(got[~sees], 0.0)
    np.testing.assert_allclose(got[sees], want[sees], rtol=2e-3, atol=2e-3)


# name: (kv heads, groups, page size or "row", table pages, chunk, offset,
#        row end or None, window, write start, dtype)
_CHUNK_CASES = {
    "first_chunk": (2, 2, 16, 48, 256, 0, 256, None, 0, jnp.float32),
    "second_chunk": (2, 2, 16, 48, 256, 256, 512, None, 0, jnp.float32),
    "later_chunk_ends_inside": (2, 2, 16, 48, 256, 512, 700, None, 0,
                                jnp.float32),
    "window_crossed": (2, 2, 16, 64, 256, 640, 896, 300, 0, jnp.float32),
    "window_at_a_block_edge": (8, 4, 16, 64, 128, 640, 768, 256, 0,
                               jnp.float32),
    "short_prompt_in_a_long_chunk": (2, 2, 16, 48, 256, 0, 70, None, 0,
                                     jnp.float32),
    "shared_prefix": (2, 2, 16, 48, 256, 63, 200, None, 64, jnp.float32),
    "groups_4_bf16": (8, 4, 16, 40, 128, 256, 384, 200, 0, jnp.bfloat16),
    "groups_20_over_1": (1, 20, 16, 24, 128, 128, 250, None, 0,
                         jnp.float32),
    "groups_20_over_1_bf16": (1, 20, 16, 24, 128, 0, 128, None, 0,
                              jnp.bfloat16),
    "chunk_narrower_than_a_block": (8, 4, 8, 96, 32, 600, 632, None, 0,
                                    jnp.float32),
    "slot_rows_from_zero": (2, 2, "row", 1, 128, 0, None, None, 0,
                            jnp.float32),
    "slot_rows_later": (8, 4, "row", 1, 128, 256, None, 300, 0,
                        jnp.bfloat16),
}


@pytest.mark.parametrize("name", list(_CHUNK_CASES))
def test_chunk_kernel_matches_the_dense_path_it_replaces(monkeypatch, name):
    """A prefill chunk through the store and the dispatch, as a layer
    makes the call (`kv_store.write` with the chunk's fences, `read`,
    `attention(..., page_table=, kv_end=)` under impl "pallas" with the
    interpreter forced: `paged_flash_chunk`), against the masked einsum
    over a clean dense cache. The pool holds NaN at every position no live
    query sees: the scratch page, pages the row does not own, the pages
    behind the window (whose table entries park on scratch, as the
    engine's do), the tail of the row's last page and everything behind
    the row's end; the row's pages lie in shuffled physical order, and the
    call reads layer 1 of a store of two. A slot cache comes as whole-row
    pages (`read`'s table; two rows). Rows at or past the row's end are
    padding: finite, and zero where their whole query tile is."""
    from megatron_tpu.ops import kv_store
    from megatron_tpu.ops.pallas import flash_template as ft

    (hkv, groups, page, n_pages, s, off, row_end, window, write_start,
     dtype) = _CHUNK_CASES[name]
    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    d, slots = 16, page == "row"
    b = 2 if slots else 1
    seq = 512 if slots else n_pages * page
    end = off + s if row_end is None else row_end
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((b, s, hkv * groups, d)), dtype)
    k = rng.standard_normal((b, seq, hkv, d)).astype(np.float32)
    v = rng.standard_normal((b, seq, hkv, d)).astype(np.float32)
    pos = np.arange(seq)
    seen = pos < end
    if window is not None:
        seen &= pos > off - window
    # what the store holds before the chunk: the live context below the
    # write fence; the chunk brings the rest
    before = np.where((seen & (pos < max(write_start, off)))[None, :, None,
                                                             None],
                      np.stack([k, v]).reshape(2 * b, seq, hkv, d), np.nan
                      ).reshape(2, b, seq, hkv, d)
    if slots:
        table = None
        store = tuple(jnp.asarray(np.stack([np.full_like(x, np.nan), x]),
                                  dtype) for x in before)
    else:
        n = seq // page
        ids = rng.permutation(np.arange(1, 3 * n))[:n]
        owned = seen.reshape(n, page).any(axis=1)
        table = jnp.asarray(np.where(owned, ids, 0)[None], jnp.int32)
        pools = np.full((2, 2, 3 * n, page, hkv, d), np.nan, np.float32)
        pools[:, 1, ids[owned]] = before.reshape(2, n, page, hkv, d)[:, owned]
        store = tuple(jnp.asarray(x, dtype) for x in pools)
    store = kv_store.write(
        store, 1, jnp.asarray(k[:, off:off + s], dtype),
        jnp.asarray(v[:, off:off + s], dtype), jnp.int32(off), table,
        None if slots else jnp.int32(write_start),
        None if slots else jnp.int32(end))
    kp, vp, tbl = kv_store.read(store, 1, table, dtype)
    got = np.asarray(attention(
        q, kp, vp, sliding_window=window, q_offset=jnp.int32(off),
        impl="pallas", page_table=tbl,
        kv_end=None if row_end is None else jnp.int32(row_end)
    ).astype(jnp.float32))
    want = np.asarray(attention(
        q, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
        sliding_window=window, q_offset=off, impl="xla"
    ).astype(jnp.float32))
    assert np.isfinite(got).all()
    live = off + np.arange(s) < end
    tol = 2e-3 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[:, live], want[:, live], rtol=tol,
                               atol=tol)
    tq = ft._chunk_geometry(s, groups, 1 if slots else n_pages,
                            seq if slots else page, hkv)[0]
    dead_tiles = off + (np.arange(s) // tq) * tq >= end
    np.testing.assert_array_equal(got[:, dead_tiles], 0.0)


# ---------------------------------------------------------------------------
# 3. dispatch gates
# ---------------------------------------------------------------------------


def test_dispatch_uses_template_bwd_when_forced(monkeypatch):
    """With interpret forced, attention(impl='pallas') routes through the
    template and the GRADIENT jaxpr contains its three kernels (forward,
    the backward's row statistics, fused backward): no XLA-generated
    O(S^2) attention gradient."""
    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    q, k, v = _qkv()

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v, impl="pallas"))

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert jaxpr.count("pallas_call") == 3
    out = attention(q, k, v, impl="pallas")
    want = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_dispatch_kernel_error_propagates(monkeypatch):
    """A geometry the template can't instantiate (seq longer than the
    block and not a multiple of 128) RAISES under impl='pallas': a kernel
    that was chosen never turns into a warning and a quiet O(S^2) run —
    in a server log that warning was lost."""
    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    q, k, v = _qkv(s=1100, hq=2, hkv=1, d=16)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        with pytest.raises(ValueError, match="flash kernel needs"):
            attention(q, k, v, impl="pallas")
        # same for the decode kernels: a cache length they cannot tile
        lens = jnp.asarray([3], jnp.int32)
        with pytest.raises(ValueError, match="divisible by 128"):
            attention(q[:, :1], k, v, kv_lengths=lens, impl="pallas")


def test_serving_bucket_lengths_tile_at_128(monkeypatch):
    """Sequences that 128 divides but the default 256 block does not
    (serving prefill buckets: 384, 640, ...) run the kernel at the 128
    tile instead of being refused."""
    from megatron_tpu.ops.pallas.flash_template import _fit_block

    assert [_fit_block(256, s) for s in (64, 128, 256, 384, 512, 640)] == [
        64, 128, 256, 128, 256, 128]
    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    q, k, v = _qkv(s=384, hq=2, hkv=1, d=16)
    out = attention(q, k, v, impl="pallas")
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention(q, k, v)),
                               rtol=2e-3, atol=2e-3)


def test_dispatch_stays_dense_on_cpu_without_forcing(monkeypatch):
    """CPU sanity runs must not pay the pallas interpreter: without the
    env var, impl='pallas' runs the fused XLA path."""
    monkeypatch.delenv("MEGATRON_TPU_FLASH_INTERPRET", raising=False)
    q, k, v = _qkv()

    def loss(q, k, v):
        return jnp.sum(attention(q, k, v, impl="pallas"))

    jaxpr = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v))
    assert "pallas_call" not in jaxpr


# ---------------------------------------------------------------------------
# 4. selective recomputation keeps the forward kernel's residuals
# ---------------------------------------------------------------------------

_B, _S, _V = 4, 32, 64


def _two_layer_case():
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params

    cfg = presets.tiny(vocab_size=_V, seq_length=_S, attention_impl="pallas")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    batch = {k: jnp.asarray(rng.integers(0, _V, (_B, _S)), jnp.int32)
             for k in ("tokens", "labels")}
    batch["loss_mask"] = jnp.asarray(rng.integers(0, 2, (_B, _S)),
                                     jnp.float32)
    return cfg, params, batch


@pytest.mark.parametrize("tp", [None, 2], ids=["nomesh", "tp2dp2"])
def test_selective_equals_no_recompute_bit_for_bit(monkeypatch, tp):
    """Loss and every gradient of a two-layer model under `selective`
    are those under `none`, bit for bit: the layer's checkpoint keeps the
    forward kernel's output and log-sum-exp (flash_template
    SAVED_RESIDUAL), the backward kernels read the very values the
    forward made, and nothing of the attention core is computed a second
    time. Under TP 2 x DP 2 the kept values cross the kernels'
    `shard_map` as each device's own shard. Both sides run operation by
    operation (`jax.disable_jit`): compiled as two whole programs, the
    CPU compiler fuses their elementwise work differently and the last
    bit of every leaf, the head's included, follows the fusion and not
    the policy."""
    from jax.sharding import NamedSharding

    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.models.language_model import lm_loss
    from megatron_tpu.models.params import param_specs
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import (
        ActivationSharder, batch_spec, shard_tree,
    )

    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    cfg, params, batch = _two_layer_case()

    def run(recompute):
        if tp is None:
            with jax.disable_jit():
                return jax.value_and_grad(
                    lambda p: lm_loss(cfg, p, batch,
                                      recompute=recompute)[0])(params)
        rt = build_mesh(ParallelConfig(tensor_parallel=tp,
                                       sequence_parallel=True),
                        devices=jax.devices()[:4])
        sharder = ActivationSharder(True)
        with jax.sharding.set_mesh(rt.mesh):
            sharded = shard_tree(rt, params, param_specs(cfg))
            placed = {k: jax.device_put(
                v, NamedSharding(rt.mesh, batch_spec()))
                for k, v in batch.items()}
            with jax.disable_jit():
                return jax.value_and_grad(
                    lambda p, b: lm_loss(cfg, p, b, recompute=recompute,
                                         sharder=sharder)[0])(sharded, placed)

    want, want_grads = run("none")
    got, got_grads = run("selective")
    assert float(got) == float(want)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_grads),
                            jax.tree.leaves(want_grads), strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b)), path


@pytest.mark.parametrize("recompute, kept", [("selective", True),
                                             ("full", False)])
def test_what_a_layer_keeps_of_the_flash_forward(monkeypatch, recompute,
                                                 kept):
    """The residuals a `selective` layer saves (what
    `jax.ad_checkpoint.print_saved_residuals` lists) hold, from the
    kernel, one [B, H, S, D] (its output) and one float32 [B, H, S] (the
    log-sum-exp, compact) and nothing in the kernel's lane-padded
    [B, H, S, 128]: that layout is twice the output. `full` saves neither
    (there the forward runs twice, which is what it is for)."""
    from jax._src.ad_checkpoint import saved_residuals

    from megatron_tpu.models.language_model import lm_loss

    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    cfg, params, batch = _two_layer_case()
    # a stack of one layer is a call, so the layer's residuals are the
    # function's own and not a scan's stacked outputs
    params["layers"] = jax.tree.map(lambda a: a[:1], params["layers"])
    cfg = dataclasses.replace(cfg, num_layers=1).validate()
    h, d = cfg.num_attention_heads, cfg.head_dim
    saved = saved_residuals(
        lambda p: lm_loss(cfg, p, batch, recompute=recompute)[0], params)
    from_kernel = [(aval.shape, str(aval.dtype)) for aval, why in saved
                   if "flash_template.py" in why]
    shapes = [aval.shape for aval, _why in saved]
    assert (_B, h, _S, 128) not in shapes
    if kept:
        assert sorted(from_kernel) == [((_B, h, _S), "float32"),
                                       ((_B, h, _S, d), "float32")]
    else:
        assert from_kernel == []


@pytest.mark.parametrize("window", [None, 8, 16, 24, 32, 48])
@pytest.mark.parametrize("causal", [True, False])
def test_tile_pieces_cover_what_the_mask_leaves(causal, window):
    """`prefill_tile_pieces` against the dense mask, tiles of 16 (halves
    of 8) at every distance under and over the diagonal: the pieces are
    disjoint, hold every visible pair, hold no quarter without one, say
    `masked` wherever they hold a hidden pair, exist exactly where
    `prefill_block_live` admits the tile, and come smaller first."""
    block, half = 16, 8
    for dist in range(-4, 5):
        qi, ki = max(dist, 0), max(-dist, 0)
        q_pos = qi * block + np.arange(block)[:, None]
        k_pos = ki * block + np.arange(block)[None, :]
        dense = np.broadcast_to(np.asarray(masks.visible(
            q_pos, k_pos, causal=causal, window=window)), (block, block))
        pieces = masks.prefill_tile_pieces(dist, block, causal=causal,
                                           window=window)
        assert bool(pieces) == bool(masks.prefill_block_live(
            qi, ki, block, block, causal=causal, window=window)), dist
        covered = np.zeros_like(dense, dtype=np.int32)
        for r0, nr, c0, nc, masked in pieces:
            covered[r0:r0 + nr, c0:c0 + nc] += 1
            part = dense[r0:r0 + nr, c0:c0 + nc]
            assert masked == (not part.all()), (dist, r0, c0)
            for r in range(0, nr, half):
                for c in range(0, nc, half):
                    assert part[r:r + half, c:c + half].any(), (dist, r, c)
        assert covered.max(initial=0) <= 1
        assert not (dense & (covered == 0)).any(), dist
        areas = [nr * nc for _, nr, _, nc, _ in pieces]
        assert areas == sorted(areas)
