"""Paged-KV subsystem units (inference/paging/ + the paged kernel).

Pool/radix/scheduler tests are pure host bookkeeping (no compiles);
the kernel test runs the Pallas paged flash-decode in interpret mode
against a gather + masked-softmax reference. Engine-level parity lives
in tests/test_serving_engine.py (the serving matrix).
"""

import numpy as np
import pytest

from megatron_tpu.inference.paging.pool import SCRATCH_PAGE, PagePool
from megatron_tpu.inference.paging.radix import RadixPrefixCache
from megatron_tpu.inference.paging.scheduler import (
    ChunkedPrefillQueue, PrefillTask,
)

# ---------------------------------------------------------------------------
# page pool


def test_pool_alloc_release_refcount():
    pool = PagePool(6)  # pages 1..5 usable
    assert pool.free_pages == 5 and pool.used_pages == 0
    a = pool.alloc(2)
    assert len(a) == 2 and all(p != SCRATCH_PAGE for p in a)
    assert pool.free_pages == 3 and pool.used_pages == 2
    pool.retain(a)  # second holder
    assert pool.release(a) == 0  # refs drop 2 -> 1, nothing freed
    assert pool.release(a) == 2  # 1 -> 0: both return
    assert pool.free_pages == 5


def test_pool_alloc_all_or_nothing():
    pool = PagePool(4)
    assert pool.alloc(5) is None  # over-ask leaks nothing
    assert pool.free_pages == 3
    assert pool.alloc(3) is not None
    assert pool.alloc(1) is None


def test_pool_misuse_raises():
    pool = PagePool(4)
    (p,) = pool.alloc(1)
    pool.release([p])
    with pytest.raises(ValueError):
        pool.release([p])  # double release
    with pytest.raises(ValueError):
        pool.retain([p])  # retain of a free page
    with pytest.raises(ValueError):
        PagePool(1)  # no room beyond the scratch page
    # scratch page is never tracked
    pool.retain([SCRATCH_PAGE])
    pool.release([SCRATCH_PAGE])


# ---------------------------------------------------------------------------
# radix prefix cache


def _cache(ps=4, pages=32):
    pool = PagePool(pages)
    return pool, RadixPrefixCache(pool, ps)


def test_radix_insert_lookup_longest_prefix():
    pool, cache = _cache()
    toks = list(range(10, 22))  # 12 tokens = 3 full pages
    pages = pool.alloc(3)
    lps = [float(-t) for t in range(1, 12)]  # scores tokens 1..11
    assert cache.insert(toks, pages, lps) == 3
    # full match
    hit, hlps = cache.lookup(toks)
    assert hit == pages
    np.testing.assert_allclose(np.concatenate(hlps), lps)
    # partial match: first 8 tokens shared, then diverges
    hit, _ = cache.lookup(toks[:8] + [99, 98, 97, 96])
    assert hit == pages[:2]
    # sub-page tails never match
    hit, _ = cache.lookup(toks[:6])
    assert hit == pages[:1]
    assert cache.lookup([1, 2, 3, 4])[0] == []


def test_radix_insert_skips_existing_nodes():
    pool, cache = _cache()
    toks = list(range(8))
    pages = pool.alloc(2)
    cache.insert(toks, pages, [0.0] * 7)
    dup = pool.alloc(2)  # a second slot recomputed the same prefix
    assert cache.insert(toks, dup, [0.0] * 7) == 0  # existing copy wins
    assert cache.lookup(toks)[0] == pages
    assert pool.refcount(pages[0]) == 2  # alloc + cache
    assert pool.refcount(dup[0]) == 1  # duplicate stays slot-private


def test_radix_evict_lru_leaves_only():
    pool, cache = _cache()
    old = list(range(8))
    new = list(range(100, 108))
    p_old, p_new = pool.alloc(2), pool.alloc(2)
    cache.insert(old, p_old, [0.0] * 7)
    cache.insert(new, p_new, [0.0] * 7)
    pool.release(p_old)
    pool.release(p_new)  # cache is now the only holder
    cache.lookup(new)  # touch: `new` is most-recently-used
    assert cache.evict(2) == 2
    assert cache.lookup(old)[0] == []  # LRU path died first
    assert cache.lookup(new)[0] == p_new


def test_radix_evict_spares_pages_slots_still_reference():
    pool, cache = _cache()
    toks = list(range(8))
    pages = pool.alloc(2)  # the "slot's" references
    cache.insert(toks, pages, [0.0] * 7)
    assert cache.evict(2) == 0  # refcount 2: not evictable
    pool.release(pages)
    assert cache.evict(2) == 2  # now cache-only -> evictable
    assert pool.free_pages == pool.num_pages - 1


def test_radix_clear_releases_everything():
    pool, cache = _cache()
    pages = pool.alloc(3)
    cache.insert(list(range(12)), pages, [0.0] * 11)
    pool.release(pages)
    assert cache.clear() == 3
    assert len(cache) == 0 and pool.free_pages == pool.num_pages - 1


# ---------------------------------------------------------------------------
# chunked-prefill queue


def test_prefill_queue_fifo_and_advance():
    q = ChunkedPrefillQueue(chunk=4)
    t1 = PrefillTask(slot=0, tokens=np.arange(10, dtype=np.int32),
                     start=0, off=0)
    t2 = PrefillTask(slot=1, tokens=np.arange(6, dtype=np.int32),
                     start=0, off=0)
    q.add(t1)
    q.add(t2)
    assert q.slots == {0, 1}
    assert q.peek() is t1  # oldest incomplete first
    assert not q.advance(t1, 4)
    assert q.peek() is t1  # still t1 until it completes
    assert not q.advance(t1, 4)
    assert q.advance(t1, 2)  # 10/10 done, removed
    assert q.peek() is t2
    assert q.advance(t2, 6)
    assert q.peek() is None and len(q) == 0


def test_prefill_queue_drop_slot_and_validation():
    q = ChunkedPrefillQueue(chunk=4)
    t = PrefillTask(slot=3, tokens=np.arange(8, dtype=np.int32),
                    start=2, off=0)
    q.add(t)
    assert t.off == 2  # add() rewinds off to start
    assert q.drop_slot(3) is t
    assert q.drop_slot(3) is None
    with pytest.raises(ValueError):
        # a fully-cached prompt must leave >= 1 position to recompute
        q.add(PrefillTask(slot=0, tokens=np.arange(4, dtype=np.int32),
                          start=4, off=0))
    with pytest.raises(ValueError):
        ChunkedPrefillQueue(chunk=0)


# ---------------------------------------------------------------------------
# paged flash-decode kernel (interpret mode on CPU)


def test_paged_flash_decode_matches_gather_reference():
    """Page-table KV gather inside the Pallas grid vs a dense gather +
    masked softmax: GQA, per-row prefix lengths, scratch-mapped entries,
    sliding window."""
    import jax.numpy as jnp

    from megatron_tpu.ops.pallas.flash_template import paged_flash_decode

    rng = np.random.default_rng(0)
    B, P, ps, Hq, Hkv, D = 3, 9, 8, 4, 2, 16
    max_pages = 4
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)), jnp.float32)
    table = rng.integers(1, P, (B, max_pages)).astype(np.int32)
    table[0, 1:] = 0  # unallocated entries point at scratch
    lens = np.asarray([1, 17, 32], np.int32)

    def ref(window=None):
        k = np.asarray(kp)[table].reshape(B, -1, Hkv, D)
        v = np.asarray(vp)[table].reshape(B, -1, Hkv, D)
        qg = (np.asarray(q, np.float64) / np.sqrt(D)).reshape(
            B, 1, Hkv, Hq // Hkv, D)
        s = np.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(np.float64))
        k_pos = np.arange(max_pages * ps)[None, :]
        allowed = k_pos < lens[:, None]
        if window is not None:
            allowed &= k_pos >= lens[:, None] - window
        s = np.where(allowed[:, None, None, None, :], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("bhgqk,bkhd->bqhgd", p, v.astype(np.float64))
        return o.reshape(B, 1, Hq, D)

    out = paged_flash_decode(q, kp, vp, jnp.asarray(table),
                             jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), ref(), atol=2e-6)
    out_w = paged_flash_decode(q, kp, vp, jnp.asarray(table),
                               jnp.asarray(lens), sliding_window=8)
    np.testing.assert_allclose(np.asarray(out_w), ref(window=8), atol=2e-6)


# the two served cells' decode shapes (benchmark/configs/*-serve.json and
# their mixes' lengths: prompt + answer so far), and the queued long-prompt
# mix, whose rows cross the window's lower edge:
# (slots, table entries, page, kv heads, window, shortest, longest row)
_SERVED_SHAPES = {
    "instruct": (64, 528, 16, 8, 4096, 17, 1280),
    "reasoning": (64, 256, 16, 1, None, 17, 3584),
    "longprompt": (64, 528, 16, 8, 4096, 2048, 8447),
}


@pytest.mark.parametrize("sq", [1, 5])
@pytest.mark.parametrize("in_use", [0, 8, 38, 64])
@pytest.mark.parametrize("shape", sorted(_SERVED_SHAPES))
def test_decode_work_is_the_live_context(shape, in_use, sq):
    """The blocks a decode call visits (the sum of the kernel's own loop
    bounds, `decode_trips`) are the blocks `decode_block_live` admits,
    row by row, for lengths drawn as the mixes draw them: nothing for an
    idle slot, so nothing at all for a table of idle slots, and never a
    block outside the table."""
    from megatron_tpu.ops.pallas import flash_template as ft
    from megatron_tpu.ops.pallas import masks

    slots, entries, ps, hkv, window, lo, hi = _SERVED_SHAPES[shape]
    rng = np.random.default_rng(in_use + sq)
    lens = np.zeros(slots, np.int64)
    rows = rng.permutation(slots)[:in_use]
    lens[rows] = rng.integers(lo, hi - sq + 2, in_use)
    unit, units, _, n_blocks = ft._decode_geometry(entries, ps, hkv)
    blk = unit * units
    assert blk * hkv <= ft._DECODE_TILE_ROWS and blk % ps == 0
    assert (n_blocks - 1) * blk < entries * ps <= n_blocks * blk
    want = sum(bool(masks.decode_block_live(ki, blk, int(n), sq,
                                            window=window))
               for n in lens[rows] for ki in range(n_blocks))
    visited, held = ft.decode_blocks_visited(lens, entries, ps, hkv, sq,
                                             window)
    assert held == slots * n_blocks
    if sq == 1:
        assert visited == want
        live = -(-lens[rows] // blk) - (
            0 if window is None
            else np.maximum(lens[rows] - window, 0) // blk)
        assert visited == live.sum()
    else:
        # an idle slot's later queries see the drafts before them: one
        # block (masks.decode_live_blocks)
        assert visited == want + (slots - in_use)
    if in_use == 0 and sq == 1:
        assert visited == 0


@pytest.mark.parametrize("engine", ["paged", "one-page", "paged-window",
                                    "paged-spec"])
def test_engine_reports_the_live_block_share(engine):
    """`engine_decode_live_block_share`, set before every decode tick
    from the host's lengths, is what the kernel's loop bounds give over
    the blocks the table holds: checked tick by tick against the block
    predicate, at pages of 8 and at one page a sequence. A decoding row
    reaches the kernel with its new token written (length + 1), an idle
    one with the length its loop reads as nothing to visit (the layer
    hands the kernel `masks.decode_idle_length` for a row the step's
    table leaves out): no block, at one query a row and at the
    speculative verify's three. Both counts add up in `stats` and in the
    journal's `serve_ticks`."""
    import jax

    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.ops.pallas import flash_template as ft
    from megatron_tpu.ops.pallas import masks
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params
    from megatron_tpu.telemetry.metrics import MetricsRegistry

    window = 16 if engine == "paged-window" else None
    cfg = presets.tiny(vocab_size=64, seq_length=128, num_layers=2,
                       sliding_window_size=window)
    params = init_params(cfg, jax.random.PRNGKey(0))
    reg = MetricsRegistry()
    spec = None
    if engine == "paged-spec":
        from megatron_tpu.inference.speculative import SpecConfig

        spec = SpecConfig(drafter="ngram", k=2)
    entries, ps = (1, 128) if engine == "one-page" else (16, 8)
    eng = InferenceEngine(cfg, params, num_slots=3, max_seq_len=128,
                          page_size=ps, prefill_chunk=16, metrics=reg,
                          speculative=spec)
    sq = 1 if spec is None else spec.k + 1
    unit, units, _, n_blocks = ft._decode_geometry(entries, ps,
                                                   cfg.n_kv_heads)
    blk = unit * units
    seen = []
    note = eng._note_live_blocks

    def spy(active):
        note(active)
        want = sum(bool(masks.decode_block_live(ki, blk,
                                                int(eng.lengths[i]) + 1,
                                                sq, window=window))
                   for i in active for ki in range(n_blocks))
        seen.append((want / (3 * n_blocks),
                     eng.stats["decode_live_block_share"],
                     reg.get("engine_decode_live_block_share").value()))

    eng._note_live_blocks = spy
    assert eng.stats["decode_live_block_share"] == 0.0
    assert eng._serve_ticks_fields()["decode_blocks"] == [0, 0]
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 64, (2, 12)).astype(np.int32)
    eng.generate(prompts, np.asarray([12, 5], np.int32), max_new_tokens=40)
    assert len(seen) >= 10
    for want, stat, gauge in seen:
        assert want == stat == gauge
    assert 0 < min(s for _, s, _ in seen) <= max(s for _, s, _ in seen) <= 1
    # the second request retires first: its slot then counts for nothing
    assert min(s for _, s, _ in seen) <= 1 / 3
    visited, held = eng._serve_ticks_fields()["decode_blocks"]
    assert held == len(seen) * 3 * n_blocks
    assert visited == round(sum(s for _, s, _ in seen) * 3 * n_blocks)
    assert "engine_decode_live_block_share " in reg.render()


@pytest.mark.parametrize("window", [None, 128], ids=["full", "window"])
def test_engine_reports_the_prefill_live_block_share(window):
    """`engine_prefill_live_block_share`, set as each chunk is dispatched
    from the host's offset and the prompt's length, is what the chunk
    kernel's loop bounds give (a query tile's trips, over the blocks its
    queries see below the prompt's end) over the blocks the table holds a
    query tile: checked chunk by chunk against the block predicate. Unset
    until a chunk has run; the counts add up in `stats` and in the
    journal's `serve_ticks`."""
    import jax

    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params
    from megatron_tpu.ops.pallas import flash_template as ft
    from megatron_tpu.ops.pallas import masks
    from megatron_tpu.telemetry.metrics import MetricsRegistry

    # 16 kv heads: a block of 128 positions, four to the table
    cfg = presets.tiny(vocab_size=64, seq_length=512, num_layers=1,
                       num_attention_heads=16, num_kv_heads=16,
                       sliding_window_size=window)
    params = init_params(cfg, jax.random.PRNGKey(0))
    reg = MetricsRegistry()
    chunk, ps, entries = 64, 8, 64
    eng = InferenceEngine(cfg, params, num_slots=2, max_seq_len=512,
                               page_size=ps, prefill_chunk=chunk,
                               metrics=reg)
    groups = cfg.num_attention_heads // cfg.n_kv_heads
    tq, unit, units, _, n_blocks = ft._chunk_geometry(
        chunk, groups, entries, ps, cfg.n_kv_heads)
    blk = unit * units
    assert (blk, n_blocks) == (128, 4)
    assert "prefill_live_block_share" not in eng.stats
    assert reg.get("engine_prefill_live_block_share").value() == 0
    assert eng._serve_ticks_fields()["prefill_blocks"] == [0, 0]
    seen = []
    note = eng._note_prefill_blocks

    def spy(off, total):
        note(off, total)
        want = 0
        for q_lo in range(off, off + chunk, tq):
            last = min(q_lo + tq, total) - 1     # the deepest live query
            want += sum(
                bool(masks.block_live(ki, blk, q_lo, last, window=window))
                for ki in range(n_blocks)) if q_lo < total else 0
        seen.append((off, total, want / (n_blocks * (chunk // tq)),
                     eng.stats["prefill_live_block_share"],
                     reg.get("engine_prefill_live_block_share").value()))

    eng._note_prefill_blocks = spy
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 64, (2, 300)).astype(np.int32)
    eng.generate(prompts, np.asarray([300, 5], np.int32), max_new_tokens=3)
    # 300 tokens in chunks of 64: offsets 0 .. 256; 5 tokens: one chunk
    assert [(off, total) for off, total, *_ in seen] == [
        (0, 300), (64, 300), (128, 300), (192, 300), (256, 300), (0, 5)]
    for _, _, want, stat, gauge in seen:
        assert want == stat == gauge
    # one block of four, then two, then three: all of them without a
    # window, the newest two behind one
    assert [s[2] for s in seen] == (
        [.25, .25, .5, .5, .75, .25] if window is None
        else [.25, .25, .5, .5, .5, .25])
    visited, held = eng._serve_ticks_fields()["prefill_blocks"]
    assert held == len(seen) * n_blocks * (chunk // tq)
    assert visited == round(sum(s[2] for s in seen) * held / len(seen))
    assert "engine_prefill_live_block_share " in reg.render()


def test_paged_flash_decode_rejects_bad_shapes():
    import jax.numpy as jnp

    from megatron_tpu.ops.pallas.flash_template import paged_flash_decode

    q = jnp.zeros((2, 1, 4, 8))
    kp = jnp.zeros((4, 8, 2, 8))
    table = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="single-token"):
        paged_flash_decode(jnp.zeros((2, 3, 4, 8)), kp, kp, table, lens)
    with pytest.raises(ValueError, match="multiple of 8"):
        paged_flash_decode(q, jnp.zeros((4, 6, 2, 8)),
                           jnp.zeros((4, 6, 2, 8)), table, lens)
    with pytest.raises(ValueError, match="rows"):
        paged_flash_decode(q, kp, kp, jnp.zeros((3, 2), jnp.int32), lens)


def test_attention_page_table_gather_matches_dense():
    """attention(page_table=...) on CPU gathers pages into the identical
    dense view: single-token decode (kv_lengths) and chunked prefill
    (causal + q_offset) both match the dense cache bit-for-bit."""
    import jax.numpy as jnp

    from megatron_tpu.ops.attention import attention

    rng = np.random.default_rng(1)
    B, P, ps, H, D = 2, 7, 4, 2, 8
    max_pages = 3
    kp = jnp.asarray(rng.standard_normal((P, ps, H, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((P, ps, H, D)), jnp.float32)
    table = jnp.asarray(rng.integers(1, P, (B, max_pages)), jnp.int32)
    dense_k = kp[table].reshape(B, -1, H, D)
    dense_v = vp[table].reshape(B, -1, H, D)

    # decode shape
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    lens = jnp.asarray([3, 12], jnp.int32)
    got = attention(q, kp, vp, kv_lengths=lens, page_table=table)
    want = attention(q, dense_k, dense_v, kv_lengths=lens)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    # chunked-prefill shape (batch 1, causal with offset)
    qc = jnp.asarray(rng.standard_normal((1, 4, H, D)), jnp.float32)
    got = attention(qc, kp, vp, mask_type="causal", q_offset=5,
                    page_table=table[:1])
    want = attention(qc, dense_k[:1], dense_v[:1], mask_type="causal",
                     q_offset=5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# sliding-window page release (ROADMAP item 1, Mistral)


def test_sliding_window_release_parity_and_accounting():  # ~5s measured
    """Pages fully behind the attention window return to the pool while
    the request still decodes — token-identical to the one-shot loop
    (masked positions contribute exactly nothing, so reading the
    scratch page in their place changes no value), with honest pool
    accounting: released pages are re-allocatable, radix-held prompt
    pages survive for future prefix hits, and a drained engine holds
    only the radix references."""
    import jax

    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.inference.generation import generate_tokens
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params

    cfg = presets.tiny(vocab_size=64, seq_length=128, num_layers=2,
                       sliding_window_size=16)
    params = init_params(cfg, jax.random.PRNGKey(0))
    paged = InferenceEngine(cfg, params, num_slots=2,
                                 max_seq_len=128, page_size=8,
                                 prefill_chunk=16)
    rng = np.random.default_rng(0)
    prompts = rng.integers(1, 64, (2, 12)).astype(np.int32)
    lengths = np.full((2,), 12, np.int32)
    a = generate_tokens(cfg, params, prompts, lengths, max_new_tokens=60,
                        temperature=0.0)
    b = paged.generate(prompts, lengths, max_new_tokens=60)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    np.testing.assert_allclose(a.logprobs, b.logprobs, atol=1e-5)
    # sequences reached length 72 with window 16: pages behind the
    # window were freed DURING decode, not just at retirement
    assert paged.stats["window_pages_released"] > 0
    assert paged.stats["decode_recompiles"] == 0
    # drained: only the radix prefix cache still references pages (one
    # full 8-token page per 12-token prompt)
    held = [p for p in range(1, paged.num_pages)
            if paged.pool.refcount(p) > 0]
    assert len(held) == 2, held
    assert (paged.pool.free_pages
            == paged.num_pages - 1 - len(held))
    # the freed pages are genuinely reusable: the same traffic drains
    # again (prefix hits alias the surviving radix pages)
    hits0 = paged.stats["prefix_hits"]
    b2 = paged.generate(prompts, lengths, max_new_tokens=60)
    np.testing.assert_array_equal(a.tokens, b2.tokens)
    assert paged.stats["prefix_hits"] > hits0


def test_window_release_noop_without_window():
    """No sliding window configured => the release pass never runs and
    the counter stays zero (the pre-existing lifetime story holds)."""
    import jax

    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params

    cfg = presets.tiny(vocab_size=64, seq_length=64, num_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    paged = InferenceEngine(cfg, params, num_slots=1,
                                 max_seq_len=64, page_size=8,
                                 prefill_chunk=16)
    prompts = np.arange(1, 9, dtype=np.int32)[None]
    paged.generate(prompts, np.array([8], np.int32), max_new_tokens=20)
    assert paged.stats["window_pages_released"] == 0


# ---------------------------------------------------------------------------
# engine sizing / rejection edges (host-only where possible)


def test_paged_engine_rejects_undersized_pool():
    import jax

    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params

    cfg = presets.tiny(vocab_size=64, seq_length=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="cannot hold even one"):
        InferenceEngine(cfg, params, num_slots=2, max_seq_len=64,
                             page_size=8, num_pages=4)
    with pytest.raises(ValueError, match="num_pages"):
        InferenceEngine(cfg, params, num_slots=1, max_seq_len=64,
                             page_size=8, num_pages=1)
    with pytest.raises(ValueError, match="page_size"):
        InferenceEngine(cfg, params, num_slots=1, max_seq_len=64,
                             page_size=0)


# ---------------------------------------------------------------------------
# the pool's format on the wire (ops/kv_store.py owns it; the wire layout
# of exported pages did not move with PR 32)


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
def test_pages_in_the_parents_layout_export_and_install(int8):
    """A fixture of a few pages written the way the parent of PR 32 laid a
    pool out — [layers, pages, page, kv_heads, head_dim], int8 scales
    [..., 1] — is what the engine holds today: its prefix pages export to
    the canonical [layers, positions, kv_heads, head_dim] sections, page
    after page, and install bit for bit into another replica's pool."""
    import jax
    import jax.numpy as jnp

    from megatron_tpu.inference.fleet.migration import (
        pack_state, unpack_state,
    )
    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params

    cfg = presets.tiny(vocab_size=64, seq_length=32, num_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))

    def mk():
        return InferenceEngine(cfg, params, num_slots=2, max_seq_len=32,
                                    page_size=4, prefill_chunk=4,
                                    num_pages=9, kv_cache_int8=int8)

    src = mk()
    L, P, ps, H, D = 2, 9, 4, cfg.n_kv_heads, cfg.head_dim
    rng = np.random.default_rng(5)
    if int8:
        fixture = [rng.integers(-127, 128, (L, P, ps, H, D)).astype(np.int8)
                   for _ in range(2)]
        fixture += [rng.random((L, P, ps, H, 1)).astype(np.float32)
                    for _ in range(2)]
    else:
        dtype = np.asarray(src.caches[0]).dtype
        fixture = [rng.standard_normal((L, P, ps, H, D)).astype(dtype)
                   for _ in range(2)]
    assert [(c.shape, c.dtype) for c in src.caches] == [
        (f.shape, f.dtype) for f in fixture]
    src.caches = tuple(jnp.asarray(f) for f in fixture)
    tokens = list(range(1, 13))                       # three full pages
    pages = src.pool.alloc(3)
    src.prefix_cache.insert(tokens, pages, np.zeros(11, np.float32))

    meta, sections = src.export_prefix_state(tokens)
    names = (["kv_k", "kv_v", "kv_k_scale", "kv_v_scale"] if int8
             else ["kv_k", "kv_v"])
    for name, f in zip(names, fixture):
        want = np.concatenate([f[:, p] for p in pages], axis=1)
        assert want.shape[:2] == (L, 12)
        np.testing.assert_array_equal(sections[name], want)

    meta, sections = unpack_state(pack_state(meta, sections))
    dst = mk()
    assert dst.import_prefix_state(meta, sections) == 3
    landed, _ = dst.prefix_cache.lookup(tokens)
    assert len(landed) == 3
    for got, f in zip(dst.caches, fixture):
        np.testing.assert_array_equal(np.asarray(got)[:, landed],
                                      f[:, pages])


# ---------------------------------------------------------------------------
# the loop runs one tick ahead of the device (docs/serving.md "Step loop"):
# the events that read what is in flight


def _tiny():
    import jax

    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params

    cfg = presets.tiny(vocab_size=64, seq_length=64)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def test_a_dry_pool_reads_the_tick_in_flight_then_preempts():
    """A pool too small for three sequences at once: the dry pool first
    reads what is in flight (it may end a request and hand its pages
    back), then `_preempt_one` takes the youngest, whose chain is the
    device's. Every request still ends with the tokens, logprobs and
    prompt logprobs `generate_tokens` gives it alone, seeded sampling
    included, and every page comes back."""
    import _engine_lookahead_cases as cases

    from megatron_tpu.inference.engine import InferenceEngine

    cfg, params = _tiny()
    # 24 positions a sequence = 6 pages of 4; 11 hold fewer than two whole
    eng = InferenceEngine(cfg, params, num_slots=3, max_seq_len=32,
                               page_size=4, prefill_chunk=8, num_pages=12)
    wants = cases.expected(cases.one_shot(cfg, params), cfg.vocab_size,
                           sampled=True)
    reqs = cases.submit_staggered(eng, wants, cfg.vocab_size, sampled=True)
    cases.assert_served(reqs, wants, eng, drained=True)
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["tick_drains"].get("pages", 0) >= 1
    assert eng.pool.used_pages == len(eng.prefix_cache)


def test_paused_exports_a_request_the_loop_had_a_tick_in_flight_for():
    """`paused()` parks the loop with nothing in flight: the export reads
    true mirrors in mid-decode, and the import resumes to the tokens of an
    uninterrupted run, seeded sampling included."""
    import time

    from _engine_lookahead_cases import one_shot

    from megatron_tpu.inference.engine import Request
    from megatron_tpu.inference.fleet.migration import (
        pack_state, unpack_state,
    )
    from megatron_tpu.inference.engine import InferenceEngine

    cfg, params = _tiny()
    prompt = np.asarray([3, 7, 11, 2, 9], np.int32)
    knobs = dict(temperature=0.8, top_k=8, top_p=0.9, seed=5)
    want = one_shot(cfg, params)(prompt, 40, knobs)

    def make():
        return InferenceEngine(cfg, params, num_slots=2,
                                    max_seq_len=64, page_size=8,
                                    prefill_chunk=8)

    src = make()
    src.generate(np.array([[1]], np.int32), np.array([1], np.int32),
                 max_new_tokens=2)          # compiled before the clock runs
    src.start()
    try:
        r = src.submit(Request(prompt=prompt, max_new_tokens=40, **knobs))
        t0 = time.monotonic()
        while len(r.generated) < 3 and time.monotonic() - t0 < 60:
            time.sleep(0.001)
        with src.paused():
            assert not src._inflight
            assert not r.done.is_set(), "the request ended before the pause"
            meta, sections = src.export_request_state(r)
            shipped = len(r.generated)
            assert meta["position"] == len(prompt) + shipped - 1
    finally:
        src.stop()
    assert 3 <= shipped < 40
    meta, sections = unpack_state(pack_state(meta, sections))
    dst = make()
    req2, path = dst.import_request_state(meta, sections)
    dst.run_until_idle()
    assert path == "kv_import" and req2.error is None
    assert req2.generated == want.generated
    np.testing.assert_allclose(req2.logprobs, want.logprobs,
                               rtol=1e-5, atol=1e-5)
