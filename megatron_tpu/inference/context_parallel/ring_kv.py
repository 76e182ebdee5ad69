"""Ring attention over sequence-striped paged KV pools.

The device half of the CP serving engine: one partial-manual shard_map
island over the "context" mesh axis that (1) scatter-writes the new
K/V rows into the LOCAL pool shard (each rank owns the pages of its
sequence stripe — logical page l lives on rank ``l % cp``), (2) runs
the exact masked attention of ops/attention.py against the local
stripe only, producing a normalized (out, lse) partial, and (3) merges
the cp partials with the ring-attention merge algebra
(ops/ring_attention._merge_normalized) under one of two geometries:

  * "ring" — cp-1 ``ppermute`` hops around the flat context axis. The
    schedule is OVERLAPPED by default (CpComm.overlap): hop l+1's
    permute of the (o, lse) partial is issued BEFORE the merge compute
    over hop l's arrival, which is legal because the permute chain
    depends only on previous permute results, never on the merges —
    the accumulator hangs off each arrival separately. Same hop count,
    same wire bytes, numerics identical to the serial schedule; an
    async backend (TPU collective-permute-start/done) can run hop l+1
    under hop l's merge instead of exposing it.
  * "2d" — cp = cp_seq x cp_head (ATTENTION2D): a tiled head
    all-to-all inside each cp_head-sized subgroup trades full-head
    partials for ITS head slice of every member's partial (site
    "cp_a2a"), the members' stripes merge locally, then cp_seq-1 ring
    hops ACROSS subgroups (1/cp_head the payload) merge the rest, and
    an intra-subgroup all_gather restores the full head dim — TASP's
    topology-aware placement: the expensive ring traverses the slow
    fabric tier once, the chatty legs stay node-local.

The hop transport is quant/collectives.ring_permute — dense fp32 or
policy-gated int8/fp8 (site "cp_ring"); the 2d legs ride
grouped_all_to_all / grouped_all_gather (site "cp_a2a").

Mask semantics mirror ops/attention.py exactly so the CP engine stays
token-identical to the dense one:

  * decode (per_slot): key position g attends iff ``g < lengths[i] + 1``
    (+ the sliding-window floor), lengths being the pre-increment slot
    length — same as one-device serving's ``kv_lengths = cache_index + 1``.
  * chunk prefill: ``g <= off + q_idx`` causal, window ``g > q_pos - w``.

The local tables arriving here are PER-RANK views ([cp, rows, mpl],
sharded on dim 0): entry [r, i, j] holds rank r's local pool index of
logical page ``j*cp + r`` of row i, or the sentinel ``npl`` (== local
pool size) when that logical page is unallocated on r or out of the
row's span. Sentinel writes drop (scatter mode="drop"); sentinel reads
are masked out of the softmax.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from megatron_tpu.ops import kv_store
from megatron_tpu.ops.ring_attention import _merge_normalized
from megatron_tpu.quant.collectives import (
    grouped_all_gather, grouped_all_to_all, ring_permute,
)


def _ring_hop(cpc, o, lse, perm):
    """One ring hop: the o partial over the (optionally compressed)
    cp_ring transport, the lse row always dense fp32."""
    no = ring_permute(o, cpc.axis, perm, mode=cpc.wire_mode(),
                      chunk=cpc.chunk)
    nl = jax.lax.ppermute(lse, cpc.axis, perm)
    return no, nl


def _ring_merge(cpc, o, lse, perm, hops):
    """Merge `hops` ring arrivals into the local partial.

    Serial schedule: permute -> merge -> permute -> ... (each hop's
    send waits for the previous merge in program order). Overlapped
    schedule (cpc.overlap): hop l+1's permute is issued BEFORE hop l's
    merge — valid because ``cur`` chains only through permutes and the
    accumulator hangs off each arrival separately, so the reorder is
    numerics-identical with the same hop count and wire bytes; it just
    stops the merge compute from serializing the collective chain."""
    acc_o, acc_lse = o, lse
    if hops <= 0:
        return acc_o, acc_lse
    if not cpc.overlap:
        cur_o, cur_lse = o, lse
        for _ in range(hops):
            cur_o, cur_lse = _ring_hop(cpc, cur_o, cur_lse, perm)
            acc_o, acc_lse = _merge_normalized((acc_o, acc_lse),
                                               cur_o, cur_lse)
        return acc_o, acc_lse
    nxt_o, nxt_lse = _ring_hop(cpc, o, lse, perm)
    for hop in range(hops):
        cur_o, cur_lse = nxt_o, nxt_lse
        if hop + 1 < hops:
            nxt_o, nxt_lse = _ring_hop(cpc, cur_o, cur_lse, perm)
        acc_o, acc_lse = _merge_normalized((acc_o, acc_lse),
                                           cur_o, cur_lse)
    return acc_o, acc_lse


def _merge_2d(cpc, o, lse):
    """The 2d-geometry merge: head scatter inside the subgroup, local
    merge of the members' stripes, overlapped ring across subgroups at
    1/subgroup the payload, head gather. Every rank ends with the full
    [B, S, Hq, D] result (replicated, like the flat ring)."""
    cp, g = cpc.cp, cpc.subgroup
    sg = cp // g
    bsz, s_len, hq, d = o.shape
    groups = [list(range(i * g, (i + 1) * g)) for i in range(sg)]
    a2a_mode = cpc.a2a_wire_mode()
    # head scatter: member h of each subgroup ends with head slice h of
    # every member's partial, stacked in member order on a leading dim
    o_st = grouped_all_to_all(o, cpc.axis, split_axis=2, concat_axis=0,
                              groups=groups, mode=a2a_mode,
                              chunk=cpc.chunk)
    l_st = jax.lax.all_to_all(lse, cpc.axis, split_axis=2,
                              concat_axis=0, tiled=True,
                              axis_index_groups=groups)
    o_st = o_st.reshape(g, bsz, s_len, hq // g, d)
    l_st = l_st.reshape(g, bsz, s_len, hq // g)
    acc_o, acc_lse = o_st[0], l_st[0]
    for m in range(1, g):
        acc_o, acc_lse = _merge_normalized((acc_o, acc_lse),
                                           o_st[m], l_st[m])
    # ring only across subgroups: rank (s, h) -> (s+1, h)
    perm = [(r, (r + g) % cp) for r in range(cp)]
    acc_o, acc_lse = _ring_merge(cpc, acc_o, acc_lse, perm, sg - 1)
    # head gather: the members' full-sequence head slices reassemble
    return grouped_all_gather(acc_o, cpc.axis, gather_axis=2,
                              groups=groups, mode=a2a_mode,
                              chunk=cpc.chunk)


def paged_ring_attention(cpc, q, k_new, v_new, kv_cache, layer, loc_tables,
                         cache_index, per_slot, page_write_start=None,
                         page_write_end=None, sliding_window=None):
    """Cross-shard paged attention for one layer.

    q [B, S, Hq, D]; k_new/v_new [B, S, Hkv, D] (post-rope);
    kv_cache = (k_pool, v_pool): the stacked bf16 store (ops/kv_store.py)
    with its pages sharded over "context"; `layer` this layer's index
    into it; loc_tables [cp, B_t, mpl] sharded over "context" on dim 0.
    per_slot decode: cache_index = lengths [B] and S must be 1. Chunk
    prefill: cache_index = scalar chunk offset, B == 1, and the write
    fences bound the page writes. Returns (ctx [B, S, Hq, D] in q.dtype,
    the store with this layer's rows written in place).
    """
    k_pool, v_pool = kv_cache
    cp, axis = cpc.cp, cpc.axis
    if per_slot and q.shape[1] != 1:
        raise ValueError(
            "context-parallel paged decode serves one token per slot "
            f"(no speculative rows); got S={q.shape[1]}")
    if not per_slot and q.shape[0] != 1:
        raise ValueError(
            f"context-parallel chunk prefill needs batch 1, got "
            f"{q.shape[0]}")
    if per_slot:
        page_write_start = jnp.int32(0)
        page_write_end = jnp.int32(2 ** 30)
    window = sliding_window

    def inner(qx, kn, vn, kp, vp, loc, idx, ws, we, layer):
        r = jax.lax.axis_index(axis)
        npl, ps = kv_store.rows_and_row_len(kp)    # this rank's pages
        loc = loc[0]                               # [B_t, mpl] local view
        mpl = loc.shape[1]
        B, S, Hq, D = qx.shape
        Hkv = kn.shape[2]

        # -- scatter-write this step's K/V into the local stripe -------
        if per_slot:
            pos = idx[:, None]                     # [B, 1] positions
            lpage = pos // ps
            j = jnp.minimum(lpage // cp, mpl - 1)
            phys = jnp.take_along_axis(loc, j, axis=1)
            owned = (lpage % cp) == r
        else:
            pos = (idx + jnp.arange(S, dtype=jnp.int32))[None, :]  # [1, S]
            lpage = pos // ps
            j = jnp.minimum(lpage // cp, mpl - 1)
            phys = jnp.take_along_axis(loc[:1], j, axis=1, mode="clip")
            owned = ((lpage % cp) == r) & (pos >= ws) & (pos < we)
        tgt = jnp.where(owned, phys, npl)          # npl: the write drops
        kp = kv_store.scatter_rows(kp, layer, tgt, pos % ps, kn, drop=True)
        vp = kv_store.scatter_rows(vp, layer, tgt, pos % ps, vn, drop=True)

        # -- gather the local stripe + its global token positions ------
        safe = jnp.minimum(loc, npl - 1)           # [B_t, mpl]
        kf = kv_store.gather_rows(kp, layer, safe)
        vf = kv_store.gather_rows(vp, layer, safe)
        s_loc = mpl * ps
        kf = kf.reshape(loc.shape[0], s_loc, Hkv, D)
        vf = vf.reshape(loc.shape[0], s_loc, Hkv, D)
        g_pos = ((jnp.arange(mpl, dtype=jnp.int32) * cp + r) * ps)[:, None] \
            + jnp.arange(ps, dtype=jnp.int32)[None, :]
        g_pos = g_pos.reshape(s_loc)               # global position per key
        valid = jnp.repeat(loc != npl, ps, axis=1)  # [B_t, s_loc]

        # -- exact masked partial softmax (ops/attention.py semantics) --
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
        qf = qx.astype(jnp.float32) * scale
        groups = Hq // Hkv
        qg = qf.reshape(B, S, Hkv, groups, D)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg,
                            kf.astype(jnp.float32))
        if per_slot:
            kv_len = idx[:, None, None] + 1        # pre-increment length
            allowed = g_pos[None, None, :] < kv_len
            if window is not None:
                allowed &= g_pos[None, None, :] >= kv_len - window
        else:
            q_pos = (idx + jnp.arange(S, dtype=jnp.int32))[None, :, None]
            allowed = g_pos[None, None, :] <= q_pos
            if window is not None:
                allowed &= g_pos[None, None, :] > q_pos - window
        allowed &= valid[:, None, :]               # [B, S, s_loc]
        scores = jnp.where(allowed[:, None, None], scores, -jnp.inf)
        m_raw = jnp.max(scores, axis=-1)           # [B, Hkv, G, S]
        m_safe = jnp.where(jnp.isfinite(m_raw), m_raw, 0.0)
        p = jnp.exp(scores - m_safe[..., None])    # exp(-inf) == 0
        tot = jnp.sum(p, axis=-1)                  # [B, Hkv, G, S]
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf.astype(jnp.float32))
        tot_t = tot.transpose(0, 3, 1, 2)          # [B, S, Hkv, G]
        o = o / jnp.maximum(tot_t, 1e-30)[..., None]
        lse = jnp.where(tot_t > 0.0,
                        m_safe.transpose(0, 3, 1, 2)
                        + jnp.log(jnp.maximum(tot_t, 1e-30)),
                        -jnp.inf)
        o = o.reshape(B, S, Hq, D)
        lse = lse.reshape(B, S, Hq)

        # -- merge: all ranks end with the full result ------------------
        if cpc.geometry == "2d":
            acc_o = _merge_2d(cpc, o, lse)
        else:
            perm = [(i, (i + 1) % cp) for i in range(cp)]
            acc_o, _ = _ring_merge(cpc, o, lse, perm, cp - 1)
        return acc_o.astype(qx.dtype), kp, vp

    shard = P(axis)
    pools = kv_store.partition_spec(rows=axis)
    ctx, k_pool, v_pool = jax.shard_map(
        inner, mesh=cpc.mesh,
        in_specs=(P(), P(), P(), pools, pools, shard, P(), P(), P(), P()),
        out_specs=(P(), pools, pools),
        axis_names={axis}, check_vma=False)(
            q, k_new, v_new, k_pool, v_pool, loc_tables,
            cache_index, page_write_start, page_write_end, layer)
    return ctx, (k_pool, v_pool)
