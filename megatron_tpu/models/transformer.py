"""The unified transformer decoder block.

One configurable block is the union of the reference's model zoo
(megatron/model/transformer.py ParallelTransformerLayer / ParallelAttention /
ParallelMLP, 1,282 LoC):

  * pre-LN GPT block (layernorm, gelu, biases, absolute pos-emb)
  * Llama/Mistral block (rmsnorm, swiglu, rotary, no biases, GQA, window)
  * Falcon block (parallel attention — mlp and attn share the residual add,
    transformer.py parallel_attn; Falcon-40B's extra mlp layernorm =
    parallel_layernorm; MQA/GQA)

The reference's Column/RowParallelLinear pairs are plain einsums here; their
sharding lives in models/params.py partition specs. KV caching for
incremental decoding follows InferenceParams (ref:
megatron/text_generation/forward_step.py:17-43) as functional state.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_tpu.config import FFN_TYPES, SSM_TYPES, AttentionKind, ModelConfig
from megatron_tpu.ops import kv_store
from megatron_tpu.ops.activations import apply_activation
from megatron_tpu.ops.attention import attention
from megatron_tpu.ops.cca import cca_mix
from megatron_tpu.ops.fp8 import maybe_fp8_matmul
from megatron_tpu.ops.moe import layer_stats, moe_block, moe_stats_zero
from megatron_tpu.ops.normalization import norm_forward, rmsnorm
from megatron_tpu.ops.pallas import masks
from megatron_tpu.ops.rotary import apply_rotary_emb
from megatron_tpu.ops.ssm import mixer_through_store, ssm_mixer
from megatron_tpu.ops.weight_quant import deq

Sharder = Callable[[jnp.ndarray, str], jnp.ndarray]


def _identity_sharder(x: jnp.ndarray, role: str) -> jnp.ndarray:
    return x


def _norm(cfg: ModelConfig, p: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
    return norm_forward(cfg.normalization, x, p["scale"], p.get("bias"),
                        cfg.layernorm_epsilon)


def _dropout(x: jnp.ndarray, rate, key: Optional[jax.Array]) -> jnp.ndarray:
    if key is None:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    # rate may be a traced fp32 scalar (LIMA per-layer ramp): keep the
    # rescale in x's dtype or bf16 activations silently promote to fp32
    inv = jnp.asarray(1.0 / (1.0 - rate), x.dtype)
    return jnp.where(keep, x * inv, jnp.zeros_like(x))


def attention_block(
    cfg: ModelConfig,
    p: Dict[str, Any],  # layers/attn subtree, unstacked
    x: jnp.ndarray,     # [B, S, h] (already normed)
    rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]],
    positions: Optional[jnp.ndarray],
    attn_dropout_key: Optional[jax.Array] = None,
    kv_cache=None,      # ops/kv_store.py store, all layers stacked
    layer=None,         # this layer's index into the store
    cache_index=None,
    padding_mask: Optional[jnp.ndarray] = None,  # [B, S] True = attend
    page_table: Optional[jnp.ndarray] = None,    # [B, max_pages] int32
    page_write_start: Optional[jnp.ndarray] = None,  # scalar int32
    page_write_end: Optional[jnp.ndarray] = None,    # scalar int32
    tp_comm=None,  # quant.TpComm: explicit/compressed TP collectives
    cp_comm=None,  # quant.CpComm: context-parallel ring transport
    kind: Optional[AttentionKind] = None,
    state_valid: Optional[jnp.ndarray] = None,   # [B] int32
):
    """Returns (out [B,S,h], kv_cache with this layer's rows written).

    kind: this layer's attention kind, whose window is static at every
    kernel call (`rope` is that kind's table); None: the model's one kind.

    tp_comm (serving, quant/collectives.py): route the row-parallel
    output projection through an explicit shard_map collective — dense
    psum or the compressed (int8/fp8) two-step — instead of GSPMD's
    inserted all-reduce. None = the GSPMD path, unchanged.

    kv_cache is the whole stacked store (ops/kv_store.py owns its
    format): this layer writes its new K/V rows into it in place at
    cache_index and attends what the store then holds. A vector
    cache_index is the continuous-batching cache (every row at its own
    depth: s == 1 plain decode, s > 1 the speculative verify pass, row
    b's queries at cache_index[b]..cache_index[b]+s-1); a scalar is a
    prefill, one chunk of one prompt, or one-shot generation's step.

    state_valid (block_forward's: the positions of each row that are
    real; a serving step's, else None): with a vector cache_index, a row
    with none, a slot that does not decode, reaches the decode kernel
    with the length at which its loop is empty
    (masks.decode_idle_length), so it copies no page and computes no
    block. Its K/V are still written (on the scratch page: its table
    holds no other) and its output, which nobody reads, is zeros from the
    kernel and a finite mean from the dense path. None: every row
    attends its cache_index + 1 positions.

    page_table: the store is a pool of pages shared by every row
    (inference/paging/). page_write_start / page_write_end (chunked
    prefill only) fence the chunk's writes: kv_store.write says why."""
    b, s, _ = x.shape
    D = cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.n_kv_heads
    window = (kind or cfg.attention_kind).sliding_window_size
    cca = cfg.attention_form == "cca"
    if cca and kv_cache is not None:
        raise NotImplementedError(
            "attention_form='cca' through a KV cache (serving, incremental "
            "decoding): a sequence's store would hold, beside its keys and "
            "values, the last positions the convolutions and the value "
            "shift read; the form trains and is not served")

    # The scopes inside a region name its parts for a device trace (docs/
    # observability.md "Runtime traces"): projections, rotary, everything
    # around the kernel, the out projection with what GSPMD hangs on it.
    with jax.named_scope("attn_qkv"):
        q = maybe_fp8_matmul(cfg, x, deq(p["wq"], x.dtype))
        k = maybe_fp8_matmul(cfg, x, deq(p["wk"], x.dtype))
        v = maybe_fp8_matmul(cfg, x, deq(p["wv"], x.dtype))
        if "bq" in p:
            q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
        if cfg.qk_norm:
            # over all heads at once: under TP the mean square crosses
            # shards (GSPMD reduces it); the cache below holds normed,
            # rotated keys
            q = rmsnorm(q, p["q_norm"]["scale"], cfg.layernorm_epsilon)
            k = rmsnorm(k, p["k_norm"]["scale"], cfg.layernorm_epsilon)
        if b * s < x.shape[-1]:
            # Fewer rows than the weights have (a decode tick, a prefill
            # chunk): keep the products apart from the split into heads.
            # Folded into the product, the split yields q head-major and
            # the chip's compiler pays for that with the WEIGHT, sliced
            # out of its stack and copied transposed every layer of every
            # call (h * n elements against the result's rows * n). Behind
            # the barrier each product reads the stack in place, as `wo`'s
            # does, and what is re-laid is the result. With h rows or more
            # (training) the fold is the right trade and stays.
            q, k, v = jax.lax.optimization_barrier((q, k, v))
        q = q.reshape(b, s, nq, D)
        k = k.reshape(b, s, nkv, D)
        if not cca:
            v = v.reshape(b, s, nkv, D)

    if cca:
        with jax.named_scope("cca_mix"):
            q, k, v = cca_mix(cfg, p, q, k, v)

    if rope is not None:
        with jax.named_scope("attn_rope"):
            q, k = apply_rotary_emb(q, k, rope[0], rope[1], positions,
                                    rotary_dim=cfg.rotary_dim)

    # CP prefill (VERDICT r4 #6): when the whole prompt enters at once
    # (cache_index is a STATIC 0 — the prefill call site passes a Python
    # int), attention over the pass's own K/V equals attention over the
    # cache (causality makes the unwritten tail unreachable), and with
    # q_len == kv_len the ring/Ulysses context-parallel path engages —
    # prefill cost shards over the context axis. The cache still gets
    # written for the decode steps that follow; decode (q_len == 1) runs
    # against the full cache on the dense path, where GSPMD shards the
    # [.., 1, S] score row over a context-sharded cache (flash-decoding
    # by partitioner).
    # Not over pages (paged serving replaces it with the ring path below)
    # and not with an int8 store: attending the fresh bf16 k/v would
    # diverge from the dequantized-cache numerics the int8 tests pin down.
    cp_prefill = (type(cache_index) is int and cache_index == 0 and s > 1
                  and cfg.attention_impl in ("ring", "ulysses")
                  and page_table is None
                  and not (kv_cache is not None
                           and kv_store.is_int8(kv_cache)))

    per_slot = getattr(cache_index, "ndim", 0) == 1
    # a 3-D page table ([cp, rows, pages_per_rank], sharded over the
    # "context" mesh axis) selects the context-parallel paged path: the
    # KV pools are sequence-striped and attention runs as a ring over
    # per-rank partials (inference/context_parallel/ring_kv.py)
    cp_paged = page_table is not None and page_table.ndim == 3
    if page_table is not None and kv_cache is None:
        raise ValueError("page_table requires a (paged) kv_cache")

    with jax.named_scope("attn_core"):
        q_offset = 0
        kv_lengths = None
        table = None
        ctx = None
        if cp_paged:
            if cp_comm is None:
                raise ValueError(
                    "a [cp, rows, pages] page table requires cp_comm "
                    "(quant/collectives.make_cp_comm)")
            if kv_store.is_int8(kv_cache):
                raise ValueError(
                    "context-parallel paged serving does not support int8 "
                    "KV pools (stripe the bf16 pools instead)")
            from megatron_tpu.inference.context_parallel.ring_kv import (
                paged_ring_attention,
            )

            ctx, kv_cache = paged_ring_attention(
                cp_comm, q, k, v, kv_cache, layer, page_table, cache_index,
                per_slot, page_write_start, page_write_end,
                sliding_window=window)
        elif kv_cache is not None:
            kv_cache = kv_store.write(kv_cache, layer, k, v, cache_index,
                                      page_table, page_write_start,
                                      page_write_end)
            if not cp_prefill:
                k, v, table = kv_store.read(kv_cache, layer, page_table,
                                            cfg.dtype)
                if per_slot:
                    kv_lengths = cache_index + 1
                    if state_valid is not None:
                        kv_lengths = jnp.where(
                            state_valid > 0, kv_lengths,
                            masks.decode_idle_length(s))
                else:
                    q_offset = cache_index

        if cfg.attn_mask_type == "padding" and padding_mask is None:
            raise ValueError(
                "attn_mask_type='padding' requires an attention_mask input — "
                "running without one would silently attend to pad tokens")
        if ctx is None:
            ctx = attention(
                q, k, v,
                mask_type=("bidirectional" if cfg.attn_mask_type == "padding"
                           else cfg.attn_mask_type),
                padding_mask=padding_mask,
                sliding_window=window,
                dropout=(cfg.attention_dropout
                         if attn_dropout_key is not None else 0.0),
                dropout_rng=attn_dropout_key,
                q_offset=q_offset,
                impl=cfg.attention_impl,
                softmax_fp32=cfg.softmax_fp32,
                kv_lengths=kv_lengths,
                page_table=table,
                kv_end=page_write_end,
            )
    with jax.named_scope("attn_out"):
        if tp_comm is not None and "attn_out" in tp_comm.sites:
            # explicit row-parallel reduction (dense psum or the compressed
            # quantize->all_to_all->reduce->all_gather; quant/collectives.py)
            from megatron_tpu.quant.collectives import row_parallel_matmul

            out = row_parallel_matmul(ctx.reshape(b, s, nq * D),
                                      deq(p["wo"], ctx.dtype), tp_comm,
                                      "attn_out")
        else:
            out = maybe_fp8_matmul(cfg, ctx.reshape(b, s, nq * D),
                                   deq(p["wo"], ctx.dtype))
        if "bo" in p:
            out = out + p["bo"]
    return out, kv_cache


def mlp_block(cfg: ModelConfig, p: Dict[str, Any], x: jnp.ndarray,
              tp_comm=None) -> jnp.ndarray:
    with jax.named_scope("mlp_in"):
        h = maybe_fp8_matmul(cfg, x, deq(p["w_in"], x.dtype))
        if "b_in" in p:
            h = h + p["b_in"]
    with jax.named_scope("mlp_act"):
        h = apply_activation(cfg.activation, h)
    with jax.named_scope("mlp_out"):
        if tp_comm is not None and "mlp_out" in tp_comm.sites:
            from megatron_tpu.quant.collectives import row_parallel_matmul

            out = row_parallel_matmul(h, deq(p["w_out"], h.dtype), tp_comm,
                                      "mlp_out")
        else:
            out = maybe_fp8_matmul(cfg, h, deq(p["w_out"], h.dtype))
        if "b_out" in p:
            out = out + p["b_out"]
    return out


def _no_moe_aux(cfg: ModelConfig) -> jnp.ndarray:
    """What a layer without experts hands up as moe_aux: a zero scalar,
    or, in a stack that has expert layers too, their statistics' zero."""
    if cfg.num_experts is None:
        return jnp.zeros((), jnp.float32)
    return moe_stats_zero(cfg)


def _ffn(cfg: ModelConfig, lp: Dict[str, Any], x: jnp.ndarray,
         tp_comm=None, grad_sink=None, layer=None, expert_stacks=None,
         rows_read=None, router=None):
    """Dense MLP or MoE, by what the layer holds (`lp`; by config where a
    layer holds an FFN whatever its type). Returns (out, moe_aux,
    grad_sink, router): moe_aux a zero fp32 scalar for a dense layer, [aux
    loss, load statistic] for an MoE one; grad_sink as block_forward has
    it (`layer` the layer's index into its stacks, and into expert_stacks:
    block_forward's); router as block_forward has it, this layer's part
    written. rows_read: block_forward's state_valid, for the experts
    (ops/moe.py moe_block); a dense MLP computes every row."""
    if "moe" not in lp:
        return (mlp_block(cfg, lp["mlp"], x, tp_comm=tp_comm),
                _no_moe_aux(cfg), grad_sink, router)
    carried = {} if router is None else {"router": (router, layer)}
    if grad_sink is not None:
        out, aux, load, stacks, *router = moe_block(
            cfg, lp["moe"], x, (grad_sink["moe"], layer), **carried)
        grad_sink = {**grad_sink, "moe": stacks}
    else:
        out, aux, load, *router = moe_block(
            cfg, lp["moe"], x, of_layer=None if expert_stacks is None
            else (*expert_stacks, layer), rows_read=rows_read, **carried)
    return (out, layer_stats(aux, load), grad_sink,
            router[0] if router else None)


def _residual_add(cfg: ModelConfig, lp: Dict[str, Any], which: str,
                  x: jnp.ndarray, out: jnp.ndarray) -> jnp.ndarray:
    """x + out, or with cfg.residual_scale (s_x * x + b_x) + (s_o * out +
    b_o) by the sub-layer's four vectors lp[which] (`res1`: the mixer's
    add, `res2`: the FFN's)."""
    if not cfg.residual_scale:
        return x + out
    with jax.named_scope("residual_scale"):
        s = lp[which]
        return (s["x_scale"] * x + s["x_bias"]) + (
            s["out_scale"] * out + s["out_bias"])


def _mixer(cfg: ModelConfig, lp: Dict[str, Any], normed: jnp.ndarray,
           layer_type: str, type_layer, rope, positions, attn_dropout_key,
           kv_cache, ssm_state, state_row, state_valid, kind, **attn_args):
    """A layer's sequence mixer over its normed input -> (out, kv_cache,
    ssm_state): a state-space mixer reads and writes its row of
    `ssm_state` and leaves kv_cache alone, attention the other way round
    (block_forward's docstring)."""
    if layer_type in SSM_TYPES:
        if ssm_state is None:
            out, _ = ssm_mixer(cfg, lp["ssm"], normed, None, state_valid)
        else:
            out, ssm_state = mixer_through_store(
                cfg, lp["ssm"], normed, ssm_state, type_layer, state_row,
                state_valid)
        return out, kv_cache, ssm_state
    out, kv_cache = attention_block(
        cfg, lp["attn"], normed, rope, positions,
        attn_dropout_key=attn_dropout_key,
        kv_cache=kv_cache, layer=type_layer, kind=kind,
        state_valid=state_valid, **attn_args)
    return out, kv_cache, ssm_state


def block_forward(
    cfg: ModelConfig,
    lp: Dict[str, Any],  # one layer's params (unstacked)
    x: jnp.ndarray,      # [B, S, h]
    rope: Optional[Tuple[jnp.ndarray, jnp.ndarray]],
    positions: Optional[jnp.ndarray] = None,
    dropout_key: Optional[jax.Array] = None,
    hidden_dropout_rate=None,
    kv_cache=None,      # ops/kv_store.py store, all layers stacked
    layer=None,         # this layer's index into the store
    cache_index=None,
    sharder: Sharder = _identity_sharder,
    padding_mask: Optional[jnp.ndarray] = None,
    page_table: Optional[jnp.ndarray] = None,  # [B, max_pages] int32
    page_write_start: Optional[jnp.ndarray] = None,
    page_write_end: Optional[jnp.ndarray] = None,
    tp_comm=None,
    cp_comm=None,
    grad_sink=None,
    kind: Optional[AttentionKind] = None,
    layer_type: str = "attention",
    type_layer=None,    # this layer's ordinal among the layers of its type
    ssm_state=None,     # ops/ssm.py state store, all state-space layers
    state_row=None,
    state_valid: Optional[jnp.ndarray] = None,
    expert_stacks=None,  # (w_in, w_out) of all the expert layers, stacked
    router=None,         # ops/moe.py router_carry's dict
):
    """One decoder layer -> (y, kv_cache, moe_aux, grad_sink, ssm_state),
    and where `router` is given that behind them: kv_cache is the whole
    store with this layer's rows written (attention_block).

    router: what the expert layers of some models carry from one to the
    next (ops/moe.py router_carry: the "mlp" router's state, the layers'
    loads), with this layer's part written on the way out.

    What a layer is follows from the stack (ModelConfig.layer_pattern). A
    sequence mixer AND a feed-forward block, each behind its norm and
    with its residual add: `x += mixer(ln1(x)); x += ffn(ln2(x))` (every
    stack whose pattern names mixers alone, or none). Or ONE block alone
    behind one norm and one add, `x += block(ln1(x))`: a mixer under the
    region `attention`, or a feed-forward block (layer_type "mlp", "moe")
    under the region `mlp`, where the pattern names a feed-forward type
    (cfg.single_block_layers: `lp` then holds `ln1` and the block's own
    leaves, no `ln2`).

    layer_type (config.LAYER_TYPES): what the layer's block, or its
    mixer, is. In a stack of several types a type's stores hold ITS
    layers, and `type_layer` indexes them (None: `layer`, every layer of
    one type). A state-space layer ("mamba", "mamba2") runs ops/ssm.py's
    mixer under the region `attention` (the layer's sequence mixer: a
    trace's regions are a fixed set) and
    leaves kv_cache alone; it reads and writes its state in `ssm_state`
    (None: the sequence starts here and its state is dropped, as in
    training): of every row, the batch the store's rows in order, or of
    `state_row` alone (one row's prefill chunk); state_valid [B]: the
    positions that are real (ops/ssm.py). An expert layer takes
    state_valid too: the positions that are not real reach no expert
    (ops/moe.py moe_block `rows_read`). So does an attention layer of a
    decode step: a row without a real position visits no block of its
    cache (attention_block).

    kind: this layer's attention kind (attention_block), with `rope` that
    kind's table. In a stack of several kinds the region `attention`
    holds the layer under the scope `attn_<kind's name>`.

    grad_sink: float32 accumulators of the gradients of some of the
    stacked layers' leaves, in a tree shaped like the layers' params
    (today {"moe": {"w_in", "w_out"}}: ops/moe.py moe_block), handed
    through for the cotangents to ride the backward pass; `layer` is this
    layer's index into them as into the store.

    expert_stacks: the expert layers' two stacked matrices, of which
    `type_layer` is this layer's, from a serving step of a stack of one
    block a layer (ops/moe.py moe_block's `of_layer`: the kernels read
    the layer's matrices in place).

    hidden_dropout_rate may be a traced scalar (LIMA per-layer ramp, ref
    transformer.py:994-1001). moe_aux is a zero scalar for dense models
    and [aux loss, load statistic] for MoE ones (ops/moe.py
    layer_stats)."""
    if dropout_key is not None:
        k_attn_drop, k_hidden1, k_hidden2 = jax.random.split(dropout_key, 3)
    else:
        k_attn_drop = k_hidden1 = k_hidden2 = None
    rate = cfg.hidden_dropout if hidden_dropout_rate is None else hidden_dropout_rate

    if type_layer is None:
        type_layer = layer

    def mixer(normed):
        return _mixer(
            cfg, lp, normed, layer_type, type_layer, rope, positions,
            k_attn_drop if cfg.attention_dropout > 0 else None,
            kv_cache, ssm_state, state_row, state_valid, kind,
            cache_index=cache_index, padding_mask=padding_mask,
            page_table=page_table, page_write_start=page_write_start,
            page_write_end=page_write_end, tp_comm=tp_comm, cp_comm=cp_comm)

    if cfg.single_block_layers:
        # one norm, one block, one residual add, under the region of what
        # the block is: `mlp` for a feed-forward block alone, `attention`
        # for a mixer alone
        ffn = layer_type in FFN_TYPES
        part = "mlp" if ffn else "attn"
        with jax.named_scope("mlp" if ffn else "attention"):
            with jax.named_scope(f"{part}_norm"):
                normed = _norm(cfg, lp["ln1"], x)
            if ffn:
                out, moe_aux, grad_sink, _ = _ffn(
                    cfg, lp, normed, tp_comm, grad_sink, type_layer,
                    expert_stacks, state_valid)
            else:
                out, kv_cache, ssm_state = mixer(normed)
                moe_aux = _no_moe_aux(cfg)
            with jax.named_scope(f"{part}_out"):
                out = _dropout(out, rate,
                               k_hidden1 if cfg.hidden_dropout > 0 else None)
                y = sharder(x + out, "residual")
        return y, kv_cache, moe_aux, grad_sink, ssm_state

    # The two named scopes are the regions a device trace is read by
    # (docs/observability.md "Runtime traces"): every operation of a layer,
    # forward, backward or recomputed, carries "attention" or "mlp" in its
    # name stack, whichever jaxpr wrapper XLA names it after. The scopes
    # inside them (`attn_norm` ... `attn_out`, `mlp_norm` ... `mlp_out`;
    # attention_block and mlp_block hold the middle ones) say which part
    # of its region an operation belongs to, and move no operation from
    # one region to another.
    mixed = kind is not None and len(set(cfg.attention_period)) > 1
    with jax.named_scope("attention"), (
            jax.named_scope(f"attn_{kind.name}") if mixed
            else contextlib.nullcontext()):
        # post-LN (ref --use_post_ln): no pre-norm; the layer ends with its
        # own LN, reusing the ln1 parameter slot as the output norm
        with jax.named_scope("attn_norm"):
            normed = x if cfg.use_post_ln else _norm(cfg, lp["ln1"], x)
        attn_out, kv_cache, ssm_state = mixer(normed)
        with jax.named_scope("attn_out"):
            attn_out = _dropout(attn_out, rate, k_hidden1 if cfg.hidden_dropout > 0 else None)
            if not cfg.parallel_attn:
                # residual from the LN output with --apply_residual_
                # connection_post_layernorm (ref transformer.py:795-799)
                res1 = normed if cfg.apply_residual_post_ln else x
                y = sharder(_residual_add(cfg, lp, "res1", res1, attn_out),
                            "residual")

    with jax.named_scope("mlp"):
        if cfg.parallel_attn:
            # Falcon: mlp input is ln1(x) (7B) or a dedicated ln_mlp(x)
            # (40B); one residual add for both branches.
            with jax.named_scope("mlp_norm"):
                mlp_in = _norm(cfg, lp["ln_mlp"], x) if cfg.parallel_layernorm else normed
            mlp_out, moe_aux, grad_sink, router = _ffn(
                cfg, lp, mlp_in, tp_comm, grad_sink, layer,
                rows_read=state_valid, router=router)
            with jax.named_scope("mlp_out"):
                mlp_out = _dropout(mlp_out, rate, k_hidden2 if cfg.hidden_dropout > 0 else None)
                res = normed if cfg.apply_residual_post_ln else x
                y = res + attn_out + mlp_out
        else:
            with jax.named_scope("mlp_norm"):
                normed2 = _norm(cfg, lp["ln2"], y)
            mlp_out, moe_aux, grad_sink, router = _ffn(
                cfg, lp, normed2, tp_comm, grad_sink, layer,
                rows_read=state_valid, router=router)
            with jax.named_scope("mlp_out"):
                mlp_out = _dropout(mlp_out, rate, k_hidden2 if cfg.hidden_dropout > 0 else None)
                res2 = normed2 if cfg.apply_residual_post_ln else y
                y = _residual_add(cfg, lp, "res2", res2, mlp_out)
                if cfg.use_post_ln:
                    y = _norm(cfg, lp["ln1"], y)
    y = sharder(y, "residual")
    if router is not None:
        return y, kv_cache, moe_aux, grad_sink, ssm_state, router
    return y, kv_cache, moe_aux, grad_sink, ssm_state
