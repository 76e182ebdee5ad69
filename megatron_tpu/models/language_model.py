"""Full language model: embedding -> scanned decoder stack -> logits/loss.

Equivalent of megatron/model/language_model.py (TransformerLanguageModel,
Embedding, parallel_lm_logits) + megatron/model/gpt_model.py
(post_language_model_processing). Differences by design:

  * The layer stack is a lax.scan over stacked params — compile time does
    not grow with depth, and activation recompute is one jax.checkpoint
    policy on the scan body instead of the reference's
    distribute_saved_activations machinery
    (megatron/core/tensor_parallel/random.py:196-248,
    transformer.py:1110-1176).
  * Vocab-parallel logits + cross-entropy are plain expressions; sharding
    specs make them "parallel" (ref: language_model.py:24-53
    parallel_lm_logits, cross_entropy.py). The chunked training loss is
    one function with its own gradient rule and, under a mesh, its own
    collectives (ops/cross_entropy.py chunked_head_loss).
"""

from __future__ import annotations

import math
import operator
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.transformer import Sharder, _dropout, _identity_sharder, block_forward
from megatron_tpu.ops import kv_store
from megatron_tpu.ops.cross_entropy import (
    chunked_head_loss, cross_entropy_loss,
)
from megatron_tpu.ops.moe import (
    EXPERT_LOAD, HELD_METRIC, LOAD_METRIC, MOVED_METRIC, SAVED_PRODUCT,
    expert_grad_sinks, merge_layer_stats, moe_stats_zero, router_carry,
)
from megatron_tpu.ops.pallas.flash_template import SAVED_RESIDUAL
from megatron_tpu.ops.weight_quant import deq, take_rows
from megatron_tpu.ops.normalization import norm_forward
from megatron_tpu.ops.rotary import SAVED_ROTATED, rope_table


def parse_recompute(recompute: str):
    """(granularity, n) for the reference's --recompute_method +
    --recompute_num_layers pair (transformer.py:1110-1172):

    * "block:N"   — fully recompute the first N layers of the stack (or
      of each pipeline chunk), save the rest ("fully use the device
      memory removing redundant re-computation").
    * "uniform:N" — checkpoint chunk BOUNDARIES every N layers: the scan
      runs as outer-chunks x inner-layers with BOTH levels rematted,
      storing L/N + N residual-stream carries instead of L (sqrt-remat at
      N ~ sqrt(L); "full" is uniform:1) at the cost of recomputing each
      layer twice. The carry saving pays at depth/batch scale — at toy
      test geometries other transients dominate the measurement.

    Everything else is a per-layer policy name, n None."""
    for prefix in ("block", "uniform"):
        if recompute and recompute.startswith(prefix + ":"):
            n = int(recompute.split(":", 1)[1])
            if n <= 0 and prefix == "uniform":
                raise ValueError(f"uniform chunk must be >= 1 ({n})")
            if n < 0:
                raise ValueError(f"recompute layer count must be >= 0 ({n})")
            return prefix, n
    return recompute, None


def is_full_remat_family(recompute: str) -> bool:
    """full / block:N / uniform:N — the memory-pressure policies whose
    pipeline tick scans should also be segment-rematted (there the live
    tick carries dominate, and a user choosing aggressive recompute must
    not silently get MORE live memory than plain 'full' would)."""
    gran, _ = parse_recompute(recompute)
    return gran in ("full", "block", "uniform")


def _remat_policy(recompute: str):
    if recompute == "none":
        return None
    if recompute in ("full", "block"):
        # block applies full remat to its rematted slice
        return jax.checkpoint_policies.nothing_saveable
    if recompute == "selective":
        # save weight-matmul outputs and recompute what is cheap beside
        # them: norms, activations, the layout changes, and the
        # dense core attention, whose S x S scores are a layer's largest
        # activation (the reference's selective recompute,
        # transformer.py:391-410). The flash kernel keeps no scores, so
        # its forward is not run again: its output and its log-sum-exp
        # (one hidden-state-sized tensor and S floats a head) are saved
        # by name, as are the dropless experts' grouped products — Pallas
        # calls' results both, which the policy does not know for dots —
        # and the rotated q and k: the backward needs them and not the
        # projections' own results, which then are not kept (without a
        # QK-norm, whose backward reads them), so rotary is not applied
        # a third time and its half turn, a dot, is not kept either
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                SAVED_PRODUCT, SAVED_RESIDUAL, SAVED_ROTATED))
    raise ValueError(f"unknown recompute policy {recompute!r}")


def scan_with_remat(bodies, carry, xs, recompute: str, types=None):
    """The loop of every layer stack (the LM's, a pipeline chunk's, T5's
    encoder and decoder slices) under the remat policy; returns the carry.

    The stack is len(bodies) layers a period, over and over (layers all
    alike: a period of one). `bodies[i]`, a scan body, runs the i-th layer
    of a period, what is static in its kind (the window at the kernel
    calls, the rotary table) closed over. A trip runs one period's layers,
    each under the policy on its own, on slices of a [L / period, period,
    ...] view of the stacked `xs` (no view, no index where the period is
    one). "block:N" / "uniform:N" count layers, N a multiple of the period.

    types: the layer TYPE of each layer of the period, where types differ
    (ModelConfig.layer_pattern). `xs` is then (common, {type: tree}): a
    type's tree is stacked over THAT type's layers alone, and bodies[i]
    is handed (common's slice for its layer, its type's tree's slice,
    the layer's index among ALL the stack's layers of its type).

    A stack of one trip is a call, not a loop: XLA inlines such a loop
    sooner or later, and how soon decides what its first CSE still sees.
    A call of ONE layer is checkpointed with prevent_cse=False, so that XLA
    merges the recomputation with the forward beside it (inlined late, a
    one-layer model ran its flash forward twice: 28 ms a step in the
    benchmark's OLMoE cell, PERF.md PR 33; with True 1.4 % of its rate,
    PR 45). A call of SEVERAL layers sets it: merged, every activation of
    the period is kept after all (the Mellum cell does not fit: PR 42).

    Every form runs under the scope `layer_stack`: the loop's own work
    (slicing the stacked weights, stacking what the backward pass saved)
    is under no region of a layer, and a device trace finds it by this
    name (docs/observability.md "Runtime traces")."""
    period = len(bodies)
    gran, n = parse_recompute(recompute)
    if n is not None and n % period:
        raise ValueError(
            f"recompute {recompute!r} counts layers of a stack of {period} "
            "layers a period: the count must be a multiple of the period")
    if n is not None and types is not None:
        raise NotImplementedError(
            f"recompute {recompute!r} slices the stack by layers; a stack "
            "of several layer types takes none, selective or full")
    length = jax.tree.leaves(xs)[0].shape[0]

    def loop(bodies, carry, xs, policy):
        layers = len(bodies)  # a trip
        trips = jax.tree.leaves(xs)[0].shape[0] // layers
        if policy is not None:
            bodies = [jax.checkpoint(body, policy=policy,
                                     prevent_cse=trips == 1 and layers > 1)
                      for body in bodies]
        if layers == 1:
            trip, scanned = bodies[0], xs
        else:
            # where a trip finds its layers' slices. Layers of one type:
            # in its own slice of the [trips, period, ...] view, which the
            # scan hands it (and stacks the gradients of). Several types,
            # each type's tree stacked over ITS layers alone: in the whole
            # stacks, by the layer's index among the layers of its type; a
            # trip's slice [period, ...] of those, scanned, is a copy of
            # the period's weights every trip (1.2 GB of one leaf in a
            # decode step of the benchmark's typed configuration)
            if types is None:
                scanned = _chunked(xs, layers)

                def slices(view, i):
                    return jax.tree.map(lambda a: a[i], view)
            else:
                scanned = jnp.arange(trips)
                ordinal = [types[:i].count(t) for i, t in enumerate(types)]
                common, typed = xs

                def slices(t, i):
                    of_type = t * types.count(types[i]) + ordinal[i]
                    return (jax.tree.map(lambda a: a[t * layers + i], common),
                            jax.tree.map(lambda a: a[of_type],
                                         typed[types[i]]), of_type)

            def trip(carry, at):
                for i, body in enumerate(bodies):
                    carry, _ = body(carry, slices(at, i))
                return carry, None

        if trips == 1:
            return trip(carry, jax.tree.map(lambda a: a[0], scanned))[0]
        return jax.lax.scan(trip, carry, scanned)[0]

    with jax.named_scope("layer_stack"):
        if gran == "block":
            n = min(n, length)
            sl = lambda lo, hi: jax.tree.map(lambda a: a[lo:hi], xs)
            if n > 0:
                carry = loop(bodies, carry, sl(0, n), _remat_policy("block"))
            if n < length:
                carry = loop(bodies, carry, sl(n, length), None)
            return carry
        if gran == "uniform":
            gran = "full"  # uniform:1 == per-layer full remat
        policy = _remat_policy(gran)
        if n is None or n == 1:
            return loop(bodies, carry, xs, policy)
        if length % n:
            raise ValueError(
                f"uniform:{n} needs the layer count ({length}) divisible "
                "by the chunk size (per pipeline chunk when pp > 1)")

        # BOTH levels rematted (classic sqrt-remat): the outer backward
        # stores L/N chunk carries; replaying a chunk stores N per-layer
        # carries because the layers inside are themselves rematted:
        # without that each replayed chunk would save N full layers'
        # internals and chunking would COST memory (measured 254 MB at
        # uniform:2 vs 101 MB plain full)
        def chunk(carry, chunk_xs):
            return loop(bodies, carry, chunk_xs, policy), None

        return loop([chunk], carry, _chunked(xs, n), policy)


def _chunked(xs, n: int):
    """The stacked xs [L, ...] seen as [L / n, n, ...]."""
    return jax.tree.map(
        lambda a: a.reshape((a.shape[0] // n, n) + a.shape[1:]), xs)


def _layer_dropout_rates(cfg: ModelConfig) -> jnp.ndarray:
    """Per-layer hidden-dropout rates; LIMA ramps linearly from 0 at the
    first layer to hidden_dropout at the last (ref transformer.py:994-1001)."""
    L = cfg.num_layers
    if cfg.lima_dropout and L > 1:
        return cfg.hidden_dropout * jnp.arange(L, dtype=jnp.float32) / (L - 1)
    return jnp.full((L,), cfg.hidden_dropout, dtype=jnp.float32)


def rope_tables(cfg: ModelConfig, kinds, length: int) -> Dict[Any, Any]:
    """One rotary table a kind of attention layer, by kind (None for a
    model without rotary embeddings)."""
    rotary = cfg.position_embedding_type == "rotary"
    return {kind: (rope_table(kind, cfg.head_dim, length,
                              rotary_dim=cfg.rotary_dim) if rotary else None)
            for kind in dict.fromkeys(kinds)}


# where a layer type's own leaves stand in the stacked layers' tree
# (models/params.py), stacked over THAT type's layers alone
TYPE_LEAVES = {"attention": "attn", "mamba": "ssm", "mamba2": "ssm",
               "mlp": "mlp", "moe": "moe"}


def run_layers(
    cfg: ModelConfig,
    layers: Dict[str, Any],   # stacked [n, ...]: the whole stack, or a slice
    carry,                    # (x, moe_aux, kv store, gradient sinks,
                              #  state-space store[, router carry])
    ropes: Dict[Any, Any],    # rope_tables
    positions: Optional[jnp.ndarray],
    first_layer=0,            # the slice's first layer in the whole network
    dropout_key: Optional[jax.Array] = None,
    recompute: str = "none",
    **layer_args,
):
    """What a layer of the stack is, for the whole model (lm_forward), a
    pipeline chunk (training/pipeline.py) and a decoding stage
    (inference/pipelined.py): `layers` through scan_with_remat, their
    kinds those of `cfg.attention_period` in order; returns the carry.

    carry: x [B, S, h]; the layers' router statistics merged so far (from
    ops/moe.py moe_stats_zero on; a dense layer adds its zero scalar to
    whatever zero the caller starts from); the KV store and the gradient
    sinks (lm_forward's kv_caches and grad_sink), or None; the
    state-space layers' state store (lm_forward's ssm_state), or None;
    and, of a model whose expert layers hand something from one to the
    next (ops/moe.py router_carry: the "mlp" router's state [B, S, R], an
    activation the backward pass goes through under every remat policy,
    and the layers' loads), that dict as a sixth element, which comes
    back as one.
    Those ride in the carry so that the layers write the donated stores,
    and the kernels the sinks' cotangents, in place: as a scanned input
    and output each would be a second copy, built layer by layer every
    call.

    A stack of several layer TYPES (cfg.layer_pattern) holds each type's
    leaves (TYPE_LEAVES: `layers["ssm"]`, `layers["attn"]`, and where a
    layer is one block alone `layers["moe"]`, `layers["mlp"]`) stacked
    over that type's layers, and the stores likewise: a layer indexes
    them by its ordinal among the layers of its type. `layers` is then
    the whole stack.

    LIMA's dropout rate and the dropout key go by a layer's index in the
    whole network, first_layer (traced or not) + its index in `layers`;
    the store and the sinks, stacked like `layers`, by the latter.
    layer_args go to every layer alike (block_forward's)."""
    # (a typed stack's first leaf may be of a type with fewer layers)
    n = jax.tree.leaves(layers["ln1"] if cfg.layer_pattern
                        else layers)[0].shape[0]
    rates = _layer_dropout_rates(cfg)
    if n != cfg.num_layers:
        rates = jax.lax.dynamic_slice_in_dim(rates, first_layer, n)
    add_aux = (operator.add if cfg.num_experts is None
               else merge_layer_stats)

    def body(carry, scanned, kind, layer_type="attention", leaves=None):
        x, aux, caches, sinks, state, *router = carry
        of_type = {"router": router[0]} if router else {}
        if leaves is None:
            (lp, rate, idx), type_layer = scanned, None
        else:
            # a typed stack: this type's own leaves, under their name in
            # `layers`, and the layer's index among the layers of its type
            (lp, rate, idx), typed, type_layer = scanned
            lp = {**lp, leaves: typed}
            if leaves == "moe" and caches is not None:
                # a serving step (nothing differentiates through a store):
                # the experts' kernels read the layer's matrices where
                # they lie in the stacks (ops/moe.py moe_block `of_layer`)
                of_type = {"expert_stacks": (layers["moe"]["w_in"],
                                             layers["moe"]["w_out"])}
        key = (None if dropout_key is None
               else jax.random.fold_in(dropout_key, first_layer + idx))
        y, caches, moe_aux, sinks, state, *router = block_forward(
            cfg, lp, x, ropes[kind], positions,
            dropout_key=key,
            hidden_dropout_rate=rate,
            kv_cache=caches,
            layer=idx,
            grad_sink=sinks,
            kind=kind,
            layer_type=layer_type,
            type_layer=type_layer,
            ssm_state=state,
            **of_type,
            **layer_args,
        )
        return (y, add_aux(aux, moe_aux), caches, sinks, state, *router), None

    if cfg.layer_pattern is None:
        # the kinds in their published order, each layer's window static
        return scan_with_remat(
            [partial(body, kind=kind) for kind in cfg.attention_period],
            carry, (layers, rates, jnp.arange(n)), recompute)
    if n != cfg.num_layers:
        raise NotImplementedError(
            "a slice of a stack of several layer types (a pipeline stage): "
            "a type's leaves are stacked over that type's layers alone")
    types = cfg.layer_period
    # the name of each type's own leaves in `layers`; what is left is
    # stacked over all the layers (the norms; where every layer holds an
    # FFN beside its mixer, that FFN)
    names = {t: TYPE_LEAVES[t] for t in dict.fromkeys(types)}
    common = {k: v for k, v in layers.items() if k not in names.values()}
    return scan_with_remat(
        [partial(body, kind=cfg.attention_kind, layer_type=t, leaves=names[t])
         for t in types],
        carry, ((common, rates, jnp.arange(n)),
                {t: layers[name] for t, name in names.items()}),
        recompute, types=types)


def embed_tokens(
    cfg: ModelConfig,
    params: Dict[str, Any],
    tokens: jnp.ndarray,                  # [B, S] int32
    positions: Optional[jnp.ndarray],
    dropout_key: Optional[jax.Array] = None,
    tokentype_ids: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Token (+ absolute position, + tokentype) embedding with embedding
    dropout (ref: language_model.py:133-262 Embedding)."""
    with jax.named_scope("embed"):
        x = take_rows(params["embed"]["tokens"], tokens, cfg.dtype)
        if cfg.position_embedding_type == "absolute":
            pos = positions if positions is not None else jnp.arange(tokens.shape[1])[None, :]
            x = x + jnp.take(params["embed"]["pos"], pos, axis=0)
        if tokentype_ids is not None:
            x = x + jnp.take(params["embed"]["tokentype"], tokentype_ids, axis=0)
        if cfg.hidden_dropout > 0 and dropout_key is not None:
            x = _dropout(x, cfg.hidden_dropout, dropout_key)
        return x


def final_hidden_norm(cfg: ModelConfig, params: Dict[str, Any],
                      x: jnp.ndarray) -> jnp.ndarray:
    """Final stack norm — identity under post-LN, where each layer ends
    with its own output norm (ref transformer.py:1278-1281)."""
    if cfg.use_post_ln:
        return x
    return norm_forward(cfg.normalization, x, params["final_ln"]["scale"],
                        params["final_ln"].get("bias"),
                        cfg.layernorm_epsilon)


def lm_logits(cfg: ModelConfig, params: Dict[str, Any], x: jnp.ndarray,
              tp_comm=None) -> jnp.ndarray:
    """Project hidden states to vocab logits, tied or untied
    (ref: parallel_lm_logits, language_model.py:24-53).

    tp_comm with the "logits" site enabled routes the vocab-parallel
    gather through the explicit (optionally compressed) all_gather
    (quant/collectives.py) instead of GSPMD's."""
    tied = cfg.tie_embed_logits
    w = deq(params["embed"]["tokens"] if tied else params["lm_head"]["w"],
            x.dtype)
    if tp_comm is not None and "logits" in tp_comm.sites:
        from megatron_tpu.quant.collectives import vocab_parallel_logits

        return vocab_parallel_logits(x, w, tp_comm, tied=tied)
    if tied:
        return jnp.einsum("bsh,vh->bsv", x, w)
    return jnp.einsum("bsh,hv->bsv", x, w)


def lm_forward(
    cfg: ModelConfig,
    params: Dict[str, Any],
    tokens: jnp.ndarray,
    positions: Optional[jnp.ndarray] = None,
    dropout_key: Optional[jax.Array] = None,
    recompute: str = "none",
    sharder: Sharder = _identity_sharder,
    kv_caches=None,   # an ops/kv_store.py store: slots, or pages
    cache_index=None,
    return_hidden: bool = False,
    return_moe_aux: bool = False,
    return_expert_load: bool = False,
    attention_mask: Optional[jnp.ndarray] = None,  # [B, S] True = attend
    tokentype_ids: Optional[jnp.ndarray] = None,   # [B, S] (BERT segments)
    page_table: Optional[jnp.ndarray] = None,      # [B, max_pages] int32
    page_write_start: Optional[jnp.ndarray] = None,
    page_write_end: Optional[jnp.ndarray] = None,
    tp_comm=None,  # quant.TpComm: explicit/compressed TP collectives
    cp_comm=None,  # quant.CpComm: context-parallel ring transport
    grad_sink=None,
    ssm_state=None,    # an ops/ssm.py state store (state-space layers)
    state_row=None,
    state_valid: Optional[jnp.ndarray] = None,   # [B] int32
):
    """Forward pass to logits.

    ssm_state: the state store of a model with state-space layers
    (ops/ssm.py `create_state`), beside kv_caches, which then holds the
    attention layers alone; returns (logits, updated_caches,
    updated_state). Donate it: the layers write it in place. The batch is
    the store's rows in order, or (state_row, a traced scalar) the one row
    a prefill chunk belongs to; state_valid [B]: how many of each row's
    positions are real (a chunk's padded tail, a slot that does not
    decode this tick: neither moves the state). None with such a model:
    every sequence starts here and its state is dropped (training).
    state_valid alone, of a serving step of any model: the expert layers
    route the real positions only (ops/moe.py moe_block `rows_read`;
    return_moe_aux then carries, of a share of the experts, the held
    experts a real position reached, summed over the layers, last), and
    with a vector cache_index (a decode step) the attention layers hand
    the decode kernel, for a row without a real position, the length it
    visits nothing for (models/transformer.py attention_block).

    grad_sink: float32 accumulators for the gradients of some leaves of
    `params`, in a tree shaped like `params` that holds None at every
    other leaf (`grad_sink_leaves` says which a call can take). They are
    handed through the layers unread and returned behind the result,
    (result, grad_sink), so that their cotangents ride the backward pass:
    the vector-Jacobian product answers a running sum of gradients with
    that sum plus this call's, added inside the kernels that make them
    (ops/moe.py moe_block), and such a leaf's own cotangent is zero.

    kv_caches: the stacked KV store for incremental decoding
    (ops/kv_store.py: `create` makes one); when given, returns
    (logits, updated_caches). Donate it: the layers write their new rows
    into it in place.

    return_moe_aux: also return [aux loss summed over the layers, the
    worst layer's load statistic] (ops/moe.py layer_stats; behind them,
    where the layers hold a share of their experts, the held rows' shares
    summed over the layers), as the last result: behind the logits, or
    behind the stores of a serving step (the serving engine counts the
    rows its held experts took from it).

    return_expert_load (with return_moe_aux, of a model that balances by
    its selection bias: cfg.moe_bias_update_rate): behind the statistics
    the expert layers' loads of the call, [layers, E] choices an expert
    (ops/moe.py router_carry), as one result with them: (moe_aux, load).

    page_table: the store is a pool of pages (inference/paging/) shared
    by every slot; each row's logical context is page_table[b] physical
    pages. The table is broadcast to
    all layers (the paging engine allocates one table per slot, not per
    layer).
    """
    if positions is None and kv_caches is not None:
        # incremental decode: q tokens sit at absolute positions
        # cache_index .. cache_index+s-1 (for RoPE and absolute pos-emb).
        # A vector cache_index (continuous-batching slot cache: every row
        # decodes at its OWN depth) broadcasts per row instead.
        if getattr(cache_index, "ndim", 0) == 1:
            positions = (jnp.asarray(cache_index)[:, None]
                         + jnp.arange(tokens.shape[1])[None, :])
        else:
            positions = cache_index + jnp.arange(tokens.shape[1])[None, :]

    train = dropout_key is not None and (cfg.hidden_dropout > 0 or cfg.attention_dropout > 0)
    x = embed_tokens(
        cfg, params, tokens, positions,
        dropout_key=jax.random.fold_in(dropout_key, 0xE0B) if train else None,
        tokentype_ids=tokentype_ids,
    )
    x = sharder(x, "residual")

    if kv_caches is not None:
        rope_len = kv_store.logical_length(kv_caches, page_table)
    else:
        rope_len = max(cfg.seq_length, tokens.shape[1])
    ropes = rope_tables(cfg, cfg.attention_period, rope_len)
    moe = cfg.num_experts is not None
    carry = (x, (moe_stats_zero(cfg) if moe
                 else jnp.zeros((), jnp.float32)),
             kv_caches, None if grad_sink is None else grad_sink["layers"],
             ssm_state)
    routed = router_carry(cfg, x)
    if routed is not None:
        carry += (routed,)
    x, moe_aux, new_caches, layer_sinks, new_state, *routed = run_layers(
        cfg, params["layers"], carry, ropes, positions,
        dropout_key=dropout_key if train else None,
        recompute=recompute,
        cache_index=cache_index,
        sharder=sharder,
        padding_mask=attention_mask,
        page_table=page_table,
        page_write_start=page_write_start,
        page_write_end=page_write_end,
        tp_comm=tp_comm,
        cp_comm=cp_comm,
        state_row=state_row,
        state_valid=state_valid,
    )

    if return_expert_load:
        moe_aux = (moe_aux, jax.lax.stop_gradient(routed[0]["load"]))

    def with_sinks(result):
        if grad_sink is None:
            return result
        return result, {**grad_sink, "layers": layer_sinks}

    # "head_loss" names the final norm, the head and (in lm_loss) the
    # cross-entropy: one region of the step in a device trace
    with jax.named_scope("head_loss"):
        x = final_hidden_norm(cfg, params, x)
    if return_hidden:
        # MoE backbones under task heads (BERT/classification/biencoder)
        # must not silently drop the router losses
        return with_sinks((x, moe_aux) if return_moe_aux else x)

    with jax.named_scope("head_loss"):
        logits = lm_logits(cfg, params, x, tp_comm=tp_comm)
        logits = sharder(logits, "logits")
    # behind the stores, for a serving step that counts the rows its
    # held experts took (inference/paging/engine.py)
    aux = (moe_aux,) if return_moe_aux else ()
    if ssm_state is not None:
        return with_sinks((logits, new_caches, new_state, *aux))
    if kv_caches is not None:
        return with_sinks((logits, new_caches, *aux))
    if return_moe_aux:
        return with_sinks((logits, moe_aux))
    return with_sinks(logits)


def chunked_lm_loss(
    cfg: ModelConfig,
    params: Dict[str, Any],
    hidden: jnp.ndarray,           # [B, S, H] final-norm'd hidden states
    labels: jnp.ndarray,           # [B, S]
    weights: Optional[jnp.ndarray] = None,   # [B, S]; None: every token 1
    sharder: Sharder = _identity_sharder,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(sum of weight x CE over the tokens, per-token CE [B, S]) computed
    over sequence chunks of cfg.ce_chunk_size tokens, LM head included:
    the [B, S, V] logits buffer (bf16 forward copy, fp32 CE intermediates,
    and its gradient) never resides in HBM; peak extra memory is one
    [B, C, V] chunk. Each chunk's logits are computed once a step: the
    gradient of the chunk is formed while the forward holds them
    (ops/cross_entropy.py chunked_head_loss), so only the weighted sum
    carries a gradient; the per-token losses are for reporting.

    Beyond the reference (which materializes full logits,
    gpt_model.py:18-42); exact same numbers as the unchunked path: the
    softmax is complete within a chunk because CE is independent per
    token, only the sequence axis is split.

    The one function serves every mesh, and the mesh alone says how. A
    sharder that carries `sequence_parallel` (the trainer's
    ActivationSharder) says whether a rank of "tensor" owns S / tp rows of
    `hidden` or all of them."""
    tied = cfg.tie_embed_logits
    w = deq(params["embed"]["tokens"] if tied else params["lm_head"]["w"],
            hidden.dtype)
    return chunked_head_loss(
        hidden, w, labels, weights, tied, cfg.ce_chunk_size,
        getattr(sharder, "sequence_parallel", False))


# the key of the gradient sinks in lm_loss's aux
GRAD_SINK = "grad_sink"


def grad_sink_leaves(cfg: ModelConfig, params: Dict[str, Any],
                     tokens_shape: Tuple[int, ...]) -> Dict[str, Any]:
    """A tree shaped like `params` with True at the leaves whose gradient
    `lm_loss`, traced here over tokens [B, S] of tokens_shape, can sum
    into an accumulator handed to it as `grad_sink`, and False at every
    other: the stacked expert matrices where a Pallas kernel makes their
    gradient (ops/moe.py expert_grad_sinks)."""
    takes = jax.tree.map(lambda _: False, params)
    moe = params["layers"].get("moe")
    if moe is not None:
        for name in expert_grad_sinks(cfg, moe, math.prod(tokens_shape)):
            takes["layers"]["moe"][name] = True
    return takes


def lm_loss(
    cfg: ModelConfig,
    params: Dict[str, Any],
    batch: Dict[str, jnp.ndarray],
    dropout_key: Optional[jax.Array] = None,
    recompute: str = "none",
    sharder: Sharder = _identity_sharder,
    grad_sink=None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Training loss on a batch dict with keys:
    tokens [B,S], labels [B,S], loss_mask [B,S], optional position_ids.

    Matches the reference contract: per-token CE weighted by loss_mask
    (gpt_model.py post_language_model_processing + finetune.py loss_func).

    grad_sink: as lm_forward's; it comes back in aux[GRAD_SINK].
    """
    moe = cfg.num_experts is not None
    balanced = cfg.balances_by_bias
    S = batch["tokens"].shape[1]
    # fall back to unchunked when the chunk doesn't tile this batch's
    # sequence (variable_seq_lengths batches may be shorter than
    # seq_length). C == S still chunks: the single chunk forms its
    # gradient beside its logits and keeps neither.
    chunked = bool(cfg.ce_chunk_size) and S % cfg.ce_chunk_size == 0
    out = lm_forward(
        cfg, params, batch["tokens"],
        positions=batch.get("position_ids"),
        dropout_key=dropout_key,
        recompute=recompute,
        sharder=sharder,
        return_moe_aux=moe,
        return_expert_load=balanced,
        return_hidden=chunked,
        grad_sink=grad_sink,
    )
    if grad_sink is not None:
        out, grad_sink = out
    if chunked:
        hidden, moe_aux = out if moe else (out, None)
        with jax.named_scope("head_loss"):
            # the mask goes in, its normaliser stays out here on the scalar
            mask = batch.get("loss_mask")
            total, per_token = chunked_lm_loss(
                cfg, params, hidden, batch["labels"], mask, sharder=sharder)
            mean = total / (per_token.size if mask is None else jnp.maximum(
                jnp.sum(mask.astype(jnp.float32)), 1.0))
    else:
        logits, moe_aux = out if moe else (out, None)
        with jax.named_scope("head_loss"):
            mean, per_token = cross_entropy_loss(
                logits, batch["labels"], loss_mask=batch.get("loss_mask"))
    ntokens = (jnp.sum(batch["loss_mask"]) if "loss_mask" in batch
               else jnp.asarray(per_token.size, jnp.float32))
    aux = {"lm_loss": mean, "ntokens": ntokens}
    if grad_sink is not None:
        aux[GRAD_SINK] = grad_sink
    if balanced:
        moe_aux, aux[EXPERT_LOAD] = moe_aux
    if moe:
        # router losses train alongside CE (load balance / ST-MoE z-loss);
        # lm_loss in metrics stays the pure CE term
        aux["moe_aux_loss"] = moe_aux[0]
        aux[LOAD_METRIC] = moe_aux[1]
        if cfg.holds_expert_share:
            aux[HELD_METRIC] = moe_aux[2] / cfg.expert_layers
            aux[MOVED_METRIC] = moe_aux[-1] / cfg.expert_layers
        return mean + moe_aux[0], aux
    return mean, aux
