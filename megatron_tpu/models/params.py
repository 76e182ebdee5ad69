"""Parameter tree: shapes, initialization, and partition specs.

Single source of truth replacing the reference's scattered parameter
creation (megatron/core/tensor_parallel/layers.py _initialize_affine_weight*,
megatron/model/transformer.py module __init__s) and its init policy
(init_method_normal / scaled_init_method_normal, megatron/model/utils.py).

Layer parameters are stacked with a leading layer axis [L, ...] so the
forward is a lax.scan (compile-time O(1) in depth) and pipeline stages are
a reshape of the same arrays — the reference's per-stage layer-offset
bookkeeping (transformer.py:1045-1075) becomes indexing.

A weight init here is *topology-independent*: the same seed gives the same
logical weights at any (dp, tp, pp) — stronger than the reference, where
changing TP changes the per-shard rng draws.
"""

from __future__ import annotations

import math
import zlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from megatron_tpu.config import ModelConfig
from megatron_tpu.ops.activations import mlp_input_width_factor
from megatron_tpu.parallel.mesh import AXIS_EXPERT, AXIS_PIPE, AXIS_TENSOR

# init kinds
_NORMAL = "normal"          # N(0, init_method_std)
_SCALED = "scaled_normal"   # N(0, std / sqrt(2 * num_layers))  (output-facing)
_ONES = "ones"
_ZEROS = "zeros"
# the state-space mixer's own (ops/ssm.py, Mamba's init)
_A_LOG = "a_log"            # log(1..N) down the state axis (Mamba-1)
_A_LOG_HEADS = "a_log_heads"  # log of uniform [1, 16) a head (Mamba-2)
_DT_BIAS = "dt_bias"        # softplus(bias) log-uniform in [1e-3, 1e-1]
# compressed convolutional attention's and its router's own
_CONV = "conv"              # U(+-1/sqrt(fan_in)), torch's Conv1d default;
#                             the kind carries the fan-in: (_CONV, n)
_HALVES = "halves"          # 0.5 (the router's depth carry)


def _defs(cfg: ModelConfig) -> Dict[str, Any]:
    """Flat {'/'-joined path: (shape, partition_spec, init_kind)}."""
    h = cfg.hidden_size
    L = cfg.num_layers
    D = cfg.head_dim
    nq, nkv = cfg.num_attention_heads, cfg.n_kv_heads
    F = cfg.ffn_size
    Fin = F * mlp_input_width_factor(cfg.activation)
    V = cfg.vocab_size

    d: Dict[str, Any] = {}
    d["embed/tokens"] = ((V, h), P(AXIS_TENSOR, None), _NORMAL)
    if cfg.position_embedding_type == "absolute":
        d["embed/pos"] = ((cfg.max_position_embeddings, h), P(None, None), _NORMAL)
    if cfg.num_tokentypes > 0:
        d["embed/tokentype"] = ((cfg.num_tokentypes, h), P(None, None), _NORMAL)

    ln_bias = cfg.normalization == "layernorm"

    def norm(prefix: str):
        d[f"{prefix}/scale"] = ((L, h), P(AXIS_PIPE, None), _ONES)
        if ln_bias:
            d[f"{prefix}/bias"] = ((L, h), P(AXIS_PIPE, None), _ZEROS)

    # a stack whose layers are one block each (cfg.single_block_layers)
    # has the one norm a layer, and a feed-forward type's leaves stacked
    # over that type's layers like a mixer's
    single = cfg.single_block_layers
    norm("layers/ln1")
    if not cfg.parallel_attn and not single:
        norm("layers/ln2")
    if cfg.parallel_layernorm:
        norm("layers/ln_mlp")

    # A layer type's leaves are stacked over THAT type's layers, in their
    # order in the network (ModelConfig.layer_pattern; every layer an
    # attention layer without one): norms and the FFN over all L.
    La = cfg.layers_of("attention")
    d["layers/attn/wq"] = ((La, h, nq * D), P(AXIS_PIPE, None, AXIS_TENSOR), _NORMAL)
    d["layers/attn/wk"] = ((La, h, nkv * D), P(AXIS_PIPE, None, AXIS_TENSOR), _NORMAL)
    d["layers/attn/wv"] = ((La, h, nkv * D), P(AXIS_PIPE, None, AXIS_TENSOR), _NORMAL)
    d["layers/attn/wo"] = ((La, nq * D, h), P(AXIS_PIPE, AXIS_TENSOR, None), _SCALED)
    if cfg.qk_norm:
        # one scale over the whole projection, sharded with its output axis
        d["layers/attn/q_norm/scale"] = ((La, nq * D), P(AXIS_PIPE, AXIS_TENSOR), _ONES)
        d["layers/attn/k_norm/scale"] = ((La, nkv * D), P(AXIS_PIPE, AXIS_TENSOR), _ONES)
    if cfg.use_bias_qkv:
        d["layers/attn/bq"] = ((La, nq * D), P(AXIS_PIPE, AXIS_TENSOR), _ZEROS)
        d["layers/attn/bk"] = ((La, nkv * D), P(AXIS_PIPE, AXIS_TENSOR), _ZEROS)
        d["layers/attn/bv"] = ((La, nkv * D), P(AXIS_PIPE, AXIS_TENSOR), _ZEROS)
    if cfg.use_bias_linear:
        d["layers/attn/bo"] = ((La, h), P(AXIS_PIPE, None), _ZEROS)
    if cfg.attention_form == "cca":
        # ops/cca.py: `wv`'s first half of columns reads the position
        # itself, the second the one before; a tap a channel of the (q, k)
        # latent, then a [D, D] matrix a tap a head; k's temperature.
        # Replicated over "tensor": the paths that shard refuse the form.
        k0, k1 = cfg.cca_conv_kernels
        d["layers/attn/conv1"] = ((La, k0, nq + nkv, D),
                                  P(AXIS_PIPE, None, None, None),
                                  (_CONV, k0))
        d["layers/attn/conv2"] = ((La, nq + nkv, k1, D, D),
                                  P(AXIS_PIPE, None, None, None, None),
                                  (_CONV, k1 * D))
        d["layers/attn/k_temp_scale"] = ((La, nkv), P(AXIS_PIPE, None), _ONES)
    if cfg.residual_scale:
        for res in ("layers/res1", "layers/res2"):
            for name, kind in (("x_scale", _ONES), ("x_bias", _ZEROS),
                               ("out_scale", _ONES), ("out_bias", _ZEROS)):
                d[f"{res}/{name}"] = ((L, h), P(AXIS_PIPE, None), kind)

    if cfg.has_ssm:
        # the state-space mixers (ops/ssm.py has the equations). The inner
        # width is the LAST axis of every leaf that has it but the
        # projections out of it: it is the one a vector lane runs along.
        # Replicated over "tensor": the paths that shard refuse the type.
        Ls = cfg.layers_of(cfg.ssm_type)
        di, N, K = cfg.ssm_d_inner, cfg.ssm_d_state, cfg.ssm_d_conv

        def ssm(name, shape, kind):
            d[f"layers/ssm/{name}"] = (
                (Ls,) + shape, P(AXIS_PIPE, *(None,) * len(shape)), kind)

        if cfg.ssm_type == "mamba":
            R = cfg.ssm_rank
            ssm("w_in", (h, 2 * di), _NORMAL)          # x and the gate z
            ssm("conv_w", (K, di), _NORMAL)
            ssm("conv_b", (di,), _ZEROS)
            ssm("w_x", (di, R + 2 * N), _NORMAL)       # dt, B, C
            if cfg.ssm_inner_norms:
                ssm("dt_norm/scale", (R,), _ONES)
                ssm("b_norm/scale", (N,), _ONES)
                ssm("c_norm/scale", (N,), _ONES)
            ssm("w_dt", (R, di), _NORMAL)
            ssm("b_dt", (di,), _DT_BIAS)
            ssm("a_log", (N, di), _A_LOG)
            ssm("d_skip", (di,), _ONES)
        else:
            # Mamba-2: one projection gives the gate z, x with every
            # group's B and C behind it (the convolution runs over all of
            # those) and a step size a head; a decay and a skip a head
            H, W = cfg.ssm_num_heads, cfg.ssm_conv_width
            ssm("w_in", (h, di + W + H), _NORMAL)
            ssm("conv_w", (K, W), _NORMAL)
            ssm("conv_b", (W,), _ZEROS)
            ssm("b_dt", (H,), _DT_BIAS)
            ssm("a_log", (H,), _A_LOG_HEADS)
            ssm("d_skip", (H,), _ONES)
            ssm("norm/scale", (di,), _ONES)            # the gated norm
        ssm("w_out", (di, h), _SCALED)

    # the dense FFN: of every layer, or of the "mlp" layers where a layer
    # is one block (none of them: no such leaves)
    Lf = cfg.layers_of("mlp") if single else (
        L if cfg.num_experts is None else 0)
    if Lf:
        d["layers/mlp/w_in"] = ((Lf, h, Fin), P(AXIS_PIPE, None, AXIS_TENSOR), _NORMAL)
        d["layers/mlp/w_out"] = ((Lf, F, h), P(AXIS_PIPE, AXIS_TENSOR, None), _SCALED)
        if cfg.use_bias_linear:
            d["layers/mlp/b_in"] = ((Lf, Fin), P(AXIS_PIPE, AXIS_TENSOR), _ZEROS)
            d["layers/mlp/b_out"] = ((Lf, h), P(AXIS_PIPE, None), _ZEROS)
    if cfg.num_experts is not None:
        # experts sharded over the dedicated "expert" mesh axis (each ep
        # group holds E/ep experts; GSPMD inserts the dispatch all-to-all
        # between (data, expert)-sharded tokens and expert-sharded weights)
        # and tensor-parallel inside each expert, composing EP x TP; the
        # expert axis is independent of dp, so E never constrains the
        # data-parallel degree (VERDICT r3 next-round #6)
        # the router keeps its width where only a share of its experts'
        # weights exist here (ModelConfig.moe_experts_held)
        Le = cfg.expert_layers
        if cfg.moe_router_form == "mlp":
            # ops/moe.py router_mlp: down, the depth carry's scale, MLP
            R = cfg.moe_router_hidden_size
            for name, shape in (("router_down", (h, R)),
                                ("router_w1", (R, R)), ("router_w2", (R, R)),
                                ("router_w3", (R, cfg.num_experts))):
                d[f"layers/moe/{name}"] = ((Le,) + shape,
                                           P(AXIS_PIPE, None, None), _NORMAL)
            d["layers/moe/router_carry_scale"] = ((Le, R), P(AXIS_PIPE, None),
                                                  _HALVES)
        else:
            d["layers/moe/router"] = ((Le, h, cfg.num_experts),
                                      P(AXIS_PIPE, None, None), _NORMAL)
        if cfg.has_router_bias:
            # the selection bias: added to the scores for the choice alone
            d["layers/moe/router_bias"] = ((Le, cfg.num_experts),
                                           P(AXIS_PIPE, None), _ZEROS)
        E = cfg.experts_held
        # the width the routed experts read and write: the hidden size, or
        # the latent one between its two projections
        w = cfg.moe_latent_size or h
        if cfg.moe_latent_size is not None:
            d["layers/moe/latent_in"] = ((Le, h, w), P(AXIS_PIPE, None, None),
                                         _NORMAL)
            d["layers/moe/latent_out"] = ((Le, w, h), P(AXIS_PIPE, None, None),
                                          _SCALED)
        if cfg.moe_shared_ffn_size is not None:
            Fs = cfg.moe_shared_ffn_size
            d["layers/moe/shared_in"] = (
                (Le, h, Fs * (Fin // F)), P(AXIS_PIPE, None, AXIS_TENSOR),
                _NORMAL)
            d["layers/moe/shared_out"] = (
                (Le, Fs, h), P(AXIS_PIPE, AXIS_TENSOR, None), _SCALED)
        d["layers/moe/w_in"] = ((Le, E, w, Fin),
                                P(AXIS_PIPE, AXIS_EXPERT, None, AXIS_TENSOR),
                                _NORMAL)
        d["layers/moe/w_out"] = ((Le, E, F, w),
                                 P(AXIS_PIPE, AXIS_EXPERT, AXIS_TENSOR, None),
                                 _SCALED)
        if cfg.use_bias_linear:
            d["layers/moe/b_in"] = ((Le, E, Fin),
                                    P(AXIS_PIPE, AXIS_EXPERT, AXIS_TENSOR),
                                    _ZEROS)
            d["layers/moe/b_out"] = ((Le, E, w),
                                     P(AXIS_PIPE, AXIS_EXPERT, None), _ZEROS)

    if not cfg.use_post_ln:  # post-LN layers carry their own output norm
        d["final_ln/scale"] = ((h,), P(None), _ONES)
        if ln_bias:
            d["final_ln/bias"] = ((h,), P(None), _ZEROS)
    if not cfg.tie_embed_logits:
        d["lm_head/w"] = ((h, V), P(None, AXIS_TENSOR), _NORMAL)
    if cfg.bert_binary_head:
        # MLM transform (dense+gelu+LN) over tied decoder + output bias,
        # pooler + binary head (ref: bert_model.py BertLMHead / Pooler)
        d["mlm_head/dense_w"] = ((h, h), P(None, None), _NORMAL)
        d["mlm_head/dense_b"] = ((h,), P(None), _ZEROS)
        d["mlm_head/norm_scale"] = ((h,), P(None), _ONES)
        d["mlm_head/norm_bias"] = ((h,), P(None), _ZEROS)
        d["mlm_head/bias"] = ((V,), P(AXIS_TENSOR), _ZEROS)
        d["pooler/w"] = ((h, h), P(None, None), _NORMAL)
        d["pooler/b"] = ((h,), P(None), _ZEROS)
        d["binary_head/w"] = ((h, 2), P(None, None), _NORMAL)
        d["binary_head/b"] = ((2,), P(None), _ZEROS)
    return d


def _nest(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return out


def param_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    return _nest({k: jax.ShapeDtypeStruct(s, cfg.dtype) for k, (s, _, _) in _defs(cfg).items()})


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    return _nest({k: spec for k, (_, spec, _) in _defs(cfg).items()})


def num_params(cfg: ModelConfig) -> int:
    return sum(math.prod(s) for s, _, _ in _defs(cfg).values())


def init_params(cfg: ModelConfig, key: jax.Array, dtype=None) -> Dict[str, Any]:
    """Initialize the full parameter pytree.

    Each tensor gets its own key folded from a stable hash of its path, so
    adding/removing optional params never perturbs the others.
    """
    dtype = dtype or cfg.dtype
    defs = _defs(cfg)
    flat = {}
    scaled_std = cfg.init_method_std / math.sqrt(2.0 * cfg.num_layers) \
        if cfg.use_scaled_init else cfg.init_method_std
    for path, (shape, _, kind) in sorted(defs.items()):
        if kind == _ONES:
            flat[path] = jnp.ones(shape, dtype)
        elif kind == _ZEROS:
            flat[path] = jnp.zeros(shape, dtype)
        elif kind == _HALVES:
            flat[path] = jnp.full(shape, 0.5, dtype)
        elif isinstance(kind, tuple) and kind[0] == _CONV:
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            bound = 1.0 / math.sqrt(kind[1])
            flat[path] = jax.random.uniform(
                k, shape, jnp.float32, -bound, bound).astype(dtype)
        elif kind == _A_LOG:
            rows = jnp.log(jnp.arange(1, shape[-2] + 1, dtype=jnp.float32))
            flat[path] = jnp.broadcast_to(rows[:, None], shape).astype(dtype)
        elif kind == _A_LOG_HEADS:
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            flat[path] = jnp.log(jax.random.uniform(
                k, shape, jnp.float32, 1.0, 16.0)).astype(dtype)
        elif kind == _DT_BIAS:
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            # the inverse of softplus
            flat[path] = (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        else:
            std = scaled_std if kind == _SCALED else cfg.init_method_std
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            flat[path] = (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)
    return _nest(flat)
