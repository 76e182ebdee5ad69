"""Plain reference for the Jamba decoder (AI21's `JambaForCausalLM`, HF
modeling_jamba.py): float32 `jax.numpy`, no kernels, no cache, no
batching, `default_matmul_precision("highest")`.

A stack of two layer TYPES. Layer i is an attention layer where
`i % attn_layer_period == attn_layer_offset`, else a Mamba-1 layer
(`JambaConfig.layers_block_type`); with `num_experts` 1 every layer's
feed-forward is the dense gated MLP and there is no router. Every layer:

    x += mixer(RMSNorm(x));  u = RMSNorm(x);  x += W_down(silu(W_gate u) * (W_up u))

final RMSNorm, the head tied to the embedding, no biases but where
stated, and NO positional encoding anywhere.

  * attention: grouped-query (here 20 heads over ONE key/value head),
    causal, full, scale head_dim^-0.5, no rotary;
  * Mamba-1 (`JambaMambaMixer.slow_forward`), d_i = mamba_expand x hidden:
        (xs, z)   = split(u W_in)
        c_t       = silu(b_conv + sum_{j<K} w_conv[j] * xs[t-K+1+j])   zeros left of 0
        (d, B, C) = split(c W_x, [R, N, N])
        d, B, C   = RMSNorm_dt(d), RMSNorm_B(B), RMSNorm_C(C)         Jamba's own
        delta     = softplus(d W_dt + b_dt)
        h_t       = exp(delta_t * A) * h_{t-1} + (delta_t * c_t) * B_t,  A = -exp(A_log), h_{-1} = 0
        y_t       = h_t . C_t + D * c_t
        out       = (y * silu(z)) W_out
    the recurrence one plain `lax.scan` over time.

Departures, all of storage and none of arithmetic: a type's layers are
stacked on a leading axis over THAT type's layers (26 and 2 of 28), the
norms and the MLP over all; runs of Mamba layers are walked with
`lax.scan`, each layer's weights raised to float32 as it is reached, so
3,584 positions fit beside what they check; gate and up arrive as one
[hidden, 2 x intermediate] matrix; matrices are [in, out]; and `A_log`,
the convolution's weight and the state are held [N, d_i] / [K, d_i] (the
inner width last), where HF holds [d_i, N] / [d_i, 1, K].

Weights, by HF's module names:
    embed_tokens [V, h]; final_layernorm [h]
    layers: input_layernorm, pre_ff_layernorm [L, h];
        gate_up_proj [L, h, 2f]; down_proj [L, f, h]
    mamba: in_proj [Lm, h, 2 d_i]; conv1d_weight [Lm, K, d_i];
        conv1d_bias [Lm, d_i]; x_proj [Lm, d_i, R + 2N];
        dt_layernorm [Lm, R]; b_layernorm, c_layernorm [Lm, N];
        dt_proj [Lm, R, d_i]; dt_proj_bias [Lm, d_i]; A_log [Lm, N, d_i];
        D [Lm, d_i]; out_proj [Lm, d_i, h]
    self_attn: q_proj [La, h, nq d]; k_proj, v_proj [La, h, nkv d];
        o_proj [La, nq d, h]

The functions are those reference/mistral.py's docstring lists. Keys of
`config` are the Hugging Face config's.
"""

from __future__ import annotations

import json
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark.reference import mistral
from benchmark.reference.mistral import fp8, rms_norm  # noqa: F401 (fp8: the control)

F32 = jnp.float32


def assumed(config: Dict[str, Any], key: str):
    """What the source leaves open and the configuration states under
    `assumed` ({key: {"value", "why"}})."""
    return config["assumed"][key]["value"]


def layer_types(cfg: Dict[str, Any]) -> List[str]:
    """"attention" or "mamba" for each layer, in order."""
    period, offset = cfg["attn_layer_period"], cfg["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba"
            for i in range(cfg["num_hidden_layers"])]


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    h, nq = cfg["hidden_size"], cfg["num_attention_heads"]
    rank = cfg["mamba_dt_rank"]
    return {
        "h": h, "v": cfg["vocab_size"], "f": cfg["intermediate_size"],
        "nq": nq, "nkv": cfg["num_key_value_heads"],
        "d": cfg.get("head_dim") or h // nq,
        "di": cfg["mamba_expand"] * h, "n": cfg["mamba_d_state"],
        "k": cfg["mamba_d_conv"],
        "r": math.ceil(h / 16) if rank == "auto" else rank,
    }


def program_flags(config: Dict[str, Any], seq_length: int) -> List[str]:
    """The architecture as the explicit flags trainer and server share."""
    if config.get("num_experts", 1) != 1:
        raise ValueError("this reference holds Jamba with a dense FFN in "
                         "every layer (num_experts 1)")
    s = sizes(config)
    types = layer_types(config)
    period = config["attn_layer_period"]
    if len(types) % period:
        raise ValueError("the depth is not whole periods of the layers")
    flags = [
        "--num_layers", str(config["num_hidden_layers"]),
        "--hidden_size", str(s["h"]),
        "--num_attention_heads", str(s["nq"]),
        "--num_attention_heads_kv", str(s["nkv"]),
        "--kv_channels", str(s["d"]),
        "--ffn_hidden_size", str(s["f"]),
        "--vocab_size", str(s["v"]),
        "--seq_length", str(seq_length),
        "--position_embedding_type", "none",
        "--layer_pattern", json.dumps(types[:period]),
        "--ssm_d_state", str(s["n"]), "--ssm_d_conv", str(s["k"]),
        "--ssm_expand", str(config["mamba_expand"]),
        "--ssm_dt_rank", str(s["r"]), "--ssm_inner_norms",
        "--use_rms_norm", "--layernorm_epsilon", str(config["rms_norm_eps"]),
        "--glu_activation", "swiglu",
        "--init_method_std", str(assumed(config, "initializer_range")),
    ]
    if not config.get("tie_word_embeddings"):
        flags.append("--no_tie_embed_logits")
    return flags


_MAMBA = {  # the reference's name: the program's (under layers/ssm)
    "in_proj": "w_in", "conv1d_weight": "conv_w", "conv1d_bias": "conv_b",
    "x_proj": "w_x", "dt_proj": "w_dt", "dt_proj_bias": "b_dt",
    "A_log": "a_log", "D": "d_skip", "out_proj": "w_out",
}
_MAMBA_NORMS = {"dt_layernorm": "dt_norm", "b_layernorm": "b_norm",
                "c_layernorm": "c_norm"}
_ATTN = {"q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo"}


def from_program_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (megatron_tpu/models/params.py) under
    the reference's names. No value is changed or copied."""
    layers = params["layers"]
    ssm, attn = layers["ssm"], layers["attn"]
    out = {
        "embed_tokens": params["embed"]["tokens"],
        "final_layernorm": params["final_ln"]["scale"],
        "layers": {
            "input_layernorm": layers["ln1"]["scale"],
            "pre_ff_layernorm": layers["ln2"]["scale"],
            "gate_up_proj": layers["mlp"]["w_in"],
            "down_proj": layers["mlp"]["w_out"],
        },
        "mamba": {**{ours: ssm[theirs] for ours, theirs in _MAMBA.items()},
                  **{ours: ssm[theirs]["scale"]
                     for ours, theirs in _MAMBA_NORMS.items()}},
        "self_attn": {ours: attn[theirs] for ours, theirs in _ATTN.items()},
    }
    if "lm_head" in params:
        out["lm_head"] = params["lm_head"]["w"]
    return out


def to_program_params(weights: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's weights under the program's names: the inverse of
    from_program_params. No value is changed or copied."""
    layers, mamba = weights["layers"], weights["mamba"]
    out = {
        "embed": {"tokens": weights["embed_tokens"]},
        "final_ln": {"scale": weights["final_layernorm"]},
        "layers": {
            "ln1": {"scale": layers["input_layernorm"]},
            "ln2": {"scale": layers["pre_ff_layernorm"]},
            "mlp": {"w_in": layers["gate_up_proj"],
                    "w_out": layers["down_proj"]},
            "ssm": {**{theirs: mamba[ours] for ours, theirs in _MAMBA.items()},
                    **{theirs: {"scale": mamba[ours]}
                       for ours, theirs in _MAMBA_NORMS.items()}},
            "attn": {theirs: weights["self_attn"][ours]
                     for ours, theirs in _ATTN.items()},
        },
    }
    if "lm_head" in weights:
        out["lm_head"] = {"w": weights["lm_head"]}
    return out


def weight_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Shape of every weight, by the reference's names (module docstring)."""
    s = sizes(cfg)
    h, v, f, d, di = s["h"], s["v"], s["f"], s["d"], s["di"]
    n, k, r, nq, nkv = s["n"], s["k"], s["r"], s["nq"], s["nkv"]
    types = layer_types(cfg)
    L, lm, la = len(types), types.count("mamba"), types.count("attention")
    shapes = {
        "embed_tokens": (v, h), "final_layernorm": (h,),
        "layers": {
            "input_layernorm": (L, h), "pre_ff_layernorm": (L, h),
            "gate_up_proj": (L, h, 2 * f), "down_proj": (L, f, h),
        },
        "mamba": {
            "in_proj": (lm, h, 2 * di), "conv1d_weight": (lm, k, di),
            "conv1d_bias": (lm, di), "x_proj": (lm, di, r + 2 * n),
            "dt_layernorm": (lm, r), "b_layernorm": (lm, n),
            "c_layernorm": (lm, n), "dt_proj": (lm, r, di),
            "dt_proj_bias": (lm, di), "A_log": (lm, n, di), "D": (lm, di),
            "out_proj": (lm, di, h),
        },
        "self_attn": {
            "q_proj": (la, h, nq * d), "k_proj": (la, h, nkv * d),
            "v_proj": (la, h, nkv * d), "o_proj": (la, nq * d, h),
        },
    }
    if not cfg.get("tie_word_embeddings"):
        shapes["lm_head"] = (h, v)
    return shapes


_ONES = ("final_layernorm", "input_layernorm", "pre_ff_layernorm",
         "dt_layernorm", "b_layernorm", "c_layernorm", "D")


def make_weights(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16):
    """Seeded weights of a served cell, in the type they are served in,
    one jitted call on the device: every matrix (the convolution's too)
    normal with the assumed `initializer_range`, every norm's scale and
    `D` 1, the convolution's bias 0, `A_log` log(1..N) down the state
    axis, and `dt_proj_bias` such that softplus(bias) is log-uniform in
    [1e-3, 1e-1] (Mamba's own init: the configuration's `assumed` says
    so). Any whole number a little over 2**31 is a seed."""
    paths, tree = jax.tree_util.tree_flatten_with_path(
        weight_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    std = assumed(cfg, "initializer_range")

    def make(key):
        out = []
        for i, (path, shape) in enumerate(paths):
            name, k = path[-1].key, jax.random.fold_in(key, i)
            if name in _ONES:
                leaf = jnp.ones(shape, F32)
            elif name == "conv1d_bias":
                leaf = jnp.zeros(shape, F32)
            elif name == "A_log":
                rows = jnp.log(jnp.arange(1, shape[-2] + 1, dtype=F32))
                leaf = jnp.broadcast_to(rows[:, None], shape)
            elif name == "dt_proj_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, F32, math.log(1e-3), math.log(1e-1)))
                leaf = dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse
            else:
                leaf = jax.random.normal(k, shape, F32) * std
            out.append(leaf.astype(dtype))
        return jax.tree.unflatten(tree, out)

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)


def attention(q, k, v):
    """q [S, nq, d], k/v [S, nkv, d] -> [S, nq, d]: causal, full."""
    return mistral.attention(q, k, v, None)


def mamba_mixer(u, w, cfg, mm, state_dtype=F32):
    """u [S, h] (normed) -> [S, h]: the module docstring's equations. (A
    `state_dtype` below float32 rounds `h` after every step: what a state
    kept in that type would be, for the tests' control.)"""
    s = sizes(cfg)
    di, n, r, k, eps = s["di"], s["n"], s["r"], s["k"], cfg["rms_norm_eps"]
    xs, z = jnp.split(mm(u, w["in_proj"]), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, di), F32), xs])
    length = xs.shape[0]
    conv = w["conv1d_bias"] + sum(
        w["conv1d_weight"][j] * padded[j:j + length] for j in range(k))
    c = jax.nn.silu(conv)
    dt, b, cc = jnp.split(mm(c, w["x_proj"]), [r, r + n], axis=-1)
    dt = rms_norm(dt, w["dt_layernorm"], eps)
    b = rms_norm(b, w["b_layernorm"], eps)
    cc = rms_norm(cc, w["c_layernorm"], eps)
    delta = jax.nn.softplus(mm(dt, w["dt_proj"]) + w["dt_proj_bias"])
    a = -jnp.exp(w["A_log"])                                  # [N, d_i]

    def step(h, at):
        delta_t, c_t, b_t, cc_t = at
        h = jnp.exp(delta_t[None, :] * a) * h \
            + (delta_t * c_t)[None, :] * b_t[:, None]
        h = h.astype(state_dtype).astype(F32)
        return h, jnp.sum(h * cc_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((n, di), F32), (delta, c, b, cc))
    y = y + w["D"] * c
    return mm(y * jax.nn.silu(z), w["out_proj"])


def logits(weights: Dict[str, Any], tokens, cfg: Dict[str, Any], lowp=None,
           state_dtype=F32):
    """tokens [S] int -> logits [S, V] float32, one sequence. With
    `lowp` (fp8) both operands of every product with a weight pass
    through it first: the control, never the reference."""
    def mm(a, w):
        return a @ w if lowp is None else lowp(a) @ lowp(w)

    with jax.default_matmul_precision("highest"):
        s = sizes(cfg)
        nq, nkv, d, eps = s["nq"], s["nkv"], s["d"], cfg["rms_norm_eps"]
        length = tokens.shape[0]
        x = weights["embed_tokens"][tokens].astype(F32)
        f32 = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: a.astype(F32), tree)

        def ffn(x, w):
            u = rms_norm(x, w["pre_ff_layernorm"], eps)
            gate, up = jnp.split(mm(u, w["gate_up_proj"]), 2, axis=-1)
            return x + mm(jax.nn.silu(gate) * up, w["down_proj"])

        def mamba_layer(x, w):
            both, own = f32(w)
            u = rms_norm(x, both["input_layernorm"], eps)
            x = x + mamba_mixer(u, own, cfg, mm, state_dtype)
            return ffn(x, both), None

        def attention_layer(x, both, own):
            both, own = f32(both), f32(own)
            u = rms_norm(x, both["input_layernorm"], eps)
            q = mm(u, own["q_proj"]).reshape(length, nq, d)
            k = mm(u, own["k_proj"]).reshape(length, nkv, d)
            v = mm(u, own["v_proj"]).reshape(length, nkv, d)
            a = attention(q, k, v).reshape(length, nq * d)
            return ffn(x + mm(a, own["o_proj"]), both)

        # runs of Mamba layers under one scan each, attention layers
        # between them, in the network's order
        types = layer_types(cfg)
        at = lambda tree, lo, hi: jax.tree.map(  # noqa: E731
            lambda a: a[lo:hi], tree)
        i = m = a = 0
        while i < len(types):
            if types[i] == "attention":
                x = attention_layer(
                    x, jax.tree.map(lambda t: t[i], weights["layers"]),
                    jax.tree.map(lambda t: t[a], weights["self_attn"]))
                i, a = i + 1, a + 1
                continue
            run = 1
            while i + run < len(types) and types[i + run] == "mamba":
                run += 1
            x, _ = jax.lax.scan(
                mamba_layer, x, (at(weights["layers"], i, i + run),
                                 at(weights["mamba"], m, m + run)))
            i, m = i + run, m + run
        x = rms_norm(x, weights["final_layernorm"].astype(F32), eps)
        if cfg.get("tie_word_embeddings"):
            return mm(x, weights["embed_tokens"].astype(F32).T)
        return mm(x, weights["lm_head"].astype(F32))


def lm_loss(weights, tokens, labels, loss_mask, cfg):
    """Mean cross-entropy of a [B, S] batch, weighted by loss_mask, as
    the trainer reports it (one sequence at a time)."""
    return mistral.lm_loss(weights, tokens, labels, loss_mask, cfg,
                           logits=logits)


# --- operations and bytes ---------------------------------------------------

def num_params(cfg: dict) -> int:
    shapes = jax.tree.leaves(weight_shapes(cfg),
                             is_leaf=lambda x: isinstance(x, tuple))
    return sum(math.prod(shape) for shape in shapes)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    s = sizes(cfg)
    return (2 * layer_types(cfg).count("attention") * s["nkv"] * s["d"]
            * bytes_per_value)


def state_bytes_per_sequence(cfg: dict, tail_bytes_per_value: int = 2) -> int:
    """The recurrent state (float32) and the convolution's tail a
    sequence holds, over the Mamba layers."""
    s = sizes(cfg)
    return layer_types(cfg).count("mamba") * s["di"] * (
        s["n"] * 4 + (s["k"] - 1) * tail_bytes_per_value)
