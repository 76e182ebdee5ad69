"""Plain reference for the Mistral decoder: float32 `jax.numpy`, no
kernels, no cache, no batching, `default_matmul_precision("highest")`.

Follows the published architecture (Mistral 7B, arXiv:2310.06825, and the
Hugging Face `MistralForCausalLM` it ships as): pre-norm residual blocks,
RMSNorm, grouped-query attention with rotary embeddings (rotate-half
layout) under a causal sliding-window mask, SwiGLU MLP, untied output
head. Departures, both of storage and not of arithmetic: layers are
stacked on a leading axis and walked with `lax.scan` (each layer's
weights are raised to float32 as it is reached, so the reference fits
beside what it checks), and the gate and up projections arrive
concatenated as one [hidden, 2 x intermediate] matrix.

Weights (matrices are [in, out]):
    embed [V, h]; final_norm [h]; lm_head [h, V]
    layers: attn_norm, mlp_norm [L, h]; wq [L, h, nq*d]; wk, wv
    [L, h, nkv*d]; wo [L, nq*d, h]; w_gate_up [L, h, 2f]; w_down [L, f, h]

This file is all the benchmark knows of one architecture, and a
configuration names it (`"reference": "mistral"`; the harness finds
<path>/reference/<name>.py). Another block type is another file beside
this one with the same functions, and no edit to the harness:

    program_flags(config, seq_length)    the architecture as the flags the
                                         program's trainer and server take
    from_program_params(params)          the program's weights, by the
                                         reference's names
    lm_loss, next_token_logprobs         what `correct` is held to
    train_flops_per_token(config, seq)   optional: model FLOPs, for the
                                         MFU a training run notes

Keys of `config` are the Hugging Face config's.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

F32 = jnp.float32


def program_flags(config: Dict[str, Any], seq_length: int) -> List[str]:
    """The architecture as the explicit flags trainer and server share
    (`--model_name mistral-7B` cannot be cut in depth). What the family
    fixes (RMSNorm, SwiGLU, rotary, no biases) is said here once; the
    sizes are the configuration file's, so the model that runs is the
    one the file holds."""
    flags = [
        "--num_layers", str(config["num_hidden_layers"]),
        "--hidden_size", str(config["hidden_size"]),
        "--num_attention_heads", str(config["num_attention_heads"]),
        "--num_attention_heads_kv", str(config["num_key_value_heads"]),
        "--ffn_hidden_size", str(config["intermediate_size"]),
        "--vocab_size", str(config["vocab_size"]),
        "--seq_length", str(seq_length),
        "--max_position_embeddings", str(seq_length),
        "--position_embedding_type", "rotary",
        "--rope_theta", str(config["rope_theta"]),
        "--use_rms_norm", "--layernorm_epsilon", str(config["rms_norm_eps"]),
        "--glu_activation", "swiglu",
        "--init_method_std", str(config["initializer_range"]),
    ]
    if not config.get("tie_word_embeddings"):
        flags.append("--no_tie_embed_logits")
    if config.get("sliding_window"):
        flags += ["--sliding_window_size", str(config["sliding_window"])]
    return flags


def from_program_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (megatron_tpu/models/params.py) under
    the reference's names. No value is changed or copied."""
    layers = params["layers"]
    return {
        "embed": params["embed"]["tokens"],
        "final_norm": params["final_ln"]["scale"],
        "lm_head": params["lm_head"]["w"],
        "layers": {
            "attn_norm": layers["ln1"]["scale"],
            "mlp_norm": layers["ln2"]["scale"],
            "wq": layers["attn"]["wq"], "wk": layers["attn"]["wk"],
            "wv": layers["attn"]["wv"], "wo": layers["attn"]["wo"],
            "w_gate_up": layers["mlp"]["w_in"],
            "w_down": layers["mlp"]["w_out"],
        },
    }


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rotary(x, theta):
    """x [S, heads, d] at positions 0..S-1, rotate-half layout."""
    s, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """q [S, nq, d], k/v [S, nkv, d] -> [S, nq, d]; one KV head (and the
    query heads that share it) at a time, so scores stay [g, S, S]."""
    s, nq, d = q.shape
    nkv = k.shape[1]
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = j <= i
    if window:
        mask &= (i - j) < window
    qg = q.reshape(s, nkv, nq // nkv, d).transpose(1, 2, 0, 3)  # [nkv,g,S,d]

    def one_group(args):
        qh, kh, vh = args  # [g, S, d], [S, d], [S, d]
        scores = jnp.einsum("gsd,td->gst", qh, kh) / jnp.sqrt(F32(d))
        scores = jnp.where(mask[None], scores, -jnp.inf)
        return jnp.einsum("gst,td->gsd", jax.nn.softmax(scores, -1), vh)

    out = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2),
                                  v.transpose(1, 0, 2)))  # [nkv, g, S, d]
    return out.transpose(2, 0, 1, 3).reshape(s, nq, d)


def logits(weights: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """tokens [S] int -> logits [S, V] float32, one sequence."""
    with jax.default_matmul_precision("highest"):
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        d = cfg.get("head_dim") or cfg["hidden_size"] // nq
        eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
        window = cfg.get("sliding_window")
        s = tokens.shape[0]
        x = weights["embed"][tokens].astype(F32)

        def layer(x, w):
            w = jax.tree.map(lambda a: a.astype(F32), w)
            h = rms_norm(x, w["attn_norm"], eps)
            q = rotary((h @ w["wq"]).reshape(s, nq, d), theta)
            k = rotary((h @ w["wk"]).reshape(s, nkv, d), theta)
            v = (h @ w["wv"]).reshape(s, nkv, d)
            a = attention(q, k, v, window).reshape(s, nq * d)
            x = x + a @ w["wo"]
            h = rms_norm(x, w["mlp_norm"], eps)
            gate, up = jnp.split(h @ w["w_gate_up"], 2, axis=-1)
            x = x + (jax.nn.silu(gate) * up) @ w["w_down"]
            return x, None

        x, _ = jax.lax.scan(layer, x, weights["layers"])
        x = rms_norm(x, weights["final_norm"].astype(F32), eps)
        return x @ weights["lm_head"].astype(F32)


def next_token_logprobs(weights, tokens, cfg, logits=logits):
    """log p(tokens[i+1] | tokens[:i+1]) for i in 0..S-2, float32 [S-1].
    (`logits`: another block type's forward, for a reference beside this
    one that shares the rest.)"""
    logp = jax.nn.log_softmax(logits(weights, tokens, cfg), -1)
    return jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=-1)[:, 0]


def lm_loss(weights, tokens, labels, loss_mask, cfg, logits=logits):
    """Mean cross-entropy of a [B, S] batch, weighted by loss_mask, as
    the trainer reports it (one sequence at a time)."""
    def one(args):
        t, y = args
        logp = jax.nn.log_softmax(logits(weights, t, cfg), -1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]

    per_token = jax.lax.map(one, (tokens, labels))
    m = loss_mask.astype(F32)
    return jnp.sum(per_token * m) / jnp.maximum(jnp.sum(m), 1.0)


# --- operations and bytes ---------------------------------------------------
# The benchmark keeps its own arithmetic so that a later PR cannot move the
# yardstick by editing the program's formula (`ModelConfig.flops_per_token_
# fwd` counts attention as dense; this counts what causal, windowed
# attention needs).

def attended_keys_mean(seq_length: int, window: int | None) -> float:
    """Mean number of keys a query attends to in a causal sequence of
    `seq_length` tokens under a sliding window (None: full causal)."""
    w = seq_length if not window else min(window, seq_length)
    # positions 0..w-1 see i+1 keys, the rest see w
    head = w * (w + 1) / 2.0
    tail = (seq_length - w) * float(w)
    return (head + tail) / seq_length


def forward_flops_per_token(cfg: dict, seq_length: int) -> float:
    """Forward FLOPs per token (a multiply-add is 2) at this sequence
    length: projections, SwiGLU MLP, causal windowed attention, logits.
    Norms, rotary, softmax and the embedding gather are not counted."""
    h = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nq
    f = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    proj = 2 * h * (nq * d) + 2 * 2 * h * (nkv * d) + 2 * (nq * d) * h
    mlp = 2 * h * 2 * f + 2 * f * h
    keys = attended_keys_mean(seq_length, cfg.get("sliding_window"))
    attn = 2 * 2 * d * nq * keys  # QK^T and PV
    return float(layers * (proj + mlp + attn) + 2 * h * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_length: int) -> float:
    """Forward plus backward: 3 x forward. Recomputation is not counted
    (model FLOPs, not hardware FLOPs)."""
    return 3.0 * forward_flops_per_token(cfg, seq_length)


def num_params(cfg: dict) -> int:
    h = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // nq
    f = cfg["intermediate_size"]
    per_layer = (h * nq * d + 2 * h * nkv * d + nq * d * h
                 + 3 * h * f + 2 * h)
    embed = cfg["vocab_size"] * h
    head = 0 if cfg.get("tie_word_embeddings") else cfg["vocab_size"] * h
    return cfg["num_hidden_layers"] * per_layer + embed + head + h


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * d
            * bytes_per_value)
