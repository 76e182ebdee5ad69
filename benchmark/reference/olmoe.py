"""Plain reference for the OLMoE decoder: float32 `jax.numpy`, no kernels,
no sort, no cache, no batching, `default_matmul_precision("highest")`.

Follows the published architecture (OLMoE, arXiv:2409.02060, and the
Hugging Face `OlmoeForCausalLM` it ships as; tests/test_olmoe.py holds
this file to that class): pre-norm residual blocks, RMSNorm, multi-head
attention whose q and k projections pass an RMSNorm with a learned scale
over the WHOLE projected vector (all heads at once) before the split into
heads and the rotary embedding (rotate-half layout), full causal mask, a
mixture of SwiGLU experts in place of the MLP (router softmax in float32
over all experts, the top k taken with their raw probabilities, no
renormalisation, no shared expert), final norm, untied output head. Each
token's experts are found by a loop over ALL experts with a mask
(`where(selected == e)`): every expert multiplies every token and the
mask keeps what was routed, so nothing here shares a mechanism (sort,
gather, grouped matmul, scatter) with the dispatch it checks.

`lm_loss` is what the trainer optimises and journals as `loss`: the
masked mean cross-entropy plus `router_aux_loss_coef` x the load-balance
loss E * sum_e f_e P_e (f_e: assignments to expert e over all k choices
a token, as `transformers`' `load_balancing_loss_func` and the paper) plus
`router_z_loss_coef` x mean(logsumexp(router logits)^2), both summed over
the layers.

Departures from the published description:
  * storage only: layers are stacked on a leading axis and walked with
    `lax.scan`, experts likewise (weights raised to float32 as they are
    reached); gate and up projections arrive concatenated as one
    [hidden, 2 x intermediate] matrix an expert;
  * the auxiliary terms are taken per SEQUENCE and the per-sequence
    totals (CE + auxiliaries) averaged: one row of the batch is one
    micro-batch of the cell, the program computes the router statistics a
    forward call (as Hugging Face and Megatron do) and the accumulation
    averages the micro-batches' losses;
  * the two coefficients and the head size are not keys of the source's
    config.json: they are read from the configuration file's `assumed`
    ({key: {"value": ..., "why": ...}}).

Weights (matrices are [in, out]):
    embed [V, h]; final_norm [h]; lm_head [h, V]
    layers: attn_norm, mlp_norm [L, h]; wq, wk, wv [L, h, n*d]; q_norm,
    k_norm [L, n*d]; wo [L, n*d, h]; router [L, h, E];
    w_gate_up [L, E, h, 2f]; w_down [L, E, f, h]

Same functions as reference/mistral.py, whose docstring says what the
harness calls (no `next_token_logprobs`: no cell serves this model yet);
helpers that are not Mistral's own are imported from it.
Keys of `config` are the Hugging Face config's.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark.reference.mistral import (
    attended_keys_mean, attention, rms_norm, rotary,
)

F32 = jnp.float32


def assumed(config: Dict[str, Any], key: str):
    """A value the source's config.json does not state, as the
    configuration file took it."""
    return config["assumed"][key]["value"]


def program_flags(config: Dict[str, Any], seq_length: int) -> List[str]:
    """The architecture as the explicit flags of the program's trainer
    (`--model_name olmoe-1B-7B` cannot be cut in depth). What the family
    fixes (RMSNorm, SwiGLU experts, rotary, no biases, QK-norm, dropless
    dispatch) is said here once; the sizes and the coefficients are the
    configuration file's."""
    assert config["hidden_act"] == "silu" and not config["attention_bias"]
    assert config["clip_qkv"] is None and config["rope_scaling"] is None
    flags = [
        "--num_layers", str(config["num_hidden_layers"]),
        "--hidden_size", str(config["hidden_size"]),
        "--num_attention_heads", str(config["num_attention_heads"]),
        "--num_attention_heads_kv", str(config["num_key_value_heads"]),
        "--kv_channels", str(assumed(config, "head_dim")),
        "--ffn_hidden_size", str(config["intermediate_size"]),
        "--vocab_size", str(config["vocab_size"]),
        "--seq_length", str(seq_length),
        "--max_position_embeddings", str(seq_length),
        "--position_embedding_type", "rotary",
        "--rope_theta", str(config["rope_theta"]),
        "--use_rms_norm", "--layernorm_epsilon", str(config["rms_norm_eps"]),
        "--glu_activation", "swiglu", "--qk_norm",
        "--init_method_std", str(assumed(config, "initializer_range")),
        "--num_experts", str(config["num_experts"]),
        "--moe_top_k", str(config["num_experts_per_tok"]),
        "--moe_dispatch", "dropless",
        "--moe_aux_loss_coeff", str(assumed(config, "router_aux_loss_coef")),
        "--moe_z_loss_coeff", str(assumed(config, "router_z_loss_coef")),
        "--moe_renorm_gates" if config["norm_topk_prob"]
        else "--no_moe_renorm_gates",
    ]
    if not config.get("tie_word_embeddings"):
        flags.append("--no_tie_embed_logits")
    return flags


def from_program_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (megatron_tpu/models/params.py) under
    the reference's names. No value is changed or copied."""
    layers = params["layers"]
    attn, moe = layers["attn"], layers["moe"]
    return {
        "embed": params["embed"]["tokens"],
        "final_norm": params["final_ln"]["scale"],
        "lm_head": params["lm_head"]["w"],
        "layers": {
            "attn_norm": layers["ln1"]["scale"],
            "mlp_norm": layers["ln2"]["scale"],
            "wq": attn["wq"], "wk": attn["wk"], "wv": attn["wv"],
            "wo": attn["wo"],
            "q_norm": attn["q_norm"]["scale"],
            "k_norm": attn["k_norm"]["scale"],
            "router": moe["router"],
            "w_gate_up": moe["w_in"], "w_down": moe["w_out"],
        },
    }


def experts(h, w, cfg):
    """h [S, hidden] -> (y [S, hidden], load-balance loss, z-loss) of one
    layer's mixture: every expert over every token, masked to the k the
    router selected."""
    n_experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    logits = h @ w["router"]                          # [S, E] float32
    probs = jax.nn.softmax(logits, -1)
    weight, selected = jax.lax.top_k(probs, k)        # [S, k]
    if cfg["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, -1, keepdims=True)

    def one_expert(y, scanned):
        e, w_gate_up, w_down = scanned
        gate, up = jnp.split(h @ w_gate_up.astype(F32), 2, axis=-1)
        out = (jax.nn.silu(gate) * up) @ w_down.astype(F32)
        mine = jnp.sum(jnp.where(selected == e, weight, 0.0), -1)  # [S]
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (jnp.arange(n_experts), w["w_gate_up"], w["w_down"]))
    # assignments to each expert over all k choices, a token
    chosen = jnp.sum(selected[:, :, None] == jnp.arange(n_experts), 1)
    balance = n_experts * jnp.sum(jnp.mean(chosen.astype(F32), 0)
                                  * jnp.mean(probs, 0))
    z = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return y, balance, z


def logits_and_router_losses(weights: Dict[str, Any], tokens,
                             cfg: Dict[str, Any]):
    """tokens [S] int -> (logits [S, V] float32, load-balance loss and
    z-loss summed over the layers), one sequence."""
    with jax.default_matmul_precision("highest"):
        nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        d = assumed(cfg, "head_dim")
        eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
        s = tokens.shape[0]
        x = weights["embed"][tokens].astype(F32)

        def layer(carry, w):
            x, balance, z = carry
            # the experts' weights are raised one expert at a time
            w = {k: a if k in ("w_gate_up", "w_down") else a.astype(F32)
                 for k, a in w.items()}
            h = rms_norm(x, w["attn_norm"], eps)
            q = rms_norm(h @ w["wq"], w["q_norm"], eps)
            k = rms_norm(h @ w["wk"], w["k_norm"], eps)
            q = rotary(q.reshape(s, nq, d), theta)
            k = rotary(k.reshape(s, nkv, d), theta)
            v = (h @ w["wv"]).reshape(s, nkv, d)
            a = attention(q, k, v, None).reshape(s, nq * d)
            x = x + a @ w["wo"]
            y, b, zz = experts(rms_norm(x, w["mlp_norm"], eps), w, cfg)
            return (x + y, balance + b, z + zz), None

        (x, balance, z), _ = jax.lax.scan(
            layer, (x, F32(0.0), F32(0.0)), weights["layers"])
        x = rms_norm(x, weights["final_norm"].astype(F32), eps)
        return x @ weights["lm_head"].astype(F32), balance, z


def lm_loss(weights, tokens, labels, loss_mask, cfg):
    """What the trainer reports as `loss` for a [B, S] batch of B
    micro-batches of one sequence: the mean over the sequences of each
    one's masked mean cross-entropy plus its router losses."""
    balance_coef = assumed(cfg, "router_aux_loss_coef")
    z_coef = assumed(cfg, "router_z_loss_coef")

    def one(args):
        t, y, m = args
        out, balance, z = logits_and_router_losses(weights, t, cfg)
        logp = jax.nn.log_softmax(out, -1)
        ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        m = m.astype(F32)
        ce = jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0)
        return ce + balance_coef * balance + z_coef * z

    return jnp.mean(jax.lax.map(one, (tokens, labels, loss_mask)))


# --- operations and bytes ---------------------------------------------------

def forward_flops_per_token(cfg: dict, seq_length: int) -> float:
    """Forward FLOPs per token (a multiply-add is 2) over the ACTIVE
    parameters: projections, the router, the k experts a token visits,
    causal attention counted as causal, logits. Norms, rotary, softmax,
    top-k and the embedding gather are not counted."""
    h = cfg["hidden_size"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = assumed(cfg, "head_dim")
    f = cfg["intermediate_size"]
    proj = 2 * h * (nq * d) + 2 * 2 * h * (nkv * d) + 2 * (nq * d) * h
    router = 2 * h * cfg["num_experts"]
    mlp = cfg["num_experts_per_tok"] * (2 * h * 2 * f + 2 * f * h)
    attn = 2 * 2 * d * nq * attended_keys_mean(seq_length, None)
    return float(cfg["num_hidden_layers"] * (proj + router + mlp + attn)
                 + 2 * h * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_length: int) -> float:
    """Forward plus backward: 3 x forward. Recomputation is not counted
    (model FLOPs, not hardware FLOPs)."""
    return 3.0 * forward_flops_per_token(cfg, seq_length)
