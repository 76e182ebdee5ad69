"""Plain reference for the Mellum 2 decoder: float32 `jax.numpy`, no
kernels, no sort, no cache, no batching,
`default_matmul_precision("highest")`.

The architecture as its config.json states it (JetBrains/
Mellum2-12B-A2.5B-Instruct; the keys are Hugging Face's): pre-norm
residual blocks with RMSNorm and no biases,

    x += Attn_kind(l)(RMSNorm(x));   x += MoE(RMSNorm(x))

grouped-query attention (q, k, v by three projections, heads of
`head_dim`, no QK-norm), rotary embedding in the rotate-half layout by the
table of the layer's KIND (`layer_types[l]`, parameters under
`rope_parameters[kind]`: "default" is the plain table of `rope_theta`;
"yarn" blends each frequency between itself and itself / factor by a
linear ramp over the dimension pairs, from the pair that turns `beta_fast`
times over `original_max_position_embeddings` to the one that turns
`beta_slow` times, and scales cos and sin by `attention_factor`), causal
mask, for a `sliding_attention` layer also i - j < `sliding_window`, scale
1/sqrt(head_dim), softmax in float32; then a mixture of SwiGLU experts of
width `moe_intermediate_size` in place of the MLP: router logits h W_r
over the router's whole width, softmax, the top `num_experts_per_tok`
renormalised to sum 1 (`norm_topk_prob`), no shared expert,

    y = sum over the experts e HELD of w_e W_down_e(silu(W_gate_e h) * W_up_e h)

final RMSNorm, untied output head over the vocabulary held.

One chip's share of a deployment: the configuration file's `num_experts`
counts the experts whose weights exist here and `whole.num_experts` the
router's width; the experts held are the share `assumed["expert_share"]`
of them (share 0 of 16 of 64: experts 0 to 15). Each token's experts are
found by a loop over the HELD experts with a mask (`where(selected == e)`):
every held expert multiplies every token and the mask keeps what was
routed to it, so nothing here shares a mechanism (sort, gather, grouped
matmul, buffer) with the dispatch it checks. What the experts held
elsewhere would add is left out, and that partial result goes on to the
next layer. A file without `whole` holds every expert: the uncut model.

`lm_loss` is what the trainer optimises and journals as `loss` for ONE
forward call over the [B, S] batch: the masked mean cross-entropy over the
call's tokens plus `router_aux_loss_coef` x the load-balance loss
E * sum_e f_e P_e summed over the layers (E the router's width; f_e the
assignments to expert e over all k choices a token, P_e the mean router
probability, both over the call's tokens). No z-loss.

Departures from the published description:
  * storage only: layers and experts are stacked on a leading axis and
    reached by index; gate and up projections arrive concatenated as one
    [hidden, 2 x width] matrix an expert; weights raised to float32 as
    they are reached;
  * attention is computed one KV head's group and one block of QUERY_BLOCK
    queries at a time (at 8192 positions a group's whole scores are 2.1 GB
    in float32), each block against all keys: the same sums in the same
    order as the unblocked form;
  * what config.json does not state is read from the configuration file's
    `assumed` ({key: {"value", "why"}}): softmax router scores, the
    load-balance coefficient, the initializer's range, the expert share;
    `intermediate_size` is read by no layer (`mlp_layer_types` is all
    "sparse"), and the multi-token-prediction head the model card mentions
    has no key in config.json: none is built.

Weights (matrices are [in, out]):
    embed [V, h]; final_norm [h]; lm_head [h, V]
    layers: attn_norm, mlp_norm [L, h]; wq [L, h, nq*d]; wk, wv
    [L, h, nkv*d]; wo [L, nq*d, h]; router [L, h, E];
    w_gate_up [L, held, h, 2f]; w_down [L, held, f, h]

The harness calls `program_flags`, `from_program_params`, `lm_loss` and
`train_flops_per_token` (reference/mistral.py's docstring says when); the
norm and the count of attended keys are imported from that file.
"""

from __future__ import annotations

import json
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from benchmark.reference.mistral import attended_keys_mean, rms_norm

F32 = jnp.float32
QUERY_BLOCK = 1024
KINDS = {"sliding_attention": "sliding", "full_attention": "full"}


def assumed(config: Dict[str, Any], key: str):
    """A value the source's config.json does not state, as the
    configuration file took it."""
    return config["assumed"][key]["value"]


def router_width(config: Dict[str, Any]) -> int:
    return config.get("whole", {}).get("num_experts", config["num_experts"])


def first_held(config: Dict[str, Any]) -> int:
    """The router's index of the first expert whose weights exist here."""
    if "whole" not in config:
        return 0
    return assumed(config, "expert_share") * config["num_experts"]


def window_of(config: Dict[str, Any], layer: int) -> Optional[int]:
    return (config["sliding_window"]
            if config["layer_types"][layer] == "sliding_attention" else None)


# --- the program's flags and weights ----------------------------------------

def attention_kind(config: Dict[str, Any], layer_type: str) -> Dict[str, Any]:
    """One layer type as the program's --attention_pattern states a kind
    (megatron_tpu/config.py AttentionKind)."""
    rope = config["rope_parameters"][layer_type]
    kind = {"name": KINDS[layer_type], "rope_theta": rope["rope_theta"],
            "sliding_window_size": (config["sliding_window"]
                                    if layer_type == "sliding_attention"
                                    else None)}
    if rope["rope_type"] == "yarn":
        kind.update(
            rope_type="yarn", rope_scaling_factor=rope["factor"],
            yarn_original_max_positions=rope[
                "original_max_position_embeddings"],
            yarn_beta_fast=rope["beta_fast"],
            yarn_beta_slow=rope["beta_slow"],
            yarn_attention_factor=rope["attention_factor"])
    else:
        assert rope["rope_type"] == "default", rope
    return kind


def program_flags(config: Dict[str, Any], seq_length: int) -> List[str]:
    """The architecture as the explicit flags of the program's trainer.
    What the family fixes (RMSNorm, SwiGLU experts, rotary, no biases, no
    QK-norm, dropless dispatch) is said here once; the sizes, the layers'
    kinds and the share held are the configuration file's."""
    assert config["hidden_act"] == "silu" and not config["attention_bias"]
    assert set(config["mlp_layer_types"]) == {"sparse"}
    assert len(config["layer_types"]) == config["num_hidden_layers"]
    pattern = [attention_kind(config, t) for t in config["layer_types"]]
    flags = [
        "--num_layers", str(config["num_hidden_layers"]),
        "--hidden_size", str(config["hidden_size"]),
        "--num_attention_heads", str(config["num_attention_heads"]),
        "--num_attention_heads_kv", str(config["num_key_value_heads"]),
        "--kv_channels", str(config["head_dim"]),
        "--ffn_hidden_size", str(config["moe_intermediate_size"]),
        "--vocab_size", str(config["vocab_size"]),
        "--seq_length", str(seq_length),
        "--max_position_embeddings", str(seq_length),
        "--position_embedding_type", "rotary",
        "--attention_pattern", json.dumps(pattern),
        "--use_rms_norm", "--layernorm_epsilon", str(config["rms_norm_eps"]),
        "--glu_activation", "swiglu",
        "--init_method_std", str(assumed(config, "initializer_range")),
        "--num_experts", str(router_width(config)),
        "--moe_top_k", str(config["num_experts_per_tok"]),
        "--moe_dispatch", "dropless",
        "--moe_aux_loss_coeff", str(assumed(config, "router_aux_loss_coef")),
        "--moe_z_loss_coeff", "0.0",
        "--moe_renorm_gates" if config["norm_topk_prob"]
        else "--no_moe_renorm_gates",
    ]
    if "whole" in config:
        flags += ["--moe_experts_held", str(config["num_experts"]),
                  "--moe_expert_share", str(assumed(config, "expert_share"))]
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
            "router": moe["router"],
            "w_gate_up": moe["w_in"], "w_down": moe["w_out"],
        },
    }


# --- the layers -------------------------------------------------------------

def inverse_frequencies(rope: Dict[str, Any], d: int):
    """([d / 2] rotations a position of each dimension pair, the factor on
    cos and sin) of one entry of `rope_parameters`."""
    plain = rope["rope_theta"] ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    if rope["rope_type"] == "default":
        return plain, 1.0
    assert rope["rope_type"] == "yarn", rope
    theta, length = rope["rope_theta"], rope["original_max_position_embeddings"]

    def pair_turning(times):
        # the pair i with length * theta^(-2i/d) / 2 pi = times
        return d * math.log(length / (times * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_turning(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rope["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=F32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    # ramp 0: the pair keeps its frequency; 1: positions interpolated
    return (plain * (1.0 - ramp) + plain / rope["factor"] * ramp,
            rope["attention_factor"])


def rotary(x, rope: Dict[str, Any]):
    """x [S, heads, d] at positions 0..S-1, rotate-half layout."""
    s, _, d = x.shape
    inv_freq, factor = inverse_frequencies(rope, d)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos = factor * jnp.cos(angle)[:, None, :]
    sin = factor * jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: Optional[int]):
    """q [S, nq, d], k/v [S, nkv, d] -> [S, nq, d]: one KV head (and the
    query heads that share it) and one block of queries at a time, each
    against all keys."""
    s, nq, d = q.shape
    nkv = k.shape[1]
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
    j = jnp.arange(s)[None, :]
    qg = q.reshape(s // block, block, nkv, nq // nkv, d)
    qg = qg.transpose(2, 0, 3, 1, 4)             # [nkv, blocks, g, block, d]

    def one_group(args):
        qh, kh, vh = args     # [blocks, g, block, d], [S, d], [S, d]

        def one_block(args):
            qb, first = args                       # [g, block, d]
            i = first + jnp.arange(block)[:, None]
            mask = j <= i
            if window:
                mask &= (i - j) < window
            scores = jnp.einsum("gsd,td->gst", qb, kh) / jnp.sqrt(F32(d))
            scores = jnp.where(mask[None], scores, -jnp.inf)
            return jnp.einsum("gst,td->gsd", jax.nn.softmax(scores, -1), vh)

        return jax.lax.map(one_block,
                           (qh, jnp.arange(0, s, block)))

    out = jax.lax.map(one_group, (qg, k.transpose(1, 0, 2),
                                  v.transpose(1, 0, 2)))
    # [nkv, blocks, g, block, d] -> [S, nq, d]
    return out.transpose(1, 3, 0, 2, 4).reshape(s, nq, d)


def experts(h, w, cfg):
    """h [S, hidden] -> (y [S, hidden]: the held experts' part of the
    mixture, f [E]: assignments a token to each of the router's experts
    over all k choices, P [E]: mean router probability)."""
    width, k = router_width(cfg), cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ w["router"], -1)        # [S, E] float32
    weight, selected = jax.lax.top_k(probs, k)         # [S, k]
    if cfg["norm_topk_prob"]:
        weight = weight / jnp.sum(weight, -1, keepdims=True)

    def one_expert(y, scanned):
        e, w_gate_up, w_down = scanned
        gate, up = jnp.split(h @ w_gate_up.astype(F32), 2, axis=-1)
        out = (jax.nn.silu(gate) * up) @ w_down.astype(F32)
        mine = jnp.sum(jnp.where(selected == e, weight, 0.0), -1)  # [S]
        return y + mine[:, None] * out, None

    held = first_held(cfg) + jnp.arange(cfg["num_experts"])
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (held, w["w_gate_up"], w["w_down"]))
    chosen = jnp.sum(selected[:, :, None] == jnp.arange(width), 1)
    return y, jnp.mean(chosen.astype(F32), 0), jnp.mean(probs, 0)


def layer_forward(x, w, cfg, layer: int):
    """One block over one sequence x [S, hidden] -> (x, f, P)."""
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps, s = cfg["head_dim"], cfg["rms_norm_eps"], x.shape[0]
    rope = cfg["rope_parameters"][cfg["layer_types"][layer]]
    # the experts' weights are raised one expert at a time
    w = {k: a if k in ("w_gate_up", "w_down") else a.astype(F32)
         for k, a in w.items()}
    h = rms_norm(x, w["attn_norm"], eps)
    q = rotary((h @ w["wq"]).reshape(s, nq, d), rope)
    k = rotary((h @ w["wk"]).reshape(s, nkv, d), rope)
    v = (h @ w["wv"]).reshape(s, nkv, d)
    a = attention(q, k, v, window_of(cfg, layer)).reshape(s, nq * d)
    x = x + a @ w["wo"]
    y, f, p = experts(rms_norm(x, w["mlp_norm"], eps), w, cfg)
    return x + y, f, p


def logits_and_router_stats(weights: Dict[str, Any], tokens,
                            cfg: Dict[str, Any]):
    """tokens [S] int -> (logits [S, V] float32, f [L, E], P [L, E]), one
    sequence."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        stats = []
        for layer in range(cfg["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[layer], weights["layers"])
            x, f, p = layer_forward(x, w, cfg, layer)
            stats.append((f, p))
        x = rms_norm(x, weights["final_norm"].astype(F32),
                     cfg["rms_norm_eps"])
        return (x @ weights["lm_head"].astype(F32),
                jnp.stack([f for f, _ in stats]),
                jnp.stack([p for _, p in stats]))


def lm_loss(weights, tokens, labels, loss_mask, cfg):
    """What the trainer reports as `loss` for one forward call over a
    [B, S] batch: the masked mean cross-entropy of its tokens plus the
    load-balance loss of its router statistics (f and P are means over
    the call's tokens: over its sequences, which are equally long)."""
    def one(args):
        t, y, m = args
        out, f, p = logits_and_router_stats(weights, t, cfg)
        logp = jax.nn.log_softmax(out, -1)
        ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return jnp.sum(ce * m.astype(F32)), f, p

    ce, f, p = jax.lax.map(one, (tokens, labels, loss_mask))
    balance = router_width(cfg) * jnp.sum(jnp.mean(f, 0) * jnp.mean(p, 0))
    return (jnp.sum(ce) / jnp.maximum(jnp.sum(loss_mask.astype(F32)), 1.0)
            + assumed(cfg, "router_aux_loss_coef") * balance)


# --- operations and bytes ---------------------------------------------------

def forward_flops_per_token(cfg: dict, seq_length: int) -> float:
    """Forward FLOPs per token (a multiply-add is 2) of what THIS chip
    computes: projections, the router over its whole width, the share of
    a token's k experts that is held here in the mean (held / width),
    causal attention by each layer's kind, logits over the vocabulary
    held. Norms, rotary, softmax, top-k and the embedding gather are not
    counted."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["moe_intermediate_size"]
    width = router_width(cfg)
    proj = 2 * h * (nq * d) + 2 * 2 * h * (nkv * d) + 2 * (nq * d) * h
    mlp = (cfg["num_experts_per_tok"] * cfg["num_experts"] / width
           * (2 * h * 2 * f + 2 * f * h))
    attn = sum(2 * 2 * d * nq * attended_keys_mean(seq_length,
                                                   window_of(cfg, layer))
               for layer in range(cfg["num_hidden_layers"]))
    return float(cfg["num_hidden_layers"] * (proj + 2 * h * width + mlp)
                 + attn + 2 * h * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_length: int) -> float:
    """Forward plus backward: 3 x forward. Recomputation is not counted
    (model FLOPs, not hardware FLOPs)."""
    return 3.0 * forward_flops_per_token(cfg, seq_length)
