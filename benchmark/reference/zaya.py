"""Plain reference for the ZAYA1 decoder: float32 `jax.numpy`, no kernels,
no sort, no cache, no batching, `default_matmul_precision("highest")`.

The architecture as its config.json (Zyphra/ZAYA1-8B, `model_type: zaya`)
gives the shapes and as arXiv:2510.04476 ("Compressed Convolutional
Attention") and arXiv:2511.17127 (the ZAYA1 report) give the forms. What
config.json does not settle is marked (A): the configuration file lists
each under `assumed` with the value taken here, and the released weights'
layout may differ in exactly those points. `h` hidden size, `d` head size,
`Hq` / `Hkv` query / KV heads, `G` = Hq / Hkv, `R` the router's width, `E`
its experts.

Layer l, pre-norm, RMSNorm, no biases anywhere ((A): the convolutions and
the router's layers have none either):

    a      = CCA(RMSNorm1(x))          x1 = (s1r * x  + b1r) + (s1o * a + b1o)
    m, r_l = MoE(RMSNorm2(x1), r_l-1)  x2 = (s2r * x1 + b2r) + (s2o * m + b2o)

Scaled residuals ((A): the form; the report's "residual scaling"): `s*`,
`b*` vectors of h, a set a sub-layer, s initialised 1 and b 0.

CCA on u = RMSNorm1(x), [S, h]; attention runs in the compressed latent
(query width Hq d, key/value width Hkv d) and nothing is projected up:

    qt = u Wq  [S,Hq,d]      kt = u Wk  [S,Hkv,d]
    v  = heads(u Wv1, shift(u) Wv2)    shift(u)_t = u_t-1, u_-1 = 0
           (A) Wv1, Wv2 [h, Hkv d / 2]: the first half of the value
           channels (KV head 0 of 2) is u_t's, the second u_t-1's
    c  = concat(qt, kt) over heads  [S, Hq+Hkv, d]
    c1_t    = w1[0] c_t-1 + w1[1] c_t
           depthwise causal, kernel `cca_time0` (2), a weight a channel a tap
    c2_t[g] = c1_t-1[g] W2[g,0] + c1_t[g] W2[g,1]
           causal, kernel `cca_time1` (2), grouped by head: W2 [Hq+Hkv,2,d,d]
    mq[t,i] = (qt[t,i] + kt[t,i//G]) / 2
    mk[t,j] = (mean over i in group j of qt[t,i] + kt[t,j]) / 2
           the q-k mean, of the PRE-convolution values
    q = c2[:Hq] + mq         k = c2[Hq:] + mk
    q = sqrt(d) q / |q|_2    k = tau_j sqrt(d) k / |k|_2
           a head over d, float32; tau [Hkv] learned, (A) init 1
    q, k = rope(q), rope(k)
           the first `partial_rotary_factor` x d channels of a head rotated
           (rotate-half inside them), the rest passed; the table of the
           layer's type in `rope_parameters`
    o = softmax(q k^T / sqrt(d) + causal) v     [S, Hq d]
    a = o Wo                 Wo [Hq d, h]

Positions before the sequence's first are zero for both convolutions and
the shift; over packed documents they run as attention does (no reset at
a document's end). `rope_parameters["hybrid_sliding"]` is a table no
layer of `layer_types` uses: read by nothing.

MoE on z = RMSNorm2(x1):

    p_l = z Wd                      [S, R]  (A) the router reads the normed input
    r_l = p_l + gamma_l r_l-1       the depth carry; r_0 = 0; gamma [R]
                                    learned, (A) the form, init 0.5
    g   = gelu(gelu(r_l W1) W2) W3  W1, W2 [R,R], W3 [R,E]; (A) three
                                    layers, the exact (erf) gelu
    P   = softmax(g)    e* = argmax(g + bias_l)    w = P[e*]
           top-1, the gate NOT renormalised; bias [E] is read for the
           choice alone and trained by no gradient (the trainer moves it
           by the step's load; zero at the first step)
    m   = w Wdown[e*](silu(Wgate[e*] z) * Wup[e*] z)

(A) no skip output: `num_experts` and `num_experts_per_tok` are all the
config states. No auxiliary loss, no z-loss. Final RMSNorm; the head is
the embedding table (`tie_word_embeddings`); the loss is the masked mean
cross-entropy over the vocabulary held.

One chip's share of a deployment: the configuration file's `num_experts`
counts the experts whose weights exist here and `whole.num_experts` the
router's width; the experts held are the share `assumed["expert_share"]`
of them (share 0 of 8 of 16: experts 0 to 7). A token whose e* is held
elsewhere gets m = 0; the router, P and the choice are over all E. Each
token's expert is found by a loop over the HELD experts with a mask, so
nothing here shares a mechanism (sort, gather, grouped matmul, buffer)
with the dispatch it checks. A file without `whole` holds every expert:
the uncut model.

Departures from the description, of storage and not of arithmetic: layers
and experts are stacked on a leading axis; gate and up projections arrive
concatenated as one [h, 2 x width] matrix an expert; Wv1 and Wv2 arrive
side by side as one [h, Hkv d] matrix (columns [0, Hkv d / 2) are Wv1);
weights are raised to float32 as they are reached; attention is computed
one KV head's group and one block of queries at a time
(reference/mellum.py `attention`); 1e-12 stands under the root of |q|_2
and |k|_2 against a row of zeros.

Weights (matrices are [in, out]):
    embed [V, h]; final_norm [h]
    layers: attn_norm, mlp_norm [L, h]; wq [L, h, Hq d]; wk [L, h, Hkv d];
    wv [L, h, Hkv d]; wo [L, Hq d, h]; conv1 [L, K0, Hq+Hkv, d]; conv2
    [L, Hq+Hkv, K1, d, d]; tau [L, Hkv]; res1, res2 {x_scale, x_bias,
    out_scale, out_bias [L, h]}; router_down [L, h, R]; router_gamma
    [L, R]; router_w1, router_w2 [L, R, R]; router_w3 [L, R, E];
    router_bias [L, E]; w_gate_up [L, held, h, 2f]; w_down [L, held, f, h]

The harness calls `program_flags`, `from_program_params`, `lm_loss` and
`train_flops_per_token` (reference/mistral.py's docstring says when).
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from benchmark.reference.mellum import (
    assumed, attention, first_held, router_width,
)
from benchmark.reference.mistral import attended_keys_mean, rms_norm

F32 = jnp.float32


# --- the program's flags and weights ----------------------------------------

def program_flags(config: Dict[str, Any], seq_length: int) -> List[str]:
    """The architecture as the explicit flags of the program's trainer.
    What the family fixes (RMSNorm, SwiGLU experts, no biases, CCA, the
    router's form, scaled residuals, top-1 with raw gates, dropless
    dispatch, no auxiliary loss) is said here once; the sizes, the rotary
    table and the share held are the configuration file's."""
    assert config["hidden_act"] == "silu" and not config["attention_bias"]
    assert not config["lm_head_bias"] and config["sliding_window"] is None
    kinds = set(config["layer_types"])
    assert len(kinds) == 1 and (
        len(config["layer_types"]) == config["num_hidden_layers"])
    rope = config["rope_parameters"][kinds.pop()]
    assert rope["rope_type"] == "default", rope
    assert rope["partial_rotary_factor"] == config["partial_rotary_factor"]
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
        "--rope_theta", str(rope["rope_theta"]),
        "--rotary_percent", str(config["partial_rotary_factor"]),
        "--attention_form", "cca",
        "--cca_conv_kernels", str(config["cca_time0"]),
        str(config["cca_time1"]),
        "--residual_scale",
        "--use_rms_norm", "--layernorm_epsilon", str(config["rms_norm_eps"]),
        "--glu_activation", "swiglu",
        "--init_method_std", str(assumed(config, "initializer_range")),
        "--num_experts", str(router_width(config)),
        "--moe_top_k", str(config["num_experts_per_tok"]),
        "--moe_dispatch", "dropless",
        "--moe_router_form", "mlp",
        "--moe_router_hidden_size", str(config["router_hidden_size"]),
        "--moe_aux_loss_coeff", str(assumed(config, "router_aux_loss_coef")),
        "--moe_z_loss_coeff", "0.0",
        "--no_moe_renorm_gates",
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
        "layers": {
            "attn_norm": layers["ln1"]["scale"],
            "mlp_norm": layers["ln2"]["scale"],
            "wq": attn["wq"], "wk": attn["wk"], "wv": attn["wv"],
            "wo": attn["wo"],
            "conv1": attn["conv1"], "conv2": attn["conv2"],
            "tau": attn["k_temp_scale"],
            "res1": layers["res1"], "res2": layers["res2"],
            "router_down": moe["router_down"],
            "router_gamma": moe["router_carry_scale"],
            "router_w1": moe["router_w1"], "router_w2": moe["router_w2"],
            "router_w3": moe["router_w3"],
            "router_bias": moe["router_bias"],
            "w_gate_up": moe["w_in"], "w_down": moe["w_out"],
        },
    }


# --- the layers -------------------------------------------------------------

def shift(x, by: int = 1):
    """x [S, ...] -> x_{t - by}, zeros before the first position."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:by]), x[:-by]], 0)


def causal_taps(x, kernel: int):
    """The `kernel` inputs of a causal convolution's output at t, oldest
    first: x_{t-kernel+1} .. x_t."""
    return [shift(x, kernel - 1 - j) for j in range(kernel)]


def partial_rotary(x, rope: Dict[str, Any]):
    """x [S, heads, d] at positions 0..S-1: the first
    `partial_rotary_factor` x d channels of a head rotated, rotate-half
    layout inside them, the rest passed."""
    s, _, d = x.shape
    r = int(d * rope["partial_rotary_factor"])
    inv_freq = rope["rope_theta"] ** (-jnp.arange(0, r, 2, dtype=F32) / r)
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., : r // 2], x[..., r // 2: r], x[..., r:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def unit_norm(x):
    """sqrt(d) x / |x|_2 over the last axis."""
    d = x.shape[-1]
    return jnp.sqrt(F32(d)) * x / jnp.sqrt(
        jnp.sum(x * x, -1, keepdims=True) + 1e-12)


def qk_mean(qt, kt):
    """(mq [S, Hq, d], mk [S, Hkv, d]) of the latents qt [S, Hq, d] and
    kt [S, Hkv, d]: query head i shares KV head i // G."""
    s, nq, d = qt.shape
    nkv = kt.shape[1]
    g = nq // nkv
    mq = (qt + jnp.repeat(kt, g, axis=1)) / 2
    mk = (jnp.mean(qt.reshape(s, nkv, g, d), 2) + kt) / 2
    return mq, mk


def cca_qkv(u, w, cfg):
    """u [S, h] -> (q [S, Hq, d], k, v [S, Hkv, d]) before the rotary."""
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, s = cfg["head_dim"], u.shape[0]
    qt = (u @ w["wq"]).reshape(s, nq, d)
    kt = (u @ w["wk"]).reshape(s, nkv, d)
    wv1, wv2 = jnp.split(w["wv"], 2, axis=-1)
    v = jnp.concatenate([u @ wv1, shift(u) @ wv2], -1).reshape(s, nkv, d)
    c = jnp.concatenate([qt, kt], 1)
    c1 = sum(w["conv1"][j] * tap
             for j, tap in enumerate(causal_taps(c, cfg["cca_time0"])))
    c2 = sum(jnp.einsum("sgd,gde->sge", tap, w["conv2"][:, j])
             for j, tap in enumerate(causal_taps(c1, cfg["cca_time1"])))
    mq, mk = qk_mean(qt, kt)
    q = unit_norm(c2[:, :nq] + mq)
    k = w["tau"][:, None] * unit_norm(c2[:, nq:] + mk)
    return q, k, v


def cca(u, w, cfg, layer: int):
    rope = cfg["rope_parameters"][cfg["layer_types"][layer]]
    q, k, v = cca_qkv(u, w, cfg)
    o = attention(partial_rotary(q, rope), partial_rotary(k, rope), v, None)
    return o.reshape(u.shape[0], -1) @ w["wo"]


def router(z, r_prev, w):
    """(logits g [S, E], the router's state r_l [S, R])."""
    r = z @ w["router_down"] + w["router_gamma"] * r_prev
    hidden = jax.nn.gelu(r @ w["router_w1"], approximate=False)
    hidden = jax.nn.gelu(hidden @ w["router_w2"], approximate=False)
    return hidden @ w["router_w3"], r


def experts(z, r_prev, w, cfg):
    """z [S, h] -> (m [S, h]: the held experts' part of the mixture, r_l
    [S, R], e* [S]: the expert each token chose among the router's E)."""
    assert cfg["num_experts_per_tok"] == 1
    logits, r = router(z, r_prev, w)
    probs = jax.nn.softmax(logits, -1)
    chosen = jnp.argmax(logits + w["router_bias"], -1)
    weight = jnp.take_along_axis(probs, chosen[:, None], axis=-1)[:, 0]

    def one_expert(y, scanned):
        e, w_gate_up, w_down = scanned
        gate, up = jnp.split(z @ w_gate_up.astype(F32), 2, axis=-1)
        out = (jax.nn.silu(gate) * up) @ w_down.astype(F32)
        return y + jnp.where(chosen == e, weight, 0.0)[:, None] * out, None

    held = first_held(cfg) + jnp.arange(cfg["num_experts"])
    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(z),
                        (held, w["w_gate_up"], w["w_down"]))
    return y, r, chosen


def scaled_add(x, out, s):
    return (s["x_scale"] * x + s["x_bias"]) + (
        s["out_scale"] * out + s["out_bias"])


def layer_forward(x, r_prev, w, cfg, layer: int):
    """One block over one sequence: (x [S, h], r_l-1 [S, R]) -> (x, r_l,
    e* [S])."""
    eps = cfg["rms_norm_eps"]
    # the experts' weights are raised one expert at a time
    w = jax.tree.map(lambda a: a.astype(F32),
                     {k: a for k, a in w.items()
                      if k not in ("w_gate_up", "w_down")}) | {
        k: w[k] for k in ("w_gate_up", "w_down")}
    a = cca(rms_norm(x, w["attn_norm"], eps), w, cfg, layer)
    x = scaled_add(x, a, w["res1"])
    m, r, chosen = experts(rms_norm(x, w["mlp_norm"], eps), r_prev, w, cfg)
    return scaled_add(x, m, w["res2"]), r, chosen


def logits_and_choices(weights: Dict[str, Any], tokens, cfg: Dict[str, Any]):
    """tokens [S] int -> (logits [S, V] float32, e* [L, S]), one sequence."""
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][tokens].astype(F32)
        r = jnp.zeros((x.shape[0], cfg["router_hidden_size"]), F32)
        choices = []
        for layer in range(cfg["num_hidden_layers"]):
            w = jax.tree.map(lambda a: a[layer], weights["layers"])
            x, r, chosen = layer_forward(x, r, w, cfg, layer)
            choices.append(chosen)
        x = rms_norm(x, weights["final_norm"].astype(F32),
                     cfg["rms_norm_eps"])
        assert cfg["tie_word_embeddings"]
        return x @ weights["embed"].astype(F32).T, jnp.stack(choices)


def lm_loss(weights, tokens, labels, loss_mask, cfg):
    """What the trainer reports as `loss` for one forward call over a
    [B, S] batch: the masked mean cross-entropy of its tokens."""
    def one(args):
        t, y, m = args
        out, _ = logits_and_choices(weights, t, cfg)
        logp = jax.nn.log_softmax(out, -1)
        ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        return jnp.sum(ce * m.astype(F32))

    ce = jax.lax.map(one, (tokens, labels, loss_mask))
    return jnp.sum(ce) / jnp.maximum(jnp.sum(loss_mask.astype(F32)), 1.0)


# --- operations and bytes ---------------------------------------------------

def forward_flops_per_token(cfg: dict, seq_length: int) -> float:
    """Forward FLOPs per token (a multiply-add is 2) of what THIS chip
    computes: the projections into and out of the latent, the two
    convolutions, the router's down projection and MLP over its whole
    width, the share of a token's expert that is held here in the mean
    (held / width), causal attention in the latent, logits over the
    vocabulary held. Norms, rotary, softmax and the embedding gather are
    not counted."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, r = cfg["moe_intermediate_size"], cfg["router_hidden_size"]
    width = router_width(cfg)
    proj = 2 * h * (nq * d) + 2 * 2 * h * (nkv * d) + 2 * (nq * d) * h
    conv = (nq + nkv) * d * 2 * (cfg["cca_time0"] + cfg["cca_time1"] * d)
    route = 2 * h * r + 2 * 2 * r * r + 2 * r * width
    mlp = (cfg["num_experts_per_tok"] * cfg["num_experts"] / width
           * (2 * h * 2 * f + 2 * f * h))
    attn = 2 * 2 * d * nq * attended_keys_mean(seq_length, None)
    return float(cfg["num_hidden_layers"] * (proj + conv + route + mlp + attn)
                 + 2 * h * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq_length: int) -> float:
    """Forward plus backward: 3 x forward. Recomputation is not counted
    (model FLOPs, not hardware FLOPs)."""
    return 3.0 * forward_flops_per_token(cfg, seq_length)
