"""Plain reference for the Nemotron-H decoder (NVIDIA's `NemotronHForCausalLM`,
HF modeling_nemotron_h.py; the family's description, arXiv:2504.03624):
float32 `jax.numpy`, no kernels, no cache, no batching,
`default_matmul_precision("highest")`.

A stack of layers that are ONE block each. Layer i is of the kind
`hybrid_override_pattern[i]`: `M` a Mamba-2 mixer, `*` attention, `E` an
expert layer, `-` a dense FFN alone. Every layer:

    x = x + block_i(RMSNorm_i(x))          one norm, one block, one add

then a final RMSNorm and an untied head. No biases but the convolution's.
Keys are the source's. H = `mamba_num_heads`, P = `mamba_head_dim`,
d_i = H P, G = `n_groups`, N = `ssm_state_size`, K = `conv_kernel`,
eps = `layer_norm_epsilon`.

  * `M`, Mamba-2 / SSD (arXiv:2405.21060; `NemotronHMamba2Mixer.
    torch_forward`), u [T, hidden]:
        (z, xBC, dt) = split(u W_in, [d_i, d_i + 2GN, H])
        xBC_t  = silu(b_conv + sum_{j<K} w_conv[j] * xBC[t-K+1+j])     depthwise over all d_i + 2GN channels, zeros left of 0
        (x, B, C) = split(xBC, [d_i, GN, GN]); x as [H, P], B and C as [G, N]; head h reads group g(h) = h // (H / G)
        dt_t[h] = softplus(dt_t[h] + dt_bias[h]);  A[h] = -exp(A_log[h])    a scalar a head
        S_t[h]  = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] * x_t[h] (outer) B_t[g(h)]      [P, N], float32, S_{-1} = 0
        y_t[h]  = S_t[h] C_t[g(h)] + D[h] x_t[h]
        y       = RMSNorm over each of the G groups of d_i / G channels of (y * silu(z)), times a learned weight [d_i]
                  (`MambaRMSNormGated`, `norm_before_gate` false)
        out     = y W_out
    the recurrence one plain `lax.scan` over positions (the program runs a
    chunked form, `chunk_size` positions a chunk: this is the independent
    statement it must equal).
  * `*`, attention: grouped-query, causal, full, scale head_dim^-0.5, no
    biases and NO positional encoding (`NemotronHAttention` applies none;
    `rope_theta` and `partial_rotary_factor` are read by nothing).
  * `E`, LatentMoE, u [T, hidden]:
        s = sigmoid(u W_r)                                  float32, [T, n_routed_experts]
        chosen = the `num_experts_per_tok` largest of s + b    b = `e_score_correction_bias`, for the choice alone
                 (`n_group` 1, `topk_group` 1: one group, no group limit)
        w_e = s_e / (sum over the chosen of s + 1e-20)      `norm_topk_prob`
              times `routed_scaling_factor`
        l = u W_dn                                          [T, moe_latent_size]: the routed experts' width
        o_e = relu(l W1_e)^2 W2_e                           `mlp_hidden_act` relu2: two matrices, no gate matrix
        routed = (sum over the chosen of w_e o_e) W_up
        shared = relu(u Ws1)^2 Ws2                          `moe_shared_expert_intermediate_size` wide, reads the full-width u
        block = routed + shared
  * `-`, a dense FFN alone: relu(u W1)^2 W2, `intermediate_size` wide.

One chip's share of a deployment: the configuration file's
`n_routed_experts` counts the experts whose weights exist here and
`whole.n_routed_experts` the router's width; the experts held are the
share `assumed["expert_share"]` of them (share 0 of 128 of 512: experts 0
to 127). Each token's experts are chosen over the router's whole width;
every held expert multiplies every token and a mask keeps what was routed
to it, so nothing here shares a mechanism (sort, gather, grouped product,
buffer) with the dispatch it checks. What the absent experts would add is
left out, here as in the program, and the partial result goes on to the
next layer; the latent projections, the shared expert and the mixers are
whole. A file without `whole` holds every expert: the uncut model. The
vocabulary held is a smaller vocabulary: logits over the slice.

Departures, all of storage and none of arithmetic: a kind's layers are
stacked on a leading axis over THAT kind's layers, the norms over all;
matrices are [in, out]; the convolution's weight is [K, channels] where HF
holds [channels, 1, K]; a layer's weights (an expert's, one at a time) are
raised to float32 as they are reached. What the source leaves open is the
configuration's `assumed`: the latent projections linear, without bias or
norm, in front of the dispatch and behind the weighted sum (Megatron-LM's
`moe_latent_size`); no multi-token-prediction module (it adds nothing to
the next-token logits).

Weights, by HF's module names:
    embeddings [V, h]; norm_f [h]; lm_head [h, V]; norm [L, h]
    mamba: in_proj [Lm, h, 2 d_i + 2GN + H]; conv1d_weight [Lm, K, d_i + 2GN];
        conv1d_bias [Lm, d_i + 2GN]; dt_bias, A_log, D [Lm, H];
        norm_weight [Lm, d_i]; out_proj [Lm, d_i, h]
    attention: q_proj [La, h, nq d]; k_proj, v_proj [La, h, nkv d];
        o_proj [La, nq d, h]
    moe: gate_weight [Le, h, E]; e_score_correction_bias [Le, E];
        fc1_latent_proj [Le, h, latent]; fc2_latent_proj [Le, latent, h];
        experts_up_proj [Le, held, latent, f]; experts_down_proj [Le, held, f, latent];
        shared_up_proj [Le, h, fs]; shared_down_proj [Le, fs, h]
    mlp (`-` layers, where the pattern has them): up_proj [Ld, h, fd];
        down_proj [Ld, fd, h]

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
# the source's letter for a kind of layer: the program's layer type
KINDS = {"M": "mamba2", "*": "attention", "E": "moe", "-": "mlp"}


def assumed(config: Dict[str, Any], key: str):
    """What the source leaves open and the configuration states under
    `assumed` ({key: {"value", "why"}})."""
    return config["assumed"][key]["value"]


def layer_kinds(cfg: Dict[str, Any]) -> str:
    """The kind of each layer, in order: the source's own string."""
    kinds = cfg["hybrid_override_pattern"]
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError(
            f"hybrid_override_pattern {kinds!r} is not one of {sorted(KINDS)} "
            f"a layer of {cfg['num_hidden_layers']}")
    return kinds


def router_width(config: Dict[str, Any]) -> int:
    return config.get("whole", {}).get("n_routed_experts",
                                       config["n_routed_experts"])


def first_held(config: Dict[str, Any]) -> int:
    """The router's index of the first expert whose weights exist here."""
    if "whole" not in config:
        return 0
    return assumed(config, "expert_share") * config["n_routed_experts"]


def sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {
        "h": cfg["hidden_size"], "v": cfg["vocab_size"],
        "nq": cfg["num_attention_heads"], "nkv": cfg["num_key_value_heads"],
        "d": cfg["head_dim"],
        "H": heads, "P": p, "di": heads * p, "G": g, "N": n,
        "K": cfg["conv_kernel"], "W": heads * p + 2 * g * n,
        "E": router_width(cfg), "held": cfg["n_routed_experts"],
        "k": cfg["num_experts_per_tok"], "latent": cfg["moe_latent_size"],
        "f": cfg["moe_intermediate_size"],
        "fs": cfg["moe_shared_expert_intermediate_size"],
        "fd": cfg["intermediate_size"],
    }


# --- the program's flags and weights ----------------------------------------

def program_flags(config: Dict[str, Any], seq_length: int) -> List[str]:
    """The architecture as the explicit flags trainer and server share."""
    s, kinds = sizes(config), layer_kinds(config)
    if config["mlp_hidden_act"] != "relu2" or config["n_shared_experts"] != 1 \
            or config["n_group"] != 1 or config["topk_group"] != 1 \
            or not config["norm_topk_prob"] or config["tie_word_embeddings"]:
        raise ValueError("this reference holds Nemotron-H with relu2, one "
                         "shared expert, one router group, gates normalised "
                         "over the chosen and an untied head")
    if s["di"] != config["expand"] * s["h"]:
        raise ValueError("mamba_num_heads x mamba_head_dim is not expand x "
                         "hidden_size")
    if "-" in kinds and s["fd"] != s["f"]:
        raise NotImplementedError(
            "a dense FFN layer of another width than an expert's: the "
            "program has one --ffn_hidden_size")
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
        "--layer_pattern", json.dumps([KINDS[kind] for kind in kinds]),
        "--ssm_d_state", str(s["N"]), "--ssm_d_conv", str(s["K"]),
        "--ssm_expand", str(config["expand"]),
        "--ssm_num_heads", str(s["H"]), "--ssm_n_groups", str(s["G"]),
        "--ssm_chunk_size", str(config["chunk_size"]),
        "--use_rms_norm", "--layernorm_epsilon",
        str(config["layer_norm_epsilon"]),
        "--activation", "squared_relu",
        "--num_experts", str(s["E"]), "--moe_top_k", str(s["k"]),
        "--moe_dispatch", "dropless", "--moe_renorm_gates",
        "--moe_router_score", "sigmoid",
        "--moe_route_scale", str(config["routed_scaling_factor"]),
        "--moe_latent_size", str(s["latent"]),
        "--moe_shared_ffn_size", str(s["fs"]),
        "--init_method_std", str(assumed(config, "initializer_range")),
        "--no_tie_embed_logits",
    ]
    if "whole" in config:
        flags += ["--moe_experts_held", str(s["held"]),
                  "--moe_expert_share", str(assumed(config, "expert_share"))]
    return flags


# the reference's name: the program's (under layers/<group>)
_NAMES = {
    "mamba": ("ssm", {
        "in_proj": "w_in", "conv1d_weight": "conv_w", "conv1d_bias": "conv_b",
        "dt_bias": "b_dt", "A_log": "a_log", "D": "d_skip",
        "norm_weight": "norm/scale", "out_proj": "w_out"}),
    "attention": ("attn", {
        "q_proj": "wq", "k_proj": "wk", "v_proj": "wv", "o_proj": "wo"}),
    "moe": ("moe", {
        "gate_weight": "router", "e_score_correction_bias": "router_bias",
        "fc1_latent_proj": "latent_in", "fc2_latent_proj": "latent_out",
        "experts_up_proj": "w_in", "experts_down_proj": "w_out",
        "shared_up_proj": "shared_in", "shared_down_proj": "shared_out"}),
    "mlp": ("mlp", {"up_proj": "w_in", "down_proj": "w_out"}),
}


def from_program_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """The program's parameter tree (megatron_tpu/models/params.py) under
    the reference's names. No value is changed or copied."""
    layers = params["layers"]
    out = {"embeddings": params["embed"]["tokens"],
           "norm_f": params["final_ln"]["scale"],
           "lm_head": params["lm_head"]["w"],
           "norm": layers["ln1"]["scale"]}
    for group, (theirs, names) in _NAMES.items():
        if theirs in layers:
            out[group] = {}
            for ours, path in names.items():
                leaf = layers[theirs]
                for part in path.split("/"):
                    leaf = leaf[part]
                out[group][ours] = leaf
    return out


def to_program_params(weights: Dict[str, Any]) -> Dict[str, Any]:
    """The reference's weights under the program's names: the inverse of
    from_program_params. No value is changed or copied."""
    layers: Dict[str, Any] = {"ln1": {"scale": weights["norm"]}}
    for group, (theirs, names) in _NAMES.items():
        if group in weights:
            layers[theirs] = {}
            for ours, path in names.items():
                node, parts = layers[theirs], path.split("/")
                for part in parts[:-1]:
                    node = node.setdefault(part, {})
                node[parts[-1]] = weights[group][ours]
    return {"embed": {"tokens": weights["embeddings"]},
            "final_ln": {"scale": weights["norm_f"]},
            "lm_head": {"w": weights["lm_head"]}, "layers": layers}


def weight_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Shape of every weight, by the reference's names (module docstring);
    a kind the pattern lacks has no group."""
    s, kinds = sizes(cfg), layer_kinds(cfg)
    h, v, d, di, nq, nkv = s["h"], s["v"], s["d"], s["di"], s["nq"], s["nkv"]
    lm, la, le, ld = (kinds.count(kind) for kind in "M*E-")
    shapes: Dict[str, Any] = {
        "embeddings": (v, h), "norm_f": (h,), "lm_head": (h, v),
        "norm": (len(kinds), h)}
    if lm:
        shapes["mamba"] = {
            "in_proj": (lm, h, di + s["W"] + s["H"]),
            "conv1d_weight": (lm, s["K"], s["W"]), "conv1d_bias": (lm, s["W"]),
            "dt_bias": (lm, s["H"]), "A_log": (lm, s["H"]), "D": (lm, s["H"]),
            "norm_weight": (lm, di), "out_proj": (lm, di, h)}
    if la:
        shapes["attention"] = {
            "q_proj": (la, h, nq * d), "k_proj": (la, h, nkv * d),
            "v_proj": (la, h, nkv * d), "o_proj": (la, nq * d, h)}
    if le:
        shapes["moe"] = {
            "gate_weight": (le, h, s["E"]),
            "e_score_correction_bias": (le, s["E"]),
            "fc1_latent_proj": (le, h, s["latent"]),
            "fc2_latent_proj": (le, s["latent"], h),
            "experts_up_proj": (le, s["held"], s["latent"], s["f"]),
            "experts_down_proj": (le, s["held"], s["f"], s["latent"]),
            "shared_up_proj": (le, h, s["fs"]),
            "shared_down_proj": (le, s["fs"], h)}
    if ld:
        shapes["mlp"] = {"up_proj": (ld, h, s["fd"]),
                         "down_proj": (ld, s["fd"], h)}
    return shapes


_ONES = ("norm_f", "norm", "norm_weight", "D")
# the matrices that write into the residual stream, scaled by the depth
# held (`rescale_prenorm_residual`, as the program's init scales them)
_SCALED = ("out_proj", "o_proj", "fc2_latent_proj", "experts_down_proj",
           "shared_down_proj", "down_proj")


def make_weights(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16):
    """Seeded weights of a served cell, in the type they are served in,
    one jitted call on the device, as the configuration's `assumed` says:
    every matrix, the convolution's weight and bias among them, normal
    with `initializer_range` (those of _SCALED over sqrt(2 x the layers
    held)); every norm's weight and `D` 1; `A_log` the log of a uniform
    draw in [1, 16) a head; `dt_bias` such that softplus(bias) is
    log-uniform in [`time_step_min`, `time_step_max`], floored at
    `time_step_floor`; `e_score_correction_bias` normal at
    `assumed["e_score_correction_bias"]`'s scale. Any whole number a
    little over 2**31 is a seed."""
    paths, tree = jax.tree_util.tree_flatten_with_path(
        weight_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    std = assumed(cfg, "initializer_range")
    scaled = std / math.sqrt(2.0 * cfg["num_hidden_layers"])
    bias_std = assumed(cfg, "e_score_correction_bias")
    lo, hi = cfg["time_step_min"], cfg["time_step_max"]

    def make(key):
        out = []
        for i, (path, shape) in enumerate(paths):
            name, k = path[-1].key, jax.random.fold_in(key, i)
            if name in _ONES:
                leaf = jnp.ones(shape, F32)
            elif name == "A_log":
                leaf = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
            elif name == "dt_bias":
                dt = jnp.maximum(
                    jnp.exp(jax.random.uniform(
                        k, shape, F32, math.log(lo), math.log(hi))),
                    cfg["time_step_floor"])
                leaf = dt + jnp.log(-jnp.expm1(-dt))  # softplus's inverse
            elif name == "e_score_correction_bias":
                leaf = jax.random.normal(k, shape, F32) * bias_std
            else:
                leaf = jax.random.normal(k, shape, F32) * (
                    scaled if name in _SCALED else std)
            out.append(leaf.astype(dtype))
        return jax.tree.unflatten(tree, out)

    seed = int(seed)
    key = jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)
    return jax.jit(make)(key)


# --- the forward pass ---------------------------------------------------------

def relu2(x):
    return jnp.square(jax.nn.relu(x))


def attention(q, k, v):
    """q [S, nq, d], k/v [S, nkv, d] -> [S, nq, d]: causal, full, one
    query head at a time (its scores are [S, S]: 16 heads share a KV head
    here, and a group's scores at 4,096 positions would be a gigabyte
    beside the weights this has to fit next to)."""
    s, nq, d = q.shape
    group = nq // k.shape[1]
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    def one_head(args):
        qh, kh, vh = args                              # [S, d] each
        scores = jnp.where(mask, (qh @ kh.T) / jnp.sqrt(F32(d)), -jnp.inf)
        return jax.nn.softmax(scores, -1) @ vh

    of_head = lambda t: jnp.repeat(  # noqa: E731
        t.transpose(1, 0, 2), group, axis=0)           # [nq, S, d]
    out = jax.lax.map(one_head, (q.transpose(1, 0, 2), of_head(k),
                                 of_head(v)))
    return out.transpose(1, 0, 2)


def mamba2_mixer(u, w, cfg, mm, state_dtype=F32):
    """u [S, h] (normed) -> [S, h]: the module docstring's equations, the
    recurrence a plain scan over positions. (A `state_dtype` below float32
    rounds `S` after every step: what a state kept in that type would be,
    for the tests' control.)"""
    s = sizes(cfg)
    di, heads, p, g, n, k = s["di"], s["H"], s["P"], s["G"], s["N"], s["K"]
    length = u.shape[0]
    z, xbc, dt = jnp.split(mm(u, w["in_proj"]), [di, di + s["W"]], axis=-1)
    padded = jnp.concatenate([jnp.zeros((k - 1, s["W"]), F32), xbc])
    xbc = jax.nn.silu(w["conv1d_bias"] + sum(
        w["conv1d_weight"][j] * padded[j:j + length] for j in range(k)))
    x, b, c = jnp.split(xbc, [di, di + g * n], axis=-1)
    x = x.reshape(length, heads, p)
    group_of = jnp.arange(heads) // (heads // g)               # g(h)
    b, c = b.reshape(length, g, n), c.reshape(length, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])                   # [S, H]
    a = -jnp.exp(w["A_log"])                                  # [H]

    def step(state, at):                                      # [H, P, N]
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = b_t[group_of], c_t[group_of]               # [H, N]
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        state = state.astype(state_dtype).astype(F32)
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), F32), (x, dt, b, c))
    y = (y + w["D"][:, None] * x).reshape(length, di) * jax.nn.silu(z)
    y = y.reshape(length, g, di // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    return mm(y.reshape(length, di) * w["norm_weight"], w["out_proj"])


def route(u, w, cfg, mm, router_score="sigmoid"):
    """u [S, h] -> (the chosen experts [S, k] of the router's whole width,
    their gates [S, k]). (`router_score` "softmax": the wrong router, for
    the tests' control.)"""
    k = cfg["num_experts_per_tok"]
    logits = mm(u, w["gate_weight"])
    if router_score == "softmax":
        weight, chosen = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + w["e_score_correction_bias"], k)
        weight = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = weight / (jnp.sum(weight, -1, keepdims=True) + 1e-20)
    return chosen, weight * cfg["routed_scaling_factor"]


def latent_moe(u, w, cfg, mm, router_score="sigmoid", held=None):
    """u [S, h] (normed) -> [S, h]: the held experts' part of the routed
    mixture through the latent width, plus the shared expert. `w` holds
    the experts' matrices in the type they are kept in (raised one expert
    at a time), everything else in float32. held: the router's indices of
    the experts `w` holds (None: the configuration's share)."""
    chosen, weight = route(u, w, cfg, mm, router_score)
    latent = mm(u, w["fc1_latent_proj"])
    up, down = w["experts_up_proj"], w["experts_down_proj"]
    # the layer's own [held, ...] matrices, or (`experts_layer`) every
    # expert layer's stacked, of which one expert's are taken as they are
    # reached: a layer's sliced out whole would be a copy of 1.4 GB
    layer = (w["experts_layer"],) if "experts_layer" in w else ()
    if held is None:
        held = first_held(cfg) + jnp.arange(up.shape[len(layer)])

    def one_expert(y, at):
        i, e = at
        out = mm(relu2(mm(latent, up[(*layer, i)].astype(F32))),
                 down[(*layer, i)].astype(F32))
        mine = jnp.sum(jnp.where(chosen == e, weight, 0.0), -1)   # [S]
        return y + mine[:, None] * out, None

    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(latent),
                             (jnp.arange(held.shape[0]), held))
    shared = mm(relu2(mm(u, w["shared_up_proj"])), w["shared_down_proj"])
    return mm(routed, w["fc2_latent_proj"]) + shared


def attention_mixer(u, w, cfg, mm):
    """u [S, h] (normed) -> [S, h]: no positional encoding."""
    s = sizes(cfg)
    nq, nkv, d, length = s["nq"], s["nkv"], s["d"], u.shape[0]
    q = mm(u, w["q_proj"]).reshape(length, nq, d)
    k = mm(u, w["k_proj"]).reshape(length, nkv, d)
    v = mm(u, w["v_proj"]).reshape(length, nkv, d)
    return mm(attention(q, k, v).reshape(length, nq * d), w["o_proj"])


def dense_ffn(u, w, cfg, mm):
    return mm(relu2(mm(u, w["up_proj"])), w["down_proj"])


_EXPERTS = ("experts_up_proj", "experts_down_proj")


def logits(weights: Dict[str, Any], tokens, cfg: Dict[str, Any], lowp=None,
           state_dtype=F32, router_score="sigmoid"):
    """tokens [S] int -> logits [S, V] float32, one sequence. With
    `lowp` (fp8) both operands of every product with a weight pass
    through it first: the control, never the reference."""
    def mm(a, w):
        return a @ w if lowp is None else lowp(a) @ lowp(w)

    blocks = {
        "M": ("mamba", lambda u, w: mamba2_mixer(u, w, cfg, mm, state_dtype)),
        "*": ("attention", lambda u, w: attention_mixer(u, w, cfg, mm)),
        "E": ("moe", lambda u, w: latent_moe(u, w, cfg, mm, router_score)),
        "-": ("mlp", lambda u, w: dense_ffn(u, w, cfg, mm)),
    }
    with jax.default_matmul_precision("highest"):
        eps = cfg["layer_norm_epsilon"]
        x = weights["embeddings"][tokens].astype(F32)
        seen = dict.fromkeys(blocks, 0)
        for i, kind in enumerate(layer_kinds(cfg)):
            group, block = blocks[kind]
            at = seen[kind]
            w = {name: leaf if name in _EXPERTS else leaf[at].astype(F32)
                 for name, leaf in weights[group].items()}
            if group == "moe":
                w["experts_layer"] = at
            x = x + block(rms_norm(x, weights["norm"][i].astype(F32), eps), w)
            seen[kind] += 1
        x = rms_norm(x, weights["norm_f"].astype(F32), eps)
        return mm(x, weights["lm_head"].astype(F32))


def lm_loss(weights, tokens, labels, loss_mask, cfg):
    """Mean cross-entropy of a [B, S] batch, weighted by loss_mask, as
    the trainer reports it (one sequence at a time); no auxiliary loss
    goes with a sigmoid router."""
    return mistral.lm_loss(weights, tokens, labels, loss_mask, cfg,
                           logits=logits)


# --- operations and bytes ---------------------------------------------------

def num_params(cfg: dict) -> int:
    shapes = jax.tree.leaves(weight_shapes(cfg),
                             is_leaf=lambda x: isinstance(x, tuple))
    return sum(math.prod(shape) for shape in shapes)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int = 2) -> int:
    s = sizes(cfg)
    return (2 * layer_kinds(cfg).count("*") * s["nkv"] * s["d"]
            * bytes_per_value)


def state_bytes_per_sequence(cfg: dict, tail_bytes_per_value: int = 2) -> int:
    """The recurrent state (float32) and the convolution's tail a
    sequence holds, over the Mamba-2 layers."""
    s = sizes(cfg)
    return layer_kinds(cfg).count("M") * (
        s["H"] * s["P"] * s["N"] * 4
        + (s["K"] - 1) * s["W"] * tail_bytes_per_value)
