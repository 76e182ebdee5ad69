"""Plain reference for a second block type, as a later PR would add it:
the Falcon decoder (tiiuae/falcon-40b, `new_decoder_architecture`), whose
attention and MLP both read the layer's input, each through a LayerNorm
of its own, and are added to the residual together; grouped-query
attention with rotary embeddings, a GELU MLP of four times the width,
no biases on the matrices, output head tied to the embedding. Float32
`jax.numpy`, `default_matmul_precision("highest")`.

A test asset (tests/benchmark): it shows that an architecture is a file
found by the name in its configuration, with no edit to the harness.
What it shares with the Mistral reference (rotary, attention over one
sequence, the loss) it takes from there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import mistral as shared

F32 = jnp.float32


def program_flags(config, seq_length):
    flags = [
        "--num_layers", str(config["num_hidden_layers"]),
        "--hidden_size", str(config["hidden_size"]),
        "--num_attention_heads", str(config["num_attention_heads"]),
        "--num_attention_heads_kv", str(config["num_kv_heads"]),
        "--ffn_hidden_size", str(4 * config["hidden_size"]),
        "--vocab_size", str(config["vocab_size"]),
        "--seq_length", str(seq_length),
        "--max_position_embeddings", str(seq_length),
        "--position_embedding_type", "rotary",
        "--rope_theta", str(config["rope_theta"]),
        "--layernorm_epsilon", str(config["layer_norm_epsilon"]),
        "--init_method_std", str(config["initializer_range"]),
    ]
    if config["parallel_attn"]:
        flags.append("--parallel_attn")
    if config["new_decoder_architecture"]:
        flags.append("--parallel_layernorm")
    return flags


def from_program_params(params):
    layers = params["layers"]
    return {
        "embed": params["embed"]["tokens"],
        "final_norm": params["final_ln"],
        "layers": {
            "ln_attn": layers["ln1"], "ln_mlp": layers["ln_mlp"],
            "wq": layers["attn"]["wq"], "wk": layers["attn"]["wk"],
            "wv": layers["attn"]["wv"], "wo": layers["attn"]["wo"],
            "w_up": layers["mlp"]["w_in"], "w_down": layers["mlp"]["w_out"],
        },
    }


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def logits(weights, tokens, cfg):
    with jax.default_matmul_precision("highest"):
        nq, nkv = cfg["num_attention_heads"], cfg["num_kv_heads"]
        d = cfg["hidden_size"] // nq
        eps, theta = cfg["layer_norm_epsilon"], cfg["rope_theta"]
        s = tokens.shape[0]
        embed = weights["embed"].astype(F32)
        x = embed[tokens]

        def layer(x, w):
            w = jax.tree.map(lambda a: a.astype(F32), w)
            h = _layer_norm(x, w["ln_attn"], eps)
            q = shared.rotary((h @ w["wq"]).reshape(s, nq, d), theta)
            k = shared.rotary((h @ w["wk"]).reshape(s, nkv, d), theta)
            v = (h @ w["wv"]).reshape(s, nkv, d)
            a = shared.attention(q, k, v, None).reshape(s, nq * d) @ w["wo"]
            m = _layer_norm(x, w["ln_mlp"], eps)
            m = jax.nn.gelu(m @ w["w_up"], approximate=False) @ w["w_down"]
            return x + a + m, None

        x, _ = jax.lax.scan(layer, x, weights["layers"])
        final = jax.tree.map(lambda a: a.astype(F32), weights["final_norm"])
        return _layer_norm(x, final, eps) @ embed.T


def next_token_logprobs(weights, tokens, cfg):
    return shared.next_token_logprobs(weights, tokens, cfg, logits)


def lm_loss(weights, tokens, labels, loss_mask, cfg):
    return shared.lm_loss(weights, tokens, labels, loss_mask, cfg, logits)
