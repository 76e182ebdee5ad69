"""Model forward tests: shapes, determinism, preset coverage, KV-cache
equivalence (counterpart of reference tests/test_layernorm_order.py's
single-layer end-to-end check)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.models import presets
from megatron_tpu.models.language_model import lm_forward, lm_loss
from megatron_tpu.models.params import init_params, num_params, param_specs, param_shapes


def _batch(cfg, batch=2, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    return {"tokens": tokens, "labels": labels,
            "loss_mask": jnp.ones((batch, seq), jnp.float32)}


@pytest.mark.parametrize("kw", [
    dict(),                                                      # llama-ish
    dict(normalization="layernorm", activation="gelu",
         use_bias_linear=True, use_bias_qkv=True,
         tie_embed_logits=True, position_embedding_type="absolute"),  # gpt-ish
    dict(normalization="layernorm", activation="gelu",
         parallel_attn=True, tie_embed_logits=True, num_kv_heads=1),  # falcon-ish
    dict(normalization="layernorm", activation="gelu", parallel_attn=True,
         parallel_layernorm=True, tie_embed_logits=True),        # falcon-40b-ish
    dict(sliding_window_size=8),                                 # mistral-ish
])
def test_forward_shapes_all_variants(kw):
    if kw.get("position_embedding_type") == "absolute":
        kw["max_position_embeddings"] = 128
    cfg = presets.tiny(**kw)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits = lm_forward(cfg, params, batch["tokens"])
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert jnp.isfinite(logits).all()


def test_param_tree_matches_specs_and_shapes():
    cfg = presets.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    specs = param_specs(cfg)
    shapes = param_shapes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(shapes)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(shapes)
    for p, s in zip(flat_p, flat_s):
        assert p.shape == s.shape
    # spec tree mirrors param tree (specs are leaves)
    from jax.sharding import PartitionSpec as P
    spec_struct = jax.tree.structure(specs, is_leaf=lambda x: isinstance(x, P))
    assert spec_struct == jax.tree.structure(params)


def test_deterministic_forward_and_init():
    cfg = presets.tiny()
    p1 = init_params(cfg, jax.random.PRNGKey(7))
    p2 = init_params(cfg, jax.random.PRNGKey(7))
    assert all((a == b).all() for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    batch = _batch(cfg)
    l1 = lm_forward(cfg, p1, batch["tokens"])
    l2 = lm_forward(cfg, p2, batch["tokens"])
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_loss_runs_and_is_finite():
    cfg = presets.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    loss, aux = lm_loss(cfg, params, _batch(cfg))
    assert np.isfinite(float(loss))
    # random init: loss should be near ln(vocab)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.0


@pytest.mark.slow  # 11s measured cacheless (PR 4 tier-1 re-budget);
# block-recompute ordering + loss tests keep remat coverage in tier-1
def test_recompute_policies_agree():
    cfg = presets.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)

    def loss_fn(recompute):
        def f(p):
            return lm_loss(cfg, p, batch, recompute=recompute)[0]
        return f

    g_none = jax.grad(loss_fn("none"))(params)
    for rec in ("full", "selective", "block:1", "block:2", "uniform:2"):
        g = jax.grad(loss_fn(rec))(params)
        for a, b in zip(jax.tree.leaves(g_none), jax.tree.leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5, err_msg=rec)


def test_block_recompute_memory_ordering():
    """--recompute_method block must actually trade memory: XLA's own
    buffer-assignment peak for grad-of-loss must order
    none >= block:half >= full (ref transformer.py:1148-1172 'fully use
    the device memory')."""
    cfg = presets.tiny(vocab_size=128, seq_length=512, hidden_size=256,
                       num_layers=8, num_attention_heads=4, num_kv_heads=4,
                       ffn_hidden_size=512)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, batch=4, seq=512)

    def temps(recompute):
        # temp_size (sum of live temporaries) is the metric that sees the
        # saved layer activations; XLA:CPU's heap-peak simulation reuses
        # buffers too aggressively to discriminate policies
        f = jax.jit(jax.grad(
            lambda p: lm_loss(cfg, p, batch, recompute=recompute)[0]))
        return int(f.lower(params).compile()
                   .memory_analysis().temp_size_in_bytes)

    t_none, t_block, t_full = temps("none"), temps("block:4"), temps("full")
    # measured 738 MB / 435 MB / 101 MB at this geometry — block:half
    # sits squarely between the extremes
    assert t_none > 1.3 * t_block > 1.3 * t_full, (t_none, t_block, t_full)


def test_kv_cache_matches_full_forward():
    """Incremental decode with per-layer caches == full forward
    (ref: InferenceParams path, text_generation/forward_step.py)."""
    cfg = presets.tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = _batch(cfg, batch=1, seq=8)["tokens"]
    full = lm_forward(cfg, params, tokens)

    L, B, S = cfg.num_layers, 1, 8
    caches = (
        jnp.zeros((L, B, S, cfg.n_kv_heads, cfg.head_dim), jnp.float32),
        jnp.zeros((L, B, S, cfg.n_kv_heads, cfg.head_dim), jnp.float32),
    )
    # prefill 4 tokens, then decode one at a time
    pos = jnp.arange(8)[None, :]
    logits, caches = lm_forward(cfg, params, tokens[:, :4], positions=pos[:, :4],
                                kv_caches=caches, cache_index=0)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, :4]),
                               rtol=2e-3, atol=2e-3)
    for t in range(4, 8):
        logits, caches = lm_forward(cfg, params, tokens[:, t:t + 1],
                                    positions=pos[:, t:t + 1],
                                    kv_caches=caches, cache_index=t)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), np.asarray(full[:, t]),
                                   rtol=2e-3, atol=2e-3)


def test_lima_dropout_ramp():
    from megatron_tpu.models.language_model import _layer_dropout_rates
    cfg = presets.tiny(hidden_dropout=0.3, lima_dropout=True, num_layers=4)
    rates = np.asarray(_layer_dropout_rates(cfg))
    np.testing.assert_allclose(rates, [0.0, 0.1, 0.2, 0.3], atol=1e-6)


def test_preset_param_counts():
    """Sanity: llama-2-7B parameter count ~6.7e9."""
    cfg = presets.llama("7B", version=2)
    n = num_params(cfg)
    assert 6.5e9 < n < 7.0e9
    cfg = presets.falcon("7B")
    n = num_params(cfg)
    assert 6.5e9 < n < 7.5e9
    cfg = presets.mistral("7B")
    n = num_params(cfg)
    assert 7.0e9 < n < 7.5e9


@pytest.mark.slow  # 11s measured cacheless (PR 4 tier-1 re-budget);
# forward_shapes_all_variants covers the post-LN wiring in tier-1
def test_post_ln_convention():
    """--use_post_ln: no pre-norm, per-layer output norm, no final stack
    norm (ref transformer.py:660-664, :1278-1281)."""
    import dataclasses

    cfg = presets.tiny(vocab_size=64, seq_length=16, num_layers=2,
                       hidden_size=32, num_attention_heads=4, num_kv_heads=2,
                       ffn_hidden_size=64, normalization="layernorm")
    post = dataclasses.replace(cfg, use_post_ln=True)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 64, (2, 16)), jnp.int32)

    out_pre = lm_forward(cfg, params, toks)
    out_post = lm_forward(post, params, toks)
    assert out_pre.shape == out_post.shape
    # genuinely different layouts
    assert float(jnp.abs(out_pre - out_post).max()) > 1e-3
    # post-LN output is normalized by the last layer's own LN: a change to
    # final_ln params must NOT affect it (final norm skipped)
    p2 = jax.tree.map(lambda x: x, params)
    p2["final_ln"] = {k: v * 3.0 for k, v in params["final_ln"].items()}
    np.testing.assert_allclose(np.asarray(lm_forward(post, p2, toks)),
                               np.asarray(out_post), rtol=1e-6)
    # residual-post-layernorm variant runs and differs from both
    rpl = dataclasses.replace(cfg, apply_residual_post_ln=True)
    out_rpl = lm_forward(rpl, params, toks)
    assert float(jnp.abs(out_rpl - out_pre).max()) > 1e-3
    # both train
    batch = {"tokens": toks, "labels": toks,
             "loss_mask": jnp.ones((2, 16), jnp.float32)}
    for c in (post, rpl):
        g = jax.grad(lambda p: lm_loss(c, p, batch)[0])(params)
        assert all(np.isfinite(np.asarray(x)).all()
                   for x in jax.tree.leaves(jax.device_get(g)))


# ---------------------------------------------------------------------------
# The q/k/v products of a call with few rows (PR 55)
# ---------------------------------------------------------------------------

def _attention_block_as_it_was(cfg, p, x, rope, positions, **rest):
    """`transformer.attention_block` without a cache as the parent of PR
    55 had it, the plain reference: the three products, bias and qk norm,
    and the split into heads straight behind them, whatever the rows."""
    from megatron_tpu.ops.attention import attention
    from megatron_tpu.ops.normalization import rmsnorm
    from megatron_tpu.ops.rotary import apply_rotary_emb

    assert rest.get("kv_cache") is None
    b, s, _ = x.shape
    nq, nkv, D = cfg.num_attention_heads, cfg.n_kv_heads, cfg.head_dim
    q, k, v = (jnp.einsum("...k,kn->...n", x, p[w])
               for w in ("wq", "wk", "wv"))
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"]["scale"], cfg.layernorm_epsilon)
        k = rmsnorm(k, p["k_norm"]["scale"], cfg.layernorm_epsilon)
    q, k, v = (t.reshape(b, s, n, D) for t, n in ((q, nq), (k, nkv), (v, nkv)))
    if rope is not None:
        q, k = apply_rotary_emb(q, k, rope[0], rope[1], positions)
    ctx = attention(q, k, v, mask_type=cfg.attn_mask_type,
                    sliding_window=cfg.attention_kind.sliding_window_size,
                    impl=cfg.attention_impl, softmax_fp32=cfg.softmax_fp32)
    out = jnp.einsum("...k,kn->...n", ctx.reshape(b, s, nq * D), p["wo"])
    return (out + p["bo"] if "bo" in p else out), None


def _barriers(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("optimization_barrier")


def _close_to_bf16_rounding(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    scale = max(float(np.abs(want).max()), 1e-6)
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -8 * scale)


_QKV_VARIANTS = {"plain": {}, "bias": dict(use_bias_qkv=True),
                 "qk_norm": dict(qk_norm=True),
                 "bias_qk_norm": dict(use_bias_qkv=True, qk_norm=True)}


@pytest.mark.parametrize("variant", list(_QKV_VARIANTS))
@pytest.mark.parametrize("batch, seq, apart", [(1, 32, True), (2, 16, True),
                                               (1, 64, False), (2, 64, False)],
                         ids=["32_rows", "2x16_rows", "64_rows", "128_rows"])
def test_attention_block_is_the_parents_on_either_side_of_its_rows(
        variant, batch, seq, apart):
    """With fewer rows than the weights have (64 here) the block keeps its
    three products apart from the split into heads, with as many or more
    it is the parent's to the letter; either way it computes what the
    parent's did, bias and qk norm between the product and the split
    included: forward and gradient equal to bf16 rounding."""
    from megatron_tpu.models import transformer
    from megatron_tpu.ops.rotary import rope_table

    cfg = presets.tiny(params_dtype="bfloat16", **_QKV_VARIANTS[variant])
    p = jax.tree.map(lambda leaf: leaf[0], init_params(
        cfg, jax.random.PRNGKey(1))["layers"]["attn"])
    if "bq" in p:   # biases start at zero: give them something to add
        p = {**p, **{b: jax.random.normal(jax.random.PRNGKey(i), p[b].shape,
                                          p[b].dtype)
                     for i, b in enumerate(("bq", "bk", "bv"))}}
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (batch, seq, cfg.hidden_size), jnp.bfloat16)
    rope = rope_table(cfg.attention_kind, cfg.head_dim, cfg.seq_length)

    def loss(block, p, x):
        out, _ = block(cfg, p, x, rope, None)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    assert _barriers(lambda p, x: transformer.attention_block(
        cfg, p, x, rope, None)[0], p, x) == (1 if apart else 0)
    (_, out), grads = jax.value_and_grad(
        lambda p, x: loss(transformer.attention_block, p, x),
        argnums=(0, 1), has_aux=True)(p, x)
    (_, want), want_grads = jax.value_and_grad(
        lambda p, x: loss(_attention_block_as_it_was, p, x),
        argnums=(0, 1), has_aux=True)(p, x)
    _close_to_bf16_rounding(out, want)
    jax.tree.map(_close_to_bf16_rounding, grads, want_grads)


@pytest.mark.parametrize("batch, seq, apart", [(1, 32, True), (2, 64, False)],
                         ids=["32_rows", "128_rows"])
def test_lm_forward_is_the_parents_on_either_side_of_its_rows(
        monkeypatch, batch, seq, apart):
    """The whole toy model, qk norm and bias on: logits and the loss's
    gradient with the block as it is against the block as it was."""
    from megatron_tpu.models import transformer

    cfg = presets.tiny(params_dtype="bfloat16", use_bias_qkv=True,
                       qk_norm=True)
    params = init_params(cfg, jax.random.PRNGKey(3))
    data = _batch(cfg, batch=batch, seq=seq)

    def run():
        logits = lm_forward(cfg, params, data["tokens"])
        grads = jax.grad(lambda p: lm_loss(cfg, p, data)[0])(params)
        return logits, grads

    assert _barriers(lambda p: lm_forward(cfg, p, data["tokens"]),
                     params) == (1 if apart else 0)
    logits, grads = run()
    monkeypatch.setattr(transformer, "attention_block",
                        _attention_block_as_it_was)
    want_logits, want_grads = run()
    _close_to_bf16_rounding(logits, want_logits)
    jax.tree.map(_close_to_bf16_rounding, grads, want_grads)
