"""ZAYA1's layer through the program's normal path, at toy widths on the
CPU, against benchmark/reference/zaya.py (float32): compressed
convolutional attention (ops/cca.py), the router that is an MLP over a
state carried from layer to layer (ops/moe.py router_mlp, run_layers'
carry), top-1 over one chip's share of the experts, scaled residuals,
partial rotary, and the selection bias that no gradient trains
(training/optimizer.py update_selection_bias)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402
from megatron_tpu.arguments import args_to_run_config, parse_args  # noqa: E402
from megatron_tpu.config import (  # noqa: E402
    AttentionKind, ModelConfig, OptimizerConfig, ParallelConfig, RunConfig,
    TrainingConfig, model_config_from_saved,
)
from megatron_tpu.models import language_model as lm  # noqa: E402
from megatron_tpu.models.params import init_params  # noqa: E402
from megatron_tpu.models.transformer import (  # noqa: E402
    attention_block, block_forward,
)
from megatron_tpu.ops import cca, moe  # noqa: E402
from megatron_tpu.ops.rotary import apply_rotary_emb, rope_table  # noqa: E402
from megatron_tpu.training import checkpointing  # noqa: E402
from megatron_tpu.training import optimizer as opt  # noqa: E402
from megatron_tpu.training.train_step import make_train_step  # noqa: E402

ref = spec.load_module(os.path.join(REPO, "benchmark", "reference", "zaya.py"))

F32 = jnp.float32
SEQ, ROWS = 32, 2
# the benchmark's configuration at toy widths: two layers, so that the
# router's state is carried and differentiated through; experts 0 to 3
# held of a router 8 wide; half of each head of 16 rotated
TOY = {
    "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64,
    "layer_types": ["hybrid", "hybrid"], "lm_head_bias": False,
    "moe_intermediate_size": 32, "num_attention_heads": 4, "num_experts": 4,
    "num_experts_per_tok": 1, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-5,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5000000,
                                   "rope_type": "default"}},
    "router_hidden_size": 16, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 128,
    "whole": {"num_experts": 8, "vocab_size": 1024},
    "assumed": {"initializer_range": {"value": 0.02},
                "router_aux_loss_coef": {"value": 0.0},
                "expert_share": {"value": 0}},
}
BALANCED = ["--moe_bias_update_rate", "1e-3"]


def model_of(config=TOY):
    return args_to_run_config(parse_args(
        ref.program_flags(config, SEQ) + ["--fp32", "--micro_batch_size", "2",
                                          "--global_batch_size", "2"]
        + BALANCED)).model


def lively(params, key, scale=0.3):
    """Weights as the initializer leaves them hide faults: vectors at 1, 0
    or 0.5 do not show how they are used, and a router at std 0.02 passes
    gradients of 1e-8. Every leaf but the selection bias gets noise of
    `scale` times its own spread (or `scale` itself, from a constant)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, a) in enumerate(flat):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith("router_bias"):
            out.append(a)
            continue
        spread = jnp.maximum(jnp.std(a), 0.0)
        std = jnp.where(spread > 0, 4.0 * spread, scale)
        if "router_w" in name or "router_down" in name:
            std = 0.5
        out.append(a + std * jax.random.normal(
            jax.random.fold_in(key, i), a.shape, a.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def batch_of(seed=0, rows=ROWS):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, TOY["vocab_size"], (rows, SEQ + 1))
    mask = (rng.random((rows, SEQ)) > 0.2).astype(np.float32)
    return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32),
            "loss_mask": jnp.asarray(mask)}


@pytest.fixture(scope="module")
def cfg():
    return model_of()


@pytest.fixture(scope="module")
def params(cfg):
    p = lively(init_params(cfg, jax.random.PRNGKey(0)), jax.random.PRNGKey(1))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(2), (2, 8), F32)
    p["layers"]["moe"]["router_bias"] = bias
    return p


def leaves_of(tree, prefix=""):
    if isinstance(tree, dict):
        return [leaf for k in sorted(tree)
                for leaf in leaves_of(tree[k], f"{prefix}/{k}")]
    return [(prefix, tree)]


REFERENCE_LEAVES = [
    "/embed", "/final_norm", "/layers/attn_norm", "/layers/mlp_norm",
    "/layers/wq", "/layers/wk", "/layers/wv", "/layers/wo", "/layers/conv1",
    "/layers/conv2", "/layers/tau", "/layers/router_down",
    "/layers/router_gamma", "/layers/router_w1", "/layers/router_w2",
    "/layers/router_w3", "/layers/w_gate_up", "/layers/w_down"] + [
    f"/layers/{res}/{v}" for res in ("res1", "res2")
    for v in ("x_scale", "x_bias", "out_scale", "out_bias")]


@pytest.fixture(scope="module")
def both_sides(cfg, params):
    """(program loss, reference loss, {leaf: program gradient}, {leaf:
    reference gradient}) on one batch, the leaves under the reference's
    names."""
    batch = batch_of()
    (loss, _), grads = jax.value_and_grad(
        lambda p: lm.lm_loss(cfg, p, batch), has_aux=True)(params)
    want, want_grads = jax.value_and_grad(lambda w: ref.lm_loss(
        w, batch["tokens"], batch["labels"], batch["loss_mask"], TOY))(
            ref.from_program_params(params))
    return (loss, want, dict(leaves_of(ref.from_program_params(grads))),
            dict(leaves_of(want_grads)))


# --- program against reference ------------------------------------------------

def test_loss_equals_the_reference(both_sides):
    loss, want, _, _ = both_sides
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)


def test_the_reference_names_every_trained_leaf(params, both_sides):
    """Nothing the program trains is left out of the comparison below."""
    _, _, got, want = both_sides
    assert set(got) == set(want) == set(REFERENCE_LEAVES) | {
        "/layers/router_bias"}
    assert len(jax.tree.leaves(params)) == len(got)


@pytest.mark.parametrize("leaf", REFERENCE_LEAVES)
def test_gradient_of_every_leaf_equals_the_reference(leaf, both_sides):
    """Two layers: the second's router reads the first's state, so the
    first layer's router leaves get part of their gradient through the
    carry."""
    _, _, got, want = both_sides
    top = float(jnp.max(jnp.abs(want[leaf])))
    assert top > 1e-6, f"{leaf}: a gradient of nothing compares nothing"
    assert float(jnp.max(jnp.abs(got[leaf] - want[leaf]))) < 2e-4 * top


def test_the_carried_state_reaches_the_first_layers_router(cfg, params):
    """With the carry's scale at zero in the second layer, the first
    layer's router keeps only the gradient through its own choice."""
    batch = batch_of()
    cut = jax.tree.map(lambda a: a, params)
    scale = params["layers"]["moe"]["router_carry_scale"]
    cut["layers"]["moe"]["router_carry_scale"] = scale.at[1].set(0.0)

    def down_grad(p):
        return jax.grad(lambda p: lm.lm_loss(cfg, p, batch)[0])(p)[
            "layers"]["moe"]["router_down"][0]

    assert float(jnp.max(jnp.abs(down_grad(params) - down_grad(cut)))) > 1e-7


def test_the_two_shares_add_up_to_the_uncut_layer(cfg, params):
    """Experts 0 to 3 and 4 to 7 of one layer, each as one chip's share,
    against the uncut reference's mixture (and its router state)."""
    key = jax.random.PRNGKey(7)
    z = jax.random.normal(key, (ROWS, SEQ, 64), F32)
    r_prev = 0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                     (ROWS, SEQ, 16), F32)
    layer = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    other = jax.tree.map(lambda a: a[1], params["layers"]["moe"])
    w_in = jnp.concatenate([layer["w_in"], other["w_in"]])      # all 8
    w_out = jnp.concatenate([layer["w_out"], other["w_out"]])
    uncut = {k: v for k, v in TOY.items() if k != "whole"} | {"num_experts": 8}
    weights = {
        "router_down": layer["router_down"],
        "router_gamma": layer["router_carry_scale"],
        "router_w1": layer["router_w1"], "router_w2": layer["router_w2"],
        "router_w3": layer["router_w3"], "router_bias": layer["router_bias"],
        "w_gate_up": w_in, "w_down": w_out}
    with jax.default_matmul_precision("highest"):
        want, want_r, chosen = jax.vmap(
            lambda zi, ri: ref.experts(zi, ri, weights, uncut))(z, r_prev)
    total = 0.0
    held_rows = []
    for share in (0, 1):
        held = dataclasses.replace(cfg, moe_expert_share=share)
        mine = {**layer, "w_in": w_in[4 * share:4 * share + 4],
                "w_out": w_out[4 * share:4 * share + 4]}
        carry = {"state": r_prev, "load": jnp.zeros((2, 8), F32)}
        y, _, load, carry = moe.moe_block(held, mine, z, router=(carry, 1))
        np.testing.assert_allclose(carry["state"], want_r, atol=2e-6)
        np.testing.assert_array_equal(
            carry["load"][1], np.bincount(np.asarray(chosen).ravel(),
                                          minlength=8))
        assert not np.any(carry["load"][0])
        held_rows.append(float(load[1]))
        total = total + y
    np.testing.assert_allclose(total, want, atol=2e-6)
    assert sum(held_rows) == pytest.approx(1.0)
    assert 0 < min(held_rows), "each share must hold some of the rows"


# --- the parts of CCA -----------------------------------------------------------

@pytest.fixture(scope="module")
def mixed(cfg, params):
    """cca_mix's (q, k, v) as a function of the latents, one layer."""
    p = jax.tree.map(lambda a: a[0], params["layers"]["attn"])

    def mix(qt, kt, v):
        return cca.cca_mix(cfg, p, qt, kt, v)

    key = jax.random.PRNGKey(11)
    qt = jax.random.normal(key, (1, SEQ, 4, 16), F32)
    kt = jax.random.normal(jax.random.fold_in(key, 1), (1, SEQ, 2, 16), F32)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, SEQ, 32), F32)
    return mix, (qt, kt, v)


@pytest.mark.parametrize("which", ["q", "k", "v"])
def test_both_convolutions_and_the_shift_are_causal(which, mixed):
    """Position t's q, k and v do not move when position t + 1 changes,
    and do move when t - 1 does (two taps, twice: q and k at t read
    t - 2 too)."""
    mix, args = mixed
    at = "qkv".index(which)
    t = 9
    base = mix(*args)[at]
    later = [a.at[:, t + 1].add(1.0) for a in args]
    np.testing.assert_array_equal(mix(*later)[at][:, :t + 1], base[:, :t + 1])
    assert np.any(np.asarray(mix(*later)[at][:, t + 1] != base[:, t + 1]))
    earlier = [a.at[:, t - 1].add(1.0) for a in args]
    assert np.any(np.asarray(mix(*earlier)[at][:, t] != base[:, t]))
    reach = 1 if which == "v" else 2
    far = [a.at[:, t - reach - 1].add(1.0) for a in args]
    np.testing.assert_array_equal(mix(*far)[at][:, t], base[:, t])


@pytest.mark.parametrize("which", ["q", "k", "v"])
def test_position_zero_reads_zeros_before_the_sequence(which, mixed):
    """The first position's result is that of a sequence of one: every
    tap and the shift before it see zeros, not the sequence's end."""
    mix, args = mixed
    at = "qkv".index(which)
    alone = mix(*[a[:, :1] for a in args])[at]
    np.testing.assert_allclose(mix(*args)[at][:, :1], alone, atol=1e-6)
    if which == "v":   # the shifted half of the first position is zero
        assert not np.any(np.asarray(alone.reshape(1, 1, 32)[..., 16:]))


def test_the_qk_mean_under_grouped_heads_against_a_loop():
    key = jax.random.PRNGKey(3)
    qt = np.asarray(jax.random.normal(key, (2, 5, 6, 4), F32))
    kt = np.asarray(jax.random.normal(jax.random.fold_in(key, 1),
                                      (2, 5, 2, 4), F32))
    mq, mk = cca.qk_mean(jnp.asarray(qt), jnp.asarray(kt))
    group = 3
    for i in range(6):
        np.testing.assert_allclose(mq[:, :, i],
                                   (qt[:, :, i] + kt[:, :, i // group]) / 2,
                                   atol=1e-6)
    for j in range(2):
        mean = sum(qt[:, :, i] for i in range(6) if i // group == j) / group
        np.testing.assert_allclose(mk[:, :, j], (mean + kt[:, :, j]) / 2,
                                   atol=1e-6)
    want = ref.qk_mean(jnp.asarray(qt[0]), jnp.asarray(kt[0]))
    np.testing.assert_allclose(mq[0], want[0], atol=1e-6)
    np.testing.assert_allclose(mk[0], want[1], atol=1e-6)


def test_q_and_k_leave_the_mix_at_unit_norm_times_the_temperature(cfg, params,
                                                                  mixed):
    mix, args = mixed
    q, k, _ = mix(*args)
    tau = params["layers"]["attn"]["k_temp_scale"][0]
    np.testing.assert_allclose(jnp.linalg.norm(q, axis=-1), 4.0, rtol=1e-5)
    np.testing.assert_allclose(jnp.linalg.norm(k, axis=-1),
                               jnp.broadcast_to(4.0 * jnp.abs(tau), (1, SEQ, 2)),
                               rtol=1e-5)


def test_cca_refuses_a_cache_by_name(cfg, params):
    p = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    with pytest.raises(NotImplementedError, match="attention_form='cca'"):
        attention_block(cfg, p, jnp.zeros((1, 4, 64)), None, None,
                        kv_cache=object(), layer=0, cache_index=0)


# --- partial rotary --------------------------------------------------------------

def _rotary_case():
    kind = AttentionKind(rope_theta=5e6)
    key = jax.random.PRNGKey(5)
    q = jax.random.normal(key, (2, 12, 3, 16), F32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 12, 2, 16), F32)
    return kind, q, k


def test_partial_rotary_is_the_full_table_on_the_first_half_of_a_head():
    kind, q, k = _rotary_case()
    cos, sin = rope_table(kind, 16, 12, rotary_dim=8)
    got_q, got_k = apply_rotary_emb(q, k, cos, sin, rotary_dim=8)
    small = rope_table(kind, 8, 12)
    want_q, want_k = apply_rotary_emb(q[..., :8], k[..., :8], *small)
    np.testing.assert_allclose(got_q[..., :8], want_q, atol=1e-6)
    np.testing.assert_allclose(got_k[..., :8], want_k, atol=1e-6)
    rope = {"partial_rotary_factor": 0.5, "rope_theta": 5e6}
    np.testing.assert_allclose(got_q[0], ref.partial_rotary(q[0], rope),
                               atol=1e-5)


def test_partial_rotary_is_the_identity_on_the_rest_of_a_head():
    kind, q, k = _rotary_case()
    cos, sin = rope_table(kind, 16, 12, rotary_dim=8)
    got_q, got_k = apply_rotary_emb(q, k, cos, sin, rotary_dim=8)
    np.testing.assert_array_equal(got_q[..., 8:], q[..., 8:])
    np.testing.assert_array_equal(got_k[..., 8:], k[..., 8:])


def test_partial_rotary_gradient_is_the_plain_forms(cfg):
    """The one-pass rule's backward (the permutation moved to the
    cotangent) against autodiff of slice, rotate and concatenate."""
    kind, q, k = _rotary_case()
    cos, sin = rope_table(kind, 16, 12, rotary_dim=8)
    dy = jax.random.normal(jax.random.PRNGKey(6), q.shape, F32)
    rope = {"partial_rotary_factor": 0.5, "rope_theta": 5e6}
    got = jax.grad(lambda q: jnp.sum(
        apply_rotary_emb(q, k, cos, sin, rotary_dim=8)[0] * dy))(q)
    want = jax.grad(lambda q: jnp.sum(
        jax.vmap(lambda x: ref.partial_rotary(x, rope))(q) * dy))(q)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_a_whole_head_takes_the_path_it_always_took():
    kind, q, k = _rotary_case()
    cos, sin = rope_table(kind, 16, 12)
    same = rope_table(kind, 16, 12, rotary_dim=16)
    np.testing.assert_array_equal(same[0], cos)
    np.testing.assert_array_equal(same[1], sin)
    plain = jax.make_jaxpr(lambda q, k: apply_rotary_emb(q, k, cos, sin))(q, k)
    whole = jax.make_jaxpr(lambda q, k: apply_rotary_emb(
        q, k, cos, sin, rotary_dim=16))(q, k)
    assert str(plain) == str(whole)


# --- the layer stack's carry under every remat policy -----------------------------

def unrolled_loss(cfg, params, batch):
    """lm_loss with the layers called one by one: no scan, no checkpoint,
    the router's carry handed on by hand."""
    x = lm.embed_tokens(cfg, params, batch["tokens"], None)
    kind = cfg.attention_kind
    rope = lm.rope_tables(cfg, [kind], SEQ)[kind]
    routed = moe.router_carry(cfg, x)
    states = []
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x, _, _, _, _, routed = block_forward(
            cfg, lp, x, rope, layer=i, kind=kind, router=routed)
        states.append(routed["state"])
    hidden = lm.final_hidden_norm(cfg, params, x)
    logits = lm.lm_logits(cfg, params, hidden)
    from megatron_tpu.ops.cross_entropy import cross_entropy_loss

    loss, _ = cross_entropy_loss(logits, batch["labels"],
                                 loss_mask=batch["loss_mask"])
    return loss, (routed["load"], states)


@pytest.mark.parametrize("policy", ["none", "selective", "full", "block:1",
                                    "uniform:1", "uniform:2"])
def test_scan_with_remat_equals_the_unrolled_stack_with_the_carry(
        policy, cfg, params):
    batch = batch_of(3)
    (want, (load, states)), want_grads = jax.value_and_grad(
        lambda p: unrolled_loss(cfg, p, batch), has_aux=True)(params)
    assert float(jnp.max(jnp.abs(states[1] - states[0]))) > 0.1
    (got, aux), grads = jax.value_and_grad(
        lambda p: lm.lm_loss(cfg, p, batch, recompute=policy),
        has_aux=True)(params)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_array_equal(aux[moe.EXPERT_LOAD], load)
    assert float(jnp.sum(load)) == 2 * ROWS * SEQ
    for (name, g), (_, w) in zip(leaves_of(grads), leaves_of(want_grads)):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(
            jnp.max(jnp.abs(w)) + 1e-12), err_msg=f"{policy} {name}")


def test_the_chunked_head_trains_the_tied_table(cfg, params):
    """The benchmark's cell runs --ce_chunk_size over the tied table: the
    embedding's gradient is the gather's and the head's together."""
    batch = batch_of(4)
    chunked = dataclasses.replace(cfg, ce_chunk_size=16)
    want = jax.grad(lambda p: lm.lm_loss(cfg, p, batch)[0])(params)
    got = jax.grad(lambda p: lm.lm_loss(chunked, p, batch)[0])(params)
    np.testing.assert_allclose(got["embed"]["tokens"],
                               want["embed"]["tokens"], atol=1e-6)


# --- the selection bias: a leaf no gradient trains ---------------------------------

def test_the_bias_decides_the_choice_and_receives_no_gradient(cfg, params):
    batch = batch_of(5)
    grads = jax.grad(lambda p: lm.lm_loss(cfg, p, batch)[0])(params)
    assert not np.any(np.asarray(grads["layers"]["moe"]["router_bias"]))
    pushed = jax.tree.map(lambda a: a, params)
    pushed["layers"]["moe"]["router_bias"] = jnp.zeros((2, 8)).at[:, 5].set(1e4)
    load = lm.lm_loss(cfg, pushed, batch)[1][moe.EXPERT_LOAD]
    np.testing.assert_array_equal(load[:, 5], [ROWS * SEQ] * 2)


def _state(cfg, params, dtype=None):
    if dtype is not None:
        params = jax.tree.map(lambda a: a.astype(dtype), params)
    return opt.init_train_state(OptimizerConfig(lr=1e-2), params)


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16])
def test_the_bias_moves_toward_an_even_load_on_a_planted_skew(cfg, params,
                                                              dtype):
    state = _state(cfg, params, dtype)
    before = (state.master or state.params)["layers"]["moe"]["router_bias"]
    # layer 0: expert 2 takes nearly everything; layer 1: even
    load = jnp.stack([jnp.full((8,), 2.0).at[2].set(50.0),
                      jnp.full((8,), 8.0)])
    moved, top = opt.update_selection_bias(state, load, 1e-3, jnp.float32(0))
    after = (moved.master or moved.params)["layers"]["moe"]["router_bias"]
    step = np.asarray(after.astype(F32) - before.astype(F32))
    want = np.full((2, 8), 1e-3)
    want[0, 2] = -1e-3
    want[1] = 0.0
    np.testing.assert_allclose(step, want, atol=1e-7)
    assert float(top) == pytest.approx(float(jnp.max(jnp.abs(after))))
    # the model's leaf follows the master, in its own dtype
    leaf = moved.params["layers"]["moe"]["router_bias"]
    assert leaf.dtype == (dtype or F32)
    np.testing.assert_array_equal(leaf, after.astype(leaf.dtype))
    # a skipped step moves nothing
    kept, _ = opt.update_selection_bias(state, load, 1e-3, jnp.float32(1))
    np.testing.assert_array_equal(
        (kept.master or kept.params)["layers"]["moe"]["router_bias"], before)


def test_the_bias_is_outside_adam_decay_and_the_clipped_norm(cfg, params):
    """A gradient planted on the bias (none ever reaches it) moves neither
    the leaf, its moments nor the norm the others are clipped by."""
    state = _state(cfg, params)
    ocfg = OptimizerConfig(lr=1e-2, weight_decay=0.1, clip_grad=1.0)
    apply = opt.make_optimizer_step(ocfg, 10)
    grads = jax.tree.map(lambda a: 1e-3 * jnp.ones_like(a), params)
    planted = jax.tree.map(lambda a: a, grads)
    planted["layers"]["moe"]["router_bias"] = jnp.full((2, 8), 1e6)
    plain_state, plain = apply(state, grads)
    new_state, metrics = apply(state, planted)
    assert float(metrics["grad_norm"]) == float(plain["grad_norm"]) < 1e3
    at = lambda tree: tree["layers"]["moe"]["router_bias"]  # noqa: E731
    np.testing.assert_array_equal(at(new_state.params), at(params))
    assert not np.any(np.asarray(at(new_state.mu)))
    assert not np.any(np.asarray(at(new_state.nu)))
    np.testing.assert_array_equal(new_state.params["layers"]["attn"]["wq"],
                                  plain_state.params["layers"]["attn"]["wq"])
    assert np.any(np.asarray(new_state.params["layers"]["attn"]["wq"]
                             != params["layers"]["attn"]["wq"]))


def test_the_train_step_moves_the_bias_by_the_whole_steps_load(cfg, params):
    """Two micro-batches: the load is the step's, summed over them, and
    the journal's field is the largest |bias| after the move."""
    params = jax.tree.map(lambda a: a, params)
    params["layers"]["moe"]["router_bias"] = jnp.zeros((2, 8), F32)
    state = _state(cfg, params)
    batch = batch_of(6, rows=4)
    step = jax.jit(make_train_step(
        cfg, OptimizerConfig(lr=0.0, weight_decay=0.0),
        TrainingConfig(micro_batch_size=2, global_batch_size=4),
        num_microbatches=2))
    new_state, metrics = step(state, batch)
    halves = [{k: v[i:i + 2] for k, v in batch.items()} for i in (0, 2)]
    load = sum(lm.lm_loss(cfg, params, h)[1][moe.EXPERT_LOAD] for h in halves)
    assert float(jnp.sum(load)) == 2 * 4 * SEQ
    want = 1e-3 * np.sign(1 / 8 - np.asarray(load) / (4 * SEQ))
    np.testing.assert_allclose(
        new_state.params["layers"]["moe"]["router_bias"], want, atol=1e-9)
    assert float(metrics[moe.BIAS_METRIC]) == pytest.approx(1e-3)
    assert moe.BIAS_METRIC in moe.STEP_METRICS
    assert set(moe.STEP_METRICS) <= set(metrics)
    assert moe.EXPERT_LOAD not in metrics


def test_a_model_without_the_rate_journals_no_bias(cfg, params):
    # the rate is the mix's flag, not the architecture's
    none = args_to_run_config(parse_args(
        ref.program_flags(TOY, SEQ) + ["--fp32", "--micro_batch_size", "2",
                                       "--global_batch_size", "2"])).model
    assert none.moe_bias_update_rate is None and not none.has_router_bias
    p = init_params(none, jax.random.PRNGKey(0))
    assert "router_bias" not in p["layers"]["moe"]
    step = make_train_step(none, OptimizerConfig(lr=1e-3),
                           TrainingConfig(micro_batch_size=2,
                                          global_batch_size=2),
                           num_microbatches=1)
    _, metrics = jax.jit(step)(_state(none, p), batch_of(7))
    assert moe.BIAS_METRIC not in metrics
    assert moe.HELD_METRIC in metrics


def test_the_bias_survives_a_checkpoint_save_and_restore(cfg, params,
                                                         tmp_path):
    state = _state(cfg, params, jnp.bfloat16)
    load = jnp.stack([jnp.arange(8.0), jnp.arange(8.0)[::-1]])
    for _ in range(3):
        state, _ = opt.update_selection_bias(state, load, 1e-3,
                                             jnp.float32(0))
    run = RunConfig(model=cfg)
    checkpointing.save_checkpoint(str(tmp_path), state, iteration=3,
                                  config=run.to_dict())
    template = _state(cfg, jax.tree.map(jnp.zeros_like, params),
                      jnp.bfloat16)
    restored, iteration, _ = checkpointing.load_checkpoint(
        str(tmp_path), template)
    assert iteration == 3
    at = lambda tree: tree["layers"]["moe"]["router_bias"]  # noqa: E731
    np.testing.assert_array_equal(at(restored.master), at(state.master))
    np.testing.assert_array_equal(at(restored.params), at(state.params))
    assert at(restored.master).dtype == F32
    assert np.any(np.asarray(at(restored.master) != at(template.master)))
    saved = checkpointing.saved_run_config(str(tmp_path))
    assert model_config_from_saved(saved["model"]) == cfg


# --- what is not built refuses by name ---------------------------------------------

def _toy(**over):
    base = dict(
        num_layers=2, hidden_size=64, num_attention_heads=4, vocab_size=128,
        seq_length=32, num_kv_heads=2, kv_channels=16, ffn_hidden_size=32,
        num_experts=8, moe_top_k=1, moe_dispatch="dropless",
        moe_renorm_gates=False, moe_aux_loss_coeff=0.0)
    return ModelConfig(**{**base, **over})


CCA = dict(attention_form="cca")
ROUTER = dict(moe_router_form="mlp", moe_router_hidden_size=16)


@pytest.mark.parametrize("axis", ["tensor_parallel", "pipeline_parallel",
                                  "context_parallel", "expert_parallel"])
@pytest.mark.parametrize("what, model", [
    ("attention_form='cca'", CCA), ("moe_router_form='mlp'", ROUTER),
    ("moe_bias_update_rate", dict(moe_bias_update_rate=1e-3))])
def test_sharding_refuses_by_name(axis, what, model):
    run = RunConfig(model=_toy(**model),
                    parallel=ParallelConfig(**{axis: 2}))
    with pytest.raises(NotImplementedError,
                       match=f"{what} under {axis}".replace("'", ".")):
        run.validate()
    RunConfig(model=_toy(**model), parallel=ParallelConfig()).validate()


@pytest.mark.parametrize("model, match", [
    (dict(ROUTER, moe_dispatch="capacity"), "moe_router_form='mlp'.*capacity"),
    (dict(moe_bias_update_rate=1e-3, moe_dispatch="capacity"),
     "moe_bias_update_rate.*capacity"),
    (dict(CCA, attention_impl="ring"), "cca.*context parallelism"),
    (dict(CCA, qk_norm=True), "attention_form='cca'.*qk_norm"),
    (dict(CCA, num_experts=None, layer_pattern=("attention", "mamba")),
     "attention_form='cca'.*layer_pattern"),
    (dict(residual_scale=True, parallel_attn=True), "residual_scale"),
])
def test_validate_refuses_what_is_not_built_by_name(model, match):
    with pytest.raises(NotImplementedError, match=match):
        _toy(**model).validate()


@pytest.mark.parametrize("model, match", [
    (dict(ROUTER, moe_router_hidden_size=None), "moe_router_hidden_size"),
    (dict(moe_router_hidden_size=16), "moe_router_hidden_size"),
    (dict(moe_bias_update_rate=0.0), "moe_bias_update_rate"),
    (dict(rotary_percent=0.2), "rotary_percent"),
    (dict(CCA, cca_conv_kernels=(0, 2)), "cca_conv_kernels"),
    (dict(attention_form="ccc"), "attention_form"),
    (dict(moe_router_form="tree"), "moe_router_form"),
])
def test_validate_names_a_bad_value(model, match):
    with pytest.raises(ValueError, match=match):
        _toy(**model).validate()


@pytest.mark.parametrize("what, model", [
    ("attention_form='cca'", CCA), ("moe_router_form='mlp'", ROUTER)])
def test_serving_refuses_by_name(what, model):
    from megatron_tpu.inference.engine import InferenceEngine

    cfg = _toy(**model).validate()
    with pytest.raises(NotImplementedError,
                       match=f"serving a model with {what}".replace("'", ".")):
        cfg.refuse_serving()
    with pytest.raises(NotImplementedError, match="is not served"):
        InferenceEngine(cfg, params=None)
    _toy().validate().refuse_serving()


def test_the_mlp_router_outside_the_training_stack_refuses_by_name(cfg,
                                                                    params):
    layer = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    with pytest.raises(NotImplementedError, match="moe_router_form='mlp'"):
        moe.moe_block(cfg, layer, jnp.zeros((1, 4, 64)))


# --- the count of operations ----------------------------------------------------------

def test_flops_per_token_counts_the_convolutions_the_router_and_one_expert():
    cfg = model_of()
    got = cfg.flops_per_token_fwd(SEQ)
    # the program counts a full S keys a query (its convention for every
    # model); the reference the causal mean
    h, d, nq, nkv, f, r, e = 64, 16, 4, 2, 32, 16, 8
    proj = 2 * h * (nq + 2 * nkv) * d + 2 * nq * d * h
    conv = 2 * (nq + nkv) * d * (2 + 2 * d)
    route = 2 * h * r + 4 * r * r + 2 * r * e
    expert = (2 * h * 2 * f + 2 * f * h) * 1 * 4 / 8
    keys = 2 * 2 * nq * d * SEQ
    want = 2 * (proj + conv + route + expert + keys) + 2 * h * cfg.vocab_size
    assert got == pytest.approx(want)
    mean_keys = 2 * 2 * nq * d * (SEQ + 1) / 2
    assert ref.forward_flops_per_token(TOY, SEQ) == pytest.approx(
        want - 2 * (keys - mean_keys))
    assert ref.train_flops_per_token(TOY, SEQ) == pytest.approx(
        3 * ref.forward_flops_per_token(TOY, SEQ))
