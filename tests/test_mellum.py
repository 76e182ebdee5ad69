"""Mellum 2 on the program's normal path, held to the plain reference of
benchmark/reference/mellum.py: layers of two attention kinds in one stack
(three window layers, then one full layer under a YaRN table), a mixture
of small experts with normalised gates, and the expert layer that holds a
share of its router's experts. Toy widths, whole structure: one period of
four layers with a window shorter than the sequence, 8 experts behind the
router, 4 a token, 2 held a share, untied head. Weights are seeded random
draws at a standard deviation of 0.1 with norm scales drawn around 1, so
that every term carries weight in the loss."""

import dataclasses
import json
import math
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
from megatron_tpu.config import AttentionKind  # noqa: E402
from megatron_tpu.models.language_model import lm_loss  # noqa: E402
from megatron_tpu.models.params import init_params  # noqa: E402
from megatron_tpu.ops import moe  # noqa: E402
from megatron_tpu.ops.pallas import flash_template as ft  # noqa: E402
from megatron_tpu.ops.rotary import rope_table, yarn_inv_freq  # noqa: E402

reference = spec.load_module(
    os.path.join(REPO, "benchmark", "reference", "mellum.py"))

SEQ, WINDOW = 32, 8
EXPERTS, HELD = 8, 2
YARN = {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.5}
# the uncut toy: every expert's weights exist
TOY = {
    "attention_bias": False, "head_dim": 16, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 256,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4, "max_position_embeddings": 128,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts": EXPERTS,
    "num_experts_per_tok": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": YARN,
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}},
    "sliding_window": WINDOW, "tie_word_embeddings": False,
    "vocab_size": 256,
    "assumed": {"initializer_range": {"value": 0.1},
                "router_aux_loss_coef": {"value": 0.01}},
}


def share_of(toy, share):
    """The configuration of one chip's share: HELD experts' weights, the
    router's width under `whole`."""
    held = dict(toy, num_experts=HELD, whole={"num_experts": EXPERTS})
    held["assumed"] = dict(toy["assumed"], expert_share={"value": share})
    return held


def program_config(toy=TOY, dtype="--fp32", **overrides):
    """The toy model as the trainer builds it from the reference's own
    translation into flags (what the benchmark's child passes)."""
    argv = reference.program_flags(toy, SEQ) + [
        dtype, "--micro_batch_size", "1", "--global_batch_size", "1"]
    model = args_to_run_config(parse_args(argv)).model
    return dataclasses.replace(model, **overrides).validate()


def seeded_params(cfg, seed=0):
    """init_params, with every norm scale drawn around 1 (ones would hide
    a scale applied in the wrong place) and the output projections and
    the router widened, so that attention and the experts weigh on the
    residual and a token's gates differ."""
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def draw(path, leaf):
        if path[-1].key == "scale":
            return 1.0 + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return 4.0 * leaf if path[-1].key in ("wo", "w_out", "router") else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def held_params(params, share):
    """The uncut model's weights as the chip of `share` holds them."""
    rows = slice(share * HELD, (share + 1) * HELD)
    moe_p = params["layers"]["moe"]
    cut = dict(moe_p, w_in=moe_p["w_in"][:, rows], w_out=moe_p["w_out"][:, rows])
    return dict(params, layers=dict(params["layers"], moe=cut))


def sequences(seed=0, rows=2):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, TOY["vocab_size"], (rows, SEQ + 1))
    mask = (rng.random((rows, SEQ)) > 0.1).astype(np.float32)
    return {"tokens": jnp.asarray(tokens[:, :-1], jnp.int32),
            "labels": jnp.asarray(tokens[:, 1:], jnp.int32),
            "loss_mask": jnp.asarray(mask)}


def reference_loss(params, batch, toy):
    return reference.lm_loss(reference.from_program_params(params),
                             batch["tokens"], batch["labels"],
                             batch["loss_mask"], toy)


def in_dtype(params, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), params)


CASES = {"uncut": None, "share0": 0, "share3": 3}


def case(name):
    """(the reference's configuration, the program's, the weights)."""
    share = CASES[name]
    params = seeded_params(program_config())
    if share is None:
        return TOY, program_config(), params
    toy = share_of(TOY, share)
    return toy, program_config(toy), held_params(params, share)


def test_the_flags_build_the_published_model():
    """The benchmark's configuration file, through the reference's
    translation into flags, is the model the source states."""
    from megatron_tpu.models.params import num_params

    with open(os.path.join(REPO, "benchmark", "configs",
                           "mellum2-12b-a2.5b-s4-d4.json")) as f:
        config = json.load(f)
    cfg = args_to_run_config(parse_args(
        reference.program_flags(config, 8192) + config["program"]["flags"]
        + ["--micro_batch_size", "2", "--global_batch_size", "2"])).model
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.ffn_size) == (
        4, 2304, 32, 4, 128, 896)
    assert (cfg.num_experts, cfg.moe_experts_held, cfg.moe_expert_share,
            cfg.moe_top_k, cfg.moe_renorm_gates, cfg.moe_dispatch,
            cfg.qk_norm, cfg.tie_embed_logits, cfg.vocab_size) == (
        64, 16, 0, 8, True, "dropless", False, False, 24576)
    sliding, full = cfg.attention_period[0], cfg.attention_period[3]
    assert [k.name for k in cfg.attention_period] == [
        "sliding", "sliding", "sliding", "full"]
    assert cfg.attention_period[:3] == (sliding,) * 3
    assert (sliding.sliding_window_size, sliding.rope_theta,
            sliding.rope_type) == (1024, 500000, "linear")
    assert (full.sliding_window_size, full.rope_type,
            full.rope_scaling_factor, full.yarn_original_max_positions,
            full.yarn_attention_factor) == (
        None, "yarn", 16, 8192, 1.2772588722239782)
    assert num_params(cfg) == 595_153_152   # ISSUE 42's 595.1 M
    # and uncut, at its depth, the model card's 12 B
    whole = dict(config, num_hidden_layers=28, num_experts=64,
                 vocab_size=98304,
                 layer_types=config["layer_types"] * 7,
                 mlp_layer_types=config["mlp_layer_types"] * 7)
    del whole["whole"]
    uncut = args_to_run_config(parse_args(
        reference.program_flags(whole, 8192)
        + ["--micro_batch_size", "1", "--global_batch_size", "1"])).model
    assert 12.1e9 < num_params(uncut) < 12.2e9
    # model FLOPs follow the kinds' windows and the share held: what the
    # reference counts with causal attention counted dense, as the
    # program's formula does (2 x the causal mean at full length)
    dense = (3 * min(8192, 1024) + 8192) / 4
    h, d, nq, nkv, f = 2304, 128, 32, 4, 896
    layer = (2 * h * (nq + 2 * nkv) * d + 2 * nq * d * h + 2 * h * 64
             + 8 * 16 / 64 * 3 * 2 * h * f + 4 * nq * d * dense)
    assert cfg.flops_per_token_fwd() == 4 * layer + 2 * h * 24576


# --- the program against the reference --------------------------------------

@pytest.mark.parametrize("name", list(CASES))
def test_float32_loss_and_every_gradient_leaf_match_the_reference(name):
    """Same mathematics in float32 by two mechanisms (a stack of kinds with
    one table each, sort, gather, grouped matmul over the held groups
    against a Python loop over layers and a masked loop over the held
    experts): they differ by the order of float32 sums only, a few 1e-7
    of the largest entry at these sizes; 1e-5 of each leaf's largest entry
    passes that and fails any wrong term. Two sequences a call: the
    router statistics are over the call."""
    toy, cfg, params = case(name)
    batch = sequences()
    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(cfg, p, batch)[0])(params)
    want, want_grads = jax.value_and_grad(
        lambda p: reference_loss(p, batch, toy))(params)
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    got = jax.tree_util.tree_leaves_with_path(grads)
    ref = jax.tree.leaves(want_grads)
    assert len(got) == len(ref) == 12
    for (path, g), w in zip(got, ref):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-5 * scale, path


@pytest.mark.parametrize("name", list(CASES))
def test_bf16_for_float32_fails_the_float32_tolerance(name):
    """The control of the test above: the same program with bf16 weights
    and activations is no float32 computation, and 1e-5 of a leaf's
    largest entry says so on most of the gradient's leaves."""
    toy, cfg, params = case(name)
    batch = sequences()
    low = dataclasses.replace(cfg, params_dtype="bfloat16")
    grads = jax.grad(lambda p: lm_loss(low, p, batch)[0])(
        in_dtype(params, jnp.bfloat16))
    want = jax.grad(lambda p: reference_loss(p, batch, toy))(params)
    failed = [float(jnp.max(jnp.abs(g - w))) > 1e-5 * float(jnp.max(jnp.abs(w)))
              for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want))]
    assert sum(failed) >= 10, failed


# bf16 weights and activations against the float32 reference differ by
# 1.2e-3 or less over four sequences of this size (measured over the three
# cases and batch seeds 2, 3, 4: 2e-6 to 1.14e-3); each wrong term below
# moves the loss by 4.0e-3 or more (the window off 4.2e-3, the plain table
# for YaRN's 4.0e-3, no attention factor 7.0e-3, a window on the full layer
# and another share's experts 3.1e-2). Whether a share's gates are
# normalised moves a random model's loss by 4e-4, less than rounding does:
# the float32 test above holds them, to 1e-5
BF16_TOLERANCE = 2e-3


def _bf16_loss(name, **overrides):
    toy, cfg, params = case(name)
    batch = sequences(seed=2, rows=4)
    low = dataclasses.replace(cfg, params_dtype="bfloat16", **overrides)
    return (float(lm_loss(low, in_dtype(params, jnp.bfloat16), batch)[0]),
            float(reference_loss(params, batch, toy)))


@pytest.mark.parametrize("name", list(CASES))
def test_bf16_program_is_within_tolerance_of_the_float32_reference(name):
    got, want = _bf16_loss(name)
    assert abs(got - want) <= BF16_TOLERANCE


def _pattern(cfg, **changes):
    """cfg's pattern with `changes` on its full-attention kind."""
    return {"attention_pattern": tuple(
        dataclasses.replace(k, **changes) if k.name == "full" else k
        for k in cfg.attention_period)}


@pytest.mark.parametrize("wrong", [
    lambda cfg: {"attention_pattern": tuple(
        dataclasses.replace(k, sliding_window_size=None)
        for k in cfg.attention_period)},
    lambda cfg: _pattern(cfg, sliding_window_size=WINDOW),
    lambda cfg: _pattern(cfg, rope_type="linear", rope_scaling_factor=1.0),
    lambda cfg: _pattern(cfg, yarn_attention_factor=1.0),
    lambda cfg: {"moe_expert_share": 1},
], ids=["no_window", "window_on_the_full_layer", "plain_table_for_yarn",
        "no_attention_factor", "another_share"])
def test_a_wrong_term_fails_the_bf16_tolerance(wrong):
    cfg = case("share0")[1]
    got, want = _bf16_loss("share0", **wrong(cfg))
    assert abs(got - want) > 1.5 * BF16_TOLERANCE


# --- the shares add up to the layer -----------------------------------------

def test_the_four_shares_partial_results_add_up_to_the_uncut_layer():
    """One expert layer: what the chips of the four shares each return for
    the same tokens (moe_block_dropless told which experts it holds, the
    router 8 wide on each) adds up to what the uncut reference's mixture
    gives; the load-balance statistics are the whole router's on every
    chip, and the held rows' shares sum to 1."""
    cfg = program_config()
    params = seeded_params(cfg)
    layer = jax.tree.map(lambda a: a[1], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, TOY["hidden_size"]))
    w = {"router": layer["router"], "w_gate_up": layer["w_in"],
         "w_down": layer["w_out"]}
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([reference.experts(row, w, TOY)[0] for row in x])
    whole, aux_whole, load_whole = moe.moe_block(cfg, layer, x)
    np.testing.assert_allclose(whole, want, atol=2e-6)
    total, shares = 0.0, []
    for share in range(EXPERTS // HELD):
        held_cfg = program_config(share_of(TOY, share))
        rows = slice(share * HELD, (share + 1) * HELD)
        mine = dict(layer, w_in=layer["w_in"][rows], w_out=layer["w_out"][rows])
        y, aux, load = moe.moe_block(held_cfg, mine, x)
        assert float(aux) == pytest.approx(float(aux_whole), rel=1e-6)
        assert float(load[0]) == pytest.approx(float(load_whole), rel=1e-6)
        shares.append(float(load[1]))
        # a part, not the whole: this share alone is far from the layer
        assert float(jnp.max(jnp.abs(y - want))) > 1e-2
        total = total + y
    np.testing.assert_allclose(total, want, atol=2e-6)
    assert sum(shares) == pytest.approx(1.0, abs=1e-6)


def test_the_four_shares_gradients_add_up_to_the_uncut_layers():
    """The backward of the same layer: under one cotangent of y the four
    shares' gradients of the tokens and of the router add up to the uncut
    layer's (a choice held elsewhere gives a share no gradient, of its row
    or of its gate), and each share's gradient of its own experts is the
    uncut layer's for those experts."""
    cfg = program_config()
    layer = jax.tree.map(lambda a: a[1],
                         seeded_params(cfg)["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, TOY["hidden_size"]))
    dy = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def grads(cfg, layer):
        return jax.grad(lambda p, x: jnp.sum(moe.moe_block(cfg, p, x)[0] * dy),
                        argnums=(0, 1))(layer, x)

    want, want_x = grads(cfg, layer)
    total_x, total_router = 0.0, 0.0
    for share in range(EXPERTS // HELD):
        rows = slice(share * HELD, (share + 1) * HELD)
        mine = dict(layer, w_in=layer["w_in"][rows], w_out=layer["w_out"][rows])
        got, got_x = grads(program_config(share_of(TOY, share)), mine)
        for name in moe.EXPERT_MATRICES:
            np.testing.assert_allclose(got[name], want[name][rows], atol=2e-5)
        total_x, total_router = total_x + got_x, total_router + got["router"]
        # a part, not the whole
        assert float(jnp.max(jnp.abs(got_x - want_x))) > 1e-2
    np.testing.assert_allclose(total_x, want_x, atol=2e-5)
    np.testing.assert_allclose(total_router, want["router"], atol=2e-5)


def test_the_held_rows_share_is_journalled_only_by_a_share():
    from megatron_tpu.config import OptimizerConfig, TrainingConfig
    from megatron_tpu.training.optimizer import init_train_state
    from megatron_tpu.training.train_step import make_train_step

    seen = {}
    for name in ("uncut", "share0"):
        _, cfg, params = case(name)
        opt = OptimizerConfig(lr=1e-3)
        state = init_train_state(opt, params)
        step = make_train_step(cfg, opt, TrainingConfig(), num_microbatches=2)
        _, seen[name] = jax.jit(step)(state, sequences())
    assert moe.HELD_METRIC not in seen["uncut"]
    assert moe.LOAD_METRIC in seen["uncut"]
    assert 0.0 < float(seen["share0"][moe.HELD_METRIC]) < 1.0
    assert float(seen["share0"][moe.LOAD_METRIC]) >= 1.0


def test_the_moved_rows_share_is_journalled_only_by_a_share(monkeypatch):
    """Beside the held rows' share a share's step says how many of the
    rows its layers' four row movements moved (ops/moe.py MOVED_METRIC):
    1.0 at the toy's row count, where each is one pass; under 1 and over
    the held rows' share once they walk the live rows (here at blocks of
    the toy's size), with the loss the one pass gives. A model that holds
    every expert journals neither."""
    from megatron_tpu.config import OptimizerConfig, TrainingConfig
    from megatron_tpu.training.optimizer import init_train_state
    from megatron_tpu.training.train_step import make_train_step

    def metrics(name):
        _, cfg, params = case(name)
        opt = OptimizerConfig(lr=1e-3)
        state = init_train_state(opt, params)
        step = make_train_step(cfg, opt, TrainingConfig(), num_microbatches=2)
        return jax.jit(step)(state, sequences())[1]

    assert moe.MOVED_METRIC not in metrics("uncut")
    one_pass = metrics("share0")
    assert float(one_pass[moe.MOVED_METRIC]) == 1.0
    monkeypatch.setattr(moe, "_walk_blocks", lambda n, k: (16, 8))
    walked = metrics("share0")
    assert (float(walked[moe.HELD_METRIC]) < float(walked[moe.MOVED_METRIC])
            < 1.0)
    assert float(walked[moe.HELD_METRIC]) == float(one_pass[moe.HELD_METRIC])
    assert float(walked["loss"]) == pytest.approx(
        float(one_pass["loss"]), rel=1e-6)
    assert float(walked["grad_norm"]) == pytest.approx(
        float(one_pass["grad_norm"]), rel=1e-5)


# --- YaRN -------------------------------------------------------------------

def test_yarn_table_is_the_closed_form_at_the_sources_numbers():
    """rope_parameters.full_attention of the source: theta 5e5 under YaRN,
    factor 16 over 8192 original positions, beta 32 / 1. Pair i of a head
    of 128 turns 8192 * theta^(-i/64) / 2 pi times: 32 times at i = 18.08
    and once at i = 34.98, so pairs 0..18 keep their frequency, pairs
    35..63 have it divided by 16, and between them the blend is linear;
    cos and sin carry the attention factor 0.1 ln 16 + 1."""
    theta, d, factor, length = 5e5, 128, 16.0, 8192
    kind = AttentionKind(
        name="full", rope_theta=theta, rope_type="yarn",
        rope_scaling_factor=factor, yarn_original_max_positions=length,
        yarn_beta_fast=32, yarn_beta_slow=1,
        yarn_attention_factor=1.2772588722239782)
    i = np.arange(d // 2, dtype=np.float64)
    plain = theta ** (-i / (d // 2))
    turns = length * plain / (2 * np.pi)
    assert turns[18] > 32 > turns[19] and turns[34] > 1 > turns[35]
    ramp = np.clip((i - 18) / (35 - 18), 0, 1)
    want = plain * (1 - ramp) + plain / factor * ramp
    got = np.asarray(yarn_inv_freq(d, theta, factor, length, 32, 1))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(got[:19], plain[:19], rtol=2e-6)
    np.testing.assert_allclose(got[35:], plain[35:] / 16, rtol=2e-6)
    cos, sin = rope_table(kind, d, 8192)
    assert cos.shape == sin.shape == (8192, d)
    scale = 0.1 * math.log(16) + 1
    assert kind.yarn_attention_factor == pytest.approx(scale, rel=1e-12)
    np.testing.assert_allclose(cos[0], scale, rtol=1e-6)
    at = 5000
    angle = at * np.concatenate([want, want])
    np.testing.assert_allclose(cos[at], scale * np.cos(angle), atol=2e-3)
    np.testing.assert_allclose(sin[at], scale * np.sin(angle), atol=2e-3)
    # the reference's own closed form is the same table
    inv, by = reference.inverse_frequencies(
        {"rope_type": "yarn", "rope_theta": theta, "factor": factor,
         "original_max_position_embeddings": length, "beta_fast": 32,
         "beta_slow": 1, "attention_factor": scale}, d)
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    assert by == scale
    # and the default attention factor is the published number
    default = dataclasses.replace(kind, yarn_attention_factor=None)
    np.testing.assert_allclose(rope_table(default, d, 4)[0], cos[:4],
                               rtol=1e-6)


# --- the flash kernels where the window clips -------------------------------

@pytest.mark.parametrize("window", [64, None], ids=["window", "full"])
def test_interpreted_flash_kernels_match_the_reference_attention(
        monkeypatch, window):
    """The Pallas kernels, interpreted, at sequence = 4 x window in tiles
    of half a window (so whole tiles lie under the window's lower edge and
    the live-tile maps skip them): forward and the fused backward against
    the reference's attention, float32; the sums differ in order only."""
    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    s, nq, nkv, d = 256, 4, 2, 32
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (1, s, nq, d))
    k = jax.random.normal(keys[1], (1, s, nkv, d))
    v = jax.random.normal(keys[2], (1, s, nkv, d))
    do = jax.random.normal(keys[3], (1, s, nq, d))
    assert ft.fused_bwd_fits(s, d, q.dtype, 32, 32)

    def kernel(q, k, v):
        return ft.flash_mha(q, k, v, sliding_window=window, block_q=32,
                            block_k=32)

    def plain(q, k, v):
        with jax.default_matmul_precision("highest"):
            return reference.attention(q[0], k[0], v[0], window)[None]

    out, vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, ref in zip(vjp(do), want_vjp(do)):
        np.testing.assert_allclose(got, ref, atol=1e-4)
    if window:
        # the window clips: the full layer's result is another
        full = ft.flash_mha(q, k, v, block_q=32, block_k=32)
        assert float(jnp.max(jnp.abs(full - out))) > 1e-2


# --- a one-kind model said as a period of two --------------------------------

def one_kind_pair(num_layers=4):
    """(a model whose layers are all alike, the same model said as a
    pattern of one kind repeated over a period of two, the parameters of
    both: the stacked [L, ...] layout is the same tree)."""
    from megatron_tpu.models import presets

    one = dataclasses.replace(
        presets.tiny(seq_length=SEQ), num_layers=num_layers,
        sliding_window_size=8, rope_theta=5e5,
        params_dtype="float32").validate()
    kind = one.attention_kind
    # (not validated: a model has one spelling, and validate() refuses
    # this one: test_a_model_has_one_spelling)
    as_pattern = dataclasses.replace(
        one, sliding_window_size=None, rope_theta=10000.0,
        attention_pattern=(kind, dataclasses.replace(kind, name="again")))
    assert len(set(as_pattern.attention_period)) == 2
    params = init_params(one, jax.random.PRNGKey(0))
    again = init_params(as_pattern, jax.random.PRNGKey(0))
    assert jax.tree.all(jax.tree.map(
        lambda a, b: a.shape == b.shape and bool(jnp.all(a == b)),
        params, again))
    return one, as_pattern, params


@pytest.mark.parametrize(
    "recompute", ["none", "selective", "full", "block:2", "uniform:2"])
def test_a_one_kind_model_is_bit_equal_through_the_period_stack(recompute):
    """The stack of periods of two gives bit-equal loss and gradients to
    the stack of single layers under every recomputation, those that count
    layers among them; a count that cuts a period is refused."""
    one, as_pattern, params = one_kind_pair()
    batch = sequences()
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: lm_loss(
        one, p, batch, recompute=recompute)[0]))(params)
    got, grads = jax.jit(jax.value_and_grad(lambda p: lm_loss(
        as_pattern, p, batch, recompute=recompute)[0]))(params)
    assert float(got) == float(want)
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool(jnp.all(a == b)), grads, want_grads))
    n = recompute.partition(":")[2]
    if n:
        with pytest.raises(ValueError, match="multiple of the period"):
            lm_loss(as_pattern, params, batch,
                    recompute=recompute.replace(n, "1"))


def _checkpoints(jaxpr, in_loop=False):
    """(under a loop?, prevent_cse) of every `jax.checkpoint` equation of
    a traced function, the ones inside other equations' bodies too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("remat"):
            found.append((in_loop, eqn.params["prevent_cse"]))
        loop = in_loop or eqn.primitive.name in ("scan", "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _checkpoints(sub, loop)
    return found


@pytest.mark.parametrize("case,layers,in_loop,prevent_cse", [
    ("one", 1, False, False),         # XLA may merge it with the forward
    ("as_pattern", 2, False, True),   # merged, the period would be kept
    ("one", 4, True, False),
    ("as_pattern", 4, True, False),
])
def test_a_stack_of_one_trip_is_a_call(case, layers, in_loop, prevent_cse):
    """scan_with_remat's one-trip rule, read off the traced loss: one
    layer is a checkpointed call that XLA may merge with its forward, one
    period of several layers a call each that it may not, and more trips
    are a loop with the layers' checkpoints inside."""
    one, as_pattern, params = one_kind_pair(layers)
    cfg = {"one": one, "as_pattern": as_pattern}[case]
    traced = jax.make_jaxpr(lambda p: lm_loss(
        cfg, p, sequences(), recompute="selective")[0])(params)
    per_trip = len(cfg.attention_period)
    assert _checkpoints(traced.jaxpr) == [(in_loop, prevent_cse)] * per_trip


def test_a_model_has_one_spelling():
    """A pattern whose layers are all alike is refused (the scalars say
    it), as are the scalars beside a pattern and the one-kind paths for a
    model of several kinds; what the scalars cannot say keeps the pattern
    of one layer."""
    one, as_pattern, _ = one_kind_pair()
    kind = one.attention_kind
    with pytest.raises(NotImplementedError, match="one kind"):
        as_pattern.attention_kind
    with pytest.raises(ValueError, match="leave sliding_window_size"):
        dataclasses.replace(as_pattern, sliding_window_size=8).validate()
    for pattern in (as_pattern.attention_pattern, (kind,)):
        with pytest.raises(ValueError, match="all alike: say a one-kind"):
            dataclasses.replace(as_pattern,
                                attention_pattern=pattern).validate()
    yarn = dataclasses.replace(kind, rope_type="yarn",
                               yarn_original_max_positions=SEQ)
    dataclasses.replace(as_pattern, attention_pattern=(yarn,)).validate()
    with pytest.raises(ValueError, match="all alike"):
        dataclasses.replace(as_pattern,
                            attention_pattern=(yarn, yarn)).validate()


def test_the_pattern_comes_back_from_a_saved_config():
    from megatron_tpu.config import RunConfig
    from megatron_tpu.training.checkpointing import check_config_compatibility

    cfg = case("share0")[1]
    saved = json.loads(json.dumps(RunConfig(model=cfg).to_dict()))
    assert RunConfig.from_dict(saved).model == cfg
    check_config_compatibility(saved, RunConfig(model=cfg).to_dict())
    other = dataclasses.replace(cfg, **_pattern(cfg, rope_theta=1e6))
    with pytest.raises(ValueError, match="attention_pattern"):
        check_config_compatibility(saved, RunConfig(model=other).to_dict())


# --- the cell's path: pretrain_gpt.main under the harness -------------------

@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The real BENCHMARK.json's metrics of the cell
    `train_mellum2_share4_seq8k` over the toy share and a mix of its
    shape (two sequences a forward call), run traced through benchmark/run.py on the CPU: the trainer's
    own entry point, data pipeline and journal."""
    import subprocess

    root = tmp_path_factory.mktemp("toy_mellum")
    cell, real = "toy_mellum_share", "train_mellum2_share4_seq8k"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = ["."]
    bench["configs"] = [{"name": "toy-mellum", "source": "none",
                         "file": "toy-mellum.json", "reduced": [],
                         "why": "CPU rehearsal"}]
    bench["workloads"] = [{"name": cell, "config": "toy-mellum",
                           "traffic": cell, "chips": 1,
                           "why": "CPU rehearsal of " + real}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cell] if real in m["workloads"] else []
    config = dict(share_of(TOY, 1), source="none", reference="mellum",
                  program={"flags": ["--fp32", "--attention_impl", "pallas"]})
    mix = {"driver": "train", "seq_length": 128, "micro_batch_size": 2,
           "global_batch_size": 2,
           "flags": ["--recompute_granularity", "selective",
                     "--ce_chunk_size", "64", "--lr", "3e-3",
                     "--lr_decay_style", "constant"],
           "warmup_steps": 2, "max_steps_per_s": 60,
           "trace_after_steps": 1, "trace_steps": 2,
           "corpus": {"tokens": 60000, "cycle": 64,
                      "doc_tokens_median": 100, "doc_tokens_sigma": 1.0,
                      "doc_tokens_min": 8, "doc_tokens_max": 1024},
           "first_loss_tolerance": 1e-4, "loss_must_fall_by": 0.0}
    os.makedirs(root / "traffic")
    for path, value in ((root / "spec.json", bench),
                        (root / "toy-mellum.json", config),
                        (root / "traffic" / (cell + ".json"), mix)):
        with open(path, "w") as f:
            json.dump(value, f)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--spec", str(root / "spec.json"), "--workload", cell, "--seed",
         "2147484042", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    run_dir = os.path.join(REPO, "runs", "benchmark", cell)
    with open(os.path.join(run_dir, "result.json")) as f:
        result = json.load(f)
    with open(os.path.join(run_dir, "tele", "events.jsonl")) as f:
        journal = [json.loads(line) for line in f if line.strip()]
    return json.loads(proc.stdout.strip().splitlines()[-1]), result, journal


def test_a_step_through_the_trainer_matches_the_reference(rehearsed):
    """One optimizer step over two sequences in one forward call through
    pretrain_gpt.main (window 8 under sequence 128, the interpreted flash
    kernels, the share's buffer): the journal's `loss` is what the
    reference computes for the first batch; float32, so to 1e-5."""
    line, result, _ = rehearsed
    assert line["correct"] is True, line.get("problems")
    first = result["steps"][0]
    assert first["iteration"] == 1 and first["ntokens"] == 2 * 128
    assert abs(first["loss"] - result["reference_first_loss"]) <= 1e-5 * abs(
        result["reference_first_loss"])
    assert result["train_flops_per_token"] == reference.train_flops_per_token(
        share_of(TOY, 1), 128)


def test_the_step_record_carries_the_held_rows_share(rehearsed):
    line, _, journal = rehearsed
    steps = [r for r in journal if r.get("kind") == "step"]
    assert steps and all(0.0 < r["moe_held_rows_share"] < 1.0
                         and r["moe_load_max_over_mean"] >= 1.0
                         for r in steps)
    # what a CPU line can hold of the cell's metrics: the journal's
    assert {"moe_held_rows_share", "moe_load_max_over_mean",
            "step_hbm_gb"} <= set(line["metrics"])
    assert line["metrics"]["moe_held_rows_share"]["unit"] == "share"
    assert not {"attention_sliding_ms_per_step", "attention_full_ms_per_step",
                "flash_fwd_by_kind_roofline_pct",
                "flash_bwd_by_kind_roofline_pct",
                "moe_held_experts_roofline_pct"} & set(line["metrics"])


def test_the_step_record_carries_the_moved_rows_share(rehearsed):
    """Every `step` record of the rehearsed share carries
    `moe_moved_rows_share` beside the held rows' share, in (0, 1] (1.0 at
    the toy's row count: ops/moe.py `_walk_blocks`); no entry of
    BENCHMARK.json reads it, so the line's metrics do not hold it."""
    line, _, journal = rehearsed
    steps = [r for r in journal if r.get("kind") == "step"]
    assert steps and all(0.0 < r["moe_moved_rows_share"] <= 1.0
                         for r in steps)
    assert "moe_moved_rows_share" not in line["metrics"]


def test_the_step_program_counts_each_kinds_tiles(rehearsed):
    """The traced run's `step_program` record says, for each kind of
    attention layer, how far the flash kernels' tile classes engage at
    the step's shape (`flash_template.tile_counts`): the toy's sequence
    of 128 is one tile, whose dead upper right quarter the full layer's
    kernels skip (the interpreter slices any even tile); the window of 8
    is no multiple of the half tile, so the sliding layers' run the one
    masked body over the whole tile."""
    _, _, journal = rehearsed
    [program] = [r for r in journal if r.get("kind") == "step_program"]
    tiles = program["attention_tiles"]
    assert sorted(tiles) == ["full", "sliding"]
    assert tiles["full"] == ft.tile_counts(128, 128, True, None)
    assert tiles["full"]["by_class"] and tiles["full"]["causal_edge"] == 1
    assert tiles["full"]["tiles_computed"] == 0.75
    assert tiles["sliding"] == ft.tile_counts(128, 128, True, WINDOW)
    assert not tiles["sliding"]["by_class"] and tiles["sliding"]["both"] == 1
    assert tiles["sliding"]["tiles_computed"] == 1.0
    assert (tiles["sliding"]["computed_over_visible"]
            > 10 * tiles["full"]["computed_over_visible"])
