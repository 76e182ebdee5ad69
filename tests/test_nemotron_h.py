"""Nemotron-H on the program's normal path, held to the plain reference of
benchmark/reference/nemotron_h.py: layers that are ONE block each (a
Mamba-2 mixer, attention, or a LatentMoE expert layer alone behind one norm
and one residual add), served by the paged engine with the Mamba-2 state
beside the KV pages and one chip's share of the experts held. Toy widths,
whole structure: the published first 11 layers `MEMEMEM*EME`, 8 Mamba
heads of 16 channels in 2 groups with a state of 16, 4 query heads over 2
key/value heads, 16 experts of which 4 are held and 3 chosen a token, a
latent width of 32, a shared expert, untied head, no positional encoding.
Weights are seeded draws at a standard deviation of 0.1 with every norm's
weight, `D`, the convolution's bias and the selection bias spread, so that
every term carries weight."""

import dataclasses
import json
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
from megatron_tpu.models.language_model import lm_forward  # noqa: E402
from megatron_tpu.models.params import init_params  # noqa: E402
from megatron_tpu.ops import moe, ssm  # noqa: E402

reference = spec.load_module(
    os.path.join(REPO, "benchmark", "reference", "nemotron_h.py"))

F32 = jnp.float32
SEQ = 24
TOY = {
    "attention_bias": False, "chunk_size": 4, "conv_kernel": 4, "expand": 2,
    "head_dim": 16, "hidden_size": 64,
    "hybrid_override_pattern": "MEMEMEM*EME", "intermediate_size": 48,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 16,
    "mamba_hidden_act": "silu", "mamba_num_heads": 8,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_group": 1, "n_groups": 2,
    "n_routed_experts": 4, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 11, "num_key_value_heads": 2,
    "routed_scaling_factor": 5, "ssm_state_size": 16,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_conv_bias": True, "vocab_size": 128,
    "whole": {"n_routed_experts": 16},
    "assumed": {"initializer_range": {"value": 0.1},
                "expert_share": {"value": 0},
                "e_score_correction_bias": {"value": 0.1}},
}
# the same model with every expert held: what the shares add up to
UNCUT = {k: v for k, v in dict(TOY, n_routed_experts=16).items()
         if k != "whole"}


def program_config(toy=TOY, dtype="--fp32", seq=SEQ, **overrides):
    """The toy model as trainer and server build it from the reference's
    own translation into flags (what the benchmark's child passes)."""
    argv = reference.program_flags(toy, seq) + [
        dtype, "--micro_batch_size", "1", "--global_batch_size", "1"]
    model = args_to_run_config(parse_args(argv)).model
    return dataclasses.replace(model, **overrides).validate()


def seeded_params(cfg, seed=0):
    """init_params with what ones and zeros would hide drawn instead:
    every norm's weight and `D` around 1, the convolution's bias and the
    router's selection bias around 0; the matrices that write into the
    residual widened so that every block weighs on it."""
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=F32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def draw(path, leaf):
        name = path[-1].key
        noise = lambda s: s * jax.random.normal(next(keys), leaf.shape)  # noqa: E731
        if name in ("scale", "d_skip"):
            return 1.0 + noise(0.3)
        if name in ("conv_b", "router_bias"):
            return leaf + noise(0.1)
        if name == "conv_w":
            return leaf + noise(0.4)
        return 4.0 * leaf if name in (
            "wo", "w_out", "latent_out", "shared_out") else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def tokens_of(seed=0, seq=SEQ):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, TOY["vocab_size"], seq), jnp.int32)


def reference_logits(params, tokens, toy=TOY, **kw):
    weights = reference.from_program_params(params)
    return jax.jit(lambda w, t: reference.logits(w, t, toy, **kw))(
        weights, tokens)


def mm(a, w):
    return a @ w


def layer_of(params, group, i):
    """Layer i of one type's leaves: the program's and the reference's."""
    ours = jax.tree.map(lambda a: a[i], params["layers"][group])
    theirs = jax.tree.map(
        lambda a: a[i], reference.from_program_params(params)[
            {"ssm": "mamba", "attn": "attention", "moe": "moe"}[group]])
    return ours, theirs


def close(got, want, tolerance):
    top = float(jnp.abs(want).max())
    assert top > 0
    assert float(jnp.abs(got - want).max()) < tolerance * top


# float32 sums in another order (a chunked scan against a walk over the
# positions, a sorted dispatch against a mask an expert): 1e-5 of the
# largest entry passes that and fails any wrong term
EXACT = 1e-5


@pytest.fixture(scope="module")
def toy():
    cfg = program_config(seq=48)
    return cfg, seeded_params(cfg)


# --- the configuration ---------------------------------------------------------

def test_the_flags_build_the_published_model():
    """The benchmark's configuration file, through the reference's
    translation into flags, is the model the source states at the cut the
    file states; the weights are ISSUE 61's arithmetic."""
    from megatron_tpu.models.params import num_params, param_shapes

    with open(os.path.join(
            REPO, "benchmark", "configs",
            "nemotron3-super-120b-a12b-s4-d11-serve.json")) as f:
        config = json.load(f)
    cfg = args_to_run_config(parse_args(
        reference.program_flags(config, 4096) + config["program"]["flags"]
        + ["--micro_batch_size", "1", "--global_batch_size", "1"])).model
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.ffn_size, cfg.vocab_size) == (
        11, 4096, 32, 2, 128, 2688, 32768)
    assert cfg.layer_period == tuple(
        reference.KINDS[k] for k in "MEMEMEM*EME")
    assert cfg.single_block_layers and cfg.ssm_type == "mamba2"
    assert (cfg.ssm_d_inner, cfg.ssm_num_heads, cfg.ssm_head_dim,
            cfg.ssm_n_groups, cfg.ssm_d_state, cfg.ssm_d_conv,
            cfg.ssm_conv_width, cfg.ssm_chunk_size) == (
        8192, 128, 64, 8, 128, 4, 10240, 128)
    assert (cfg.num_experts, cfg.moe_experts_held, cfg.moe_top_k,
            cfg.moe_router_score, cfg.moe_route_scale, cfg.moe_latent_size,
            cfg.moe_shared_ffn_size, cfg.activation, cfg.moe_renorm_gates) == (
        512, 128, 22, "sigmoid", 5.0, 1024, 5376, "squared_relu", True)
    assert (cfg.position_embedding_type, cfg.tie_embed_logits,
            cfg.layers_of("mamba2"), cfg.layers_of("moe"),
            cfg.layers_of("attention"), cfg.expert_layers) == (
        "none", False, 5, 5, 1, 5)
    shapes = param_shapes(cfg)["layers"]
    assert set(shapes) == {"ln1", "ssm", "attn", "moe"}   # no ln2, no mlp
    assert shapes["ln1"]["scale"].shape == (11, 4096)
    assert shapes["ssm"]["w_in"].shape == (5, 4096, 18560)
    assert shapes["moe"]["w_in"].shape == (5, 128, 1024, 2688)
    assert shapes["moe"]["router"].shape == (5, 4096, 512)
    # 5 x 109.64 M + 35.66 M + 5 x 759.2 M + 2 x 134.2 M, 9.30 GB in bf16
    assert num_params(cfg) == reference.num_params(config) == 4_648_163_712
    # a state row: 5 x (128 x 8192 x 4 B + 3 x 10240 x 2 B), 21.3 MB
    assert reference.state_bytes_per_sequence(config) == 21_278_720
    assert sum(leaf.size * leaf.dtype.itemsize // leaf.shape[1] for leaf in
               jax.eval_shape(lambda: ssm.create_state(cfg, 2))) == 21_278_720
    assert reference.kv_bytes_per_token(config) == 1024


def test_what_a_typed_stack_still_refuses_is_named():
    cfg = program_config()
    for change, match in [
            ({"parallel_attn": True}, "parallel_attn"),
            ({"fp8_format": "e4m3"}, "fp8_format"),
            ({"num_experts": None, "moe_experts_held": None}, "num_experts"),
            ({"layer_pattern": ("moe", "moe")}, "no sequence mixer"),
            ({"layer_pattern": ("mamba", "mamba2", "moe")}, "both"),
            ({"ssm_num_heads": 7}, "ssm_num_heads"),
            ({"moe_dispatch": "capacity"}, "dropless")]:
        with pytest.raises((NotImplementedError, ValueError), match=match):
            program_config(num_layers=12 if "layer_pattern" in change
                           else 11, **change)


# --- each layer kind alone -----------------------------------------------------

def test_the_mamba2_mixer_matches_the_reference():
    """A full sequence of 24 through the chunked form (chunks of 4)
    against the reference's walk over the positions."""
    cfg = program_config()
    ours, theirs = layer_of(seeded_params(cfg), "ssm", 1)
    u = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64), F32)
    got, _ = jax.jit(lambda p, u: ssm.ssm_mixer(cfg, p, u))(ours, u)
    want = jax.jit(jax.vmap(
        lambda row: reference.mamba2_mixer(row, theirs, TOY, mm)))(u)
    close(got, want, EXACT)


def test_mamba2_chunks_then_decode_steps_match_one_pass():
    """A prompt of 13 through the state store in two chunks of 8 (the
    second padded behind 5), then 6 decode steps over three rows of which
    one decodes, against the reference's one pass over all 19; the rows
    that did not take part are as they were."""
    cfg = program_config()
    ours, theirs = layer_of(seeded_params(cfg), "ssm", 0)
    u = jax.random.normal(jax.random.PRNGKey(6), (19, 64), F32)
    want = reference.mamba2_mixer(u, theirs, TOY, mm)
    store = jax.tree.map(lambda a: a + 0.5, ssm.create_state(cfg, 3))
    store = ssm.zero_row(store, 1)
    out = []
    for off, n in ((0, 8), (8, 5)):
        chunk = jnp.zeros((1, 8, 64), F32).at[0, :n].set(u[off:off + n])
        y, state = ssm.ssm_mixer(cfg, ours, chunk,
                                 ssm.read_state(store, 2, 1),
                                 jnp.asarray([n], jnp.int32))
        store = ssm.write_state(store, 2, state, 1)
        out.append(y[0, :n])
    decoding = jnp.asarray([0, 1, 0], jnp.int32)
    for pos in range(13, 19):
        rows = jnp.zeros((3, 1, 64), F32).at[1, 0].set(u[pos])
        y, state = ssm.ssm_mixer(cfg, ours, rows, ssm.read_state(store, 2),
                                 decoding)
        store = ssm.write_state(store, 2, state)
        out.append(y[1])
    close(jnp.concatenate(out), want, EXACT)
    for leaf in store:
        others = np.delete(np.asarray(leaf[2], np.float32), 1, axis=0)
        assert (others == 0.5).all()
        # (zero_row cleared row 1 of every layer; the layers that did not
        # run hold that still)
        assert (np.asarray(leaf[:2, 1], np.float32) == 0.0).all()


def test_the_chunked_form_equals_the_recurrence_and_leaves_its_state():
    """`ssd_chunked` over 19 positions in chunks of 8 (the last padded)
    from a state that is not zero, against `ssd_step` position by
    position: the outputs, and the state after the last position."""
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    B, T, G, Hg, P, N = 2, 19, 2, 4, 16, 16
    x = jax.random.normal(keys[0], (B, T, G, Hg, P), F32)
    dt = jax.nn.softplus(jax.random.normal(keys[1], (B, T, G, Hg), F32) - 2)
    dt = dt.at[1, 15:].set(0.0)           # positions that are not real
    a = -jnp.exp(jax.random.normal(keys[2], (G, Hg), F32))
    b = jax.random.normal(keys[3], (B, T, G, N), F32)
    c = jax.random.normal(keys[4], (B, T, G, N), F32)
    s0 = jax.random.normal(keys[5], (B, N, G, Hg * P), F32)
    got, state = ssm.ssd_chunked(x, dt, a, b, c, s0, 8)
    # the one step works on the state as the store holds it: [B, N, d_i]
    s, want = s0.reshape(B, N, -1), []
    for t in range(T):
        if t == 15:
            kept = s[1]
        y, s = ssm.ssd_step(x[:, t].reshape(B, -1), dt[:, t].reshape(B, -1),
                            a.reshape(-1), b[:, t], c[:, t], s)
        want.append(y.reshape(B, G, Hg, P))
    close(got, jnp.stack(want, axis=1), EXACT)
    close(state.reshape(B, N, -1), s, EXACT)
    np.testing.assert_array_equal(s[1], kept)     # dt 0 moves no state


def test_the_expert_layer_matches_the_reference():
    """The program's dispatch (a sort, grouped products, a gather back)
    against a mask an expert, on one chip's share: the router 16 wide, 3 a
    token by score + bias, the 4 held experts' terms through the latent
    width, the shared expert whole."""
    cfg = program_config()
    ours, theirs = layer_of(seeded_params(cfg), "moe", 2)
    u = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, 64), F32)
    got, aux, load = moe.moe_block(cfg, ours, u)
    assert float(aux) == 0.0 and load.shape == (4,)
    for row in range(2):
        close(got[row], reference.latent_moe(u[row], theirs, TOY, mm), EXACT)
    chosen, _ = reference.route(u.reshape(-1, 64), theirs, TOY, mm)
    assert float(load[1]) == pytest.approx(float(jnp.mean(chosen < 4)))


def test_the_attention_layer_matches_the_reference():
    from megatron_tpu.models.transformer import attention_block

    cfg = program_config()
    ours, theirs = layer_of(seeded_params(cfg), "attn", 0)
    u = jax.random.normal(jax.random.PRNGKey(9), (2, SEQ, 64), F32)
    got, _ = attention_block(cfg, ours, u, None, None)
    for row in range(2):
        close(got[row], reference.attention_mixer(u[row], theirs, TOY, mm),
              EXACT)


# --- the share ------------------------------------------------------------------

def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each of the 4 chips that share the layer holds 4 of the 16 experts
    and computes its own part; the four routed parts, with the shared
    expert (and nothing else) counted once, are the uncut reference's
    layer."""
    uncut = program_config(UNCUT)
    whole, theirs = layer_of(seeded_params(uncut), "moe", 3)
    u = jax.random.normal(jax.random.PRNGKey(10), (1, SEQ, 64), F32)
    want = reference.latent_moe(u[0], theirs, UNCUT, mm)
    total = 0.0
    for share in range(4):
        cfg = program_config(moe_expert_share=share)
        assert cfg.moe_experts_held == 4 and cfg.num_experts == 16
        held = dict(whole, **{name: whole[name][4 * share:4 * share + 4]
                              for name in ("w_in", "w_out")})
        if share:       # the shared expert is counted once, with share 0
            cfg = dataclasses.replace(cfg, moe_shared_ffn_size=None)
            held = {k: v for k, v in held.items() if "shared" not in k}
        part, _, load = moe.moe_block(cfg, held, u)
        assert 0.0 < float(load[1]) < 1.0
        total = total + part[0]
    close(total, want, EXACT)
    # and the program's uncut layer is the same
    close(moe.moe_block(uncut, whole, u)[0][0], want, EXACT)


def test_the_selection_bias_changes_the_choice_and_the_program_follows_it():
    """With the bias the chosen set differs from the scores' own top 3 for
    a stated share of tokens (here more than half), the gates stay the
    scores', and the program's layer is the biased choice's."""
    cfg = program_config()
    ours, theirs = layer_of(seeded_params(cfg), "moe", 0)
    u = jax.random.normal(jax.random.PRNGKey(11), (1, 64, 64), F32)
    plain = dict(theirs, e_score_correction_bias=jnp.zeros(16))
    with_bias, _ = reference.route(u[0], theirs, TOY, mm)
    without, _ = reference.route(u[0], plain, TOY, mm)
    differs = jnp.any(jnp.sort(with_bias, -1) != jnp.sort(without, -1), -1)
    assert float(jnp.mean(differs)) > 0.5
    got = moe.moe_block(cfg, ours, u)[0][0]
    want = reference.latent_moe(u[0], theirs, TOY, mm)
    close(got, want, EXACT)
    wrong = reference.latent_moe(u[0], plain, TOY, mm)
    assert float(jnp.abs(wrong - want).max()) > 1e3 * EXACT * float(
        jnp.abs(want).max())


# --- the whole stack -------------------------------------------------------------

def test_float32_logits_of_the_whole_stack_match_the_reference():
    cfg = program_config()
    params = seeded_params(cfg)
    tokens = jnp.stack([tokens_of(1), tokens_of(2)])
    got = lm_forward(cfg, params, tokens)
    for row in range(2):
        close(got[row], reference_logits(params, tokens[row]), 2 * EXACT)


CHUNK, PAGE = 8, 4
# what float32 sums in another order come to, of the largest logit, over
# 11 layers: the program's served logits read 8.9e-7 of it from the
# reference's; a state kept in bfloat16 reads 9.1e-4 and a softmax router
# 0.21 (test_a_lower_precision_or_a_wrong_router_fails holds the limit
# between: thirty times over the one reading, thirty under the other)
SERVED_TOLERANCE = 3e-5


def served_logits(cfg, params, tokens, prompt_len, slot=1, slots=3):
    """Logits at every position of `tokens`, as the paged engine computes
    them: the prompt in chunks of CHUNK through the state row `slot` (the
    last chunk padded), then one decode step a token over all `slots`, of
    which only `slot` decodes."""
    from megatron_tpu.ops import kv_store

    pages = -(-len(tokens) // PAGE)
    kv = kv_store.create(cfg, 1 + slots * pages, PAGE)
    state = ssm.zero_row(ssm.create_state(cfg, slots), slot)
    table = np.zeros((slots, pages), np.int32)
    table[slot] = 1 + slot * pages + np.arange(pages)
    @jax.jit
    def chunk_step(kv, state, chunk, off, n):
        return lm_forward(
            cfg, params, chunk, kv_caches=kv, cache_index=off,
            page_table=jnp.asarray(table[slot:slot + 1]),
            page_write_start=jnp.int32(0),
            page_write_end=jnp.int32(prompt_len),
            ssm_state=state, state_row=jnp.int32(slot), state_valid=n[None])

    @jax.jit
    def decode_step(kv, state, last, lengths):
        return lm_forward(
            cfg, params, last[:, None], kv_caches=kv, cache_index=lengths,
            page_table=jnp.asarray(table), ssm_state=state,
            state_valid=(jnp.arange(slots) == slot).astype(jnp.int32))

    out = []
    for off in range(0, prompt_len, CHUNK):
        chunk = np.zeros((1, CHUNK), np.int32)
        n = min(CHUNK, prompt_len - off)
        chunk[0, :n] = tokens[off:off + n]
        logits, kv, state = chunk_step(kv, state, jnp.asarray(chunk),
                                       jnp.int32(off), jnp.int32(n))
        out.append(logits[0, :n])
    for pos in range(prompt_len, len(tokens)):
        last = jnp.zeros((slots,), jnp.int32).at[slot].set(tokens[pos])
        lengths = jnp.zeros((slots,), jnp.int32).at[slot].set(pos)
        logits, kv, state = decode_step(kv, state, last, lengths)
        out.append(logits[slot])
    return jnp.concatenate(out)


def test_served_logits_match_the_reference_at_every_position():
    """A prompt of 21 tokens (two whole chunks of 8 and one of 5, padded),
    then 9 decode steps: logits at all 30 positions against the
    reference's one pass."""
    cfg = program_config(seq=32)
    params = seeded_params(cfg)
    tokens = np.asarray(tokens_of(3, 30))
    got = served_logits(cfg, params, tokens, prompt_len=21)
    close(got, reference_logits(params, jnp.asarray(tokens)),
          SERVED_TOLERANCE)


@pytest.mark.parametrize("variant", [{"state_dtype": jnp.bfloat16},
                                     {"router_score": "softmax"}],
                         ids=["bfloat16_state", "softmax_router"])
def test_a_lower_precision_or_a_wrong_router_fails(variant):
    """The control of SERVED_TOLERANCE: the same pass with the recurrent
    state rounded to bfloat16 after every step, or with a softmax router
    in the sigmoid one's place, is not inside it."""
    cfg = program_config(seq=32)
    params = seeded_params(cfg)
    tokens = tokens_of(3, 30)
    want = reference_logits(params, tokens)
    wrong = reference_logits(params, tokens, **variant)
    top = float(jnp.abs(want).max())
    assert float(jnp.abs(wrong - want).max()) > 10 * SERVED_TOLERANCE * top


# --- the paged engine ------------------------------------------------------------

def make_engine(cfg, params, **kw):
    from megatron_tpu.inference.engine import InferenceEngine

    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("prefill_chunk", CHUNK)
    return InferenceEngine(cfg, params, **kw)


def test_the_engine_serves_what_the_reference_computes(toy):
    """One request through the engine's own jitted steps: its greedy
    tokens are the reference's first choice at every served position, and
    the log-probabilities it reports are the reference's."""
    from megatron_tpu.inference.engine import Request

    cfg, params = toy
    prompt = np.asarray(tokens_of(4, 21))         # 3 chunks
    eng = make_engine(cfg, params)
    req = eng.submit(Request(prompt=prompt, max_new_tokens=10))
    eng.run_until_idle()
    assert req.error is None, req.error
    tokens = jnp.asarray(req.tokens)
    logp = jax.nn.log_softmax(reference_logits(params, tokens), -1)
    p = len(prompt)
    assert req.generated == [int(t) for t in jnp.argmax(logp[p - 1:-1], -1)]
    at = jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=-1)[:, 0]
    np.testing.assert_allclose(req.prompt_logprobs, at[:p - 1], atol=1e-4)
    np.testing.assert_allclose(req.logprobs, at[p - 1:], atol=1e-4)
    assert eng.stats["decode_recompiles"] == 0


def test_the_engine_counts_the_rows_its_held_experts_took(toy):
    """Both steps add to the counters with their tokens: every (row,
    choice) pair of a row somebody reads over the 5 expert layers (a
    tick: the decoding slots' rows; a chunk: its real positions, not its
    padded tail), and those of them sent to the 4 held experts; the
    ticks alone, the held experts such a row reached of the 4 x 5 there
    are a tick."""
    from megatron_tpu.inference.engine import Request
    from megatron_tpu.telemetry.metrics import MetricsRegistry

    cfg, params = toy
    eng = make_engine(cfg, params, metrics=MetricsRegistry())
    reqs = [eng.submit(Request(prompt=np.asarray(tokens_of(s, n)),
                               max_new_tokens=6))
            for s, n in ((5, 13), (6, 5))]
    eng.run_until_idle()
    assert [r.error for r in reqs] == [None, None]
    chunks, ticks = eng.stats["prefill_chunks"], eng.stats["ticks"]
    assert chunks == 3
    # (the device takes a slot for decoding while its table row holds a
    # page: a request's last tick in flight may be followed by one more)
    pairs = eng.stats["moe_rows"]
    rows = 13 + 5 + eng.stats["decode_rows"]
    assert rows * 15 <= pairs <= (rows + len(reqs)) * 15
    assert pairs % 15 == 0 and pairs < (chunks * CHUNK + ticks * 2) * 15
    assert 0 < eng.stats["moe_held_rows"] < pairs
    assert eng.stats["moe_experts_offered"] == ticks * 4 * 5
    assert 0 < eng.stats["moe_experts_read"] < ticks * 4 * 5
    text = eng.metrics.render()
    assert f"engine_moe_rows_total {pairs}" in text
    assert f"engine_moe_held_rows_total {eng.stats['moe_held_rows']}" in text
    assert (f"engine_moe_experts_read_total "
            f"{eng.stats['moe_experts_read']}") in text
    assert f"engine_moe_experts_offered_total {ticks * 20}" in text
    fields = eng._serve_ticks_fields()
    assert fields["moe_rows"] == [eng.stats["moe_held_rows"], pairs]
    assert fields["moe_experts"] == [eng.stats["moe_experts_read"],
                                     ticks * 20]


def test_continuous_batching_serves_each_request_as_if_alone(toy):
    """Four requests through two slots, admitted at different ticks, a
    prompt in mid-prefill while the other slot decodes, every slot reused:
    each request's greedy tokens are those it gets served alone."""
    from megatron_tpu.inference.engine import Request

    cfg, params = toy
    prompts = [np.asarray(tokens_of(20 + i, n))
               for i, n in enumerate((13, 21, 5, 9))]
    new = [7, 9, 12, 5]

    def alone(prompt, n):
        eng = make_engine(cfg, params)
        req = eng.submit(Request(prompt=prompt, max_new_tokens=n))
        eng.run_until_idle()
        return req.generated

    want = [alone(p, n) for p, n in zip(prompts, new)]
    eng = make_engine(cfg, params)
    reqs = []
    for p, n in zip(prompts, new):
        reqs.append(eng.submit(Request(prompt=p, max_new_tokens=n)))
        eng.step()
        eng.step()
    eng.run_until_idle()
    assert [r.error for r in reqs] == [None] * 4
    assert [r.generated for r in reqs] == want
    assert eng.stats["state_resets"] == 4
    assert eng.stats["decode_recompiles"] == 0


# --- a dense FFN alone -------------------------------------------------------------

def test_a_dense_ffn_alone_is_a_layer_too():
    """The pattern's `-`: a dense FFN behind one norm and one add, its
    leaves stacked over its own layers."""
    toy = dict(UNCUT, hybrid_override_pattern="M-*E-M", num_hidden_layers=6)
    cfg = program_config(toy)
    assert cfg.layer_period == ("mamba2", "mlp", "attention", "moe", "mlp",
                                "mamba2")
    params = seeded_params(cfg)
    assert params["layers"]["mlp"]["w_in"].shape == (2, 64, 48)
    assert "ln2" not in params["layers"]
    tokens = tokens_of(12)
    close(lm_forward(cfg, params, tokens[None])[0],
          reference_logits(params, tokens, toy), 2 * EXACT)


# --- the decode tick's kernel ---------------------------------------------------

def test_the_step_kernel_advances_the_store_in_place_as_the_plain_step():
    """`ssd_step`'s Pallas kernel (interpreted here) over a store of 3
    layers and 4 rows against the plain step on layer 1's rows: y, the
    layer's state after, a row whose dt is 0 unmoved, and the other
    layers untouched."""
    from megatron_tpu.ops.pallas.ssd_step import serves, ssd_step

    keys = jax.random.split(jax.random.PRNGKey(13), 6)
    R, H, P, G, N = 4, 4, 64, 2, 128
    di = H * P
    assert serves(N, di // G) and not serves(16, di // G)
    store = jax.random.normal(keys[0], (3, R, N, di), F32)
    x = jax.random.normal(keys[1], (R, di), F32)
    dt = jax.nn.softplus(jax.random.normal(keys[2], (R, H), F32) - 2)
    dt = dt.at[2].set(0.0)                   # a row that does not decode
    a = -jnp.exp(jax.random.normal(keys[3], (H,), F32))
    b = jax.random.normal(keys[4], (R, G, N), F32)
    c = jax.random.normal(keys[5], (R, G, N), F32)
    want_y, want_s = ssm.ssd_step(x, dt, a, b, c, store[1])
    wide = lambda t: jnp.repeat(t, P, axis=-1)  # noqa: E731
    y, after = ssd_step(store, jnp.int32(1), wide(jnp.exp(dt * a)),
                        wide(dt) * x, b, c)
    close(y, want_y, EXACT)
    close(after[1], want_s, EXACT)
    np.testing.assert_array_equal(after[1, 2], store[1, 2])
    np.testing.assert_array_equal(after[0], store[0])
    np.testing.assert_array_equal(after[2], store[2])
