"""Jamba on the program's normal path, held to the plain reference of
benchmark/reference/jamba.py: Mamba-1 state-space layers and attention
layers as layer TYPES in one stack, trained through `lm_loss` and served
by the paged engine with the recurrent state beside the KV pages. Toy
widths, whole structure: 8 layers in two periods of 4 with the attention
layer at offset 2, a convolution of 4, a state of 4, 4 query heads over
ONE key/value head, tied head, no positional encoding. Weights are seeded
draws at a standard deviation of 0.1 with every norm's scale drawn around
1 and `A_log`, `D`, the convolution's bias and the step size's bias
spread, so that every term carries weight."""

import dataclasses
import functools
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402
from megatron_tpu.arguments import args_to_run_config, parse_args  # noqa: E402
from megatron_tpu.models.language_model import lm_forward, lm_loss  # noqa: E402
from megatron_tpu.models.params import init_params  # noqa: E402

reference = spec.load_module(
    os.path.join(REPO, "benchmark", "reference", "jamba.py"))

SEQ = 24
TOY = {
    "attn_layer_offset": 2, "attn_layer_period": 4,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 32, "intermediate_size": 48,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 4,
    "mamba_dt_rank": 3, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 128, "model_type": "jamba",
    "num_attention_heads": 4, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 8, "num_key_value_heads": 1,
    "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "vocab_size": 128,
    "assumed": {"initializer_range": {"value": 0.1}},
}


def program_config(dtype="--fp32", seq=SEQ, **overrides):
    """The toy model as trainer and server build it from the reference's
    own translation into flags (what the benchmark's child passes)."""
    argv = reference.program_flags(TOY, seq) + [
        dtype, "--micro_batch_size", "1", "--global_batch_size", "1"]
    model = args_to_run_config(parse_args(argv)).model
    return dataclasses.replace(model, **overrides).validate()


def seeded_params(cfg, seed=0):
    """init_params with what ones, zeros and a fixed ladder would hide
    drawn instead: every norm's scale and `D` around 1, the convolution's
    bias around 0, `A_log` around its ladder; the output projections
    widened so that both mixers weigh on the residual."""
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def draw(path, leaf):
        name = path[-1].key
        noise = lambda s: s * jax.random.normal(next(keys), leaf.shape)  # noqa: E731
        if name in ("scale", "d_skip"):
            return 1.0 + noise(0.3)
        if name in ("conv_b", "a_log"):
            return leaf + noise(0.2)
        if name == "conv_w":
            return leaf + noise(0.4)
        return 4.0 * leaf if name in ("wo", "w_out") else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def sequences(seed=0, rows=2, seq=SEQ):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, TOY["vocab_size"], (rows, seq + 1))
    mask = (rng.random((rows, seq)) > 0.1).astype(np.float32)
    return {"tokens": jnp.asarray(tokens[:, :-1], jnp.int32),
            "labels": jnp.asarray(tokens[:, 1:], jnp.int32),
            "loss_mask": jnp.asarray(mask)}


def reference_logits(params, tokens, **kw):
    weights = reference.from_program_params(params)
    return jax.jit(lambda w, t: reference.logits(w, t, TOY, **kw))(
        weights, tokens)


def test_the_flags_build_the_published_model():
    """The benchmark's configuration file, through the reference's
    translation into flags, is the model the source states, whole; the
    preset is the same model."""
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import num_params, param_shapes

    with open(os.path.join(REPO, "benchmark", "configs",
                           "jamba2-3b-serve.json")) as f:
        config = json.load(f)
    cfg = args_to_run_config(parse_args(
        reference.program_flags(config, 4096) + config["program"]["flags"]
        + ["--micro_batch_size", "1", "--global_batch_size", "1"])).model
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.ffn_size, cfg.vocab_size) == (
        28, 2560, 20, 1, 128, 8192, 65536)
    assert cfg.layer_period == ("mamba",) * 7 + ("attention",) + ("mamba",) * 6
    assert (cfg.ssm_d_inner, cfg.ssm_d_state, cfg.ssm_d_conv, cfg.ssm_rank,
            cfg.ssm_inner_norms) == (5120, 16, 4, 160, True)
    assert (cfg.position_embedding_type, cfg.tie_embed_logits,
            cfg.layers_of("mamba"), cfg.layers_of("attention")) == (
        "none", True, 26, 2)
    # ISSUE 47's arithmetic: 26 x 104.16 M + 2 x 76.68 M + 167.8 M
    assert num_params(cfg) == reference.num_params(config) == 3_029_337_472
    shapes = param_shapes(cfg)["layers"]
    assert shapes["ssm"]["w_in"].shape == (26, 2560, 10240)
    assert shapes["attn"]["wq"].shape == (2, 2560, 2560)
    assert shapes["mlp"]["w_in"].shape == (28, 2560, 16384)
    preset = presets.PRESETS["jamba"]()
    assert dataclasses.replace(
        preset, params_dtype=cfg.params_dtype,
        attention_impl=cfg.attention_impl) == cfg


# --- (a) trained through lm_loss against the reference ------------------------

@pytest.mark.parametrize("recompute", ["none", "full"])
def test_float32_logits_loss_and_every_gradient_leaf_match_the_reference(
        recompute):
    """Same mathematics in float32 by two mechanisms (a scan over periods
    of typed layers with batched time steps against a Python walk over
    the layers, one sequence at a time): they differ by the order of
    float32 sums only; 1e-5 of each leaf's largest entry passes that and
    fails any wrong term."""
    cfg = program_config()
    params = seeded_params(cfg)
    batch = sequences()
    got = lm_forward(cfg, params, batch["tokens"])
    for row in range(2):
        want = reference_logits(params, batch["tokens"][row])
        np.testing.assert_allclose(got[row], want,
                                   atol=1e-5 * float(jnp.abs(want).max()))

    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(cfg, p, batch, recompute=recompute)[0])(params)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.lm_loss(reference.from_program_params(p),
                                    batch["tokens"], batch["labels"],
                                    batch["loss_mask"], TOY))(params)
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    wanted = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in flat:
        w = wanted[path]
        top = float(jnp.abs(w).max())
        assert top > 0, jax.tree_util.keystr(path)   # every leaf is used
        np.testing.assert_allclose(g, w, atol=1e-5 * top, rtol=0,
                                   err_msg=jax.tree_util.keystr(path))


# --- (b) served: chunks, then decode steps, against the full pass -------------

CHUNK, PAGE = 8, 4
# what float32 sums in another order come to, of the largest logit: the
# program's served logits read 6e-7 of it from the reference's; a state
# kept in bfloat16 reads 1.0e-3 (test_a_bfloat16_state_fails_the_served_
# tolerance holds the limit between the two)
SERVED_TOLERANCE = 1e-4


def served_logits(cfg, params, tokens, prompt_len, slot=1, slots=3):
    """Logits at every position of `tokens`, as the paged engine computes
    them: the prompt in chunks of CHUNK through the state row `slot` (the
    last chunk padded), then one decode step a token over all `slots`,
    of which only `slot` decodes."""
    from megatron_tpu.ops import kv_store, ssm

    pages = -(-len(tokens) // PAGE)
    kv = kv_store.create(cfg, 1 + slots * pages, PAGE)
    state = ssm.create_state(cfg, slots)
    # the other rows hold what another sequence left: it must not be read
    state = jax.tree.map(lambda a: a + 0.5, state)
    state = ssm.zero_row(state, slot)
    table = np.zeros((slots, pages), np.int32)
    table[slot] = 1 + slot * pages + np.arange(pages)
    out = []
    for off in range(0, prompt_len, CHUNK):
        chunk = np.zeros((1, CHUNK), np.int32)
        n = min(CHUNK, prompt_len - off)
        chunk[0, :n] = tokens[off:off + n]
        logits, kv, state = lm_forward(
            cfg, params, jnp.asarray(chunk), kv_caches=kv,
            cache_index=jnp.int32(off), page_table=jnp.asarray(table[slot:slot + 1]),
            page_write_start=jnp.int32(0), page_write_end=jnp.int32(prompt_len),
            ssm_state=state, state_row=jnp.int32(slot),
            state_valid=jnp.asarray([n], jnp.int32))
        out.append(logits[0, :n])
    decoding = jnp.arange(slots) == slot
    for pos in range(prompt_len, len(tokens)):
        last = jnp.zeros((slots,), jnp.int32).at[slot].set(tokens[pos])
        lengths = jnp.zeros((slots,), jnp.int32).at[slot].set(pos)
        logits, kv, state = lm_forward(
            cfg, params, last[:, None], kv_caches=kv, cache_index=lengths,
            page_table=jnp.asarray(table), ssm_state=state,
            state_valid=decoding.astype(jnp.int32))
        out.append(logits[slot])
    # the rows that did not decode are as they were
    for leaf in state:
        others = np.delete(np.asarray(leaf, np.float32), slot, axis=1)
        assert (others == 0.5).all()
    return jnp.concatenate(out)


def test_served_logits_match_the_reference_at_every_position():
    """A prompt of 21 tokens (two whole chunks of 8 and one of 5, padded),
    then 9 decode steps: the state is carried from chunk to chunk, the
    padded tail moves it not, and decode takes it up where prefill left
    it. Logits at all 30 positions against the reference's one pass."""
    cfg = program_config(seq=32)
    params = seeded_params(cfg)
    tokens = np.asarray(sequences(seed=3, rows=1, seq=30)["tokens"][0])
    got = served_logits(cfg, params, tokens, prompt_len=21)
    want = reference_logits(params, jnp.asarray(tokens))
    top = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < SERVED_TOLERANCE * top


def test_a_bfloat16_state_fails_the_served_tolerance():
    """(f) The control of SERVED_TOLERANCE: the same pass with the
    recurrent state rounded to bfloat16 after every step is not inside
    it, so the tolerance tells a float32 state from a bfloat16 one."""
    cfg = program_config(seq=32)
    params = seeded_params(cfg)
    tokens = jnp.asarray(sequences(seed=3, rows=1, seq=30)["tokens"][0])
    want = reference_logits(params, tokens)
    low = reference_logits(params, tokens, state_dtype=jnp.bfloat16)
    top = float(jnp.abs(want).max())
    assert float(jnp.abs(low - want).max()) > 10 * SERVED_TOLERANCE * top


# --- (c), (e) the engine --------------------------------------------------------

def make_engine(cfg, params, **kw):
    from megatron_tpu.inference.engine import InferenceEngine

    kw.setdefault("num_slots", 2)
    kw.setdefault("max_seq_len", 48)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("prefill_chunk", CHUNK)
    return InferenceEngine(cfg, params, **kw)


@pytest.fixture(scope="module")
def toy():
    cfg = program_config(seq=48)
    return cfg, seeded_params(cfg)


def prompts(n, seed=11):
    rng = np.random.default_rng(seed)
    lengths = [13, 21, 5, 9, 17, 6][:n]
    return [rng.integers(0, TOY["vocab_size"], k).astype(np.int32)
            for k in lengths]


def served_alone(cfg, params, prompt, new_tokens):
    from megatron_tpu.inference.engine import Request

    eng = make_engine(cfg, params)
    req = eng.submit(Request(prompt=prompt, max_new_tokens=new_tokens))
    eng.run_until_idle()
    assert req.error is None, req.error
    return req


def test_the_engine_serves_what_the_reference_computes(toy):
    """One request through the engine's own jitted steps: its greedy
    tokens are the reference's first choice at every served position, and
    the log-probabilities it reports, of the prompt's tokens and of its
    own, are the reference's."""
    cfg, params = toy
    prompt = prompts(2)[1]                      # 21 tokens: 3 chunks
    req = served_alone(cfg, params, prompt, 10)
    tokens = jnp.asarray(req.tokens)
    logp = jax.nn.log_softmax(reference_logits(params, tokens), -1)
    p = len(prompt)
    assert req.generated == [int(t) for t in jnp.argmax(logp[p - 1:-1], -1)]
    at = jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=-1)[:, 0]
    np.testing.assert_allclose(req.prompt_logprobs, at[:p - 1], atol=1e-4)
    np.testing.assert_allclose(req.logprobs, at[p - 1:], atol=1e-4)
    # what the benchmark's generator sends (top_k 1 at the default
    # temperature) is a greedy row to the sampler: the same tokens, and
    # the request keeps the knobs it came with
    from megatron_tpu.inference.engine import Request

    eng = make_engine(cfg, params)
    one = eng.submit(Request(prompt=prompt, max_new_tokens=10,
                             temperature=1.0, top_k=1, seed=7))
    eng.run_until_idle()
    assert (one.temperature, one.top_k) == (1.0, 1)
    assert one.generated == req.generated


def test_continuous_batching_serves_each_request_as_if_alone(toy):
    """Six requests through two slots: admitted at different ticks, a
    prompt in mid-prefill (three chunks) while the other slot decodes,
    every slot reused after a retire. Each request's greedy tokens are
    those it gets served alone."""
    from megatron_tpu.inference.engine import Request

    cfg, params = toy
    new = [7, 9, 12, 5, 8, 10]
    alone = [served_alone(cfg, params, p, n).generated
             for p, n in zip(prompts(6), new)]
    eng = make_engine(cfg, params)
    reqs, seen_mixed = [], False
    for p, n in zip(prompts(6), new):
        reqs.append(eng.submit(Request(prompt=p, max_new_tokens=n)))
        for _ in range(2):   # the next arrives two ticks later
            eng.step()
            seen_mixed |= bool(eng.prefill_queue.slots
                               and eng._decode_rows())
    eng.run_until_idle()
    assert seen_mixed
    assert [r.error for r in reqs] == [None] * 6
    assert [r.generated for r in reqs] == alone
    assert eng.stats["state_resets"] == 6 and eng.stats["preemptions"] == 0
    assert eng.stats["decode_recompiles"] == 0


def test_a_preempted_request_resumes_from_a_fresh_state(toy):
    """Under page-pool pressure the younger request is preempted in
    mid-decode: its state is dropped with its pages, and it resumes by
    prefilling prompt + generated from position 0 into a zeroed row. Both
    finish with the tokens they get served alone."""
    from megatron_tpu.inference.engine import Request

    cfg, params = toy
    pa, pb = prompts(2)
    alone = [served_alone(cfg, params, p, 16).generated for p in (pa, pb)]
    # 13 + 16 and 21 + 16 tokens want 8 + 10 pages; 12 hold one of them
    eng = make_engine(cfg, params, num_pages=13)
    ra = eng.submit(Request(prompt=pa, max_new_tokens=16))
    rb = eng.submit(Request(prompt=pb, max_new_tokens=16))
    eng.run_until_idle()
    assert (ra.error, rb.error) == (None, None)
    assert eng.stats["preemptions"] >= 1
    assert eng.stats["state_resets"] == 2 + eng.stats["preemptions"]
    assert [ra.generated, rb.generated] == alone
    assert eng.pool.used_pages == 0     # nothing is kept for a prefix hit


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "seeded"])
def test_a_tick_in_flight_changes_no_token_of_a_typed_stack(toy, sampled):
    """The loop runs one tick ahead of the device (docs/serving.md "Step
    loop") over state rows too: staggered admissions, ends by eod in
    mid-stream (such a row's state advances one tick more, and is zeroed
    before the slot's next prompt) and by count. Each request's tokens,
    logprobs and prompt logprobs are those the same engine gives it alone
    with every tick read before the next is dispatched (`generate_tokens`
    carries no recurrent state)."""
    import _engine_lookahead_cases as cases

    cfg, params = toy

    def make():
        return make_engine(cfg, params, num_slots=3)

    cases.staggered_parity(make(), cases.served_alone_tick_by_tick(make),
                           TOY["vocab_size"], sampled)


def test_one_prompt_twice_is_no_prefix_hit(toy):
    """(e) A hit would need the state at the prefix's end: the tree is
    not asked, the finished prompt's pages are released, and the second
    request prefills whole, to the same answer."""
    from megatron_tpu.inference.engine import Request

    from megatron_tpu.telemetry.metrics import MetricsRegistry

    cfg, params = toy
    prompt = prompts(2)[1]
    # a registry of its own: the process's default one counts every
    # engine of this file
    eng = make_engine(cfg, params, metrics=MetricsRegistry())
    first = eng.submit(Request(prompt=prompt, max_new_tokens=6))
    eng.run_until_idle()
    second = eng.submit(Request(prompt=prompt, max_new_tokens=6))
    eng.run_until_idle()
    assert first.generated == second.generated
    assert eng.stats["prefix_hits"] == 0
    assert eng.stats["prefill_tokens"] == 2 * len(prompt)
    assert len(eng.prefix_cache) == 0 and eng.pool.used_pages == 0
    text = eng.metrics.render()
    assert "engine_state_resets_total 2\n" in text
    state_bytes = 6 * 2 * 64 * (4 * 4 + 3 * 4)   # layers x slots x d_i x ...
    assert f"engine_state_bytes {state_bytes}" in text


def test_paths_that_cannot_carry_the_state_refuse_by_name(toy):
    from megatron_tpu.inference.engine import Request
    from megatron_tpu.inference.speculative import SpecConfig
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.config import ParallelConfig

    cfg, params = toy
    with pytest.raises(NotImplementedError, match="no rollback"):
        make_engine(cfg, params, speculative=SpecConfig(k=2))
    rt = build_mesh(ParallelConfig(tensor_parallel=2))
    with pytest.raises(NotImplementedError, match="sharded serving"):
        make_engine(cfg, params, mesh=rt.mesh)
    eng = make_engine(cfg, params)
    req = eng.submit(Request(prompt=prompts(1)[0], max_new_tokens=4))
    eng.step()
    with pytest.raises(NotImplementedError, match="KV export"):
        eng.export_request_state(req)
    meta, sections = eng.export_request_state(req, include_kv=False)
    with pytest.raises(NotImplementedError, match="KV import"):
        eng.import_request_state(dict(meta, kv={"length": 1}), sections)
    with pytest.raises(NotImplementedError, match="prefix directory"):
        eng.export_prefix_state(req.prompt)
    with pytest.raises(NotImplementedError, match="prefix directory"):
        eng.import_prefix_state({}, {})
    # training under pipeline stages refuses the typed stack too
    from megatron_tpu.models.language_model import run_layers

    half = jax.tree.map(lambda a: a[: a.shape[0] // 2], params["layers"])
    with pytest.raises(NotImplementedError, match="pipeline stage"):
        run_layers(cfg, half, (jnp.zeros((1, 4, 32)), 0.0, None, None, None),
                   {cfg.attention_kind: None}, None)


def test_a_server_with_nothing_set_serves_the_typed_stack(toy):
    """No page size, no chunk, no flag: the service's engine holds the
    state store beside its default pool, and the reply's greedy tokens are
    those the engine at the toy's own geometry serves (before PR 63 the
    default was an engine that refused the model by name)."""
    from megatron_tpu.inference.server import GenerationService
    from megatron_tpu.telemetry.metrics import MetricsRegistry
    from megatron_tpu.tokenizer.tokenizer import NullTokenizer

    cfg, params = toy
    prompt = prompts(2)[1]
    want = served_alone(cfg, params, prompt, 6)
    service = GenerationService(
        cfg, params, NullTokenizer(TOY["vocab_size"] - 1), engine_slots=2,
        engine_max_seq_len=48, metrics=MetricsRegistry())
    try:
        eng = service.engine
        assert eng.state is not None
        assert (eng.page_size, eng.prefill_chunk, eng.num_pages) == (16, 32, 7)
        out = service.handle({"prompts": [" ".join(map(str, prompt))],
                              "tokens_to_generate": 6, "top_k": 1})
        assert out["text"][0].split()[len(prompt):] == [
            str(t) for t in want.generated]
        assert eng.stats["state_resets"] == 1
    finally:
        service.shutdown()


def test_the_one_shot_loops_refuse_a_typed_stack(toy):
    """`generate_tokens` and beam search carry keys and values alone: before
    they refused, a model with state-space layers got every token after
    its first from a zeroed recurrent state, and no error."""
    from megatron_tpu.inference.generation import (
        beam_search_tokens, generate_tokens)

    cfg, params = toy
    prompt = prompts(1)[0]
    with pytest.raises(NotImplementedError, match="no recurrent state"):
        generate_tokens(cfg, params, prompt[None], np.asarray([len(prompt)]),
                        max_new_tokens=4, temperature=0.0)
    with pytest.raises(NotImplementedError, match="no recurrent state"):
        beam_search_tokens(cfg, params, prompt, 4, beam_size=2, eod=0)


# --- (d) the kernel against the plain form ---------------------------------------

def scan_operands(seed, rows, T, di=64, n=4):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 9))
    normal = lambda *shape: jax.random.normal(next(keys), shape)  # noqa: E731
    return (normal(rows, T, di),                               # x
            jax.nn.softplus(normal(rows, T, di) - 2.0),        # delta
            -jnp.exp(0.3 * normal(n, di)),                     # a
            normal(rows, T, n), normal(rows, T, n),            # b, c
            1.0 + 0.3 * normal(di), normal(rows, T, di),       # d_skip, z
            normal(rows, n, di))                               # h0


@pytest.mark.parametrize("T,valid", [(24, (24, 24)), (24, (13, 0)),
                                     (1, (1, 0)), (21, (21, 5))])
def test_the_scan_kernel_is_the_plain_form(T, valid):
    """`ssm_scan` in interpret mode against `selective_scan`: a state
    carried in and out, a padded tail (positions past `valid` move no
    state; a row of none keeps its own), one position a row (decode's
    shape), a length that is no whole group of 8; and its gradient rule,
    the plain form's."""
    from megatron_tpu.ops.pallas.ssm_scan import ssm_scan
    from megatron_tpu.ops.ssm import selective_scan

    operands = scan_operands(T, 2, T)
    valid = jnp.asarray(valid, jnp.int32)
    y, h = ssm_scan(*operands, valid)
    want_y, want_h = selective_scan(*operands, valid)
    live = (jnp.arange(T)[None, :] < valid[:, None])[..., None]
    np.testing.assert_allclose(jnp.where(live, y, 0),
                               jnp.where(live, want_y, 0), atol=2e-5)
    np.testing.assert_allclose(h, want_h, atol=2e-5)
    assert (np.asarray(h[1]) == np.asarray(operands[-1][1])).all() \
        or int(valid[1]) > 0

    def loss(fn, *ops):
        y, h = fn(*ops, valid)
        return jnp.sum(jnp.where(live, y, 0) ** 2) + jnp.sum(h ** 2)

    every = tuple(range(len(operands)))
    got = jax.grad(functools.partial(loss, ssm_scan), every)(*operands)
    want = jax.grad(functools.partial(loss, selective_scan), every)(*operands)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4 * float(jnp.abs(w).max()))


# --- the cell's path: the server's entry point under the harness ------------------

def test_the_served_cell_runs_through_the_unchanged_harness(tmp_path):
    """The real BENCHMARK.json's metrics over the toy configuration and a
    small open-loop mix, run traced through benchmark/run.py on the CPU:
    `tools/run_text_generation_server.main` behind the harness's child,
    the benchmark's own weights handed over leaf for leaf, the paged
    engine with its state store, the kernels interpreted, and `correct`
    decided by the reference's forward pass. The readers of the device's
    trace find no device plane on the CPU and give None, not an error."""
    cell, real = "toy_jamba_reasoning", "serve_jamba2_3b_reasoning"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = ["."]
    bench["configs"] = [{"name": "toy-jamba", "source": "none",
                         "file": "toy-jamba.json", "reduced": [],
                         "why": "CPU rehearsal"}]
    bench["workloads"] = [{"name": cell, "config": "toy-jamba",
                           "traffic": cell, "chips": 1,
                           "why": "CPU rehearsal of " + real}]
    listed = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            if real in m["workloads"]:
                listed.append(m["name"])
            m["workloads"] = [cell] if real in m["workloads"] else []
    config = dict(TOY, source="none", reference="jamba", program={
        "flags": ["--bf16", "--attention_impl", "pallas"],
        "serve": {"seq_length": 128, "flags": [
            "--serve_kv_paging", "--serve_page_size", "8",
            "--serve_prefill_chunk", "16", "--serve_num_slots", "4",
            "--serve_max_seq_len", "128", "--serve_drain_timeout", "5"]}})
    mix = {"driver": "serve_open", "rate_rps": 8.0,
           "prompt_tokens": {"dist": "lognormal", "median": 20, "sigma": 0.6,
                             "min": 4, "max": 60},
           "new_tokens": {"dist": "lognormal", "median": 6, "sigma": 0.4,
                          "min": 2, "max": 12},
           "stratify": 4, "lead_s": 1, "trail_s": 10, "trace_after_s": 0.5,
           "trace_s": 1,
           "check": {"requests": 4, "logit_gap_tolerance": 0.05}}
    os.makedirs(tmp_path / "traffic")
    for path, value in ((tmp_path / "spec.json", bench),
                        (tmp_path / "toy-jamba.json", config),
                        (tmp_path / "traffic" / (cell + ".json"), mix)):
        with open(path, "w") as f:
            json.dump(value, f)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--spec", str(tmp_path / "spec.json"), "--workload", cell, "--seed",
         "2147484047", "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] == 32
    assert line["compared"]["logit_gap"]["value"] <= 0.05
    # the journal's and the generator's metrics are there; the device
    # trace's have nothing to read on the CPU
    from_device = {"device_idle_pct.reasoning", "decode_step_ms_p50",
                   "chunk_step_ms_p50", "ssm_decode_ms_per_step",
                   "ssm_prefill_ms_per_chunk", "ssm_scan_roofline_pct"}
    assert set(listed) >= from_device
    end_to_end = {"request_ms_p50", "request_ms_p95"}  # no traced run's
    # a 95th percentile wants more than this mix's 32 requests
    too_few = {"engine_queue_ms_p95"}
    assert set(line["metrics"]) == (set(listed) - from_device - end_to_end
                                    - too_few)
    with open(os.path.join(REPO, "runs", "benchmark", cell,
                           "child.log")) as f:
        assert "paged KV" in f.read()


def test_pretrain_gpt_trains_the_toy(tmp_path):
    """`pretrain_gpt.py` with the reference's flags and no side script:
    the typed stack through the trainer's own data pipeline, step and
    checkpoint (selective recomputation: a trip's layers each under the
    policy) over the 8 virtual devices, data parallel, the loss falling
    from ln(vocabulary); the saved
    run configuration states the layer pattern and builds the same
    model again."""
    from tools import preprocess_data

    rng = np.random.default_rng(0)
    with open(tmp_path / "docs.jsonl", "w") as f:
        for _ in range(200):
            start, n = int(rng.integers(0, 90)), int(rng.integers(20, 60))
            f.write(json.dumps({"text": " ".join(
                str((start + 3 * i) % 97) for i in range(n))}) + "\n")
    prefix = str(tmp_path / "corpus")
    preprocess_data.main([
        "--input", str(tmp_path / "docs.jsonl"), "--output_prefix", prefix,
        "--tokenizer_type", "null", "--vocab_size", "97", "--append_eod"])
    save = str(tmp_path / "ckpt")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "pretrain_gpt.py")]
        + reference.program_flags(TOY, 32)
        + ["--fp32", "--micro_batch_size", "2", "--global_batch_size", "16",
           "--train_iters", "30", "--log_interval", "5", "--lr", "1e-2",
           "--lr_decay_style", "constant", "--data_path", prefix,
           "--split", "95,5,0", "--eval_interval", "10000",
           "--recompute_granularity", "selective",
           "--save", save, "--save_interval", "30", "--seed", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    losses = [float(x) for x in re.findall(r"lm loss[: ]+([0-9.]+)",
                                           proc.stdout + proc.stderr)]
    # the first line is the mean of steps 1 to 5, from ln(128) = 4.85 down
    assert len(losses) >= 5 and 2.5 < losses[0] < 5.0, losses
    assert losses[-1] < losses[0] - 1.0, losses
    from megatron_tpu.config import model_config_from_saved

    metas = [os.path.join(d, "meta.json") for d, _, files in os.walk(save)
             if "meta.json" in files]
    assert metas
    with open(metas[0]) as f:
        saved = json.load(f)
    model = saved["config"]["model"] if "config" in saved else saved["model"]
    assert model_config_from_saved(model) == program_config(seq=32)
