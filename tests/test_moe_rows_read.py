"""A serving step tells its expert layers which rows somebody reads
(`state_valid` -> ops/moe.py `rows_read`): the others reach no expert, the
rows that count get the bits they get without the word, and the paged
engine counts how many of its held experts' matrices a tick's kernels
moved. On the toy Nemotron-H of tests/test_nemotron_h.py (one chip's share
of the experts: 4 of 16 held, 3 a token) and on the same model with every
expert held."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_nemotron_h import (
    F32, SEQ, UNCUT, layer_of, make_engine, program_config, seeded_params,
    tokens_of,
)

from megatron_tpu.models import transformer
from megatron_tpu.ops import moe

MODELS = {"share": lambda: program_config(seq=48),
          "whole": lambda: program_config(UNCUT, seq=48)}
# rows_read [B] over inputs [B, S, h]: what a decode tick hands (0 / 1 a
# row of one position) and what a prefill chunk does (its real positions)
CALLS = {"tick_some_rows": ((6, 1), [1, 0, 1, 1, 0, 0]),
         "tick_no_row": ((4, 1), [0, 0, 0, 0]),
         "tick_every_row": ((4, 1), [1, 1, 1, 1]),
         "chunk_padded_tail": ((1, SEQ), [17]),
         "chunk_whole": ((1, SEQ), [SEQ])}


@pytest.fixture(scope="module", params=sorted(MODELS))
def layer(request):
    cfg = MODELS[request.param]()
    return cfg, layer_of(seeded_params(cfg), "moe", 2)[0]


@pytest.mark.parametrize("call", sorted(CALLS))
def test_rows_that_count_get_their_bits_and_the_others_reach_no_expert(
        layer, call, monkeypatch):
    """With a count that leaves out some rows, `moe_block_dropless` gives
    the counted rows the bits it gives them without the count, the groups
    hold the counted rows' choices alone, and a row that does not count
    gets what the dense parts of the layer give it."""
    cfg, p = layer
    shape, rows_read = CALLS[call]
    x = jax.random.normal(jax.random.PRNGKey(3), (*shape, 64), F32)
    rows_read = jnp.asarray(rows_read, jnp.int32)
    seen = []
    mlp = moe.experts_mlp
    monkeypatch.setattr(
        moe, "experts_mlp",
        lambda cfg, p, xs, group_sizes, *a, **kw: seen.append(
            np.asarray(group_sizes)) or mlp(cfg, p, xs, group_sizes, *a,
                                            **kw))
    want, _, _ = moe.moe_block_dropless(cfg, p, x)
    got, _, load = moe.moe_block_dropless(cfg, p, x, rows_read=rows_read)
    all_rows, counted = seen
    read = np.asarray(jnp.arange(shape[1]) < rows_read[:, None])
    np.testing.assert_array_equal(np.asarray(got)[read],
                                  np.asarray(want)[read])
    # the groups: the counted rows' choices among the experts held here
    _, _, _, topi = moe._route(cfg, p, x.reshape(-1, 64))
    held = cfg.experts_held
    chosen = np.asarray(topi)[read.reshape(-1)].reshape(-1)
    np.testing.assert_array_equal(
        counted, np.bincount(chosen[chosen < held], minlength=held))
    assert (counted <= all_rows).all()
    if cfg.holds_expert_share:
        pairs = x.shape[0] * x.shape[1] * cfg.moe_top_k
        assert load.shape == (4,)
        assert round(float(load[1]) * pairs) == counted.sum()
        assert float(load[2]) == (counted > 0).sum()
    # a row nobody reads: the shared expert's part, no routed expert's
    xf = x.reshape(-1, 64)[~read.reshape(-1)]
    if len(xf):
        from megatron_tpu.ops.activations import apply_activation

        dense = apply_activation(cfg.activation,
                                 xf @ p["shared_in"]) @ p["shared_out"]
        np.testing.assert_allclose(np.asarray(got)[~read], dense,
                                   rtol=1e-6, atol=1e-6)


def test_without_the_word_the_block_is_the_one_it_was(layer):
    """No count: the same jaxpr as a call that never heard of one (the
    training step's), and the statistics' shapes of a call without one."""
    cfg, p = layer
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 8, 64), F32)
    told = jax.make_jaxpr(
        lambda p, x: moe.moe_block_dropless(cfg, p, x, rows_read=None))(p, x)
    plain = jax.make_jaxpr(lambda p, x: moe.moe_block_dropless(cfg, p, x))(
        p, x)
    assert str(told) == str(plain)
    _, _, load = moe.moe_block_dropless(cfg, p, x)
    # (a share's: the held rows' share, no expert read, the moved rows')
    assert load.shape == ((4,) if cfg.holds_expert_share else ())
    if cfg.holds_expert_share:
        assert float(load[2]) == 0.0


def _as_the_parent(monkeypatch):
    """The engine's steps as they were: the expert layers are not told
    which rows count and route every slot's row."""
    block = transformer.moe_block
    monkeypatch.setattr(
        transformer, "moe_block",
        lambda *a, rows_read=None, **kw: block(*a, **kw))


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_engine_serves_the_tokens_it_served_with_every_row_routed(
        model, monkeypatch):
    """Three requests through four slots (an idle slot every tick, a slot
    in mid-prefill beside decoding ones, padded chunks): the tokens and
    their log-probabilities are those of an engine whose expert layers
    route every row, as the parent's did."""
    from megatron_tpu.inference.engine import Request

    cfg = MODELS[model]()
    params = seeded_params(cfg)
    prompts = [np.asarray(tokens_of(30 + i, n))
               for i, n in enumerate((13, 5, 21))]

    def serve():
        eng = make_engine(cfg, params, num_slots=4, want_logprobs=True)
        reqs = []
        for prompt, n in zip(prompts, (7, 9, 5)):
            reqs.append(eng.submit(Request(prompt=prompt,
                                           max_new_tokens=n)))
            eng.step()
        eng.run_until_idle()
        assert [r.error for r in reqs] == [None] * 3
        assert eng.stats["decode_recompiles"] == 0
        return reqs, eng

    reqs, eng = serve()
    if cfg.holds_expert_share:
        assert 0 < eng.stats["moe_experts_read"] < eng.stats[
            "moe_experts_offered"]
    with monkeypatch.context() as mp:
        _as_the_parent(mp)
        parents, parent = serve()
    assert [r.generated for r in reqs] == [r.generated for r in parents]
    for ours, theirs in zip(reqs, parents):
        np.testing.assert_array_equal(ours.logprobs, theirs.logprobs)
    if cfg.holds_expert_share:
        # every slot's row of every tick, every position of every chunk
        assert parent.stats["moe_held_rows"] > eng.stats["moe_held_rows"]
        assert parent.stats["moe_experts_read"] == 0


def _rigged(cfg, params, experts):
    """The router's selection bias lifted so far for `experts` that every
    token chooses exactly them."""
    bias = params["layers"]["moe"]["router_bias"]
    lifted = bias.at[:, jnp.asarray(experts)].add(100.0)
    return {**params, "layers": {**params["layers"], "moe": {
        **params["layers"]["moe"], "router_bias": lifted}}}


def _read_share(eng):
    return eng.stats["moe_experts_read"] / eng.stats["moe_experts_offered"]


@pytest.mark.parametrize("slots, requests", [(2, 2), (3, 1)],
                         ids=["every_slot_decodes", "one_slot_of_three"])
def test_read_share_is_one_where_every_tick_reaches_every_held_expert(
        slots, requests):
    """Two of 16 experts held and a router that sends every token to both
    (and to a third, held elsewhere): every decode tick with a decoding
    row reads both matrices of all 5 layers, so the read share is 1.0
    however many slots stand idle."""
    from megatron_tpu.inference.engine import Request

    cfg = dataclasses.replace(program_config(seq=48),
                              moe_experts_held=2).validate()
    params = _rigged(cfg, seeded_params(cfg), [0, 1, 5])
    eng = make_engine(cfg, params, num_slots=slots)
    reqs = [eng.submit(Request(prompt=np.asarray(tokens_of(40 + i, 9)),
                               max_new_tokens=6)) for i in range(requests)]
    eng.run_until_idle()
    assert [r.error for r in reqs] == [None] * requests
    assert eng.stats["moe_experts_offered"] == eng.stats["ticks"] * 2 * 5
    assert _read_share(eng) == 1.0
    assert eng.stats["moe_held_rows"] * 3 == eng.stats["moe_rows"] * 2


def test_read_share_is_under_one_with_one_slot_decoding():
    """One request in two slots of the toy share: a row reaches 3 experts
    of 16, so at most 3 of the 4 held: under 0.75 of the matrices a tick."""
    from megatron_tpu.inference.engine import Request

    cfg = program_config(seq=48)
    eng = make_engine(cfg, seeded_params(cfg))
    req = eng.submit(Request(prompt=np.asarray(tokens_of(7, 9)),
                             max_new_tokens=8))
    eng.run_until_idle()
    assert req.error is None
    assert 0 < _read_share(eng) <= 0.75


def test_a_dense_models_step_reads_the_rows_in_its_attention_alone():
    """A served model without expert layers or a state store: no router
    and no mixer reads the rows that count (`state_valid`), so its decode
    step over the page pool, told or not, gives the rows that decode the
    same logits bit for bit and writes their pages the same (the other
    rows write the scratch page, page 0, which nobody reads). Since PR 64 the
    attention layers read them (a row that does not decode visits no
    block of its cache), so the two steps' texts differ."""
    from megatron_tpu.models import presets
    from megatron_tpu.models.language_model import lm_forward
    from megatron_tpu.models.params import init_params
    from megatron_tpu.ops import kv_store

    cfg = presets.tiny(seq_length=32)
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    kv = kv_store.create(cfg, 9, 4)
    table = jnp.asarray([[1, 2, 3, 4], [0, 0, 0, 0]], jnp.int32)

    def step(told, params, kv, table, tok, lengths):
        decoding = (jnp.any(table != 0, axis=1).astype(jnp.int32) if told
                    else None)
        return lm_forward(cfg, params, tok[:, None], kv_caches=kv,
                          cache_index=lengths, page_table=table,
                          state_valid=decoding)

    args = (params, kv, table, jnp.asarray([3, 5], jnp.int32),
            jnp.asarray([6, 0], jnp.int32))
    steps = [jax.jit(step, static_argnums=0).lower(told, *args)
             for told in (False, True)]
    assert steps[0].as_text() != steps[1].as_text()
    (plain, plain_kv), (told, told_kv) = (s.compile()(*args) for s in steps)
    np.testing.assert_array_equal(np.asarray(told)[0], np.asarray(plain)[0])
    assert np.isfinite(np.asarray(told)).all()
    for ours, theirs in zip(told_kv, plain_kv):
        np.testing.assert_array_equal(ours[:, 1:], theirs[:, 1:])


def _windowed(model):
    """(config, parameters) of a served model with expert layers and a
    sliding window of 8 positions."""
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params

    if model == "tiny_moe":
        cfg = presets.tiny(vocab_size=64, seq_length=48, num_experts=4,
                           moe_top_k=2, moe_dispatch="dropless",
                           sliding_window_size=8)
        return cfg, init_params(cfg, jax.random.PRNGKey(0), dtype=F32)
    cfg = program_config(seq=48, sliding_window_size=8)
    return cfg, seeded_params(cfg)


@pytest.mark.parametrize("model", ["tiny_moe", "share"])
def test_a_slot_decoding_past_its_window_is_still_routed(model, monkeypatch):
    """A sliding window hands a decoding slot's first pages back to the
    pool and parks their entries on scratch (_release_window_pages) while
    the slot decodes on: its row still counts. Two requests in three slots
    decode far past window + page; token for token what an engine serves
    whose expert layers route every row."""
    from megatron_tpu.inference.engine import Request

    cfg, params = _windowed(model)
    prompts = [np.asarray(tokens_of(50 + i, n) % 64)
               for i, n in enumerate((6, 11))]

    def serve():
        eng = make_engine(cfg, params, num_slots=3, want_logprobs=True)
        reqs = [eng.submit(Request(prompt=p, max_new_tokens=30))
                for p in prompts]
        eng.run_until_idle()
        assert [r.error for r in reqs] == [None] * 2
        return reqs, eng

    reqs, eng = serve()
    assert eng.stats["window_pages_released"] >= 8
    with monkeypatch.context() as mp:
        _as_the_parent(mp)
        parents, _ = serve()
    assert [r.generated for r in reqs] == [r.generated for r in parents]
    for ours, theirs in zip(reqs, parents):
        np.testing.assert_array_equal(ours.logprobs, theirs.logprobs)


def test_the_context_parallel_engine_tells_its_rows_from_the_ranks_tables():
    """The context-parallel engine's decode table is the ranks' local
    tables [cp, slots, pages a rank], whose empty entries are rank 0's
    scratch and a sentinel elsewhere: its rows that decode are read from
    those (its own `_rows_decoding`), and a dropless expert model with an
    idle slot serves the flat paged engine's tokens."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (fake) devices")
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.inference.context_parallel import ContextParallelEngine
    from megatron_tpu.inference.engine import Request
    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params, param_specs
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import shard_tree

    cfg = presets.tiny(vocab_size=64, seq_length=64, num_experts=4,
                       moe_top_k=2, moe_dispatch="dropless")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = build_mesh(ParallelConfig(context_parallel=2),
                    devices=jax.devices()[:2])
    geometry = dict(num_slots=3, max_seq_len=64, page_size=8,
                    prefill_chunk=8)

    def serve(eng):
        reqs = [eng.submit(Request(prompt=np.arange(1, n, dtype=np.int32),
                                   max_new_tokens=12)) for n in (7, 19)]
        eng.run_until_idle()
        assert [r.error for r in reqs] == [None] * 2
        return [r.generated for r in reqs]

    flat = serve(InferenceEngine(cfg, params, **geometry))
    ranks = serve(ContextParallelEngine(
        cfg, shard_tree(rt, params, param_specs(cfg)), mesh=rt.mesh,
        **geometry))
    assert ranks == flat
