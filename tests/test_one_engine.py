"""One serving engine (PR 63): `InferenceEngine` over the page pool serves
every request that goes through an engine, and nothing a caller sets chooses
the KV layout.

  * the entry points take no `kv_paging`, `prefill_bucket` or `paged`, and
    the class tower is the engine and its context-parallel subclass;
  * `--serve_kv_paging` parses and changes nothing: the server is built with
    the same arguments with and without it, and answers the one-shot loop's
    tokens;
  * an operator who sets nothing gets chunked prefill and a prefix cache;
  * the default pool is the capacity of `num_slots` whole sequences: every
    slot grown to `max_seq_len` at once preempts nobody and evicts nothing;
  * an admission reads nothing (the `admission` drain went with the
    whole-prompt prefill): a long prompt admitted beside a decoding batch
    costs the batch no tick and the loop no drain;
  * a failed chunk fails its request alone where nothing was donated, and
    every request in flight, prefilling ones included, where the pool was;
  * `rows_decoding` reads the decoding slots off the decode step's table;
  * the attention layers read it too (PR 64): a slot that does not decode
    reaches the decode kernel with the length its loop reads as nothing to
    visit, the request beside it is served the tokens and log-probabilities
    of an engine whose layers are not told, and a caller that passes no
    vector traces the program it traced before.

The fake model of tests/test_serving_engine.py stands behind the host-only
cases (no compiles).
"""

import dataclasses
import importlib.util
import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.inference.engine import InferenceEngine, Request
from megatron_tpu.inference.paging import engine as steps
from megatron_tpu.inference.paging.pool import SCRATCH_PAGE
from test_serving_engine import CFG, PARAMS, _fake_steps, make_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("module, name", [
    ("engine", "InferenceEngine"),
    ("context_parallel", "ContextParallelEngine"),
    ("server", "GenerationService"), ("server", "run_server"),
    ("speculative", "build_spec_decode_step")])
def test_no_argument_chooses_the_kv_layout(module, name):
    entry = getattr(importlib.import_module(
        "megatron_tpu.inference." + module), name)
    names = set(inspect.signature(entry).parameters)
    assert not names & {"kv_paging", "prefill_bucket", "paged"}, names


def test_the_engine_and_its_context_parallel_subclass_are_the_tower():
    """Every class of the serving package whose name ends in `Engine`."""
    import pkgutil

    import megatron_tpu.inference as pkg
    from megatron_tpu.inference.context_parallel import ContextParallelEngine

    found = set()
    for info in pkgutil.walk_packages(pkg.__path__, pkg.__name__ + "."):
        mod = importlib.import_module(info.name)
        found |= {obj for name, obj in vars(mod).items()
                  if inspect.isclass(obj) and name.endswith("Engine")
                  and obj.__module__ == info.name}
    assert found == {InferenceEngine, ContextParallelEngine}
    assert ContextParallelEngine.__bases__ == (InferenceEngine,)
    assert InferenceEngine.__bases__ == (object,)


def _server_cli():
    spec = importlib.util.spec_from_file_location(
        "_serve_cli", os.path.join(REPO, "tools",
                                   "run_text_generation_server.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_kv_paging_parses_and_changes_nothing(monkeypatch):
    """The CLI with and without the flag hands `run_server` the same
    arguments (none of them names a layout), and the service built from
    them answers what `generate_tokens` answers."""
    from megatron_tpu.inference import server
    from megatron_tpu.inference.generation import generate_tokens

    calls = []
    monkeypatch.setattr(server, "run_server",
                        lambda *a, **kw: calls.append((a, kw)))
    flags = ["--num_layers", "2", "--hidden_size", "32",
             "--num_attention_heads", "4", "--seq_length", "64",
             "--max_position_embeddings", "64", "--vocab_size", "63",
             "--tokenizer_type", "null", "--fp32",
             "--micro_batch_size", "1", "--global_batch_size", "1",
             "--serve_num_slots", "2", "--serve_max_seq_len", "64"]
    cli = _server_cli()
    cli.main(flags)
    cli.main(flags + ["--serve_kv_paging"])
    (a0, kw0), (a1, kw1) = calls
    assert "kv_paging" not in kw0
    assert repr(kw0) == repr(kw1)
    cfg, params, tokenizer = a0
    assert a1[0] == cfg
    keep = set(inspect.signature(
        server.GenerationService.__init__).parameters)
    service = server.GenerationService(
        cfg, params, tokenizer,
        **{k: v for k, v in kw0.items() if k in keep and k != "warmup"})
    try:
        assert type(service.engine) is InferenceEngine
        eng = service.engine
        assert (eng.page_size, eng.prefill_chunk) == (16, 32)
        assert eng.num_pages == 2 * (64 // 16) + 1
        out = service.handle({"prompts": ["3 7 11 2"], "top_k": 1,
                              "tokens_to_generate": 6})
        want = generate_tokens(cfg, params, np.asarray([[3, 7, 11, 2]]),
                               np.asarray([4]), max_new_tokens=6,
                               temperature=0.0)
        assert out["text"][0].split() == [str(t) for t in want.tokens[0]]
    finally:
        service.shutdown()


def test_an_operator_who_sets_nothing_gets_chunks_and_a_prefix_cache():
    """`InferenceEngine(cfg, params)` and no more: a prompt longer than
    the default chunk goes in by chunks, and the same prompt again aliases
    its full pages."""
    eng = _fake_steps(InferenceEngine(CFG, PARAMS))
    assert (eng.num_slots, eng.page_size, eng.prefill_chunk) == (8, 16, 32)
    assert eng.max_seq_len == CFG.seq_length
    prompt = np.arange(1, 41, dtype=np.int32)          # 40 tokens: 2 chunks
    first = eng.submit(Request(prompt=prompt, max_new_tokens=3))
    eng.run_until_idle()
    again = eng.submit(Request(prompt=prompt, max_new_tokens=3))
    eng.run_until_idle()
    assert first.chunks == 2 and first.generated == [41, 42, 43]
    assert again.generated == first.generated
    assert eng.stats["prefix_hits"] == 1
    assert again.prefix_tokens == 2 * 16 - 1           # two full pages
    assert 'engine_prefix_cache_hits_total 1' in eng.metrics.render()


@pytest.mark.parametrize("page", [8, 16, 64],
                         ids=["page8", "page16", "one-page-a-sequence"])
def test_the_default_pool_holds_every_slot_at_its_full_length(page):
    """No `num_pages`: slots x pages-a-sequence + the scratch page. Twice
    as many requests as slots, each grown to the sequence limit, prompts
    all different: nobody is preempted, nothing is evicted while a slot
    still needs a page, and the drained pool holds the radix tree's pages
    alone."""
    slots, limit = 3, 64
    eng = _fake_steps(make_engine(num_slots=slots, max_seq_len=limit,
                                  page_size=page, prefill_chunk=16))
    assert eng.num_pages == slots * (limit // page) + 1
    reqs = [eng.submit(Request(
        prompt=np.arange(10 * i, 10 * i + 9, dtype=np.int32) % 64,
        max_new_tokens=limit - 9)) for i in range(2 * slots)]
    low = eng.pool.free_pages
    while True:
        served = eng.step()
        low = min(low, eng.pool.free_pages)
        if served == 0 and not eng._queue:
            break
    assert all(r.error is None and len(r.generated) == limit - 9
               for r in reqs)
    assert eng.stats["preemptions"] == 0
    assert all(r.preemptions == 0 for r in reqs)
    assert low >= 0 and eng.stats["tick_drains"].get("pages", 0) == 0
    assert eng.pool.used_pages == len(eng.prefix_cache)


def test_a_long_prompt_beside_a_decoding_batch_costs_no_tick_and_no_drain():
    """Host only. Two rows decode; a prompt of five chunks is admitted.
    While it prefills, one chunk a tick, each decoding row gets exactly
    one token a tick, every one of those ticks was dispatched ahead, and
    the loop drained for nothing: an admission reads nothing."""
    eng = _fake_steps(make_engine(num_slots=3, prefill_chunk=8))
    rows = [eng.submit(Request(prompt=np.asarray([i + 1], np.int32),
                               max_new_tokens=40)) for i in range(2)]
    for _ in range(3):
        eng.step()
    ticks, ahead = eng.stats["ticks"], eng.stats["ticks_dispatched_ahead"]
    long = eng.submit(Request(prompt=np.arange(1, 37, dtype=np.int32),
                              max_new_tokens=4))
    before = [len(r.generated) for r in rows]
    steps_taken = 0
    while long.chunks < 5:
        eng.step()
        steps_taken += 1
    assert steps_taken == 5 and long.first_token_time is None
    assert [len(r.generated) - n for r, n in zip(rows, before)] == [5, 5]
    assert eng.stats["ticks"] - ticks == 5
    assert eng.stats["ticks_dispatched_ahead"] - ahead == 5
    assert eng.stats["tick_drains"] == {}
    eng.run_until_idle()
    assert long.generated == [37, 38, 39, 40]
    assert eng.stats["tick_drains"] == {}
    assert 'cause="admission"' not in eng.metrics.render()


@pytest.mark.parametrize("donated", [False, True],
                         ids=["nothing-donated", "pool-donated"])
def test_a_failed_chunk_fails_whom_it_must(donated):
    """Host only. A chunk step that raises fails its request. Where the
    pool was not donated the others go on; where it was, the failed call
    may have consumed it: every request in a slot fails once, what is in
    flight is dropped unread, the pool and the radix tree start empty, and
    the engine serves the next request."""
    eng = _fake_steps(make_engine(num_slots=3, prefill_chunk=8,
                                  force_donate=donated))
    first = eng.submit(Request(prompt=np.asarray([5], np.int32),
                               max_new_tokens=30))
    warm = eng.submit(Request(prompt=np.arange(1, 10, dtype=np.int32),
                              max_new_tokens=2))
    for _ in range(4):
        eng.step()
    assert warm.done.is_set() and len(eng.prefix_cache) == 1
    chunk = eng._chunk_step
    calls = []

    def failing(*args):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("chunk lost")
        return chunk(*args)

    eng._chunk_step = failing
    long = eng.submit(Request(prompt=np.arange(20, 40, dtype=np.int32),
                              max_new_tokens=3))
    other = eng.submit(Request(prompt=np.arange(40, 60, dtype=np.int32),
                               max_new_tokens=3))
    eng.run_until_idle()
    eng._chunk_step = chunk
    assert "prefill failed: chunk lost" in long.error
    assert eng.stats["rejected"] == 1
    if donated:
        assert "prefill failed" in first.error
        assert other.error in ("prefill failed: chunk lost",
                               "engine cache rebuilt after a failed step")
        assert len(eng.prefix_cache) == 0
    else:
        assert first.error is None and len(first.generated) == 30
        assert other.error is None and other.generated == [60, 61, 62]
    assert not eng._inflight and eng.num_active == 0
    assert eng.pool.used_pages == len(eng.prefix_cache)
    ok = eng.submit(Request(prompt=np.asarray([9], np.int32),
                            max_new_tokens=3))
    eng.run_until_idle()
    assert ok.error is None and ok.generated == [10, 11, 12]


@pytest.mark.parametrize("row, decodes", [
    ([SCRATCH_PAGE] * 4, 0),          # idle, or its pages wait in pending
    ([3, 7, SCRATCH_PAGE, SCRATCH_PAGE], 1),
    ([SCRATCH_PAGE, SCRATCH_PAGE, 5, SCRATCH_PAGE], 1),   # window released
], ids=["idle-or-prefilling", "decoding", "window-released-its-first-pages"])
def test_rows_decoding_reads_the_table(row, decodes):
    table = jnp.asarray([row, [SCRATCH_PAGE] * 4], jnp.int32)
    assert steps.rows_decoding(table).tolist() == [decodes, 0]


# ---------------------------------------------------------------------------
# the third reader of `rows_decoding`: the attention layers


def _attention_not_told(monkeypatch):
    """The engine's steps as they were: every row reaches the kernel with
    `cache_index + 1`, an idle slot's too."""
    from megatron_tpu.models import transformer

    block = transformer.attention_block
    monkeypatch.setattr(
        transformer, "attention_block",
        lambda *a, state_valid=None, **kw: block(*a, **kw))


@pytest.mark.parametrize("window", [None, 8], ids=["full", "window"])
@pytest.mark.parametrize("path", ["dense", "interpreted"])
def test_a_request_beside_idle_slots_is_served_what_it_was(path, window,
                                                           monkeypatch):
    """One request in four slots, so three rows of every decode tick
    carry the idle length: its tokens and log-probabilities are, bit for
    bit, those of an engine whose attention layers are not told which
    rows decode (the parent's: an idle row made a trip and nobody read
    it). On the dense path a CPU host runs and through the kernel, and
    behind a window whose release parks the decoding row's first pages
    on scratch: the row still decodes (`rows_decoding`: any page) and
    still attends."""
    cfg = dataclasses.replace(CFG, sliding_window_size=window)
    if path == "interpreted":
        monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
        cfg = dataclasses.replace(cfg, attention_impl="pallas")
    prompt = np.asarray([3, 7, 11, 2, 9, 4], np.int32)

    def serve():
        eng = make_engine(cfg)
        req = eng.submit(Request(prompt=prompt, max_new_tokens=40))
        eng.run_until_idle()
        assert req.error is None
        assert eng.stats["decode_rows"] == eng.stats["ticks"]   # one a tick
        if window is not None:
            assert eng.stats["window_pages_released"] > 0
        return req, eng

    told, eng = serve()
    visited, held = eng._serve_ticks_fields()["decode_blocks"]
    assert 0 < visited <= held // 4      # one row of four, at the most
    _attention_not_told(monkeypatch)
    plain, _ = serve()
    assert told.generated == plain.generated and len(told.generated) == 40
    np.testing.assert_array_equal(told.logprobs, plain.logprobs)


def test_a_caller_without_the_vector_traces_the_program_it_traced(
        monkeypatch):
    """`attention_block` forms the kernel's lengths from the vector only
    where one is given: a per-slot call without it (generation.py's slot
    decode, a test's direct call) and a training call trace, with the
    kernels in the program, to the jaxpr of a tree whose layer has no
    such argument; with it the lengths pass through one select more."""
    from megatron_tpu.models.language_model import lm_forward
    from megatron_tpu.ops import kv_store

    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    cfg = dataclasses.replace(CFG, attention_impl="pallas")
    caches = kv_store.create(cfg, 9, 8)
    table = jnp.arange(1, 9, dtype=jnp.int32).reshape(2, 4)
    tokens = jnp.asarray([[5], [6]], jnp.int32)
    lens = jnp.asarray([3, 0], jnp.int32)

    def decode(params, caches, lens, **kw):
        return lm_forward(cfg, params, tokens, kv_caches=caches,
                          cache_index=lens, page_table=table, **kw)[0]

    def train(params, **kw):
        return lm_forward(cfg, params, jnp.ones((1, 64), jnp.int32), **kw)

    def traced():
        return (str(jax.make_jaxpr(decode)(PARAMS, caches, lens)),
                str(jax.make_jaxpr(train)(PARAMS)))

    plain = traced()
    none = (str(jax.make_jaxpr(lambda *a: decode(*a, state_valid=None))(
                PARAMS, caches, lens)),
            str(jax.make_jaxpr(lambda p: train(p, state_valid=None))(
                PARAMS)))
    told = str(jax.make_jaxpr(
        lambda *a: decode(*a, state_valid=jnp.asarray([1, 0])))(
            PARAMS, caches, lens))
    _attention_not_told(monkeypatch)
    assert plain == none == traced()
    assert told != plain[0]
    assert told.count("select_n") > plain[0].count("select_n")
    assert "paged_flash_decode" in plain[0]
