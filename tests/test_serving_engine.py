"""Continuous-batching engine tests.

Pins the invariants the serving rewrite promises:
  * slot admit/retire/reuse bookkeeping (deterministic fake model — no
    compiles, pure scheduler logic);
  * greedy parity: a single request through the engine is token-identical
    to the one-shot generate_tokens path (the PR's parity gate);
  * interleaved-traffic parity: a request's tokens must not change when
    other slots are active (per-slot PRNG chains + per-slot-length
    attention masking);
  * quantized (int8) cache mode parity;
  * the flash-decode kernel vs the masked-einsum reference (interpret
    mode on CPU);
  * batched per-slot sampling vs the scalar sampler's semantics;
  * HTTP serving where concurrent requests share decode ticks.

The offered-load throughput check is `slow` (it times real compiled
steps); everything else is tier-1.
"""

import dataclasses
import json
import time
import threading
import urllib.request
from http.server import ThreadingHTTPServer

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.inference.engine import (
    EngineOverloadedError, InferenceEngine, Request,
)
from megatron_tpu.inference.generation import generate_tokens
from megatron_tpu.inference.sampling import sample_logits, sample_logits_batched
from megatron_tpu.models import presets
from megatron_tpu.models.params import init_params
from megatron_tpu.tokenizer.tokenizer import NullTokenizer

CFG = presets.tiny(vocab_size=64, seq_length=64)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))


def make_engine(cfg=CFG, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return InferenceEngine(cfg, PARAMS, **kw)


# ---------------------------------------------------------------------------
# scheduler invariants on a fake model (tier-1: no XLA compiles)


def _fake_steps(eng, V=64):
    """Deterministic fake model behind the engine's two programs: every
    step emits (last_token + 1) % V, a prompt's first token is its last
    token + 1."""
    C = eng.prefill_chunk

    def fake_chunk(params, caches, state, table_row, tokens_ext, off,
                   write_start, write_end, sample_pos, key, temp, top_k,
                   top_p, slot=None):
        at = int(np.clip(int(sample_pos) - int(off), 0, C - 1))
        tok = (jnp.asarray(tokens_ext)[0, at] + 1) % V
        return (tok, jnp.float32(-1.0), jnp.zeros((C,), jnp.float32),
                caches, state, jnp.asarray(key))

    def fake_decode(params, caches, state, table, last, lengths, keys,
                    temps, tks, tps):
        return ((last + 1) % V, jnp.full(last.shape, -1.0, jnp.float32),
                caches, state, keys, lengths + 1)

    eng._chunk_step = fake_chunk
    eng._decode_step = fake_decode
    return eng


def test_slot_admit_retire_reuse_fake_model():
    """5 requests over 2 slots: all complete with the right tokens, slots
    are reused after retirement, and the counters add up."""
    eng = _fake_steps(make_engine(num_slots=2))
    reqs = [eng.submit(Request(prompt=np.asarray([i + 1], np.int32),
                               max_new_tokens=3)) for i in range(5)]
    eng.run_until_idle()
    for i, r in enumerate(reqs):
        assert r.done.is_set() and r.error is None
        assert r.generated == [(i + 2 + j) % 64 for j in range(3)]
        np.testing.assert_array_equal(
            r.tokens, [i + 1] + [(i + 2 + j) % 64 for j in range(3)])
    assert eng.num_active == 0
    assert eng.stats["admitted"] == 5 and eng.stats["retired"] == 5
    assert (eng.lengths == 0).all()  # every slot reset for reuse


def test_eod_at_prefill_retires_immediately():
    eng = _fake_steps(make_engine(num_slots=1))
    # fake model emits prompt+1, which we declare to be EOD
    r = eng.submit(Request(prompt=np.asarray([10], np.int32),
                           max_new_tokens=5, eod=11))
    eng.run_until_idle()
    assert r.generated == [11] and r.done.is_set()
    assert eng.num_active == 0


def test_oversized_request_rejected_not_queued():
    eng = _fake_steps(make_engine(num_slots=1, max_seq_len=16))
    r = eng.submit(Request(prompt=np.asarray([1] * 10, np.int32),
                           max_new_tokens=10))
    assert r.done.is_set() and "exceeds" in r.error
    assert eng.stats["rejected"] == 1
    # the engine still serves well-sized requests afterwards
    ok = eng.submit(Request(prompt=np.asarray([1], np.int32),
                            max_new_tokens=2))
    eng.run_until_idle()
    assert ok.error is None and len(ok.generated) == 2


def test_stop_fails_inflight_and_queued_requests():
    """stop() must unblock every waiter: in-flight and still-queued
    requests get error='engine stopped' instead of hanging done.wait()
    forever (server teardown with traffic in the air)."""
    eng = _fake_steps(make_engine(num_slots=1))
    fast_decode = eng._decode_step

    def slow_decode(*a):
        time.sleep(0.01)
        return fast_decode(*a)

    eng._decode_step = slow_decode
    eng.start()
    # 1 slot, 3 long requests: one decodes, two queue behind it
    reqs = [eng.submit(Request(prompt=np.asarray([1], np.int32),
                               max_new_tokens=60))
            for _ in range(3)]
    deadline = time.monotonic() + 30
    while eng.stats["admitted"] == 0:
        assert time.monotonic() < deadline, "no request ever admitted"
        time.sleep(0.001)
    eng.stop()
    for r in reqs:
        assert r.done.wait(timeout=10)
        assert r.error == "engine stopped"
    assert eng.num_active == 0 and not eng._queue


# ---------------------------------------------------------------------------
# parity gates (real tiny model)


def test_engine_greedy_parity_single_request():
    """The acceptance gate: single-request greedy decode through the
    engine is token-identical to the pre-change generate_tokens path."""
    prompts = np.asarray([[3, 7, 11, 2]], np.int32)
    lengths = np.asarray([4], np.int32)
    want = generate_tokens(CFG, PARAMS, prompts, lengths, max_new_tokens=8,
                           temperature=0.0)
    got = make_engine().generate(prompts, lengths, max_new_tokens=8,
                                 temperature=0.0)
    np.testing.assert_array_equal(got.tokens, want.tokens)
    # full logprob row parity: teacher-forced prompt region (from the
    # admission prefill) AND the generated tokens
    np.testing.assert_allclose(got.logprobs, want.logprobs,
                               rtol=1e-5, atol=1e-5)


def test_engine_greedy_parity_with_eod():
    # pick the greedy-next token after [3] as eod so the engine must stop
    from megatron_tpu.models.language_model import lm_forward

    logits = lm_forward(CFG, PARAMS, jnp.asarray([[3]], jnp.int32))
    eod = int(jnp.argmax(logits[0, -1]))
    prompts = np.asarray([[3]], np.int32)
    lengths = np.asarray([1], np.int32)
    want = generate_tokens(CFG, PARAMS, prompts, lengths, max_new_tokens=8,
                           temperature=0.0, eod=eod)
    got = make_engine().generate(prompts, lengths, max_new_tokens=8,
                                 temperature=0.0, eod=eod)
    assert int(got.lengths[0]) == int(want.lengths[0]) == 2
    np.testing.assert_array_equal(got.tokens[0, :2], want.tokens[0, :2])


def test_slot_reuse_does_not_leak_stale_cache():
    """After a long request retires, a short request in the same slot must
    not attend the old request's stale cache rows (per-slot length
    masking), so its tokens equal a fresh engine's."""
    eng = make_engine(num_slots=1)
    long = eng.submit(Request(prompt=np.asarray([13, 17, 21, 9], np.int32),
                              max_new_tokens=20))
    eng.run_until_idle()
    assert len(long.generated) == 20
    short = eng.submit(Request(prompt=np.asarray([3, 7], np.int32),
                               max_new_tokens=6))
    eng.run_until_idle()

    eng2 = make_engine(num_slots=1)
    fresh = eng2.submit(Request(prompt=np.asarray([3, 7], np.int32),
                                max_new_tokens=6))
    eng2.run_until_idle()
    assert short.generated == fresh.generated


# ---------------------------------------------------------------------------
# parity matrix on both of attention's paths: token-identical to the
# one-shot loop on the same traffic, zero decode recompiles after warmup


@pytest.fixture(params=["dense", "interpreted"])
def attention_path(request, monkeypatch):
    """The engine's parity tests on both of attention's paths: the
    dense one a CPU host runs, and the kernels forced through the
    interpreter (`interpret_forced`), where a prefill chunk runs
    `paged_flash_chunk` and a decode tick `paged_flash_decode`, as on the
    chip. The one-shot reference then takes the kernels too (its whole
    prompt is a chunk over a slot cache's rows). Returns the model's
    configuration for that path."""
    if request.param == "dense":
        return CFG
    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    return dataclasses.replace(CFG, attention_impl="pallas")


def test_paged_engine_greedy_parity_multi_chunk(attention_path):
    """Greedy decode through the paged engine (chunked prefill crossing
    page boundaries) is token-identical to the one-shot path, full
    logprob rows included."""
    prompts = np.asarray([[3, 7, 11, 2, 9, 4, 1, 8, 5, 2]], np.int32)
    lengths = np.asarray([10], np.int32)
    want = generate_tokens(attention_path, PARAMS, prompts, lengths,
                           max_new_tokens=8, temperature=0.0)
    # chunk 4 < prompt 10 < 2 pages: 3 chunks, page-spanning writes
    eng = make_engine(attention_path, prefill_chunk=4)
    got = eng.generate(prompts, lengths, max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(got.tokens, want.tokens)
    np.testing.assert_allclose(got.logprobs, want.logprobs,
                               rtol=1e-5, atol=1e-5)
    assert eng.stats["prefill_chunks"] == 3
    assert eng.stats["decode_recompiles"] == 0


def test_paged_engine_ragged_batch_parity(attention_path):
    prompts = np.asarray([[3, 7, 11, 2], [5, 0, 0, 0]], np.int32)
    lengths = np.asarray([4, 1], np.int32)
    want = generate_tokens(attention_path, PARAMS, prompts, lengths,
                           max_new_tokens=6, temperature=0.0)
    got = make_engine(attention_path).generate(
        prompts, lengths, max_new_tokens=6, temperature=0.0)
    np.testing.assert_array_equal(got.tokens, want.tokens)
    np.testing.assert_array_equal(got.lengths, want.lengths)
    np.testing.assert_allclose(got.logprobs, want.logprobs,
                               rtol=1e-5, atol=1e-5)


def test_paged_engine_int8_cache_parity(attention_path):
    """int8 paged pools (quantize-on-write through the page table) match
    the one-shot int8 path."""
    prompts = np.asarray([[3, 7, 11, 2]], np.int32)
    lengths = np.asarray([4], np.int32)
    want = generate_tokens(attention_path, PARAMS, prompts, lengths,
                           max_new_tokens=6, temperature=0.0,
                           kv_cache_int8=True)
    got = make_engine(attention_path, kv_cache_int8=True).generate(
        prompts, lengths, max_new_tokens=6, temperature=0.0)
    np.testing.assert_array_equal(got.tokens, want.tokens)


def test_paged_prefix_cache_hit_parity(attention_path):
    """A request sharing another's prompt prefix aliases its pages, skips
    the shared prefill span, and still produces identical tokens AND
    teacher-forced prompt logprobs."""
    rng = np.random.default_rng(3)
    shared = rng.integers(1, 60, 16).astype(np.int32)
    p1 = np.concatenate([shared, [7, 3]]).astype(np.int32)
    p2 = np.concatenate([shared, [9, 5, 2]]).astype(np.int32)

    from _engine_lookahead_cases import one_shot

    alone = one_shot(attention_path, PARAMS)
    paged = make_engine(attention_path)
    for prompt in (p1, p2):
        a = alone(prompt, 6, {})
        b = paged.submit(Request(prompt=prompt, max_new_tokens=6))
        paged.run_until_idle()
        assert b.error is None, b.error
        assert a.generated == b.generated
        np.testing.assert_allclose(a.prompt_logprobs, b.prompt_logprobs,
                                   rtol=1e-5, atol=1e-5)
    # p2 aliased p1's two full prefix pages: 16 shared tokens -> only the
    # boundary token + suffix recomputed (15 positions skipped)
    assert paged.stats["prefix_hits"] == 1
    assert paged.stats["prefix_tokens_saved"] == 15
    # shared-prompt traffic computes well under its prompt tokens: the
    # prefix cache's saving as a ratio of counts (>= 1.5x here)
    assert (len(p1) + len(p2)) / paged.stats["prefill_tokens"] >= 1.5
    assert paged.stats["decode_recompiles"] == 0


def test_paged_preemption_midstream_parity(attention_path):
    """Under page-pool pressure the youngest request is preempted
    mid-stream and later resumed by teacher-forced recompute — both
    requests still finish token-identical to uncontended runs (greedy
    AND sampled: the preserved PRNG chain must resume exactly)."""
    pa = np.asarray([3, 7, 11, 2, 9, 4], np.int32)
    pb = np.asarray([5, 8, 1, 6, 2, 7], np.int32)
    # the kernels take pages of 8 and more
    page = 4 if attention_path is CFG else 8
    kw = dict(num_slots=2, max_seq_len=32, page_size=page, prefill_chunk=8)
    sampled = dict(temperature=0.7, top_k=8, seed=5)

    def solo(prompt, **skw):
        eng = make_engine(attention_path, **kw)
        r = eng.submit(Request(prompt=prompt, max_new_tokens=16, **skw))
        eng.run_until_idle()
        assert r.error is None, r.error
        return r

    a_solo, b_solo = solo(pa), solo(pb, **sampled)

    # 9 usable pages of 4 (4 of 8) can't hold both sequences at full
    # length (6 pages each; 3): B (younger) gets preempted, A finishes, B
    # resumes
    eng = make_engine(attention_path, num_pages=40 // page, **kw)
    ra = eng.submit(Request(prompt=pa, max_new_tokens=16))
    rb = eng.submit(Request(prompt=pb, max_new_tokens=16, **sampled))
    eng.run_until_idle()
    assert ra.error is None and rb.error is None, (ra.error, rb.error)
    assert eng.stats["preemptions"] >= 1
    assert ra.generated == a_solo.generated
    assert rb.generated == b_solo.generated
    np.testing.assert_allclose(rb.prompt_logprobs, b_solo.prompt_logprobs,
                               rtol=1e-5, atol=1e-5)
    assert eng.stats["decode_recompiles"] == 0
    # every page accounted for after the drain: slots released theirs,
    # only the radix tree still holds cached prefixes
    assert eng.pool.used_pages == len(eng.prefix_cache)


@pytest.mark.slow  # ~15s measured cacheless;
# greedy/int8/prefix/preemption parity stay tier-1
def test_paged_interleaved_traffic_parity():
    """Paged engine: a request's tokens must not change when other slots
    are active — greedy AND sampled (per-slot PRNG chains survive the
    page-table indirection)."""
    promptA = np.asarray([3, 7, 11], np.int32)
    sampledB = dict(prompt=np.asarray([5], np.int32), max_new_tokens=16,
                    temperature=0.8, top_k=5, seed=7)

    eng = make_engine()
    a_solo = eng.submit(Request(prompt=promptA, max_new_tokens=10))
    eng.run_until_idle()
    eng = make_engine()
    b_solo = eng.submit(Request(**sampledB))
    eng.run_until_idle()

    eng = make_engine()
    b_mix = eng.submit(Request(**sampledB))
    eng.step()
    eng.step()
    eng.step()
    a_mix = eng.submit(Request(prompt=promptA, max_new_tokens=10))
    c = eng.submit(Request(prompt=np.asarray([9, 2], np.int32),
                           max_new_tokens=5, temperature=1.2, top_p=0.9,
                           seed=3))
    eng.run_until_idle()

    assert a_mix.generated == a_solo.generated
    assert b_mix.generated == b_solo.generated
    assert c.done.is_set() and len(c.generated) == 5


def test_paged_chunked_prefill_interleaves_with_decode():
    """A long prompt enters the cache one chunk per tick while an active
    request keeps decoding — chunked prefill can't stall the batch."""
    eng = make_engine(prefill_chunk=4, max_seq_len=64)
    a = eng.submit(Request(prompt=np.asarray([3, 7], np.int32),
                           max_new_tokens=20))
    # admit A and give it a couple of ticks
    eng.step()
    eng.step()
    done_before = len(a.generated)
    long_prompt = np.arange(1, 25, dtype=np.int32)  # 24 tokens = 6 chunks
    b = eng.submit(Request(prompt=long_prompt, max_new_tokens=2))
    progressed = 0
    while b.first_token_time is None and not b.done.is_set():
        before = len(a.generated)
        eng.step()
        progressed += int(len(a.generated) > before)
    # A kept generating during B's multi-tick prefill
    assert progressed >= 4, (progressed, len(a.generated), done_before)
    eng.run_until_idle()
    assert a.error is None and b.error is None
    assert len(a.generated) == 20 and len(b.generated) == 2
    assert eng.stats["prefill_chunks"] >= 7


# ---------------------------------------------------------------------------
# satellite: max_seq_len is whole pages


def test_engine_max_seq_len_of_whole_pages_is_kept():
    import warnings as w

    with w.catch_warnings():
        w.simplefilter("error")
        eng = make_engine(max_seq_len=96)
    assert eng.max_seq_len == 96 and eng.max_pages == 12


def test_engine_rounds_max_seq_len_to_page_multiple():
    with pytest.warns(UserWarning, match="rounding"):
        eng = make_engine(max_seq_len=60, page_size=8)
    assert eng.max_seq_len == 64 and eng.max_pages == 8
    # oversized-request validation uses the rounded value
    r = eng.submit(Request(prompt=np.asarray([1] * 60, np.int32),
                           max_new_tokens=10))
    assert r.error and "64" in r.error


# ---------------------------------------------------------------------------
# satellite: bounded admission (--serve_max_queue)


def test_engine_max_queue_rejects_overload():
    """Beyond max_queue waiting requests, submit() rejects instead of
    queueing — overload degrades to fast 503s upstream, not unbounded
    latency."""
    eng = _fake_steps(make_engine(num_slots=1, max_queue=2))
    held = [eng.submit(Request(prompt=np.asarray([1], np.int32),
                               max_new_tokens=3)) for _ in range(2)]
    rejected = eng.submit(Request(prompt=np.asarray([2], np.int32),
                                  max_new_tokens=3))
    assert rejected.done.is_set() and rejected.overloaded
    assert "queue full" in rejected.error
    assert eng.stats["rejected"] == 1
    eng.run_until_idle()
    for r in held:
        assert r.error is None and len(r.generated) == 3

    # the batch API surfaces overload as EngineOverloadedError
    eng2 = _fake_steps(make_engine(num_slots=1, max_queue=1))
    with eng2._cv:
        eng2._queue.append(Request(prompt=np.asarray([1], np.int32),
                                   max_new_tokens=1))
    with pytest.raises(EngineOverloadedError):
        eng2.generate(np.asarray([[1]], np.int32), np.asarray([1]),
                      max_new_tokens=1)


def test_server_replies_503_with_retry_after_when_queue_full():
    """HTTP face of --serve_max_queue: overload answers 503 + Retry-After
    (fake-stepped engine: scheduler logic only, no compiles)."""
    from megatron_tpu.inference.server import GenerationService, make_handler
    from megatron_tpu.telemetry.metrics import MetricsRegistry

    tok = NullTokenizer(64)
    service = GenerationService(CFG, PARAMS, tok, engine_slots=1,
                                engine_max_queue=1,
                                metrics=MetricsRegistry())
    eng = _fake_steps(service.engine)
    fast_decode = eng._decode_step

    def slow_decode(*a):
        time.sleep(0.02)
        return fast_decode(*a)

    eng._decode_step = slow_decode
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(service))
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()

    def fire(n_toks, results):
        body = json.dumps({"prompts": ["3 7"],
                           "tokens_to_generate": n_toks}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api", data=body, method="PUT",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                results.append((resp.status, dict(resp.headers)))
        except urllib.error.HTTPError as e:
            results.append((e.code, dict(e.headers)))

    try:
        import urllib.error

        held = []
        t1 = threading.Thread(target=fire, args=(50, held))
        t1.start()  # occupies the single slot for ~1s of slow ticks
        deadline = time.monotonic() + 30
        while eng.stats["admitted"] == 0:
            assert time.monotonic() < deadline, "request never admitted"
            time.sleep(0.005)
        t2 = threading.Thread(target=fire, args=(50, held))
        t2.start()  # waits in the queue (now at max_queue=1)
        while not eng._queue:
            assert time.monotonic() < deadline, "request never queued"
            time.sleep(0.005)
        overload = []
        fire(5, overload)  # third concurrent request: queue full
        assert overload and overload[0][0] == 503, overload
        assert "Retry-After" in overload[0][1], overload[0][1]
        assert eng.stats["rejected"] >= 1
        t1.join(timeout=60)
        t2.join(timeout=60)
        assert [s for s, _ in held] == [200, 200], held
    finally:
        server.shutdown()
        service.shutdown()


# ---------------------------------------------------------------------------
# kernels + sampling


def test_flash_decode_matches_masked_einsum():
    """Split-KV flash-decode kernel (interpret mode on CPU) vs the dense
    masked reference, GQA + per-row lengths + sliding window."""
    from megatron_tpu.ops.pallas.flash_template import flash_decode

    rng = np.random.default_rng(0)
    B, S, Hq, Hkv, D = 3, 256, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((B, 1, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    lens = jnp.asarray([1, 100, 256], jnp.int32)

    def ref(window=None):
        qg = (q.astype(jnp.float32) / np.sqrt(D)).reshape(B, 1, Hkv, 2, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
        k_pos = jnp.arange(S)[None, :]
        allowed = k_pos < lens[:, None]
        if window is not None:
            allowed &= k_pos >= lens[:, None] - window
        s = jnp.where(allowed[:, None, None, None, :], s, -np.inf)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1),
                       v.astype(jnp.float32))
        return o.reshape(B, 1, Hq, D)

    np.testing.assert_allclose(flash_decode(q, k, v, lens, block_k=128),
                               ref(), atol=2e-6)
    np.testing.assert_allclose(
        flash_decode(q, k, v, lens, sliding_window=32, block_k=128),
        ref(window=32), atol=2e-6)


def test_attention_kv_lengths_matches_causal_suffix():
    """attention(kv_lengths=...) over a padded cache equals plain causal
    attention over each row's exact prefix."""
    from megatron_tpu.ops.attention import attention

    rng = np.random.default_rng(1)
    B, S, H, D = 2, 32, 2, 8
    q = jnp.asarray(rng.standard_normal((B, 1, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    lens = np.asarray([5, 32], np.int32)
    got = attention(q, k, v, kv_lengths=jnp.asarray(lens))
    for b, L in enumerate(lens):
        want = attention(q[b:b + 1], k[b:b + 1, :L], v[b:b + 1, :L],
                         mask_type="causal", q_offset=L - 1)
        np.testing.assert_allclose(got[b:b + 1], want, atol=1e-6)


@pytest.mark.slow  # 10s measured cacheless (PR 4 tier-1 re-budget);
# greedy/int8 parity keeps sampler coverage in tier-1
def test_sample_logits_batched_matches_scalar_semantics():
    logits = jnp.asarray([[1.0, 5.0, 2.0, 0.0], [0.0, -1.0, 3.0, 1.0]])
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(2, dtype=jnp.uint32))

    # greedy rows (temperature 0) = argmax, regardless of filters
    out = sample_logits_batched(logits, keys,
                                temperature=jnp.zeros(2),
                                top_k=jnp.asarray([0, 2], jnp.int32),
                                top_p=jnp.zeros(2))
    np.testing.assert_array_equal(np.asarray(out), [1, 2])

    # top_k restricts support per row
    flat = jnp.asarray([[0.0, 1.0, 2.0, 3.0]] * 64)
    keys64 = jax.vmap(jax.random.PRNGKey)(jnp.arange(64, dtype=jnp.uint32))
    outs = np.asarray(sample_logits_batched(
        flat, keys64, temperature=jnp.ones(64),
        top_k=jnp.full(64, 2, jnp.int32), top_p=jnp.zeros(64)))
    assert set(outs.tolist()) <= {2, 3}

    # top_p keeps only the dominant token
    dom = jnp.asarray([[10.0, 5.0, 1.0, 0.0]] * 32)
    keys32 = jax.vmap(jax.random.PRNGKey)(jnp.arange(32, dtype=jnp.uint32))
    outs = np.asarray(sample_logits_batched(
        dom, keys32, temperature=jnp.ones(32),
        top_k=jnp.zeros(32, jnp.int32), top_p=jnp.full(32, 0.5)))
    assert set(outs.tolist()) == {0}

    # heterogeneous rows in ONE call: row 0 greedy, row 1 top-k limited
    het = sample_logits_batched(
        jnp.asarray([[0.0, 9.0, 1.0, 2.0]] * 2), keys,
        temperature=jnp.asarray([0.0, 1.0]),
        top_k=jnp.asarray([0, 1], jnp.int32), top_p=jnp.zeros(2))
    np.testing.assert_array_equal(np.asarray(het), [1, 1])

    # vocab clamp
    clamp = sample_logits_batched(
        jnp.asarray([[0.0, 0.0, 0.0, 100.0]] * 2), keys,
        temperature=jnp.ones(2), top_k=jnp.zeros(2, jnp.int32),
        top_p=jnp.zeros(2), vocab_size=3)
    assert (np.asarray(clamp) < 3).all()

    # greedy agrees with the scalar sampler
    scalar = sample_logits(logits, None)
    batched = sample_logits_batched(logits, keys, jnp.zeros(2),
                                    jnp.zeros(2, jnp.int32), jnp.zeros(2))
    np.testing.assert_array_equal(np.asarray(scalar), np.asarray(batched))


def test_a_row_with_one_candidate_is_a_greedy_row():
    """top_k 1 leaves the argmax, whatever the temperature: such a row is
    greedy to the batched sampler (it keeps neither the draw nor the
    filter's sort live), beside rows that do draw."""
    logits = jnp.asarray([[0.0, 9.0, 1.0, 2.0], [4.0, 0.0, 1.0, 3.0],
                          [0.0, 1.0, 2.0, 3.0]])
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(3, dtype=jnp.uint32))
    drawn = set()
    for seed in range(16):
        out = np.asarray(sample_logits_batched(
            logits, keys + seed, temperature=jnp.asarray([1.0, 50.0, 50.0]),
            top_k=jnp.asarray([1, 1, 2], jnp.int32), top_p=jnp.zeros(3)))
        assert out[:2].tolist() == [1, 0]
        drawn.add(int(out[2]))
    assert drawn == {2, 3}


# ---------------------------------------------------------------------------
# HTTP serving through the engine


@pytest.mark.slow  # 21s measured cacheless (PR 4 tier-1 re-budget);
# engine parity + HTTP roundtrip tests keep serving coverage in tier-1
def test_server_engine_concurrent_requests():
    """Concurrent HTTP requests share the engine's decode ticks and each
    gets the same greedy output as the one-shot service."""
    from megatron_tpu.inference.server import GenerationService, make_handler

    tok = NullTokenizer(64)
    cfg = presets.tiny(vocab_size=65, seq_length=64)
    params = init_params(cfg, jax.random.PRNGKey(1))

    base = GenerationService(cfg, params, tok)
    prompts = ["3 7 11", "5 9", "2 4 6 8"]
    want = {p: base.handle({"prompts": [p], "tokens_to_generate": 4,
                            "top_k": 1})["text"][0] for p in prompts}

    service = GenerationService(cfg, params, tok, engine_slots=4)
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(service))
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        results = {}
        errs = []

        def fire(p):
            body = json.dumps({"prompts": [p], "tokens_to_generate": 4,
                               "top_k": 1}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api", data=body, method="PUT",
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=120) as resp:
                    results[p] = json.loads(resp.read())["text"][0]
            except Exception as e:  # noqa: BLE001
                errs.append(f"{p}: {e}")

        threads = [threading.Thread(target=fire, args=(p,)) for p in prompts]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errs, errs
        assert results == want
        # the engine genuinely ran (admitted all three requests)
        assert service.engine.stats["admitted"] >= 3
    finally:
        server.shutdown()
        service.shutdown()


# ---------------------------------------------------------------------------
# offered-load throughput (slow: times compiled steps)


@pytest.mark.slow
def test_offered_load_throughput_scales_with_slots():
    """Continuous batching must beat sequential one-request-at-a-time
    handling for >= 4 concurrent requests (a CPU wall: it says the
    batched step is shared, nothing about the chip)."""
    import time

    prompt_len, new_tokens, n_req = 8, 24, 4
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, 60, (n_req, prompt_len)).astype(np.int32)
    lengths = np.full((n_req,), prompt_len, np.int32)

    # warm both paths (compiles excluded from timing)
    eng = make_engine(num_slots=n_req)
    eng.generate(prompts[:1], lengths[:1], max_new_tokens=new_tokens)
    generate_tokens(CFG, PARAMS, prompts[:1], lengths[:1],
                    max_new_tokens=new_tokens, temperature=0.0)

    t0 = time.perf_counter()
    for i in range(n_req):
        generate_tokens(CFG, PARAMS, prompts[i:i + 1], lengths[i:i + 1],
                        max_new_tokens=new_tokens, temperature=0.0)
    t_seq = time.perf_counter() - t0

    t0 = time.perf_counter()
    eng.generate(prompts, lengths, max_new_tokens=new_tokens)
    t_eng = time.perf_counter() - t0

    assert t_eng < t_seq, (t_eng, t_seq)


# ---------------------------------------------------------------------------
# the loop runs one tick ahead of the device (docs/serving.md "Step loop")


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "seeded"])
def test_a_tick_in_flight_changes_no_token(sampled):
    """Staggered admissions, ends by eod in mid-stream and by count: each
    request's tokens, logprobs and prompt logprobs are what
    `generate_tokens` gives it alone."""
    import _engine_lookahead_cases as cases

    cases.staggered_parity(make_engine(num_slots=3),
                           cases.one_shot(CFG, PARAMS),
                           CFG.vocab_size, sampled)


def _record_order(eng):
    """Every dispatch of the decode step and every fetch of one, in the
    order the host made them: ("dispatch" | "read", tick number)."""
    events, step, fetch = [], eng._decode_step, eng._fetch
    dispatched, read = [0], [0]

    def decode_step(*args):
        dispatched[0] += 1
        events.append(("dispatch", dispatched[0]))
        return step(*args)

    def fetch_one(rec):
        if rec.task is None:
            read[0] += 1
            events.append(("read", read[0]))
        return fetch(rec)

    eng._decode_step, eng._fetch = decode_step, fetch_one
    return events


def test_dispatch_of_the_next_tick_precedes_the_read_of_this_one():
    """Host only. On a run of ticks that only decode, tick k + 1 is in the
    queue before tick k is read, every such tick counts as dispatched
    ahead, and the run ends with nothing in flight."""
    eng = _fake_steps(make_engine(num_slots=2))
    reqs = [eng.submit(Request(prompt=np.asarray([i + 1], np.int32),
                               max_new_tokens=12)) for i in range(2)]
    eng.step()           # both admitted, their prompts' ends dispatched
    eng.step()
    events = _record_order(eng)
    ticks, ahead = eng.stats["ticks"], eng.stats["ticks_dispatched_ahead"]
    for _ in range(6):   # ticks that only decode
        eng.step()
    assert eng.stats["ticks"] - ticks == 6 and len(eng._inflight) == 1
    assert eng.stats["ticks_dispatched_ahead"] - ahead == 6
    assert eng.stats["tick_drains"] == {}
    # the window found one tick in flight, so its k-th read is of the
    # tick before its k-th dispatch: d1 r1 d2 r2 ...
    for k in range(1, 7):
        assert (events.index(("dispatch", k))
                < events.index(("read", k))), (k, events)
    eng.run_until_idle()
    assert not eng._inflight and eng.num_active == 0
    for i, r in enumerate(reqs):
        assert r.generated == [(i + 2 + j) % 64 for j in range(12)]


def test_an_eod_rows_extra_tick_leaks_no_page():
    """Host only. A row that ends by eod runs one tick more than it
    should: the pool ends with the pages the tick-by-tick run ends with,
    the token reaches nobody, and the request's reply is the same."""
    from _engine_lookahead_cases import drive_tick_by_tick

    def run(drive):
        eng = _fake_steps(make_engine(num_slots=2, page_size=4,
                                           prefill_chunk=4))
        # fake model counts up from the prompt's last token: 7 tokens to
        # 30, crossing a page; the other request ends by count
        a = eng.submit(Request(prompt=np.asarray([20, 21, 22, 23], np.int32),
                               max_new_tokens=20, eod=30))
        b = eng.submit(Request(prompt=np.asarray([40], np.int32),
                               max_new_tokens=4))
        drive(eng)
        assert a.generated == list(range(24, 31))
        assert b.generated == list(range(41, 45))
        assert not eng._inflight and eng.num_active == 0
        return eng

    ahead = run(lambda eng: eng.run_until_idle())
    plain = run(drive_tick_by_tick)
    assert ahead.stats["tokens_dropped_after_eod"] == 1
    assert plain.stats["tokens_dropped_after_eod"] == 0
    assert ahead.pool.free_pages == plain.pool.free_pages
    assert ahead.pool.used_pages == len(ahead.prefix_cache)
    assert ahead.stats["ticks"] == plain.stats["ticks"] + 1


def test_wait_idle_returns_with_nothing_in_flight():
    """The loop thread reads what it dispatched before it parks: once
    wait_idle() says idle no tick is unread, though the last request
    ended by eod with the next tick already in the queue."""
    eng = _fake_steps(make_engine(num_slots=2))
    eng.start()
    try:
        r = eng.submit(Request(prompt=np.asarray([10], np.int32),
                               max_new_tokens=30, eod=20))
        assert r.done.wait(timeout=30)
        assert eng.wait_idle(timeout=30)
        assert not eng._inflight
        assert r.generated == list(range(11, 21))
        assert eng.stats["tokens_dropped_after_eod"] == 1
        assert not eng.stalled(0.0)
    finally:
        eng.stop()


def test_a_deadline_expires_with_a_tick_in_flight():
    """The expiry reads the tick in flight first (it may be the one that
    ends the request), then fails who is still there; the other request's
    tokens are those it gets alone."""
    eng = make_engine(num_slots=2)
    prompt = np.asarray([3, 7, 11, 2], np.int32)
    late = eng.submit(Request(prompt=np.asarray([5, 9], np.int32),
                              max_new_tokens=40, deadline_s=600.0))
    other = eng.submit(Request(prompt=prompt, max_new_tokens=14))
    for _ in range(4):
        eng.step()
    assert eng._inflight and not late.done.is_set()
    seen = len(late.generated)
    late._deadline = time.monotonic()   # (the steps above compiled)
    eng.step()
    assert late.timed_out and "deadline exceeded" in late.error
    # the tick that was in flight reached the request before it failed
    assert len(late.generated) == seen + 1
    assert eng.stats["tick_drains"] == {"deadline": 1}
    eng.run_until_idle()
    from _engine_lookahead_cases import one_shot

    assert other.generated == one_shot(CFG, PARAMS)(prompt, 14, {}).generated
    assert eng.pool.used_pages == len(eng.prefix_cache)
    assert 'engine_tick_drains_total{cause="deadline"} 1' in (
        eng.metrics.render())


@pytest.mark.parametrize("where", ["dispatch", "read"])
def test_a_failed_step_fails_the_requests_of_every_tick_in_flight(where):
    """Host only. Under asynchronous dispatch a device failure surfaces at
    a dispatch or at the read of a later tick: the requests of every tick
    in flight fail, once, nothing of those ticks is read, and the engine
    serves the next request."""
    eng = _fake_steps(make_engine(num_slots=2))
    reqs = [eng.submit(Request(prompt=np.asarray([i + 1], np.int32),
                               max_new_tokens=12)) for i in range(2)]
    for _ in range(3):
        eng.step()
    assert len(eng._inflight) == 1
    seen = [len(r.generated) for r in reqs]
    step = eng._decode_step

    class Lost:
        def __array__(self, *args, **kwargs):
            raise RuntimeError("device lost")

    def boom(*args):
        raise RuntimeError("device lost")

    if where == "dispatch":
        eng._decode_step = boom
    else:
        eng._inflight[0].out = (Lost(), None)
    with pytest.raises(RuntimeError, match="device lost"):
        eng.step()
    eng._decode_step = step
    assert not eng._inflight and eng.num_active == 0 and eng._carry is None
    for r, n in zip(reqs, seen):
        assert r.done.is_set() and "decode step failed" in r.error
        assert len(r.generated) == n   # the tick in flight reached nobody
    ok = eng.submit(Request(prompt=np.asarray([9], np.int32),
                            max_new_tokens=3))
    eng.run_until_idle()
    assert ok.error is None and ok.generated == [10, 11, 12]
