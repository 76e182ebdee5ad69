"""Speculative decoding tests (inference/speculative.py).

Pins the exactness contract and the zero-recompile invariant:
  * accept/reject math units (pure function, no engine): greedy accept
    counting, point-mass sampled acceptance with the right acceptance
    probability, residual exclusion, spec-off rows, vocab clamp;
  * n-gram / prompt-lookup drafter units;
  * multi-query decode attention: the kv_lengths q_len>1 einsum mask
    and both Pallas mq kernels (interpret mode) vs a dense reference;
  * greedy parity: speculative engines (ngram AND
    model drafter) are token-identical to the non-speculative engine —
    regardless of acceptance rate — with decode_recompiles == 0 read
    off the live PR 3 counter;
  * rollback: per-slot length roll-back after rejection, eod and
    max_new truncation mid-speculation, preempt-and-resume
    mid-speculation (greedy identity; sampled chain-determinism);
  * the retire-path knob-hygiene regression: an all-greedy spec tick
    after a sampled request retires must see all-zero sampling knobs
    in the device carry (the predicate that keeps the [N, k+1, V]
    filter sort dead).

Budget (the 870s tier-1 ceiling): every test that compiles its own
real-model engine pair is slow-marked with its measured cost — each
fresh engine's spec-step compile is ~4-6s on the 2-core host — while
tier-1 keeps the full logic surface cheaply: the accept/reject math,
the n-gram drafter, the mq kernels, and the rollback / knob-hygiene /
parity gates on ONE module-shared pair of zero-weight engines (same
code paths, one compile set; the zero model's constant greedy
continuation also makes it the high-acceptance bench-claim fixture).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.inference.engine import InferenceEngine, Request
from megatron_tpu.inference.generation import generate_tokens
from megatron_tpu.inference.speculative import (
    SpecConfig, ngram_propose, speculative_accept, validate_spec,
)
from megatron_tpu.models import presets
from megatron_tpu.models.params import init_params

CFG = presets.tiny(vocab_size=64, seq_length=64)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))
DCFG = presets.tiny(vocab_size=64, seq_length=64, num_layers=2)
DPARAMS = init_params(DCFG, jax.random.PRNGKey(7))


def make_engine(cfg=CFG, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 8)
    return InferenceEngine(cfg, PARAMS, **kw)


def run_one(eng, prompt, n=10, **kw):
    r = eng.submit(Request(prompt=np.asarray(prompt, np.int32),
                           max_new_tokens=n, **kw))
    eng.run_until_idle()
    assert r.error is None, r.error
    return r


@pytest.fixture(scope="module")
def zero_engines():
    """One compiled (base, speculative-ngram) engine pair over ZERO
    weights, shared by the tier-1 engine tests: the constant greedy
    continuation (argmax of all-equal logits = token 0) drives the
    n-gram drafter to ~full acceptance, so multi-token ticks, rollback
    truncation and the knob-hygiene predicate are all exercised for
    ONE compile set. Engines are reused sequentially after drains (the
    retire path resets every per-slot mirror — that reset is itself
    under test)."""
    params0 = jax.tree.map(lambda a: jnp.zeros_like(a), PARAMS)
    base = InferenceEngine(CFG, params0, num_slots=4, max_seq_len=64)
    spec = InferenceEngine(CFG, params0, num_slots=4, max_seq_len=64,
                           speculative=SpecConfig(k=3, drafter="ngram"))
    return params0, base, spec


# ---------------------------------------------------------------------------
# accept/reject math (pure function)


def _crafted_logits(rows):
    """[N, K1, V] with a dominant token per (row, position)."""
    N, K1, V = len(rows), len(rows[0]), 16
    logits = np.full((N, K1, V), -8.0, np.float32)
    for i, row in enumerate(rows):
        for j, t in enumerate(row):
            logits[i, j, t] = 8.0
    return jnp.asarray(logits)


def _accept(logits, drafts, temps=None, top_ks=None, top_ps=None,
            keys=None, spec_rows=None, lengths=None, vocab=None):
    N = logits.shape[0]
    return speculative_accept(
        logits, jnp.asarray(drafts, jnp.int32),
        jnp.zeros(N, jnp.int32) if lengths is None else lengths,
        (jax.vmap(jax.random.PRNGKey)(jnp.arange(N, dtype=jnp.uint32))
         if keys is None else keys),
        jnp.zeros(N) if temps is None else temps,
        jnp.zeros(N, jnp.int32) if top_ks is None else top_ks,
        jnp.zeros(N) if top_ps is None else top_ps,
        vocab_size=vocab, spec_rows=spec_rows)


def test_accept_greedy_counts_and_tokens():
    """Greedy: accepts = longest matching draft prefix; the emitted
    tokens are the target argmaxes at every position — exactly the
    non-speculative greedy continuation."""
    logits = _crafted_logits([[2, 3, 4, 5], [1, 6, 0, 7], [9, 9, 9, 9]])
    drafts = [[2, 3, 11], [0, 6, 0], [9, 9, 9]]
    toks, lps, accepts = _accept(logits, drafts)
    assert np.asarray(accepts).tolist() == [2, 0, 3]
    assert np.asarray(toks)[0].tolist() == [2, 3, 4, 5]
    assert np.asarray(toks)[1, 0] == 1
    assert np.asarray(toks)[2].tolist() == [9, 9, 9, 9]
    # logprobs are the fp32 log-softmax at the emitted token
    want = np.asarray(jax.nn.log_softmax(np.asarray(logits)[0], -1))
    np.testing.assert_allclose(np.asarray(lps)[0],
                               want[np.arange(4), [2, 3, 4, 5]],
                               rtol=1e-6)


def test_accept_spec_rows_off_forces_single_token():
    logits = _crafted_logits([[2, 3, 4, 5], [2, 3, 4, 5]])
    toks, _, accepts = _accept(logits, [[2, 3, 4]] * 2,
                               spec_rows=jnp.asarray([False, True]))
    assert np.asarray(accepts).tolist() == [0, 3]
    assert np.asarray(toks)[0, 0] == 2  # still the greedy token


def test_accept_sampled_point_mass_exactness():
    """Sampled rows: a draft equal to a ~certain token is accepted; a
    ~impossible draft is rejected and the residual sample excludes it
    (here: the dominant token, since everything else is ~0)."""
    logits = _crafted_logits([[3, 3, 3, 3], [3, 3, 3, 3]])
    temps = jnp.ones(2)
    toks, _, accepts = _accept(logits, [[3, 3, 3], [4, 3, 3]],
                               temps=temps)
    acc = np.asarray(accepts)
    assert acc[0] == 3                       # p(draft) ~ 1 everywhere
    assert np.asarray(toks)[0].tolist() == [3, 3, 3, 3]
    assert acc[1] == 0                       # p(4) ~ 0 -> rejected
    assert np.asarray(toks)[1, 0] == 3       # residual = dominant token


def test_accept_sampled_acceptance_probability():
    """The accept test fires with probability p(draft): a 50/50
    two-token distribution accepts the drafted token about half the
    time over many independent chains."""
    V, N = 16, 128
    row = np.full((1, 2, V), -30.0, np.float32)
    row[:, :, 3] = 5.0
    row[:, :, 5] = 5.0  # p(3) = p(5) = 0.5
    logits = jnp.asarray(np.repeat(row, N, axis=0))
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(N, dtype=jnp.uint32))
    _, _, accepts = _accept(logits, [[3]] * N, temps=jnp.ones(N),
                            keys=keys,
                            lengths=jnp.arange(N, dtype=jnp.int32))
    rate = float(np.asarray(accepts).mean())
    assert 0.35 < rate < 0.65, rate  # +-3.4 sigma at N=128


def test_accept_vocab_clamp():
    """Padded vocab columns can never be emitted, even when a draft
    points at one."""
    logits = jnp.asarray(np.zeros((2, 3, 16), np.float32)
                         + np.arange(16, dtype=np.float32))
    toks, _, _ = _accept(logits, [[15, 15], [14, 14]], vocab=8)
    assert (np.asarray(toks) < 8).all()


def test_validate_spec_errors():
    with pytest.raises(ValueError, match="k must be"):
        validate_spec(CFG, SpecConfig(k=0))
    with pytest.raises(ValueError, match="drafter"):
        validate_spec(CFG, SpecConfig(drafter="oracle"))
    with pytest.raises(ValueError, match="draft_cfg"):
        validate_spec(CFG, SpecConfig(drafter="model"))
    bad = presets.tiny(vocab_size=32, seq_length=64)
    with pytest.raises(ValueError, match="vocab"):
        validate_spec(CFG, SpecConfig(drafter="model", draft_cfg=bad,
                                      draft_params={}))


# ---------------------------------------------------------------------------
# n-gram / prompt-lookup drafter


def test_ngram_propose_lookup_and_fallbacks():
    h = np.asarray([1, 2, 3, 4, 1, 2], np.int32)
    assert ngram_propose(h, 3, 2).tolist() == [3, 4, 1]
    # most RECENT earlier occurrence wins
    h2 = np.asarray([1, 2, 9, 1, 2, 7, 1, 2], np.int32)
    assert ngram_propose(h2, 2, 2).tolist() == [7, 1]
    # no n-gram match falls back to shorter suffixes, then last-token
    assert ngram_propose(np.asarray([5, 5, 5], np.int32), 2, 2).tolist() \
        == [5, 5]
    assert ngram_propose(np.asarray([1, 2, 3], np.int32), 2, 2).tolist() \
        == [3, 3]
    # continuation shorter than k pads with its last token
    h3 = np.asarray([1, 2, 9, 1, 2], np.int32)
    assert ngram_propose(h3, 4, 2).tolist() == [9, 1, 2, 2]


# ---------------------------------------------------------------------------
# multi-query decode attention (the verify pass's kernel surface)


def _mq_reference(q, k, v, lens, window=None):
    B, SQ, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = (q.astype(jnp.float32) / np.sqrt(D)).reshape(B, SQ, Hkv, G, D)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    k_pos = jnp.arange(k.shape[1])[None, None, :]
    qi = jnp.arange(SQ)[None, :, None]
    allowed = k_pos < lens[:, None, None] + qi
    if window is not None:
        allowed &= k_pos >= lens[:, None, None] + qi - window
    s = jnp.where(allowed[:, None, None, :, :], s, -np.inf)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1),
                   v.astype(jnp.float32))
    return o.reshape(B, SQ, Hq, D)


def test_multi_query_kv_lengths_attention_matches_reference():
    """attention(kv_lengths=..., q_len>1): query j sees exactly
    k_pos < kv_lengths + j (each verify query one position deeper)."""
    from megatron_tpu.ops.attention import attention

    rng = np.random.default_rng(1)
    B, S, H, D, SQ = 2, 32, 2, 8, 4
    q = jnp.asarray(rng.standard_normal((B, SQ, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    lens = jnp.asarray([5, 28], jnp.int32)
    got = attention(q, k, v, kv_lengths=lens)
    np.testing.assert_allclose(got, _mq_reference(q, k, v, lens),
                               atol=1e-6)


def test_flash_decode_mq_matches_reference():
    """Multi-query flash-decode kernel (interpret mode on CPU) vs the
    dense masked reference: GQA + per-row lengths + sliding window."""
    from megatron_tpu.ops.pallas.flash_template import flash_decode_mq

    rng = np.random.default_rng(0)
    B, S, Hq, Hkv, D, SQ = 3, 256, 4, 2, 16, 3
    q = jnp.asarray(rng.standard_normal((B, SQ, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    lens = jnp.asarray([1, 100, 254], jnp.int32)
    np.testing.assert_allclose(
        flash_decode_mq(q, k, v, lens, block_k=128),
        _mq_reference(q, k, v, lens), atol=2e-6)
    np.testing.assert_allclose(
        flash_decode_mq(q, k, v, lens, sliding_window=32, block_k=128),
        _mq_reference(q, k, v, lens, window=32), atol=2e-6)


def test_paged_flash_decode_mq_matches_reference():
    """Paged multi-query kernel: page-table resolution + the per-query
    prefix mask agree with the dense reference."""
    from megatron_tpu.ops.pallas.flash_template import (
        paged_flash_decode_mq,
    )

    rng = np.random.default_rng(2)
    B, S, Hq, Hkv, D, SQ, ps = 2, 64, 4, 2, 8, 3, 8
    q = jnp.asarray(rng.standard_normal((B, SQ, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    lens = jnp.asarray([5, 60], jnp.int32)
    P = B * (S // ps) + 1
    kp = np.zeros((P, ps, Hkv, D), np.float32)
    vp = np.zeros_like(kp)
    table = np.zeros((B, S // ps), np.int32)
    n = 1
    for b in range(B):
        for pg in range(S // ps):
            kp[n] = np.asarray(k[b, pg * ps:(pg + 1) * ps])
            vp[n] = np.asarray(v[b, pg * ps:(pg + 1) * ps])
            table[b, pg] = n
            n += 1
    got = paged_flash_decode_mq(q, jnp.asarray(kp), jnp.asarray(vp),
                                jnp.asarray(table), lens)
    np.testing.assert_allclose(got, _mq_reference(q, k, v, lens),
                               atol=2e-6)


# ---------------------------------------------------------------------------
# engine parity gates (real tiny model)


@pytest.mark.slow  # 8s measured cacheless (fresh engine + spec-step
# compiles on the real random model = the LOW-acceptance regime); the
# zero-engines tier-1 tests pin the same parity at high acceptance
def test_spec_ngram_greedy_parity():
    """The acceptance gate (ngram drafter): speculative
    greedy decode is token-identical to the non-speculative engine AND
    the one-shot path — at the random model's low acceptance rate —
    with zero decode recompiles after warmup."""
    prompts = np.asarray([[3, 7, 11, 2]], np.int32)
    lengths = np.asarray([4], np.int32)
    want = generate_tokens(CFG, PARAMS, prompts, lengths, max_new_tokens=8,
                           temperature=0.0)
    eng = make_engine(speculative=SpecConfig(k=3, drafter="ngram"))
    got = eng.generate(prompts, lengths, max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(got.tokens, want.tokens)
    np.testing.assert_allclose(got.logprobs, want.logprobs,
                               rtol=1e-5, atol=1e-5)
    assert eng.stats["decode_recompiles"] == 0
    assert eng.stats["spec_proposed"] > 0


@pytest.mark.slow  # 12s measured cacheless (the model-drafter spec
# step's proposal-scan trace is the big compile); the ngram
# parity gates + the eod mid-spec rollback test keep greedy token-
# identity in tier-1, and the analysis audits trace this exact step
def test_spec_model_drafter_greedy_parity_and_full_acceptance():
    """Model drafter with draft == target: every draft is accepted
    (argmax agrees with itself), so n tokens arrive in ~n/(k+1) ticks —
    and the output is still token-identical to plain decode."""
    base = make_engine()
    a = run_one(base, [3, 7, 11, 2], n=12)
    eng = make_engine(speculative=SpecConfig(
        k=3, drafter="model", draft_cfg=CFG, draft_params=PARAMS))
    b = run_one(eng, [3, 7, 11, 2], n=12)
    assert a.generated == b.generated
    np.testing.assert_allclose(a.logprobs, b.logprobs,
                               rtol=1e-5, atol=1e-5)
    assert eng.stats["spec_accepted"] == eng.stats["spec_proposed"]
    assert eng.stats["ticks"] <= 4      # 12 tokens, ~4 per tick
    assert eng.stats["spec_emitted"] / eng.stats["ticks"] > 2.0
    assert eng.stats["decode_recompiles"] == 0


@pytest.mark.slow  # 12s measured cacheless (second model-drafter
# compile set); partial-acceptance greedy identity is also pinned
# tier-1 by the ngram gates (whose random-model acceptance is low)
def test_spec_small_draft_partial_acceptance_parity():
    """A DIFFERENT (2-layer, differently-seeded) draft proposes mostly
    wrong tokens — greedy output must be identical anyway (the verify
    emits the target argmax at every position regardless)."""
    base = make_engine()
    a = run_one(base, [5, 9, 1], n=10)
    eng = make_engine(speculative=SpecConfig(
        k=3, drafter="model", draft_cfg=DCFG, draft_params=DPARAMS))
    b = run_one(eng, [5, 9, 1], n=10)
    assert a.generated == b.generated
    assert eng.stats["spec_accepted"] < eng.stats["spec_proposed"]
    assert eng.stats["decode_recompiles"] == 0


def test_spec_request_knob_opt_out_parity(zero_engines):
    """Request(spec=False) on a speculating engine: no drafts are
    counted for it and its greedy output is bit-identical; spec=True
    traffic in the same engine is unaffected. (The same knob is pinned
    over HTTP through the fleet router in test_fleet.py.)"""
    _, base, eng = zero_engines
    a = run_one(base, [9, 4, 2], n=8)
    prop0 = eng.stats["spec_proposed"]
    off = run_one(eng, [9, 4, 2], n=8, spec=False)
    assert a.generated == off.generated
    assert eng.stats["spec_proposed"] == prop0
    on = run_one(eng, [9, 4, 2], n=8)
    assert a.generated == on.generated
    assert eng.stats["spec_proposed"] > prop0


def test_spec_eod_truncates_mid_speculation(zero_engines):
    """eod emitted mid-tick: the accepted tokens after it are dropped,
    matching the one-shot path's early stop exactly. The zero-weights
    model makes the constant argmax (token 0) the eod AND drives the
    n-gram drafter to full acceptance, so the eod genuinely lands
    inside a multi-token tick."""
    params0, _, eng = zero_engines
    prompts = np.asarray([[3]], np.int32)
    lengths = np.asarray([1], np.int32)
    want = generate_tokens(CFG, params0, prompts, lengths, max_new_tokens=8,
                           temperature=0.0, eod=0)
    got = eng.generate(prompts, lengths, max_new_tokens=8,
                       temperature=0.0, eod=0)
    assert int(got.lengths[0]) == int(want.lengths[0]) == 2
    np.testing.assert_array_equal(got.tokens[0, :2], want.tokens[0, :2])


def test_spec_capacity_margin_enforced(zero_engines):
    """A speculating engine reserves k positions of headroom: the tick
    always writes k+1 positions, so prompt + max_new must fit under
    max_seq_len - k (plain engines keep the old bound)."""
    _, base, eng = zero_engines                  # k = 3
    r = eng.submit(Request(prompt=np.asarray([1] * 30, np.int32),
                           max_new_tokens=32))   # 62 > 64 - 3
    assert r.done.is_set() and "headroom" in r.error
    ok = base.submit(Request(prompt=np.asarray([1] * 30, np.int32),
                             max_new_tokens=32))
    assert not ok.done.is_set()  # plain engine accepts 62 <= 64
    base.run_until_idle()        # drain for the next shared-fixture test


@pytest.mark.slow  # 10s measured cacheless (two fresh engine compile
# sets); chain determinism is also exercised by the preempt chaos test
# below, and the positional-PRNG draws are pinned by the accept units
def test_spec_sampled_chain_deterministic():
    """temperature > 0: same seed + same engine config => same tokens
    (positional PRNG draws), and the run completes at the engine's
    normal cadence."""
    spec = SpecConfig(k=3, drafter="ngram")
    outs = []
    for _ in range(2):
        eng = make_engine(speculative=spec)
        r = run_one(eng, [5], n=10, temperature=0.8, top_k=5, seed=9)
        outs.append(r.generated)
    assert outs[0] == outs[1]
    assert len(outs[0]) == 10


def test_all_greedy_spec_tick_filter_branch_stays_dead(zero_engines):
    """Retire-path knob hygiene under spec rollback: after a sampled
    request retires, the next tick's device carry must hold all-zero
    temps/top_ks/top_ps for the freed row — that predicate is what
    keeps the [N, k+1, V] filter sort (and the whole sampling branch)
    dead on all-greedy ticks."""
    _, _, eng = zero_engines
    greedy = eng.submit(Request(prompt=np.asarray([3, 7], np.int32),
                                max_new_tokens=30))
    sampled = eng.submit(Request(prompt=np.asarray([5], np.int32),
                                 max_new_tokens=2, temperature=0.9,
                                 top_k=7, top_p=0.5, seed=3))
    while not sampled.done.is_set():
        eng.step()
    assert sampled.error is None
    # the sampled request retired; the greedy one keeps decoding. After
    # one more tick the rebuilt carry must show zero knobs everywhere.
    eng.step()
    assert eng._carry is not None
    temps, top_ks, top_ps = (np.asarray(eng._carry[3]),
                             np.asarray(eng._carry[4]),
                             np.asarray(eng._carry[5]))
    assert (temps == 0).all() and (top_ks == 0).all() and (top_ps == 0).all()
    eng.run_until_idle()
    assert greedy.error is None and len(greedy.generated) == 30


def test_spec_high_acceptance_emits_multi_token_ticks(zero_engines):
    """High-acceptance traffic: a constant-continuation model
    (zero weights) + the n-gram drafter reach ~full acceptance, so
    tokens-per-forward approaches k+1 — and the output still equals the
    plain engine's, with zero decode recompiles."""
    _, base, eng = zero_engines
    t0, e0 = eng.stats["ticks"], eng.stats["spec_emitted"]
    p0, a0 = eng.stats["spec_proposed"], eng.stats["spec_accepted"]
    r = run_one(eng, [3, 7, 11], n=16)
    assert len(r.generated) == 16
    tpf = ((eng.stats["spec_emitted"] - e0)
           / max(eng.stats["ticks"] - t0, 1))
    assert tpf > 2.5, (tpf, eng.stats)
    accept_rate = ((eng.stats["spec_accepted"] - a0)
                   / max(eng.stats["spec_proposed"] - p0, 1))
    assert accept_rate >= 0.9, (accept_rate, eng.stats)
    b = run_one(base, [3, 7, 11], n=16)
    assert r.generated == b.generated
    # max_new truncation mid-tick rides the same (already-compiled)
    # engines: 7 % (k+1) != 0, so the full-acceptance final tick must
    # be cut to exactly max_new tokens
    r7 = run_one(eng, [5, 9], n=7)
    b7 = run_one(base, [5, 9], n=7)
    assert len(r7.generated) == 7
    assert r7.generated == b7.generated
    assert eng.stats["decode_recompiles"] == 0


# ---------------------------------------------------------------------------
# multi-chunk prompts, prefix hits and preemption under speculation
# (slow-marked matrices)


@pytest.mark.slow  # 5s measured cacheless (fresh engine: chunk +
# spec-step compiles); the spec step's device contract stays
# tier-1 via the decode_spec_paged audit (test_analysis), and the
# scheduler/rollback machinery via test_paging
def test_paged_spec_ngram_greedy_parity_multi_chunk():
    """The ngram drafter after a chunked prefill crossing page
    boundaries, then speculative decode — token-identical to the
    one-shot path, prompt logprobs included, zero recompiles."""
    prompts = np.asarray([[3, 7, 11, 2, 9, 4, 1, 8, 5, 2]], np.int32)
    lengths = np.asarray([10], np.int32)
    want = generate_tokens(CFG, PARAMS, prompts, lengths, max_new_tokens=8,
                           temperature=0.0)
    eng = make_engine(prefill_chunk=4,
                     speculative=SpecConfig(k=3, drafter="ngram"))
    got = eng.generate(prompts, lengths, max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(got.tokens, want.tokens)
    np.testing.assert_allclose(got.logprobs, want.logprobs,
                               rtol=1e-5, atol=1e-5)
    assert eng.stats["decode_recompiles"] == 0


@pytest.mark.slow  # ~25s measured cacheless (3 engine compile sets:
# paged spec model-drafter steps are the big traces); the ngram paged
# gate + the model-drafter gates keep the coverage in tier-1
@pytest.mark.parametrize("path", ["dense", "interpreted"])
def test_paged_spec_model_drafter_parity_and_prefix_hit(monkeypatch, path):
    """The draft model: the draft pools ride the SAME page
    tables (prefix-cache hits alias pages in both trees) — greedy
    token-identical at full acceptance, prompt logprobs exact on the
    aliased request. On the dense path a CPU host runs, and with the
    kernels forced through the interpreter, where the drafter's chunk
    (`draft_chunk`) and the target's run `paged_flash_chunk` over the one
    table and the verify step `paged_flash_decode`'s multi-query form."""
    cfg = CFG
    if path == "interpreted":
        monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
        cfg = dataclasses.replace(CFG, attention_impl="pallas")
    base = make_engine(cfg)
    p1 = np.asarray([3, 7, 11, 2, 9, 4, 1, 8, 5, 2], np.int32)
    shared = p1[:8]
    p2 = np.concatenate([shared, [9, 5]]).astype(np.int32)
    a1, a2 = run_one(base, p1), run_one(base, p2, n=8)
    eng = make_engine(cfg, speculative=SpecConfig(
        k=3, drafter="model", draft_cfg=cfg, draft_params=PARAMS))
    b1 = run_one(eng, p1)
    b2 = run_one(eng, p2, n=8)
    assert a1.generated == b1.generated
    assert a2.generated == b2.generated
    assert eng.stats["prefix_hits"] == 1
    np.testing.assert_allclose(a2.prompt_logprobs, b2.prompt_logprobs,
                               rtol=1e-5, atol=1e-5)
    assert eng.stats["spec_accepted"] == eng.stats["spec_proposed"]
    assert eng.stats["decode_recompiles"] == 0


@pytest.mark.slow  # ~35s measured cacheless (4 paged spec engines:
# solo references + the contended run, each with its own compiles);
# the preemption machinery itself stays tier-1 via test_paging
def test_paged_spec_preempt_and_resume_mid_speculation():
    """Page-pool pressure preempts the youngest slot MID-SPECULATION;
    the resumed request recomputes prompt + generated (both cache
    trees via the chunked path) and finishes: greedy output is
    token-identical to an uncontended run; the sampled request is
    chain-deterministic (two identical contended runs agree); zero
    recompiles throughout and every page accounted for."""
    pa = np.asarray([3, 7, 11, 2, 9, 4], np.int32)
    pb = np.asarray([5, 8, 1, 6, 2, 7], np.int32)
    kw = dict(num_slots=2, max_seq_len=32, page_size=4, prefill_chunk=8)
    spec = SpecConfig(k=3, drafter="ngram")
    a_solo = run_one(InferenceEngine(CFG, PARAMS, speculative=spec, **kw),
                     pa, n=16)

    def contended():
        eng = InferenceEngine(CFG, PARAMS, num_pages=10,
                              speculative=spec, **kw)
        ra = eng.submit(Request(prompt=pa, max_new_tokens=16))
        rb = eng.submit(Request(prompt=pb, max_new_tokens=16,
                                temperature=0.7, top_k=8, seed=5))
        eng.run_until_idle()
        assert ra.error is None and rb.error is None, (ra.error, rb.error)
        assert eng.stats["preemptions"] >= 1
        assert eng.stats["decode_recompiles"] == 0
        assert eng.pool.used_pages == len(eng.prefix_cache)
        return ra.generated, rb.generated

    a1, b1 = contended()
    a2, b2 = contended()
    # greedy: identical to the uncontended run (the preemption is
    # invisible); sampled: deterministic across identical schedules
    # (tick alignment shifts which drafts exist per position, so
    # schedule-independence is a greedy-only guarantee — docs/serving.md)
    assert a1 == a_solo.generated
    assert (a1, b1) == (a2, b2)
    assert len(b1) == 16


@pytest.mark.parametrize("path", ["dense", "interpreted"])
def test_a_slot_that_does_not_speculate_costs_the_verify_no_trip(
        monkeypatch, path):
    """One request in three slots under the n-gram drafter: the verify
    pass hands the multi-query kernel, for the two idle rows, the length
    at which none of its k + 1 queries sees a position
    (`masks.decode_idle_length`: 1 - sq, not 0, where query j sees
    k_pos < kv_len + j), the engine's count of the kernel's trips holds
    nothing for them, and the request is served the tokens and
    log-probabilities of an engine whose layers are not told which rows
    decode."""
    from megatron_tpu.ops.pallas import flash_template as ft
    from test_one_engine import _attention_not_told

    cfg = CFG
    if path == "interpreted":
        monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
        cfg = dataclasses.replace(CFG, attention_impl="pallas")
    prompt = [3, 7, 3, 7, 3, 7, 3, 7]

    def serve():
        eng = InferenceEngine(cfg, PARAMS, num_slots=3, max_seq_len=64,
                              page_size=8, prefill_chunk=8,
                              speculative=SpecConfig(k=2, drafter="ngram"))
        return run_one(eng, prompt, n=24), eng

    told, eng = serve()
    visited, held = eng._serve_ticks_fields()["decode_blocks"]
    _, _, _, n_blocks = ft._decode_geometry(8, 8, cfg.n_kv_heads)
    ticks = eng.stats["ticks"]
    assert held == ticks * 3 * n_blocks
    assert ticks <= visited <= ticks * n_blocks   # the one row's, alone
    _attention_not_told(monkeypatch)
    plain, _ = serve()
    assert told.generated == plain.generated and len(told.generated) == 24
    np.testing.assert_array_equal(told.logprobs, plain.logprobs)
