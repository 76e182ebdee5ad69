"""int8 KV-cache quantization (beyond the reference: serving memory
optimization — cache bytes halve at bounded logit drift)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.models import presets
from megatron_tpu.models.language_model import lm_forward
from megatron_tpu.models.params import init_params
from megatron_tpu.ops.kv_quant import dequantize_kv, quantize_kv

CFG = presets.tiny(vocab_size=128, seq_length=48, params_dtype="float32")
PARAMS = init_params(CFG, jax.random.PRNGKey(0))


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3.0, (2, 7, 4, 64)), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 7, 4, 1)
    back = dequantize_kv(q, s, jnp.float32)
    # symmetric 127-level quantization: error <= scale/2 per element
    err = np.abs(np.asarray(back - x))
    bound = np.asarray(s) / 2 + 1e-7
    assert (err <= bound).all()
    # zero vectors stay exactly zero
    q0, s0 = quantize_kv(jnp.zeros((1, 1, 1, 8)))
    assert np.asarray(dequantize_kv(q0, s0, jnp.float32)).sum() == 0.0


def _caches(int8):
    from megatron_tpu.ops.kv_store import create as _init_caches

    return _init_caches(CFG, 2, 48, int8=int8)


def test_int8_cache_halves_kv_bytes():
    full = _caches(False)
    quant = _caches(int8=True)
    full_bytes = sum(c.nbytes for c in full)
    # int8 payload is 1/4 the fp32 payload; scales add D-fraction overhead
    payload = sum(c.nbytes for c in quant[:2])
    scales = sum(c.nbytes for c in quant[2:])
    assert payload == full_bytes // 4  # fp32 test dtype; bf16 -> 1/2
    # one fp32 scale per D int8 values: overhead = 4/D of the payload
    # (3% at llama head_dim 128; D=16 here)
    assert scales * CFG.head_dim == payload * 4


@pytest.mark.slow  # 12s measured cacheless (PR 4 tier-1 re-budget);
# the quantize/dequant unit parity tests keep kv-int8 coverage in tier-1
def test_cached_decode_with_int8_matches_full_forward():
    """Decode token-by-token with the int8 cache; logits must track the
    uncached full forward within quantization tolerance and agree on
    argmax at essentially every position."""
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, 128, (2, 16)), jnp.int32)
    ref = lm_forward(CFG, PARAMS, toks)

    caches = _caches(int8=True)
    # prefill 8, then decode 8 single tokens
    logits_pre, caches = lm_forward(CFG, PARAMS, toks[:, :8],
                                    positions=jnp.arange(8)[None, :],
                                    kv_caches=caches, cache_index=0)
    outs = [logits_pre]
    for t in range(8, 16):
        lg, caches = lm_forward(CFG, PARAMS, toks[:, t:t + 1],
                                positions=jnp.full((2, 1), t),
                                kv_caches=caches, cache_index=t)
        outs.append(lg)
    got = jnp.concatenate(outs, axis=1)
    ref_n = np.asarray(ref, np.float32)
    got_n = np.asarray(got, np.float32)
    # bounded drift relative to the logit scale
    denom = np.abs(ref_n).max()
    assert np.abs(got_n - ref_n).max() / denom < 0.05
    agree = (ref_n.argmax(-1) == got_n.argmax(-1)).mean()
    assert agree >= 0.9


def test_generate_with_int8_cache_runs_and_matches_greedy():
    from megatron_tpu.inference.generation import generate_tokens

    rng = np.random.default_rng(2)
    prompts = rng.integers(1, 128, (2, 6)).astype(np.int32)
    lengths = np.array([6, 4], np.int32)
    kw = dict(max_new_tokens=8, temperature=0.0, top_k=1, seed=0,
              want_logprobs=False)
    out_fp = generate_tokens(CFG, PARAMS, prompts, lengths, **kw)
    out_q = generate_tokens(CFG, PARAMS, prompts, lengths,
                            kv_cache_int8=True, **kw)
    assert out_q.tokens.shape == out_fp.tokens.shape
    # greedy on a random-init model: near-ties may flip a step, but most
    # emitted tokens should agree
    agree = (out_q.tokens == out_fp.tokens).mean()
    assert agree > 0.7


def test_beam_search_with_int8_cache():
    """Beam search shares the cached decode path; the int8 cache tuple
    flows through the tree-mapped per-beam gathers."""
    from megatron_tpu.inference.generation import beam_search_tokens

    prompt = np.array([5, 9, 12, 44], np.int32)
    beams_fp, scores_fp = beam_search_tokens(
        CFG, PARAMS, prompt, max_new_tokens=6, beam_size=3, eod=0)
    beams_q, scores_q = beam_search_tokens(
        CFG, PARAMS, prompt, max_new_tokens=6, beam_size=3, eod=0,
        kv_cache_int8=True)
    assert beams_q.shape == beams_fp.shape
    assert np.isfinite(scores_q).all()
    # quantization noise may reorder near-tied beams; the top beam's
    # prompt region must be intact either way
    np.testing.assert_array_equal(beams_q[0, :4], prompt)


def test_int8_cache_rejects_pipelined_forward():
    import pytest

    from megatron_tpu.inference.generation import generate_tokens

    with pytest.raises(ValueError, match="single-stage"):
        generate_tokens(CFG, PARAMS, np.zeros((1, 4), np.int32),
                        np.array([4]), max_new_tokens=2,
                        forward_fn=lambda *a: None, kv_cache_int8=True)


# ---------------------------------------------------------------------------
# every cache kind against the cache-free forward (ops/kv_store.py: the
# store rides in lm_forward's scan carry and is written in place)

_KINDS = [(paged, int8, spec) for paged in (False, True)
          for int8 in (False, True) for spec in (False, True)]


@pytest.mark.parametrize(
    "paged,int8,spec", _KINDS,
    ids=[f"{'paged' if p else 'slots'}-{'int8' if i else 'f32'}-"
         f"{'verify3' if s else 'decode1'}" for p, i, s in _KINDS])
def test_prefill_then_decode_matches_the_cache_free_forward(paged, int8,
                                                            spec):
    """Prefill 8 positions, then decode the next 6 with every row at its
    own depth (one token a row, or the 3 tokens of a speculative verify),
    through a slot store and through a page pool (chunks of 4 through the
    table, scattered pages), float and int8: the logits are the cache-free
    forward's on the same seeded random weights — to float32 rounding for
    a float store, to the quantization's drift for an int8 one."""
    from megatron_tpu.ops import kv_store

    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, 128, (2, 14)), jnp.int32)
    ref = np.asarray(lm_forward(CFG, PARAMS, toks), np.float32)
    if paged:
        page, table = 4, jnp.asarray([[7, 2, 9, 4], [1, 8, 3, 6]], jnp.int32)
        store = kv_store.create(CFG, 10, page, int8=int8)
        outs = []
        for r in range(2):          # chunked prefill: one row a call
            row = []
            for off in (0, 4):
                lg, store = lm_forward(
                    CFG, PARAMS, toks[r:r + 1, off:off + 4], kv_caches=store,
                    cache_index=jnp.int32(off), page_table=table[r:r + 1],
                    page_write_start=jnp.int32(0), page_write_end=jnp.int32(8))
                row.append(lg)
            outs.append(jnp.concatenate(row, axis=1))
        got = [jnp.concatenate(outs, axis=0)]
        kw = {"page_table": table}
    else:
        store = kv_store.create(CFG, 2, 16, int8=int8)
        lg, store = lm_forward(CFG, PARAMS, toks[:, :8], kv_caches=store,
                               cache_index=0)
        got, kw = [lg], {}
    step = 3 if spec else 1
    for t in range(8, 14, step):
        lg, store = lm_forward(CFG, PARAMS, toks[:, t:t + step],
                               kv_caches=store,
                               cache_index=jnp.full((2,), t, jnp.int32), **kw)
        got.append(lg)
    got = np.asarray(jnp.concatenate(got, axis=1), np.float32)
    if int8:
        assert np.abs(got - ref).max() < 0.05
        assert (got.argmax(-1) == ref.argmax(-1)).mean() >= 0.95
    else:
        np.testing.assert_allclose(got, ref, atol=2e-5)


@pytest.mark.parametrize("paged", [False, True], ids=["slots", "paged"])
@pytest.mark.parametrize("step", [1, 3], ids=["decode1", "verify3"])
def test_decode_kernel_reads_the_store_where_it_lies(monkeypatch, paged,
                                                     step):
    """The same decode through the Pallas decode kernel (interpret mode):
    the kernel tiles the stacked store as it lies in memory, a layer's
    share addressed through the table kv_store.read builds (a slot row is
    one page of 128 positions, split into the kernel's blocks), and gives
    the logits of the XLA path over the same store."""
    import dataclasses

    from megatron_tpu.ops import kv_store

    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    kernel_cfg = dataclasses.replace(CFG, attention_impl="pallas")
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(0, 128, (2, 14)), jnp.int32)
    if paged:
        table = jnp.asarray([[5, 2], [1, 4]], jnp.int32)
        store, kw = kv_store.create(CFG, 6, 8), {"page_table": table}
        for r in range(2):
            _, store = lm_forward(
                CFG, PARAMS, toks[r:r + 1, :8], kv_caches=store,
                cache_index=jnp.int32(0), page_table=table[r:r + 1],
                page_write_start=jnp.int32(0), page_write_end=jnp.int32(8))
    else:
        store, kw = kv_store.create(CFG, 2, 128), {}
        _, store = lm_forward(CFG, PARAMS, toks[:, :8], kv_caches=store,
                              cache_index=0)
    at = jnp.full((2,), 8, jnp.int32)
    want, _ = lm_forward(CFG, PARAMS, toks[:, 8:8 + step], kv_caches=store,
                         cache_index=at, **kw)

    def through_the_kernel(s):
        return lm_forward(kernel_cfg, PARAMS, toks[:, 8:8 + step],
                          kv_caches=s, cache_index=at, **kw)[0]

    assert "pallas_call" in str(jax.make_jaxpr(through_the_kernel)(store))
    got = jax.jit(through_the_kernel)(store)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
