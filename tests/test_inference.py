"""Inference tests: sampling filters, KV-cache generation vs teacher
forcing, EOD stop, scoring, beam search, and the REST server over real HTTP
(counterparts: the reference's text_generation stack had no unit tests —
this is strictly more coverage)."""

import json
import threading
import urllib.error
import urllib.request
from http.server import ThreadingHTTPServer

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.inference.api import generate_and_post_process, tokenize_prompts
from megatron_tpu.inference.generation import (
    beam_search_tokens, generate_tokens, score_tokens,
)
from megatron_tpu.inference.sampling import sample_logits
from megatron_tpu.models import presets
from megatron_tpu.models.language_model import lm_forward
from megatron_tpu.models.params import init_params
from megatron_tpu.tokenizer.tokenizer import NullTokenizer

CFG = presets.tiny(vocab_size=64, seq_length=64)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))


def test_sample_greedy():
    logits = jnp.asarray([[1.0, 5.0, 2.0], [0.0, -1.0, 3.0]])
    out = sample_logits(logits, None)
    np.testing.assert_array_equal(np.asarray(out), [1, 2])
    out = sample_logits(logits, jax.random.PRNGKey(0), temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out), [1, 2])


def test_sample_top_k_restricts_support():
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]] * 64)
    outs = np.asarray(sample_logits(logits, jax.random.PRNGKey(1),
                                    temperature=1.0, top_k=2))
    assert set(outs.tolist()) <= {2, 3}


def test_sample_top_p_restricts_support():
    # one dominant token (p~0.97) -> top_p=0.5 keeps only it
    logits = jnp.asarray([[10.0, 5.0, 1.0, 0.0]] * 32)
    outs = np.asarray(sample_logits(logits, jax.random.PRNGKey(2),
                                    temperature=1.0, top_p=0.5))
    assert set(outs.tolist()) == {0}


def test_sample_vocab_clamp():
    logits = jnp.asarray([[0.0, 0.0, 0.0, 100.0]] * 8)
    outs = np.asarray(sample_logits(logits, jax.random.PRNGKey(3),
                                    temperature=1.0, vocab_size=3))
    assert (outs < 3).all()


def test_greedy_generation_matches_teacher_forcing():
    """Greedy incremental decode must equal repeated full forwards."""
    prompts = np.asarray([[3, 7, 11, 2]], np.int32)
    lengths = np.asarray([4], np.int32)
    out = generate_tokens(CFG, PARAMS, prompts, lengths, max_new_tokens=6,
                          temperature=0.0)
    # replay with full forward passes
    toks = prompts[0].tolist()
    for _ in range(6):
        logits = lm_forward(CFG, PARAMS, jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    np.testing.assert_array_equal(out.tokens[0], np.asarray(toks))


def test_unequal_prompt_lengths_forced_tokens():
    """Shorter rows decode while longer rows still consume their prompt."""
    prompts = np.asarray([[3, 7, 11, 2], [5, 9, 0, 0]], np.int32)
    lengths = np.asarray([4, 2], np.int32)
    out = generate_tokens(CFG, PARAMS, prompts, lengths, max_new_tokens=4,
                          temperature=0.0)
    # prompt regions are preserved verbatim
    np.testing.assert_array_equal(out.tokens[0, :4], prompts[0])
    np.testing.assert_array_equal(out.tokens[1, :2], prompts[1][:2])
    # row 1's continuation matches its own single-row greedy decode
    solo = generate_tokens(CFG, PARAMS, prompts[1:2, :2],
                           np.asarray([2], np.int32), max_new_tokens=6,
                           temperature=0.0)
    np.testing.assert_array_equal(out.tokens[1, 2:6], solo.tokens[0, 2:6])


def test_eod_stops_generation():
    # pick the greedy-next token after prompt [3] as a fake EOD so the model
    # "emits" it immediately
    logits = lm_forward(CFG, PARAMS, jnp.asarray([[3]], jnp.int32))
    eod = int(jnp.argmax(logits[0, -1]))
    out = generate_tokens(CFG, PARAMS, np.asarray([[3]], np.int32),
                          np.asarray([1], np.int32), max_new_tokens=8,
                          temperature=0.0, eod=eod)
    assert out.lengths[0] == 2  # prompt + eod
    assert out.tokens[0, 1] == eod


def test_score_tokens_is_logprob():
    toks = np.asarray([[1, 2, 3, 4]], np.int32)
    lp = score_tokens(CFG, PARAMS, toks)
    assert lp.shape == (1, 3)
    assert (lp <= 0).all()
    logits = lm_forward(CFG, PARAMS, jnp.asarray(toks[:, :-1]))
    want = jax.nn.log_softmax(logits.astype(jnp.float32), -1)[0, 2, 4]
    np.testing.assert_allclose(lp[0, 2], float(want), rtol=1e-5)


def test_beam_search_beats_greedy_logprob():
    prompt = np.asarray([3, 7], np.int32)
    beams, scores = beam_search_tokens(CFG, PARAMS, prompt, max_new_tokens=5,
                                       beam_size=3, eod=63)
    assert beams.shape[0] == 3
    assert (scores[:-1] >= scores[1:]).all()  # sorted best-first
    np.testing.assert_array_equal(beams[0, :2], prompt)


def test_generate_and_post_process_roundtrip():
    tok = NullTokenizer(64)  # vocab becomes 65, eod=64
    cfg = presets.tiny(vocab_size=65, seq_length=64)
    params = init_params(cfg, jax.random.PRNGKey(1))
    texts, segments, logprobs, tokens = generate_and_post_process(
        cfg, params, tok, ["3 7 11"], tokens_to_generate=4,
        temperature=0.0, return_output_log_probs=True)
    assert len(texts) == 1
    assert texts[0].startswith("3 7 11")
    assert len(texts[0].split()) == 7
    assert logprobs.shape[1] == 6


def test_server_http_roundtrip():
    from megatron_tpu.inference.server import GenerationService, make_handler

    tok = NullTokenizer(64)
    cfg = presets.tiny(vocab_size=65, seq_length=64)
    params = init_params(cfg, jax.random.PRNGKey(1))
    service = GenerationService(cfg, params, tok)
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(service))
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        body = json.dumps({"prompts": ["3 7 11"], "tokens_to_generate": 4,
                           "temperature": 0.0}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api", data=body, method="PUT",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as resp:
            out = json.loads(resp.read())
        assert out["text"][0].startswith("3 7 11")

        # malformed request -> 400 with message, server stays alive
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/api",
            data=json.dumps({"prompts": []}).encode(), method="PUT")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad)
        assert ei.value.code == 400
    finally:
        server.shutdown()


def test_tokenize_prompts_padding():
    tok = NullTokenizer(100)
    batch, lengths = tokenize_prompts(tok, ["1 2 3", "4"])
    assert batch.shape == (2, 3)
    np.testing.assert_array_equal(lengths, [3, 1])
    assert batch[1, 1] == tok.pad


@pytest.mark.slow  # 11s measured cacheless (PR 4 tier-1 re-budget);
# test_beam_search_beats_greedy_logprob keeps beam coverage in tier-1
def test_beam_search_kv_cache_matches_full_reforward():
    """The cached incremental beam decode must produce the same beams as a
    brute-force full-re-forward implementation (the pre-KV-cache behavior)."""
    from megatron_tpu.models.language_model import lm_forward

    prompt = np.asarray([5, 11, 3], np.int32)
    beam_size, new = 3, 6
    eod = 63
    got_beams, got_scores = beam_search_tokens(
        CFG, PARAMS, prompt, max_new_tokens=new, beam_size=beam_size, eod=eod)

    # reference: identical selection logic, logits from a full forward
    plen, total = len(prompt), len(prompt) + new
    beams = np.tile(prompt[None, :], (beam_size, 1))
    scores = np.full((beam_size,), -1e9, np.float64)
    scores[0] = 0.0
    finished = []
    for t in range(plen, total):
        logits = np.asarray(
            lm_forward(CFG, PARAMS, jnp.asarray(beams))[:, -1], np.float64)
        logprobs = (logits
                    - np.log(np.exp(logits - logits.max(-1, keepdims=True))
                             .sum(-1, keepdims=True))
                    - logits.max(-1, keepdims=True))
        cand = (scores[:, None] + logprobs).reshape(-1)
        top = np.argpartition(-cand, 2 * beam_size)[: 2 * beam_size]
        top = top[np.argsort(-cand[top])]
        nb, ns = [], []
        for idx in top:
            b, v = divmod(int(idx), logits.shape[-1])
            seq = np.concatenate([beams[b], [v]])
            if v == eod:
                finished.append((cand[idx] / ((len(seq) - plen) ** 1.0), seq))
            else:
                nb.append(seq)
                ns.append(cand[idx])
            if len(nb) == beam_size:
                break
        beams = np.stack(nb)
        scores = np.asarray(ns)
        if len(finished) >= beam_size:
            best_possible = scores.max() / max(1, t + 1 - plen)
            worst_kept = sorted(finished, key=lambda x: -x[0])[beam_size - 1][0]
            if worst_kept >= best_possible:
                break
    for s, b in zip(scores, beams):
        finished.append((s / max(1, beams.shape[1] - plen),
                         np.concatenate([b, [eod]])))
    finished.sort(key=lambda x: -x[0])
    want = np.stack([np.pad(f[1], (0, total + 1 - len(f[1])),
                            constant_values=eod) for f in finished[:beam_size]])

    np.testing.assert_array_equal(got_beams, want)
    np.testing.assert_allclose(got_scores,
                               [f[0] for f in finished[:beam_size]], rtol=1e-4)


def test_pipelined_generation_matches_single_stage():
    """Generation with the pipe axis active (pp=2) must produce the same
    tokens as the single-stage path (ref forward_step.py:45-204's pipelined
    inference, parity-tested here on the fake mesh)."""
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.inference.pipelined import make_pipelined_lm_forward
    from megatron_tpu.models.params import param_specs
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import shard_tree

    prompts = np.asarray([[5, 11, 3], [9, 2, 0]], np.int32)
    lengths = np.asarray([3, 2], np.int32)

    base = generate_tokens(CFG, PARAMS, prompts, lengths, max_new_tokens=6,
                           top_k=1, eod=63, want_logprobs=False)

    rt = build_mesh(ParallelConfig(pipeline_parallel=2))
    sharded = shard_tree(rt, PARAMS, param_specs(CFG))
    fwd = make_pipelined_lm_forward(CFG, rt.mesh, num_stages=2)
    with jax.sharding.set_mesh(rt.mesh):
        piped = generate_tokens(CFG, sharded, prompts, lengths,
                                max_new_tokens=6, top_k=1, eod=63,
                                want_logprobs=False, forward_fn=fwd)
    np.testing.assert_array_equal(base.tokens, piped.tokens)
    np.testing.assert_array_equal(base.lengths, piped.lengths)


def test_context_parallel_generation_matches_dense():
    """Serving under context parallelism (VERDICT r4 #6): prefill runs
    ring-sharded over the context axis (no fallback warning), decode runs
    against the context-sharded KV cache; tokens match the dense
    single-device path exactly."""
    import warnings as _warnings

    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.models.params import param_specs
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import shard_tree

    cfg = presets.tiny(vocab_size=64, seq_length=64, attention_impl="ring")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.asarray([[5, 11, 3, 9, 2, 17, 8, 1]], np.int32)
    lengths = np.asarray([8], np.int32)

    dense_cfg = presets.tiny(vocab_size=64, seq_length=64)
    # max_new_tokens chosen so the bucketed prefill length stays at 64
    # (divisible by 2*cp — the zig-zag ring shape)
    base = generate_tokens(dense_cfg, params, prompts, lengths,
                           max_new_tokens=64, top_k=1, eod=63,
                           want_logprobs=False)

    rt = build_mesh(ParallelConfig(context_parallel=2))
    sharded = shard_tree(rt, params, param_specs(cfg))
    with jax.sharding.set_mesh(rt.mesh):
        with _warnings.catch_warnings():
            _warnings.simplefilter("error", UserWarning)  # no CP fallback
            cp = generate_tokens(cfg, sharded, prompts, lengths,
                                 max_new_tokens=64, top_k=1, eod=63,
                                 want_logprobs=False)
    np.testing.assert_array_equal(base.tokens, cp.tokens)
    np.testing.assert_array_equal(base.lengths, cp.lengths)


def test_server_http_roundtrip_sharded_pipelined():
    """REST serving over a pp=2 mesh with the pipelined forward: same
    output as the unsharded service for a greedy request."""
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.inference.pipelined import make_pipelined_lm_forward
    from megatron_tpu.inference.server import GenerationService, make_handler
    from megatron_tpu.models.params import param_specs
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import shard_tree

    tok = NullTokenizer(64)
    cfg = presets.tiny(vocab_size=65, seq_length=64)
    params = init_params(cfg, jax.random.PRNGKey(1))

    base = GenerationService(cfg, params, tok)
    want = base.handle({"prompts": ["3 7 11"], "tokens_to_generate": 4,
                        "top_k": 1})["text"]

    rt = build_mesh(ParallelConfig(pipeline_parallel=2))
    sharded = shard_tree(rt, params, param_specs(cfg))
    fwd = make_pipelined_lm_forward(cfg, rt.mesh, 2)
    service = GenerationService(cfg, sharded, tok, mesh=rt.mesh,
                                forward_fn=fwd)
    server = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(service))
    port = server.server_address[1]
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        body = json.dumps({"prompts": ["3 7 11"], "tokens_to_generate": 4,
                           "top_k": 1}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/api", data=body, method="PUT",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as resp:
            out = json.loads(resp.read())
        assert out["text"] == want

        # beam on pipelined serving is a clear 400, not silence
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/api",
            data=json.dumps({"prompts": ["3 7"], "tokens_to_generate": 4,
                             "beam_width": 2}).encode(), method="PUT")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad)
        assert ei.value.code == 400
    finally:
        server.shutdown()


@pytest.mark.slow  # 13s measured cacheless (PR 4 tier-1 re-budget);
# generation/teacher-forcing parity keeps inference coverage in tier-1
def test_zeroshot_wikitext_adjusted_ppl(tmp_path):
    """--task wikitext reports word-level adjusted perplexity with the
    reference's token-ratio normalization (zeroshot_gpt/evaluate.py)."""
    import subprocess
    import sys

    import os
    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    rng = np.random.default_rng(0)
    text = " ".join(str(int(x)) for x in rng.integers(0, 60, 400))
    (tmp_path / "wiki.txt").write_text(text)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/evaluate_zeroshot.py"),
         "--task", "wikitext", "--text", str(tmp_path / "wiki.txt"),
         "--num_layers", "2", "--hidden_size", "32",
         "--num_attention_heads", "4", "--seq_length", "32",
         "--vocab_size", "64", "--fp32", "--tokenizer_type", "null"],
        env=env, capture_output=True, text=True, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert "adjusted_ppl" in res and res["adjusted_ppl"] > 0
    assert abs(res["token_ratio"] - 1.0) < 0.05  # null tokenizer: ~1 tok/word
