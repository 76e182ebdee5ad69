"""Chunked fused logits+cross-entropy (beyond the reference, which
materializes the full [B,S,V] logits — gpt_model.py:18-42). The chunked
path must be numerically identical to the unchunked one: the softmax is
complete within a chunk because CE is per-token; only the sequence axis
is split. Its gradient is formed chunk by chunk beside the logits
(ops/cross_entropy.py chunked_head_loss; the head alone against autodiff
on every mesh: tests/test_vocab_parallel_loss.py)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.models import presets
from megatron_tpu.models.language_model import (
    chunked_lm_loss, final_hidden_norm, lm_forward, lm_loss,
)
from megatron_tpu.models.params import init_params


def _batch(cfg, batch=2, seq=None, seed=0, masked=False):
    seq = seq or cfg.seq_length
    rng = np.random.default_rng(seed)
    b = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                               jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)),
                               jnp.int32)}
    if masked:
        b["loss_mask"] = jnp.asarray(rng.integers(0, 2, (batch, seq)),
                                     jnp.float32)
    return b


@pytest.mark.parametrize("tie", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_chunked_ce_matches_unchunked(tie, masked):
    cfg = presets.tiny(seq_length=32, tie_embed_logits=tie)
    chunked = dataclasses.replace(cfg, ce_chunk_size=8).validate()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, masked=masked)

    loss0, aux0 = lm_loss(cfg, params, batch)
    loss1, aux1 = lm_loss(chunked, params, batch)
    np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-6)
    np.testing.assert_allclose(float(aux0["ntokens"]), float(aux1["ntokens"]))

    g0 = jax.grad(lambda p: lm_loss(cfg, p, batch)[0])(params)
    g1 = jax.grad(lambda p: lm_loss(chunked, p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_chunked_ce_full_size_chunk():
    """C == S is a single chunk whose gradient is formed beside its
    logits (neither is kept), not a silent no-op; numbers still match."""
    cfg = presets.tiny(seq_length=32)
    chunked = dataclasses.replace(cfg, ce_chunk_size=32).validate()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss0, _ = lm_loss(cfg, params, batch)
    loss1, _ = lm_loss(chunked, params, batch)
    np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-6)
    g0 = jax.grad(lambda p: lm_loss(cfg, p, batch)[0])(params)
    g1 = jax.grad(lambda p: lm_loss(chunked, p, batch)[0])(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tie", [False, True])
def test_chunked_ce_under_checkpoint_equals_the_bare_call(tie):
    """The pipeline schedule's form: the chunked loss wrapped in
    jax.checkpoint keeps the hidden state alone and forms its gradients
    in the backward pass. Same loss, same gradients, to the bit; and no
    gradient work where nothing is differentiated."""
    cfg = presets.tiny(seq_length=32, tie_embed_logits=tie, ce_chunk_size=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, masked=True)
    hidden = lm_forward(cfg, params, batch["tokens"], return_hidden=True)

    def bare(p, h):
        return chunked_lm_loss(cfg, p, h, batch["labels"],
                               batch["loss_mask"])[0]

    want, want_grads = jax.value_and_grad(bare, argnums=(0, 1))(
        params, hidden)
    got, got_grads = jax.value_and_grad(jax.checkpoint(bare), argnums=(0, 1))(
        params, hidden)
    assert float(got) == float(want)
    for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads),
                    strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # evaluated, the loop forms no gradient: the one product of the head
    # gradient's shape is absent from the program
    evaluated = str(jax.make_jaxpr(bare)(params, hidden))
    differentiated = str(jax.make_jaxpr(jax.grad(bare))(params, hidden))
    assert "optimization_barrier" not in evaluated
    assert "optimization_barrier" in differentiated


def test_per_token_losses_carry_no_gradient():
    """The per-token losses are for reporting: the gradient is that of the
    weighted sum, formed before any cotangent is known. Differentiating
    the per-token output raises; it is never a silent zero. The weights'
    own gradient is exact (the per-token losses)."""
    cfg = presets.tiny(seq_length=32, ce_chunk_size=8)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, masked=True)
    hidden = lm_forward(cfg, params, batch["tokens"], return_hidden=True)
    with pytest.raises(TypeError, match="for reporting"):
        jax.grad(lambda h: jnp.sum(chunked_lm_loss(
            cfg, params, h, batch["labels"])[1]))(hidden)
    total, per_token = chunked_lm_loss(cfg, params, hidden, batch["labels"],
                                       batch["loss_mask"])
    d_weights = jax.grad(lambda m: chunked_lm_loss(
        cfg, params, hidden, batch["labels"], m)[0])(batch["loss_mask"])
    np.testing.assert_allclose(np.asarray(d_weights), np.asarray(per_token),
                               rtol=1e-6)
    np.testing.assert_allclose(
        float(total), float(jnp.sum(per_token * batch["loss_mask"])),
        rtol=1e-6)


def test_chunked_ce_falls_back_on_non_tiling_seq():
    """variable_seq_lengths batches shorter than seq_length: when the chunk
    doesn't tile the actual sequence, the unchunked path runs (same loss,
    no crash)."""
    cfg = presets.tiny(seq_length=32)
    chunked = dataclasses.replace(cfg, ce_chunk_size=8).validate()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, seq=12)  # 12 % 8 != 0 -> fallback
    loss0, _ = lm_loss(cfg, params, batch)
    loss1, _ = lm_loss(chunked, params, batch)
    np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-6)


def test_chunked_ce_validate_rejects_non_divisor():
    with pytest.raises(ValueError):
        presets.tiny(seq_length=32, ce_chunk_size=7)


def test_chunked_ce_in_pipeline_last_stage():
    """pp=2 with chunked CE on the last stage matches the unpipelined
    unchunked loss, value and every gradient: the last stage wraps the
    chunked loss in jax.checkpoint, inside the cond inside the scan over
    the ticks, so that a tick keeps its hidden state and not the head's
    gradient."""
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import shard_tree
    from megatron_tpu.models.params import param_specs
    from megatron_tpu.training.pipeline import make_pipeline_loss_fn

    cfg = presets.tiny(vocab_size=64, seq_length=16, num_layers=4,
                       hidden_size=32, num_attention_heads=4, num_kv_heads=2,
                       ffn_hidden_size=64)
    chunked = dataclasses.replace(cfg, ce_chunk_size=4).validate()
    rt = build_mesh(ParallelConfig(pipeline_parallel=2))
    params = init_params(cfg, jax.random.PRNGKey(0))
    sp = shard_tree(rt, params, param_specs(cfg))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, 64, (8, 16)), jnp.int32),
        "loss_mask": jnp.ones((8, 16), jnp.float32),
    }
    pp_loss_fn = make_pipeline_loss_fn(chunked, rt.mesh, num_stages=2,
                                       num_microbatches=4, recompute="full")
    with jax.sharding.set_mesh(rt.mesh):
        loss_pp, grads_pp = jax.jit(jax.value_and_grad(
            lambda p, b: pp_loss_fn(p, b, None)[0]))(sp, batch)
    loss_ref, grads_ref = jax.value_and_grad(
        lambda p: lm_loss(cfg, p, batch)[0])(params)
    np.testing.assert_allclose(float(loss_pp), float(loss_ref), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(grads_pp), jax.tree.leaves(grads_ref),
                    strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_chunked_ce_under_tensor_parallel():
    """tp=2 sharded run with chunking matches the unsharded unchunked loss
    (each rank computes the chunk's logits over its half of the vocabulary:
    ops/cross_entropy.py chunked_head_loss)."""
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import ActivationSharder, shard_tree
    from megatron_tpu.models.params import param_specs

    cfg = presets.tiny(seq_length=32, vocab_size=64)
    chunked = dataclasses.replace(cfg, ce_chunk_size=8).validate()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss0, _ = lm_loss(cfg, params, batch)

    sharder = ActivationSharder(sequence_parallel=False)
    rt = build_mesh(ParallelConfig(tensor_parallel=2))
    with jax.sharding.set_mesh(rt.mesh):
        sp = shard_tree(rt, params, param_specs(cfg))
        loss1, _ = jax.jit(
            lambda p, b: lm_loss(chunked, p, b, sharder=sharder))(sp, batch)
    np.testing.assert_allclose(float(loss0), float(loss1),
                               rtol=1e-5, atol=1e-6)
