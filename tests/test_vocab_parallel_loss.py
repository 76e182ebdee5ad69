"""The head and the chunked cross-entropy under a mesh with "tensor" > 1
(ops/cross_entropy.py vocab_parallel_chunked_loss) against the plain
expressions: the unsharded `chunked_lm_loss_tokens` and the unchunked
`cross_entropy_loss` over `lm_logits`. Four of the suite's fake CPU
devices; float32 unless a case says otherwise."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_tpu.config import ParallelConfig
from megatron_tpu.models import presets
from megatron_tpu.models.language_model import (
    chunked_lm_loss_tokens, lm_logits, lm_loss,
)
from megatron_tpu.models.params import init_params, param_specs
from megatron_tpu.ops import cross_entropy as ce
from megatron_tpu.parallel.mesh import AXIS_TENSOR, build_mesh
from megatron_tpu.parallel.sharding import (
    ActivationSharder, activation_spec, batch_spec, shard_tree,
)

B, S, H, V = 4, 32, 16, 64
MESHES = {"tp2dp2": 2, "tp4dp1": 4}


def _mesh(tp, sequence_parallel=True):
    return build_mesh(
        ParallelConfig(tensor_parallel=tp,
                       sequence_parallel=sequence_parallel),
        devices=jax.devices()[:4])


def _head_case(tied, dtype=jnp.float32, seed=0):
    """(cfg, head parameters, hidden, labels, mask) and the parameters'
    PartitionSpecs: the head alone, so that `d hidden` and the head's
    gradient are the loss's own."""
    cfg = presets.tiny(vocab_size=V, seq_length=S, hidden_size=H,
                       tie_embed_logits=tied)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    if tied:
        params = {"embed": {"tokens": jax.random.normal(k1, (V, H), dtype)}}
        specs = {"embed": {"tokens": P(AXIS_TENSOR, None)}}
    else:
        params = {"lm_head": {"w": jax.random.normal(k1, (H, V), dtype)}}
        specs = {"lm_head": {"w": P(None, AXIS_TENSOR)}}
    hidden = jax.random.normal(k2, (B, S, H), dtype)
    labels = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (B, S)), jnp.float32)
    return cfg, params, specs, hidden, labels, mask


def _mean_and_tokens(cfg, sharder, masked):
    def f(params, hidden, labels, mask):
        per_token = chunked_lm_loss_tokens(cfg, params, hidden, labels,
                                           sharder=sharder)
        m = mask if masked else jnp.ones_like(mask)
        return jnp.sum(per_token * m) / jnp.maximum(jnp.sum(m), 1.0), per_token
    return f


def _sharded(rt, params, specs, hidden, labels, mask):
    put = lambda x, spec: jax.device_put(x, NamedSharding(rt.mesh, spec))  # noqa: E731
    return (shard_tree(rt, params, specs),
            put(hidden, activation_spec(sequence_parallel=True)),
            put(labels, batch_spec()), put(mask, batch_spec()))


def _value_and_grads(f, args, mesh=None):
    fn = jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))
    if mesh is None:
        return fn(*args)
    with jax.sharding.set_mesh(mesh):
        return fn(*args)


def _assert_trees_close(got, want, rtol, atol):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=rtol, atol=atol)


@pytest.mark.parametrize("mesh_name", sorted(MESHES))
@pytest.mark.parametrize("chunk", [S, S // 4])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_equals_the_unsharded_loss(tied, masked, chunk, mesh_name):
    """Loss, per-token losses, `d hidden` and the head's gradient (a tied
    head's is the embedding table's) at TP x DP with sequence parallelism
    equal the unsharded chunked loss and the unchunked one to 1e-5
    relative: only the order of the sums over the vocabulary differs."""
    cfg, params, specs, hidden, labels, mask = _head_case(tied)
    cfg = dataclasses.replace(cfg, ce_chunk_size=chunk).validate()
    args = (params, hidden, labels, mask)

    (want, want_tok), want_grads = _value_and_grads(
        _mean_and_tokens(cfg, lambda x, role: x, masked), args)

    def unchunked(params, hidden, labels, mask):
        return ce.cross_entropy_loss(
            lm_logits(cfg, params, hidden), labels,
            loss_mask=mask if masked else None)

    (plain, plain_tok), plain_grads = _value_and_grads(unchunked, args)

    rt = _mesh(MESHES[mesh_name])
    sharder = ActivationSharder(sequence_parallel=True)
    with jax.sharding.set_mesh(rt.mesh):
        assert ce.head_loss_plan(B, S, V, chunk, True) is not None
    (got, got_tok), got_grads = _value_and_grads(
        _mean_and_tokens(cfg, sharder, masked),
        _sharded(rt, params, specs, *args[1:]), mesh=rt.mesh)

    for ref, ref_tok, ref_grads in ((want, want_tok, want_grads),
                                    (plain, plain_tok, plain_grads)):
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(got_tok), np.asarray(ref_tok),
                                   rtol=1e-5, atol=1e-6)
        _assert_trees_close(got_grads, ref_grads, rtol=1e-5, atol=1e-7)


def test_context_parallel_outside_sequence_parallel():
    """TP 2 x CP 2: the sequence is cut over `context` first and each
    context shard's rows over `tensor`; the chunk loop runs inside a
    context shard."""
    cfg, params, specs, hidden, labels, mask = _head_case(tied=False)
    cfg = dataclasses.replace(cfg, ce_chunk_size=S // 4).validate()
    args = (params, hidden, labels, mask)
    (want, want_tok), want_grads = _value_and_grads(
        _mean_and_tokens(cfg, lambda x, role: x, True), args)
    rt = build_mesh(ParallelConfig(tensor_parallel=2, context_parallel=2,
                                   sequence_parallel=True),
                    devices=jax.devices()[:4])
    with jax.sharding.set_mesh(rt.mesh):
        plan = ce.head_loss_plan(B, S, V, S // 4, True)
    assert plan.context == "context" and plan.rows == S // 8
    (got, got_tok), got_grads = _value_and_grads(
        _mean_and_tokens(cfg, ActivationSharder(True), True),
        _sharded(rt, params, specs, *args[1:]), mesh=rt.mesh)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_tok), np.asarray(want_tok),
                               rtol=1e-5, atol=1e-6)
    _assert_trees_close(got_grads, want_grads, rtol=1e-5, atol=1e-7)


def test_bf16_head_against_the_unsharded_bf16_loss():
    """bf16 hidden state and head. The logits are the same bf16 products
    on both sides and every reduction of the cross-entropy is float32, so
    losses agree to float32 rounding of sums in another order (1e-5).
    The gradients do not agree that closely, and should not: the
    unsharded scan adds each chunk's head gradient into a bf16 sum (8
    significant bits: up to 2**-9 relative a chunk, four chunks), where
    this path sums the chunks in float32 and rounds once; `d hidden` is a
    bf16 sum of two ranks' bf16 partial products where the unsharded one
    rounds the whole product once. Hence 2**-6 of the largest entry."""
    cfg, params, specs, hidden, labels, mask = _head_case(
        False, dtype=jnp.bfloat16)
    cfg = dataclasses.replace(cfg, ce_chunk_size=S // 4).validate()
    args = (params, hidden, labels, mask)
    (want, want_tok), want_grads = _value_and_grads(
        _mean_and_tokens(cfg, lambda x, role: x, True), args)
    rt = _mesh(2)
    (got, got_tok), got_grads = _value_and_grads(
        _mean_and_tokens(cfg, ActivationSharder(True), True),
        _sharded(rt, params, specs, *args[1:]), mesh=rt.mesh)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(got_tok), np.asarray(want_tok),
                               rtol=1e-5, atol=1e-5)
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads),
                    strict=True):
        assert g.dtype == w.dtype == jnp.bfloat16
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=2.0 ** -6 * np.abs(w).max())


@pytest.mark.parametrize("tp", [2, 4])
def test_a_label_outside_a_ranks_columns_picks_nothing_there(tp):
    """The classic vocabulary-parallel off-by-one: for labels on both
    sides of every boundary between two ranks' columns, the rank that
    holds the label's column picks exactly that logit and every other
    rank picks exactly 0.0."""
    width = V // tp
    edges = sorted({v for r in range(tp) for v in
                    (r * width, r * width + 1, (r + 1) * width - 1)})
    labels = jnp.asarray(edges, jnp.int32)[None, :]            # [1, n]
    logits = (jnp.arange(V, dtype=jnp.float32) + 1.0) * jnp.ones(
        (1, len(edges), 1))                                    # never 0.0
    rt = _mesh(tp)

    def picked(y, z):
        mine = ce._label_onehot(y, z.shape[-1])
        return jnp.sum(jnp.where(mine, z, 0.0), axis=-1)[None]

    with jax.sharding.set_mesh(rt.mesh):
        per_rank = jax.jit(jax.shard_map(
            picked, in_specs=(P(), P(None, None, AXIS_TENSOR)),
            out_specs=P(AXIS_TENSOR), axis_names=set(rt.mesh.axis_names),
            check_vma=False))(labels, logits)                  # [tp, 1, n]
    per_rank = np.asarray(per_rank)[:, 0, :]
    for i, v in enumerate(edges):
        want = np.zeros(tp, np.float32)
        want[v // width] = v + 1.0
        np.testing.assert_array_equal(per_rank[:, i], want)


@pytest.mark.parametrize("sequence_parallel", [True, False],
                         ids=["sp", "nosp"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_model_gradients_under_tp2_dp2(tied, sequence_parallel):
    """The whole model through `lm_loss` at TP 2 x DP 2: every gradient
    leaf equals the unsharded run's. A tied head's gradient lands in the
    embedding table's, beside the embedding's own; without sequence
    parallelism the gather is an identity and `d hidden` is all-reduced."""
    cfg = presets.tiny(vocab_size=V, seq_length=S, tie_embed_logits=tied,
                       ce_chunk_size=S // 4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    batch = {k: jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
             for k in ("tokens", "labels")}
    batch["loss_mask"] = jnp.asarray(rng.integers(0, 2, (B, S)), jnp.float32)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: lm_loss(cfg, p, batch)[0]))(params)

    rt = _mesh(2, sequence_parallel)
    sharder = ActivationSharder(sequence_parallel)
    with jax.sharding.set_mesh(rt.mesh):
        sharded = shard_tree(rt, params, param_specs(cfg))
        placed = {k: jax.device_put(v, NamedSharding(rt.mesh, batch_spec()))
                  for k, v in batch.items()}
        got, got_grads = jax.jit(jax.value_and_grad(
            lambda p, b: lm_loss(cfg, p, b, sharder=sharder)[0]))(
                sharded, placed)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    _assert_trees_close(got_grads, want_grads, rtol=2e-4, atol=1e-6)


def test_no_plan_without_a_tensor_axis_or_inside_a_manual_region():
    """No mesh, "tensor" of size 1, or a trace point inside somebody
    else's shard_map (the pipeline schedule): the plain expression."""
    assert ce.head_loss_plan(B, S, V, 8, True) is None
    rt = build_mesh(ParallelConfig(), devices=jax.devices()[:4])
    with jax.sharding.set_mesh(rt.mesh):
        assert ce.head_loss_plan(B, S, V, 8, False) is None
    rt = _mesh(2)
    seen = []

    def inside(x):
        seen.append(ce.head_loss_plan(B, S, V, 8, True))
        return x

    with jax.sharding.set_mesh(rt.mesh):
        jax.jit(jax.shard_map(inside, in_specs=P("data"), out_specs=P("data"),
                              axis_names={"data"}, check_vma=False)
                )(jnp.zeros((4,)))
        with pytest.warns(UserWarning, match="does not divide"):
            assert ce.head_loss_plan(B, S, V + 1, 8, True) is None
    assert seen == [None]
