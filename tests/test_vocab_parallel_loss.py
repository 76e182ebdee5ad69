"""The head and the chunked cross-entropy (ops/cross_entropy.py
chunked_head_loss: one loop that forms each chunk's gradient beside its
logits, under a mesh inside one shard_map with its own collectives)
against the plain expression: autodiff of the unchunked
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
    chunked_lm_loss, lm_logits, lm_loss,
)
from megatron_tpu.models.params import init_params, param_specs
from megatron_tpu.ops import cross_entropy as ce
from megatron_tpu.parallel.mesh import AXIS_TENSOR, build_mesh
from megatron_tpu.parallel.sharding import (
    ActivationSharder, activation_spec, batch_spec, shard_tree,
)

B, S, H, V = 4, 32, 16, 64
# tensor-parallel size; None: no mesh at all. "dp4" is data parallel alone
MESHES = {"nomesh": None, "dp4": 1, "tp2dp2": 2, "tp4dp1": 4}


def _mesh(tp, sequence_parallel=True):
    return build_mesh(
        ParallelConfig(tensor_parallel=tp,
                       sequence_parallel=sequence_parallel and tp > 1),
        devices=jax.devices()[:4])


def _head_case(tied, dtype=jnp.float32, seed=0, B=B, S=S):
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


def _mean_and_tokens(cfg, sharder, masked, cotangent=1.0):
    """The chunked loss as `lm_loss` calls it: the mask goes in, its
    normaliser stays outside on the scalar (times `cotangent`, which is
    then the cotangent of the weighted sum but for the normaliser)."""
    def f(params, hidden, labels, mask):
        m = mask if masked else None
        total, per_token = chunked_lm_loss(cfg, params, hidden, labels, m,
                                           sharder=sharder)
        denom = jnp.maximum(jnp.sum(mask), 1.0) if masked else mask.size
        return cotangent * total / denom, per_token
    return f


def _unchunked(cfg, masked, cotangent=1.0):
    """The plain expression: autodiff of `cross_entropy_loss` over the
    whole `lm_logits`."""
    def f(params, hidden, labels, mask):
        mean, per_token = ce.cross_entropy_loss(
            lm_logits(cfg, params, hidden), labels,
            loss_mask=mask if masked else None)
        return cotangent * mean, per_token
    return f


def _identity(x, role):
    return x


def _on_mesh(mesh_name, f, params, specs, hidden, labels, mask):
    """value_and_grad of f(sharder) on the named mesh of MESHES."""
    tp = MESHES[mesh_name]
    if tp is None:
        return _value_and_grads(f(_identity), (params, hidden, labels, mask))
    rt = _mesh(tp)
    sp = tp > 1
    return _value_and_grads(
        f(ActivationSharder(sequence_parallel=sp)),
        _sharded(rt, params, specs, hidden, labels, mask, sp), mesh=rt.mesh)


def _sharded(rt, params, specs, hidden, labels, mask, sequence_parallel=True):
    put = lambda x, spec: jax.device_put(x, NamedSharding(rt.mesh, spec))  # noqa: E731
    return (shard_tree(rt, params, specs),
            put(hidden, activation_spec(sequence_parallel)),
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
def test_equals_the_unchunked_loss(tied, masked, chunk, mesh_name):
    """Loss, per-token losses, `d hidden` and the head's gradient (a tied
    head's is the embedding table's) of the one chunk loop, with no mesh,
    data parallel alone and at TP x DP with sequence parallelism, equal
    autodiff of the unchunked loss to 1e-6: only the order of the sums
    over the vocabulary and over the chunks differs."""
    cfg, params, specs, hidden, labels, mask = _head_case(tied)
    cfg = dataclasses.replace(cfg, ce_chunk_size=chunk).validate()
    args = (params, hidden, labels, mask)
    (want, want_tok), want_grads = _value_and_grads(
        _unchunked(cfg, masked), args)
    if MESHES[mesh_name]:
        with jax.sharding.set_mesh(_mesh(MESHES[mesh_name]).mesh):
            assert ce.head_loss_plan(B, S, V, chunk, True).axes
    (got, got_tok), got_grads = _on_mesh(
        mesh_name, lambda sharder: _mean_and_tokens(cfg, sharder, masked),
        params, specs, *args[1:])
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got_tok), np.asarray(want_tok),
                               rtol=1e-6, atol=1e-6)
    _assert_trees_close(got_grads, want_grads, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mesh_name", ["nomesh", "tp2dp2"])
@pytest.mark.parametrize("cotangent", [2.0 ** 16, 3.0],
                         ids=["power_of_two", "three"])
def test_a_cotangent_that_is_not_one(cotangent, mesh_name):
    """The gradients are formed in the forward rule, before the cotangent
    is known, and multiplied by it in the backward rule: a loss scale (a
    power of two) and a factor that is none give the unchunked loss's
    gradients times that factor, and never the bare ones."""
    cfg, params, specs, hidden, labels, mask = _head_case(tied=False)
    cfg = dataclasses.replace(cfg, ce_chunk_size=S // 4).validate()
    args = (params, hidden, labels, mask)
    (want, _), want_grads = _value_and_grads(
        _unchunked(cfg, True, cotangent), args)
    (got, _), got_grads = _on_mesh(
        mesh_name,
        lambda sharder: _mean_and_tokens(cfg, sharder, True, cotangent),
        params, specs, *args[1:])
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    _assert_trees_close(got_grads, want_grads, rtol=2e-6,
                        atol=1e-6 * cotangent)
    (_, _), bare = _value_and_grads(_unchunked(cfg, True), args)
    assert not np.allclose(np.asarray(got_grads[1]), np.asarray(bare[1]),
                           rtol=1e-2)


def test_fp16_with_a_loss_scale_underflows_nothing_more():
    """float16 hidden state and head, loss scale 2**16, a mask whose sum
    (61) is no power of two. The gradients are formed while the scale is
    still unknown, at the magnitude of the mask (`d logits` in [-1, 1]),
    and the scale over the mask's sum multiplies them in float32
    afterwards. Against the arithmetic of the loop this one replaced,
    which knew the scale and rounded `d logits` to float16 at scale /
    sum(mask) times that magnitude: no gradient element is zero that was
    not, and they agree to float16's rounding (these 61 tokens cannot
    overflow: the next test's 32,768 can)."""
    cfg, params, specs, hidden, labels, _ = _head_case(
        False, dtype=jnp.float16)
    cfg = dataclasses.replace(cfg, ce_chunk_size=S // 4).validate()
    mask = np.ones((B, S), np.float32)
    mask.reshape(-1)[np.random.default_rng(3).choice(B * S, 67, False)] = 0
    mask = jnp.asarray(mask)
    assert float(mask.sum()) == 61.0
    scale = 2.0 ** 16
    (_, _), (got_w, got_h) = _value_and_grads(
        _mean_and_tokens(cfg, _identity, True, scale),
        (params, hidden, labels, mask))

    def before(params, hidden, labels, mask):
        w = params["lm_head"]["w"]
        logits = jnp.einsum("bsh,hv->bsv", hidden, w).astype(jnp.float32)
        p = jax.nn.softmax(logits, axis=-1)
        g = scale * mask / jnp.sum(mask)
        dlogits = ((p - jax.nn.one_hot(labels, V)) * g[..., None]
                   ).astype(jnp.float16)
        return (jnp.einsum("bsh,bsv->hv", hidden, dlogits),
                jnp.einsum("bsv,hv->bsh", dlogits, w))

    want_w, want_h = jax.jit(before)(params, hidden, labels, mask)
    for got, want in ((got_w["lm_head"]["w"], want_w), (got_h, want_h)):
        assert got.dtype == want.dtype == jnp.float16
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.isfinite(got).all()
        assert not ((got == 0) & (want != 0)).any()
        np.testing.assert_allclose(got, want, rtol=2.0 ** -8,
                                   atol=2.0 ** -9 * np.abs(want).max())


@pytest.mark.parametrize("mesh_name", ["nomesh", "dp4", "tp2dp2"])
@pytest.mark.parametrize("scale", [1.0, 2.0 ** 8, 2.0 ** 16],
                         ids=["scale1", "scale2p8", "scale2p16"])
def test_fp16_head_gradient_survives_32k_tokens(scale, mesh_name):
    """float16 at a realistic token count: 8 x 4,096 tokens, one hidden
    channel at 12 and one label on a fifth of the tokens. The head
    gradient's sum over the tokens at the magnitude of the mask is then
    about 12 x 0.2 x 32,768 = 79,000 in that channel and column, past
    float16's 65,504 whatever the loss scale is, while the gradient itself
    (times scale / 32,768) is 2.4 at scale 1 and 610 at 2**8: the sum,
    the residual and the sum over the replicas stay float32 until the
    cotangent has multiplied them. Every gradient element is finite where
    float16 autodiff of the unchunked loss is (everywhere at scale 1 and
    2**8; at 2**16 the true gradient is out of range and the dynamic
    scaler backs off, as it always did), and equals the float32 gradient
    of the same inputs to float16's rounding."""
    b, s = 8, 4096
    cfg, params, specs, hidden, labels, mask = _head_case(
        False, dtype=jnp.float16, B=b, S=s)
    cfg = dataclasses.replace(cfg, ce_chunk_size=512).validate()
    hidden = hidden.at[..., 0].set(12.0)
    frequent = np.random.default_rng(5).random((b, s)) < 0.2
    labels = jnp.where(frequent, 0, labels)
    args = (params, hidden, labels, mask)
    (_, _), want = _value_and_grads(_unchunked(cfg, False, scale), args)
    (_, _), exact = _value_and_grads(
        _unchunked(cfg, False, scale),
        jax.tree.map(lambda x: x.astype(jnp.float32)
                     if x.dtype == jnp.float16 else x, args))
    (_, _), got = _on_mesh(
        mesh_name,
        lambda sharder: _mean_and_tokens(cfg, sharder, False, scale),
        params, specs, *args[1:])
    sum_at_mask_magnitude = (np.abs(np.asarray(exact[0]["lm_head"]["w"]))
                             .max() * b * s / scale)
    assert sum_at_mask_magnitude > np.finfo(np.float16).max
    for g, w, e in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                       jax.tree.leaves(exact), strict=True):
        assert g.dtype == w.dtype == jnp.float16
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        e = np.asarray(e)
        if scale < 2.0 ** 16:
            assert np.isfinite(w).all()
        assert np.isfinite(g[np.isfinite(w)]).all()
        inside = np.abs(e) < 2.0 ** 15
        np.testing.assert_allclose(
            g[inside], e[inside], rtol=2.0 ** -8,
            atol=2.0 ** -8 * min(np.abs(e).max(), 2.0 ** 15))


def test_context_parallel_outside_sequence_parallel():
    """TP 2 x CP 2: the sequence is cut over `context` first and each
    context shard's rows over `tensor`; the chunk loop runs inside a
    context shard."""
    cfg, params, specs, hidden, labels, mask = _head_case(tied=False)
    cfg = dataclasses.replace(cfg, ce_chunk_size=S // 4).validate()
    args = (params, hidden, labels, mask)
    (want, want_tok), want_grads = _value_and_grads(
        _mean_and_tokens(cfg, _identity, True), args)
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
    The gradients do not agree that closely, and should not: at this
    size both sides add each chunk's head gradient into a bf16 sum (8
    significant bits: up to 2**-9 relative a chunk, four chunks), but a
    rank sums its own half of the batch and the halves meet in one more
    bf16 addition across `data`; `d hidden` is a bf16 sum of two ranks'
    bf16 partial products where the unsharded one rounds the whole
    product once. Hence 2**-6 of the largest entry."""
    cfg, params, specs, hidden, labels, mask = _head_case(
        False, dtype=jnp.bfloat16)
    cfg = dataclasses.replace(cfg, ce_chunk_size=S // 4).validate()
    args = (params, hidden, labels, mask)
    (want, want_tok), want_grads = _value_and_grads(
        _mean_and_tokens(cfg, _identity, True), args)
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


@pytest.mark.parametrize("mesh_name, dtype, carried", [
    ("nomesh", jnp.bfloat16, "bf16"), ("dp4", jnp.bfloat16, "bf16"),
    ("tp2dp2", jnp.bfloat16, "f32"), ("tp4dp1", jnp.bfloat16, "f32"),
    ("nomesh", jnp.float16, "f32"), ("dp4", jnp.float16, "f32")])
def test_which_dtype_carries_the_head_gradients_sum(mesh_name, dtype,
                                                    carried):
    """The head gradient's sum over the chunks is carried in float32 and
    rounded once under tensor parallelism, and in the head's dtype
    without it: in both the arithmetic of the loops that this one
    replaced, none lowered (PERF.md section 6, PR 31, has the price of
    float32 on one chip). A float16 head's sum is float32 on every mesh
    and leaves the forward rule so, for the cotangent to multiply it
    first. The loop's carry and the rule's residual say which."""
    cfg, params, _, hidden, labels, mask = _head_case(False, dtype=dtype)
    cfg = dataclasses.replace(cfg, ce_chunk_size=S // 4).validate()
    tp = MESHES[mesh_name]
    sharder = _identity if tp is None else ActivationSharder(tp > 1)

    def program():
        return str(jax.make_jaxpr(jax.grad(
            lambda p: _mean_and_tokens(cfg, sharder, True)(
                p, hidden, labels, mask)[0]))(params))

    if tp is None:
        program = program()
    else:
        with jax.sharding.set_mesh(_mesh(tp).mesh):
            program = program()
    low = {jnp.bfloat16: "bf16", jnp.float16: "f16"}[dtype]
    columns = V // (tp or 1)
    carries = {"f32": f"f32[{H},{columns}] = add",
               low: f"{low}[{H},{columns}] = add"}
    assert carries.pop(carried) in program
    assert next(iter(carries.values())) not in program
    # the float16 head's gradient is rounded after the multiplication by
    # the cotangent, and nowhere before it
    if dtype == jnp.float16:
        rounded = f"f16[{H},{V}] = convert_element_type"
        assert program.count(rounded) == 1
        assert program.index(rounded) > program.rindex(f"f32[{H},{V}] = mul")


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

    plan = ce.head_loss_plan(1, len(edges), V, len(edges), False)._replace(
        tp=tp)

    def picked(y, z):
        mine = ce._label_onehot(y, z.shape[-1], plan)
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


def test_which_plan_the_mesh_gives():
    """No mesh, or a trace point inside somebody else's shard_map (the
    pipeline schedule): a plan without axes, the loop runs on the arrays
    as they are. Under a mesh the plan names every axis, whatever the size
    of "tensor"; rows are gathered only where sequence parallelism cut
    them. Shapes that do not divide fall back, out loud."""
    direct = ce.head_loss_plan(B, S, V, 8, True)
    assert direct.axes == () and direct.tp == 1 and not direct.gather
    assert direct.rows == 8 and not direct.everyone
    rt = build_mesh(ParallelConfig(), devices=jax.devices()[:4])
    with jax.sharding.set_mesh(rt.mesh):
        dp = ce.head_loss_plan(B, S, V, 8, True)
    assert set(dp.axes) == set(rt.mesh.axis_names)
    assert dp.tp == 1 and not dp.gather and dp.rows == 8
    assert dp.everyone == ("data",)
    rt = _mesh(2)
    seen = []

    def inside(x):
        seen.append(ce.head_loss_plan(B, S, V, 8, True))
        return x

    with jax.sharding.set_mesh(rt.mesh):
        tp = ce.head_loss_plan(B, S, V, 8, True)
        assert tp.tp == 2 and tp.gather and tp.rows == 4
        assert set(tp.everyone) == {"data", "tensor"}
        assert not ce.head_loss_plan(B, S, V, 8, False).gather
        jax.jit(jax.shard_map(inside, in_specs=P("data"), out_specs=P("data"),
                              axis_names={"data"}, check_vma=False)
                )(jnp.zeros((4,)))
        with pytest.warns(UserWarning, match="does not divide"):
            assert ce.head_loss_plan(B, S, V + 1, 8, True) == direct
    assert seen == [direct]
