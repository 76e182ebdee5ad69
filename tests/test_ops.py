"""Numerics tests for core ops vs numpy closed forms
(counterpart of reference tests/test_activations.py and
megatron/mpu/tests/test_cross_entropy.py)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.ops.activations import apply_activation
from megatron_tpu.ops.attention import attention
from megatron_tpu.ops.cross_entropy import cross_entropy_loss
from megatron_tpu.ops.normalization import layernorm, rmsnorm
from megatron_tpu.config import AttentionKind
from megatron_tpu.ops.rotary import (
    apply_rotary_emb, precompute_rope, rope_table,
)

RNG = np.random.default_rng(0)


def test_rmsnorm():
    x = RNG.standard_normal((2, 5, 16)).astype(np.float32)
    w = RNG.standard_normal(16).astype(np.float32)
    got = rmsnorm(jnp.asarray(x), jnp.asarray(w), eps=1e-5)
    want = x / np.sqrt((x**2).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_layernorm():
    x = RNG.standard_normal((2, 5, 16)).astype(np.float32)
    w = RNG.standard_normal(16).astype(np.float32)
    b = RNG.standard_normal(16).astype(np.float32)
    got = layernorm(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), eps=1e-5)
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    want = (x - mu) / np.sqrt(var + 1e-5) * w + b
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("name", ["swiglu", "geglu", "reglu", "liglu"])
def test_glu_closed_form(name):
    """GLU = act(gate) * up on a halved last dim
    (ref tests/test_activations.py checks the same closed forms)."""
    x = RNG.standard_normal((3, 8)).astype(np.float32)
    gate, up = x[:, :4], x[:, 4:]
    got = np.asarray(apply_activation(name, jnp.asarray(x)))
    if name == "geglu":
        import math
        erf = np.vectorize(math.erf)
        want = gate * 0.5 * (1 + erf(gate / np.sqrt(2))) * up
    else:
        acts = {
            "swiglu": lambda g: g * (1 / (1 + np.exp(-g))),
            "reglu": lambda g: np.maximum(g, 0),
            "liglu": lambda g: g,
        }
        want = acts[name](gate) * up
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_rope_rotation_preserves_norm():
    cos, sin = precompute_rope(8, 32)
    q = jnp.asarray(RNG.standard_normal((1, 16, 2, 8)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((1, 16, 2, 8)).astype(np.float32))
    qr, kr = apply_rotary_emb(q, k, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(qr), axis=-1),
        np.linalg.norm(np.asarray(q), axis=-1), rtol=1e-5)
    # position 0 is the identity rotation
    np.testing.assert_allclose(np.asarray(qr)[:, 0], np.asarray(q)[:, 0], rtol=1e-6)


def test_rope_relative_property():
    """Scores depend only on relative distance: rotating q,k by equal offset
    leaves q . k unchanged."""
    cos, sin = precompute_rope(8, 64)
    q = jnp.asarray(RNG.standard_normal((1, 1, 1, 8)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((1, 1, 1, 8)).astype(np.float32))
    pos_a = jnp.asarray([[5]])
    pos_b = jnp.asarray([[2]])
    qa, ka = apply_rotary_emb(q, k, cos, sin, pos_a), apply_rotary_emb(q, k, cos, sin, pos_b)
    # dot(q@p, k@p+d) invariant to p
    q5, _ = apply_rotary_emb(q, k, cos, sin, jnp.asarray([[5]]))
    _, k8 = apply_rotary_emb(q, k, cos, sin, jnp.asarray([[8]]))
    q15, _ = apply_rotary_emb(q, k, cos, sin, jnp.asarray([[15]]))
    _, k18 = apply_rotary_emb(q, k, cos, sin, jnp.asarray([[18]]))
    d1 = float(jnp.sum(q5 * k8))
    d2 = float(jnp.sum(q15 * k18))
    assert abs(d1 - d2) < 1e-4


def test_rope_scaling_interpolates():
    cos1, _ = precompute_rope(8, 64, scaling_factor=1.0)
    cos2, _ = precompute_rope(8, 64, scaling_factor=2.0)
    # position 2p at scale 2 == position p at scale 1
    np.testing.assert_allclose(np.asarray(cos2)[10], np.asarray(cos1)[5], atol=1e-6)


def _plain_rotary(q, k, cos, sin, positions=None):
    """The formula as it is written down: the head's halves sliced,
    one negated, concatenated, in float32. The reference that
    apply_rotary_emb, which never splits the head, is held to."""
    if positions is None:
        cos_g, sin_g = cos[None, :q.shape[1]], sin[None, :q.shape[1]]
    else:
        cos_g, sin_g = cos[positions], sin[positions]
    cos_g = cos_g[:, :, None, :].astype(jnp.float32)
    sin_g = sin_g[:, :, None, :].astype(jnp.float32)

    def rot(x):
        xf = x.astype(jnp.float32)
        half = x.shape[-1] // 2
        turned = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
        return (xf * cos_g + turned * sin_g).astype(x.dtype)

    return rot(q), rot(k)


def _rope_tables(kind_name, head_dim, max_positions):
    if kind_name == "plain":
        return precompute_rope(head_dim, max_positions)
    if kind_name == "yarn":
        return rope_table(
            AttentionKind(rope_type="yarn", rope_theta=500000.0,
                          rope_scaling_factor=16.0,
                          yarn_original_max_positions=16).validate(),
            head_dim, max_positions)
    # no table of the program's: halves that differ, so a backward that
    # forgot to swap the sine's halves is seen
    cos, sin = precompute_rope(head_dim, max_positions)
    return cos, sin * jnp.linspace(0.5, 1.5, head_dim)


@pytest.mark.parametrize("table", ["plain", "yarn", "unequal_halves"])
@pytest.mark.parametrize("with_positions", [False, True],
                         ids=["in_order", "positions"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16, jnp.float32],
                         ids=lambda d: jnp.dtype(d).name)
@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_rotary_equals_the_plain_formula(table, with_positions, dtype, jit):
    """apply_rotary_emb (the half turn as a product with a signed
    permutation, a hand-written backward) against slice-negate-concatenate
    and its autodiff: the same bits for bf16 and float16 operands, forward
    and both cotangents, GQA shapes, positions in order and not (op by op;
    as one compiled function each, to a unit of the dtype's last place);
    float32 operands to 1e-6."""
    b, s, hq, hkv, d, pmax = 2, 12, 4, 2, 16, 64
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(keys[0], (b, s, hq, d), dtype)
    k = jax.random.normal(keys[1], (b, s, hkv, d), dtype)
    cos, sin = _rope_tables(table, d, pmax)
    positions = (jax.random.randint(keys[2], (b, s), 0, pmax)
                 if with_positions else None)
    if with_positions:
        assert not bool(jnp.all(jnp.diff(positions, axis=1) > 0))
    ours = lambda q, k: apply_rotary_emb(q, k, cos, sin, positions)
    plain = lambda q, k: _plain_rotary(q, k, cos, sin, positions)
    if jit:
        ours, plain = jax.jit(ours), jax.jit(plain)
    got, got_vjp = jax.vjp(ours, q, k)
    want, want_vjp = jax.vjp(plain, q, k)
    cts = (jax.random.normal(keys[3], q.shape, dtype),
           jax.random.normal(keys[4], k.shape, dtype))
    for a, w in zip(got + got_vjp(cts), want + want_vjp(cts)):
        assert a.dtype == w.dtype and a.shape == w.shape
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, w, rtol=0, atol=1e-6)
        elif not jit:
            assert jnp.array_equal(a, w)
        else:
            # one compiled function each: the CPU's compiler may contract
            # the multiply-add of one form and not of the other, which
            # moves the float32 sum by a unit and so, rarely, the rounding
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(w, np.float32),
                rtol=float(jnp.finfo(dtype).eps), atol=0)


def test_rotary_never_splits_the_head():
    """No concatenate in the traced function or its backward, and no
    slice that cuts the head dimension (the tables' rows are sliced)."""
    d = 16
    q = jnp.zeros((2, 8, 4, d), jnp.bfloat16)
    k = jnp.zeros((2, 8, 2, d), jnp.bfloat16)
    cos, sin = precompute_rope(d, 32)

    def loss(q, k):
        qr, kr = apply_rotary_emb(q, k, cos, sin)
        return (qr.astype(jnp.float32).sum() + kr.astype(jnp.float32).sum())

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(q, k))
    assert "dot_general" in text and "concatenate" not in text
    sliced = re.findall(r":\w+\[([\d,]+)\] = (?:dynamic_)?slice\[", text)
    assert all(dims.endswith(f",{d}") for dims in sliced), sliced


def _ref_attention(q, k, v, causal=True, window=None):
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    k = np.repeat(k, g, axis=2)
    v = np.repeat(v, g, axis=2)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    qpos = np.arange(sq)[:, None]
    kpos = np.arange(skv)[None, :]
    mask = np.ones((sq, skv), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = np.where(mask, scores, -1e30)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", p, v)


def test_attention_gqa_causal():
    q = RNG.standard_normal((2, 8, 4, 16)).astype(np.float32)
    k = RNG.standard_normal((2, 8, 2, 16)).astype(np.float32)
    v = RNG.standard_normal((2, 8, 2, 16)).astype(np.float32)
    got = attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    want = _ref_attention(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_attention_sliding_window():
    q = RNG.standard_normal((1, 12, 2, 8)).astype(np.float32)
    k = RNG.standard_normal((1, 12, 2, 8)).astype(np.float32)
    v = RNG.standard_normal((1, 12, 2, 8)).astype(np.float32)
    got = attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), sliding_window=4)
    want = _ref_attention(q, k, v, window=4)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_cross_entropy_matches_numpy():
    logits = RNG.standard_normal((2, 6, 32)).astype(np.float32)
    targets = RNG.integers(0, 32, (2, 6))
    mean, per_tok = cross_entropy_loss(jnp.asarray(logits), jnp.asarray(targets))
    lse = np.log(np.exp(logits).sum(-1))
    want = lse - np.take_along_axis(logits, targets[..., None], -1)[..., 0]
    np.testing.assert_allclose(per_tok, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(mean, want.mean(), rtol=1e-5)


def test_cross_entropy_label_smoothing_and_mask():
    logits = RNG.standard_normal((1, 4, 16)).astype(np.float32)
    targets = RNG.integers(0, 16, (1, 4))
    mask = np.array([[1, 1, 0, 1]], np.float32)
    eps = 0.1
    mean, per_tok = cross_entropy_loss(
        jnp.asarray(logits), jnp.asarray(targets),
        loss_mask=jnp.asarray(mask), label_smoothing=eps)
    lse = np.log(np.exp(logits).sum(-1))
    tl = np.take_along_axis(logits, targets[..., None], -1)[..., 0]
    want = lse - (1 - eps) * tl - eps * logits.mean(-1)
    np.testing.assert_allclose(per_tok, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(mean, (want * mask).sum() / mask.sum(), rtol=1e-5)
