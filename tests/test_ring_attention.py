"""Ring attention vs single-device attention (no reference counterpart —
the reference has no context parallelism; gate is exact-math equivalence)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ParallelConfig
from megatron_tpu.ops.attention import attention
from megatron_tpu.ops.ring_attention import ring_attention_sharded
from megatron_tpu.parallel.mesh import build_mesh

RNG = np.random.default_rng(42)


def _qkv(b=2, s=32, hq=4, hkv=2, d=16):
    q = RNG.standard_normal((b, s, hq, d)).astype(np.float32)
    k = RNG.standard_normal((b, s, hkv, d)).astype(np.float32)
    v = RNG.standard_normal((b, s, hkv, d)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("cp", [2, 4])
@pytest.mark.parametrize("mask_type,window", [
    ("causal", None), ("causal", 8), ("causal", 3), ("causal", 40),
    ("bidirectional", None),
])
def test_ring_matches_dense(cp, mask_type, window):
    rt = build_mesh(ParallelConfig(context_parallel=cp))
    q, k, v = _qkv()
    want = attention(q, k, v, mask_type=mask_type, sliding_window=window)
    with jax.sharding.set_mesh(rt.mesh):
        got = jax.jit(lambda q, k, v: ring_attention_sharded(
            q, k, v, rt.mesh, mask_type=mask_type, sliding_window=window))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ring_grads_match_dense():
    rt = build_mesh(ParallelConfig(context_parallel=4))
    q, k, v = _qkv(b=1, s=16, hq=2, hkv=1, d=8)

    def dense_loss(q, k, v):
        return jnp.sum(jnp.square(attention(q, k, v)))

    def ring_loss(q, k, v):
        return jnp.sum(jnp.square(ring_attention_sharded(q, k, v, rt.mesh)))

    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    with jax.sharding.set_mesh(rt.mesh):
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.slow  # 9s measured cacheless (PR 4 tier-1 re-budget);
# the other three ring-grads parity cases stay tier-1
def test_ring_zigzag_window_grads_match_dense():
    """Sliding-window causal now rides the zig-zag balanced path — its
    stripe-skip predicates must be gradient-exact too."""
    rt = build_mesh(ParallelConfig(context_parallel=4))
    q, k, v = _qkv(b=1, s=32, hq=2, hkv=1, d=8)

    def dense_loss(q, k, v):
        return jnp.sum(jnp.square(attention(q, k, v, sliding_window=6)))

    def ring_loss(q, k, v):
        return jnp.sum(jnp.square(ring_attention_sharded(
            q, k, v, rt.mesh, sliding_window=6)))

    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    with jax.sharding.set_mesh(rt.mesh):
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("cp", [2, 4])
def test_ring_flash_inner_matches_dense(cp):
    """The flash-stripe zig-zag path (forced through the pallas
    interpreter on CPU) is value-exact against dense attention — no
    per-hop dense score buffer, same math (VERDICT r3 next-round #5)."""
    rt = build_mesh(ParallelConfig(context_parallel=cp))
    q, k, v = _qkv()
    want = attention(q, k, v, mask_type="causal")
    with jax.sharding.set_mesh(rt.mesh):
        got = jax.jit(lambda q, k, v: ring_attention_sharded(
            q, k, v, rt.mesh, inner_impl="flash"))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ring_flash_inner_grads_match_dense():
    """Whole-ring custom_vjp: per-stripe kernel backwards with the global
    lse must sum to the exact dense gradient, dk/dv rotating home."""
    rt = build_mesh(ParallelConfig(context_parallel=4))
    q, k, v = _qkv(b=1, s=32, hq=2, hkv=1, d=8)

    def dense_loss(q, k, v):
        return jnp.sum(jnp.square(attention(q, k, v)))

    def ring_loss(q, k, v):
        return jnp.sum(jnp.square(ring_attention_sharded(
            q, k, v, rt.mesh, inner_impl="flash")))

    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    with jax.sharding.set_mesh(rt.mesh):
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_ring_flash_inner_gqa_grads():
    """GQA: kernel runs per query head; dk/dv group-sum back to kv heads."""
    rt = build_mesh(ParallelConfig(context_parallel=2))
    q, k, v = _qkv(b=1, s=16, hq=4, hkv=2, d=8)

    def dense_loss(q, k, v):
        return jnp.sum(jnp.square(attention(q, k, v)))

    def ring_loss(q, k, v):
        return jnp.sum(jnp.square(ring_attention_sharded(
            q, k, v, rt.mesh, inner_impl="flash")))

    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    with jax.sharding.set_mesh(rt.mesh):
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("cp,window", [(2, 8), (4, 6), (2, 3), (4, 40)])
def test_ring_flash_inner_window_matches_dense(cp, window):
    """Sliding windows on the kernel path: the stripe delta + static
    window band must reproduce dense windowed attention exactly."""
    rt = build_mesh(ParallelConfig(context_parallel=cp))
    q, k, v = _qkv()
    want = attention(q, k, v, mask_type="causal", sliding_window=window)
    with jax.sharding.set_mesh(rt.mesh):
        got = jax.jit(lambda q, k, v: ring_attention_sharded(
            q, k, v, rt.mesh, inner_impl="flash",
            sliding_window=window))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_ring_flash_inner_window_grads_match_dense():
    rt = build_mesh(ParallelConfig(context_parallel=4))
    q, k, v = _qkv(b=1, s=32, hq=2, hkv=1, d=8)

    def dense_loss(q, k, v):
        return jnp.sum(jnp.square(attention(q, k, v, sliding_window=6)))

    def ring_loss(q, k, v):
        return jnp.sum(jnp.square(ring_attention_sharded(
            q, k, v, rt.mesh, inner_impl="flash", sliding_window=6)))

    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    with jax.sharding.set_mesh(rt.mesh):
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


@pytest.mark.parametrize("mask_type", ["bidirectional", "causal"])
def test_contiguous_ring_flash_matches_dense(mask_type):
    """The contiguous ring's flash inner (bidirectional CP, and causal
    shapes zig-zag can't stripe) — values AND grads vs dense."""
    rt = build_mesh(ParallelConfig(context_parallel=4))
    q, k, v = _qkv(b=1, s=32, hq=4, hkv=2, d=8)
    want = attention(q, k, v, mask_type=mask_type)

    def make(impl):
        # mask_type='causal' with S % (2*cp) == 0 would take the zig-zag
        # branch; drive the contiguous one via a non-zigzag length
        return lambda q, k, v: ring_attention_sharded(
            q, k, v, rt.mesh, mask_type=mask_type, inner_impl=impl)

    if mask_type == "causal":
        # 36 = 4*9: divisible by cp, not by 2*cp — contiguous branch
        q, k, v = _qkv(b=1, s=36, hq=4, hkv=2, d=8)
        want = attention(q, k, v, mask_type=mask_type)
    with jax.sharding.set_mesh(rt.mesh):
        got = jax.jit(make("flash"))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)

    def dense_loss(q, k, v):
        return jnp.sum(jnp.square(attention(q, k, v, mask_type=mask_type)))

    def ring_loss(q, k, v):
        return jnp.sum(jnp.square(make("flash")(q, k, v)))

    g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    with jax.sharding.set_mesh(rt.mesh):
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_cp_chunked_prefill_warns_decode_does_not():
    """Single-token decode against a longer cache is the DESIGNED CP
    serving path (flash-decoding by the partitioner) — silent; a
    multi-token pass into cached context (chunked prefill) is the one
    genuine fallback and stays loud."""
    import warnings as w

    k = jnp.asarray(RNG.standard_normal((1, 16, 2, 8)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((1, 16, 2, 8)).astype(np.float32))

    q1 = jnp.asarray(RNG.standard_normal((1, 1, 2, 8)).astype(np.float32))
    with w.catch_warnings(record=True) as caught:
        w.simplefilter("always")
        attention(q1, k, v, impl="ring", q_offset=15)
    assert not any("chunked prefill" in str(c.message) for c in caught)

    q4 = jnp.asarray(RNG.standard_normal((1, 4, 2, 8)).astype(np.float32))
    with w.catch_warnings(record=True) as caught:
        w.simplefilter("always")
        attention(q4, k, v, impl="ring", q_offset=12)
    assert any("chunked prefill" in str(c.message) for c in caught)


def test_model_forward_with_ring_impl():
    """Full model with attention_impl='ring' on a cp=2 mesh matches the
    xla-impl forward."""
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params
    from megatron_tpu.models.language_model import lm_forward

    cfg_xla = presets.tiny(vocab_size=64, seq_length=32)
    cfg_ring = presets.tiny(vocab_size=64, seq_length=32, attention_impl="ring")
    params = init_params(cfg_xla, jax.random.PRNGKey(0))
    tokens = jnp.asarray(RNG.integers(0, 64, (2, 32)), jnp.int32)
    want = lm_forward(cfg_xla, params, tokens)
    rt = build_mesh(ParallelConfig(context_parallel=2))
    with jax.sharding.set_mesh(rt.mesh):
        got = jax.jit(lambda p, t: lm_forward(cfg_ring, p, t))(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_zigzag_fallback_when_seq_not_divisible():
    """S % 2cp != 0 falls back to the contiguous path, still exact."""
    rt = build_mesh(ParallelConfig(context_parallel=4))
    rng = np.random.default_rng(3)
    S = 20  # 20 % 8 != 0, but 20 % 4 == 0
    q = jnp.asarray(rng.standard_normal((1, S, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, S, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, S, 2, 16)), jnp.float32)
    want = attention(q, k, v)
    with jax.sharding.set_mesh(rt.mesh):
        got = jax.jit(lambda q, k, v: ring_attention_sharded(
            q, k, v, rt.mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_template_stripe_pair_matches_dense():
    """flash_template.stripe_fwd / stripe_bwd — the two functions the ring
    schedules call — against the dense path on one aligned stripe pair
    (delta 0 is plain causal): normalized output, per-row lse [B, H, c]
    and, given that lse, the exact dense gradients."""
    from megatron_tpu.ops.pallas import flash_template as ft

    rng = np.random.default_rng(5)
    q, k, v, do = (jnp.asarray(rng.standard_normal((1, 2, 16, 8)),
                               jnp.float32) for _ in range(4))
    scale = 8 ** -0.5

    def dense(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((16, 16), bool)), s, -jnp.inf)
        return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v),
                jax.nn.logsumexp(s, -1))

    (want_o, want_lse), vjp = jax.vjp(dense, q, k, v)
    o, lse = ft.stripe_fwd(q, k, v, 0, None, scale, 8)
    assert o.dtype == jnp.float32 and lse.shape == (1, 2, 16)
    np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=1e-5, atol=1e-5)
    got = ft.stripe_bwd(q, k, v, o, lse, do, 0, None, scale, 8)
    for g, w in zip(got, vjp((do, jnp.zeros_like(want_lse)))):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def test_ring_flash_dispatches_into_template_kernel(monkeypatch):
    """The ring stripes' inner flash forward really lands in the
    flash_template kernel (under MEGATRON_TPU_FLASH_INTERPRET=1 on CPU)
    — count calls through the module global the stripe resolves at call
    time."""
    from megatron_tpu.ops.pallas import flash_template as fa

    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    calls = {"n": 0}
    real_fwd = fa._fwd

    def counting_fwd(*args, **kwargs):
        calls["n"] += 1
        return real_fwd(*args, **kwargs)

    monkeypatch.setattr(fa, "_fwd", counting_fwd)
    rt = build_mesh(ParallelConfig(context_parallel=2))
    q, k, v = _qkv()
    want = attention(q, k, v)
    with jax.sharding.set_mesh(rt.mesh):
        # fresh jit instance: a cached trace would bypass the wrapper
        got = jax.jit(lambda q, k, v: ring_attention_sharded(
            q, k, v, rt.mesh, inner_impl="flash"))(q, k, v)
    assert calls["n"] > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
