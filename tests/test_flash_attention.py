"""Pallas flash-attention kernel vs the XLA einsum path (interpret mode on
the CPU suite; the same kernels compile for a described v5e in
tests/test_chip_compile.py and run on the chip in every benchmark cell)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.ops.attention import attention
from megatron_tpu.ops.pallas.flash_template import flash_mha, supported

RNG = np.random.default_rng(7)


def _qkv(b=1, s=256, hq=4, hkv=2, d=64):
    q = jnp.asarray(RNG.standard_normal((b, s, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, s, hkv, d)), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("window", [None, 64])
def test_flash_forward_matches_xla(window):
    q, k, v = _qkv()
    got = flash_mha(q, k, v, sliding_window=window,
                          block_q=128, block_k=128)
    want = attention(q, k, v, sliding_window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_mha_no_gqa():
    q, k, v = _qkv(hq=4, hkv=4)
    got = flash_mha(q, k, v, block_q=128, block_k=128)
    want = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_grads_match_xla():
    q, k, v = _qkv(s=256, hq=2, hkv=1, d=64)

    def f_flash(q, k, v):
        return jnp.sum(jnp.square(flash_mha(q, k, v, block_q=128,
                                                  block_k=128)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.square(attention(q, k, v)))

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gf, gr):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale,
                                   rtol=2e-2, atol=2e-3, err_msg=f"d{name}")


def test_supported_predicate_and_rejection():
    assert supported(512, 512, 128, 128)
    assert not supported(200, 200, 128, 128)
    assert not supported(512, 256, 128, 128)
    q, k, v = _qkv(s=200)
    with pytest.raises(ValueError, match="flash kernel"):
        flash_mha(q[:, :200], k[:, :200], v[:, :200],
                        block_q=128, block_k=128)


def test_model_dispatch_falls_back_cleanly():
    """attention(impl='pallas') uses the kernel when shapes allow and the
    XLA path otherwise (decode steps)."""
    q, k, v = _qkv(s=256)
    out = attention(q, k, v, impl="pallas")
    want = attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    # decode shape (q_len != kv_len) silently uses XLA
    out2 = attention(q[:, :1], k, v, impl="pallas", q_offset=255)
    assert out2.shape == (1, 1, 4, 64)


# ---------------------------------------------------------------------------
# bf16 inputs: the operands reach the MXU as given, statistics and
# accumulators stay float32 (flash_template's precision contract)
# ---------------------------------------------------------------------------


def _bf16_case(window, hkv):
    """bf16 q/k/v/weights and, on the SAME bf16 values, the float32 XLA
    reference's output and gradients."""
    rng = np.random.default_rng(11)
    q, k, v, w = (jnp.asarray(rng.standard_normal((1, 256, 4, 64)),
                              jnp.bfloat16) for _ in range(4))
    k, v = k[:, :, :hkv], v[:, :, :hkv]
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

    def loss(fn, q, k, v):
        return jnp.sum(f32(fn(q, k, v)) * f32(w))

    def ref(q, k, v):
        return attention(q, k, v, sliding_window=window)

    def flash(q, k, v):
        return flash_mha(q, k, v, sliding_window=window,
                               block_q=128, block_k=128)

    want_o = ref(f32(q), f32(k), f32(v))
    want_g = jax.grad(functools.partial(loss, ref), argnums=(0, 1, 2))(
        f32(q), f32(k), f32(v))
    return (q, k, v), flash, functools.partial(loss, flash), want_o, want_g


def _share_of_range(got, want):
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(np.asarray(got, np.float32) - want))
                 / np.max(np.abs(want)))


@pytest.mark.parametrize("hkv", [2, 4], ids=["gqa", "mha"])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_bf16_forward_matches_float32_reference(window, hkv):
    """Measured 2.6e-3 of range at the cell's shapes (one bf16 rounding
    of the output is 2e-3); 1e-2 leaves headroom and still catches a
    rounded statistic or a bf16 accumulator."""
    args, flash, _, want_o, _ = _bf16_case(window, hkv)
    got = flash(*args)
    assert got.dtype == jnp.bfloat16
    assert _share_of_range(got, want_o) <= 1e-2


@pytest.mark.parametrize("hkv", [2, 4], ids=["gqa", "mha"])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_bf16_grads_match_float32_reference(window, hkv):
    """dq/dk/dv measured <= 4.0e-3 of their range; 1.5e-2 is the gate."""
    args, _, loss, _, want_g = _bf16_case(window, hkv)
    got_g = jax.grad(loss, argnums=(0, 1, 2))(*args)
    for name, got, want in zip("qkv", got_g, want_g):
        assert got.dtype == jnp.bfloat16
        assert _share_of_range(got, want) <= 1.5e-2, f"d{name}"


def _kernel_dots(jaxpr, kernel=None):
    """(kernel name, operand dtypes, result dtype) of every dot_general
    inside a pallas_call, through nested jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield from _kernel_dots(eqn.params["jaxpr"], eqn.params["name"])
            continue
        if kernel and eqn.primitive.name == "dot_general":
            yield (kernel, tuple(v.aval.dtype for v in eqn.invars),
                   eqn.outvars[0].aval.dtype)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_dots(sub, kernel)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kernel_matmuls_take_operands_as_given(dtype):
    """Every matmul of the three training kernels multiplies the dtype
    the caller passed and accumulates in float32: bf16 tensors reach the
    MXU as bf16 (no cast to float32 comes back), and float32 tensors
    still multiply in float32 — which is what keeps the float32 numerics
    tests above meaning what they say."""
    from megatron_tpu.ops.pallas.flash_template import flash_mha

    q, k, v = (x.astype(dtype) for x in _qkv(s=256, hq=2, hkv=1, d=64))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_mha(q, k, v, block_q=128, block_k=128)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    dots = list(_kernel_dots(jaxpr.jaxpr))
    per_kernel = {name: sum(1 for d in dots if d[0] == name)
                  for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}
    assert per_kernel == {"flash_fwd": 2, "flash_bwd_dq": 3,
                          "flash_bwd_dkv": 4}, dots
    for kernel, operands, result in dots:
        assert operands == (dtype, dtype), (kernel, operands)
        assert result == jnp.float32, (kernel, result)
