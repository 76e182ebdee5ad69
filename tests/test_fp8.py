"""fp8 training GEMMs (the TransformerEngine parity row, ops/fp8.py):
quantization numerics, gradient structure, end-to-end training vs bf16,
and CLI wiring. On CPU XLA upcasts the f8 operands, so results are exactly
the quantize->matmul->rescale reference — which is what these tests pin;
real-f8-MXU behavior is what tools/fp8_probe.py reports on a chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.ops.fp8 import E4M3, E5M2, fp8_matmul


def _ref_q(t, fmax):
    s = fmax / max(float(jnp.max(jnp.abs(t))), 1e-12)
    return t.astype(jnp.float32) * s, s


def test_fp8_matmul_forward_is_quantized_matmul():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    out = fp8_matmul(x, w)
    xs, sx = _ref_q(x, float(jnp.finfo(E4M3).max))
    ws, sw = _ref_q(w, float(jnp.finfo(E4M3).max))
    ref = (xs.astype(E4M3).astype(jnp.float32)
           @ ws.astype(E4M3).astype(jnp.float32)) / (sx * sw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    # and the quantized product is a real approximation of the fp32 one
    full = np.asarray(x @ w)
    err = np.abs(np.asarray(out) - full).max() / np.abs(full).max()
    assert err < 0.05, err


def test_fp8_matmul_margin_backs_off_scale():
    """Margin divides the quantization scale by 2^m. Because e4m3 is a
    FLOAT format, a power-of-two rescale is exact away from the
    over/underflow boundaries — so outputs match margin=0 bit-for-bit on
    ordinary data (asserted: margin costs nothing) and the headroom only
    matters for values that would saturate under a stale scale (moot
    under current scaling, kept for reference CLI parity)."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    out0 = fp8_matmul(x, w, margin=0)
    out2 = fp8_matmul(x, w, margin=2)
    full = np.asarray(x @ w)
    for o in (out0, out2):
        assert np.abs(np.asarray(o) - full).max() / np.abs(full).max() < 0.1
    np.testing.assert_array_equal(np.asarray(out0), np.asarray(out2))


def test_fp8_inf_amax_degrades_to_unit_scale():
    """An inf in the tensor must poison only itself, not the whole GEMM:
    amax=inf -> scale 1 (NOT fmax/inf = 0, which is finite and would NaN
    every element through the epilogue divide)."""
    x = jnp.asarray([[1.0, jnp.inf], [2.0, 3.0]], jnp.float32)
    w = jnp.eye(2, dtype=jnp.float32)
    out = np.asarray(fp8_matmul(x, w))
    assert np.isfinite(out[1]).all(), out  # untouched row stays finite
    assert not np.isfinite(out[0]).all()   # the inf row saturates/infs


def test_fp8_matmul_grads_match_quantized_reference():
    """bwd must be the e5m2(g) x e4m3(w/x) GEMMs with the scale epilogue —
    checked against hand-built quantized grads (hybrid format)."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((4, 8)), jnp.float32)

    def f(x, w):
        return jnp.sum(fp8_matmul(x, w) * g)

    dx, dw = jax.grad(f, argnums=(0, 1))(x, w)

    xs, sx = _ref_q(x, float(jnp.finfo(E4M3).max))
    ws, sw = _ref_q(w, float(jnp.finfo(E4M3).max))
    gs, sg = _ref_q(g, float(jnp.finfo(E5M2).max))
    x8 = xs.astype(E4M3).astype(jnp.float32)
    w8 = ws.astype(E4M3).astype(jnp.float32)
    g8 = gs.astype(E5M2).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(g8 @ w8.T) / (sg * sw),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(x8.T @ g8) / (sx * sg),
                               rtol=1e-5, atol=1e-6)


def test_fp8_no_wgrad_runs_fp32_wgrad():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)

    def loss(wgrad):
        def f(x, w):
            return jnp.sum(fp8_matmul(x, w, fp8_wgrad=wgrad) * g)
        return jax.grad(f, argnums=1)(x, w)

    dw_fp8 = np.asarray(loss(True))
    dw_hi = np.asarray(loss(False))
    # the higher-precision wgrad is closer to the true fp32 wgrad computed
    # on the same quantized activations
    xs, sx = _ref_q(x, float(jnp.finfo(E4M3).max))
    x8 = xs.astype(E4M3).astype(jnp.float32)
    true = np.asarray(x8.T @ g) / sx
    assert np.abs(dw_hi - true).max() <= np.abs(dw_fp8 - true).max() + 1e-6


@pytest.mark.slow  # 12s measured cacheless (PR 4 tier-1 re-budget);
# the TP-sharding exactness + probe tests keep fp8 coverage in tier-1
def test_fp8_training_tracks_bf16():
    """10 optimizer steps on a tiny llama: the fp8-hybrid loss curve stays
    within a few percent of the bf16 curve and both learn (the reference's
    TE fp8 contract — numerically-degraded-but-training)."""
    from megatron_tpu.models import presets
    from megatron_tpu.models.language_model import lm_loss
    from megatron_tpu.models.params import init_params
    from megatron_tpu.config import OptimizerConfig
    from megatron_tpu.training.optimizer import (init_train_state,
                                                 make_optimizer_step)

    def run(fp8_format):
        cfg = presets.tiny(vocab_size=128, seq_length=32, hidden_size=64,
                           num_layers=2, num_attention_heads=4,
                           ffn_hidden_size=128, params_dtype="float32",
                           fp8_format=fp8_format)
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = OptimizerConfig(lr=1e-3, lr_decay_style="constant")
        state = init_train_state(opt, params)
        step_fn = make_optimizer_step(opt, train_iters=10)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, 128, (4, 32)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, 128, (4, 32)), jnp.int32),
            "loss_mask": jnp.ones((4, 32), jnp.float32)}

        @jax.jit
        def one(state):
            loss, grads = jax.value_and_grad(
                lambda p: lm_loss(cfg, p, batch)[0])(state.params)
            state, _ = step_fn(state, grads)
            return state, loss

        losses = []
        for _ in range(10):
            state, loss = one(state)
            losses.append(float(loss))
        return losses

    bf = run(None)
    f8 = run("hybrid")
    assert all(np.isfinite(f8))
    assert f8[-1] < f8[0]  # fp8 training learns
    for a, b in zip(f8, bf):
        assert abs(a - b) / b < 0.05, (a, b)


def test_fp8_exact_under_tensor_parallel_sharding():
    """fp8 GEMMs compose with GSPMD sharding: the per-tensor amax is a
    global reduction over the sharded weight, so tp2 x dp loss and grads
    equal the unsharded run exactly (fp32 params on CPU)."""
    from jax.sharding import NamedSharding
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.models import presets
    from megatron_tpu.models.language_model import lm_loss
    from megatron_tpu.models.params import init_params, param_specs
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import batch_spec, shard_tree

    cfg = presets.tiny(vocab_size=128, seq_length=32, hidden_size=64,
                       num_layers=2, num_attention_heads=4,
                       ffn_hidden_size=128, params_dtype="float32",
                       fp8_format="hybrid")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 128, (4, 32)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 128, (4, 32)), jnp.int32),
             "loss_mask": jnp.ones((4, 32), jnp.float32)}
    l_ref, g_ref = jax.value_and_grad(
        lambda p: lm_loss(cfg, p, batch)[0])(params)

    rt = build_mesh(ParallelConfig(tensor_parallel=2,
                                   sequence_parallel=True))
    sp = shard_tree(rt, params, param_specs(cfg))
    sb = {k: jax.device_put(v, NamedSharding(rt.mesh, batch_spec()))
          for k, v in batch.items()}
    with jax.sharding.set_mesh(rt.mesh):
        l_tp, g_tp = jax.jit(jax.value_and_grad(
            lambda p, b: lm_loss(cfg, p, b)[0]))(sp, sb)
    np.testing.assert_allclose(float(l_tp), float(l_ref), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(g_tp), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_fp8_cli_flags():
    from megatron_tpu.arguments import args_to_run_config, parse_args

    BASE = ["--num_layers", "2", "--hidden_size", "32",
            "--num_attention_heads", "4", "--seq_length", "32",
            "--vocab_size", "128", "--micro_batch_size", "1",
            "--global_batch_size", "1"]

    run = args_to_run_config(parse_args(
        BASE + ["--fp8_hybrid", "--fp8_margin", "1", "--no_fp8_wgrad"]))
    assert run.model.fp8_format == "hybrid"
    assert run.model.fp8_margin == 1
    assert run.model.fp8_wgrad is False

    import pytest

    with pytest.raises(ValueError, match="both fp8"):
        args_to_run_config(parse_args(
            BASE + ["--fp8_e4m3", "--fp8_hybrid"]))
