"""Pallas flash-attention kernel vs the XLA einsum path (interpret mode on
the CPU suite; the same kernels compile for a described v5e in
tests/test_chip_compile.py and run on the chip in every benchmark cell)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Var

from megatron_tpu.ops.attention import attention
from megatron_tpu.ops.pallas import flash_template as ft
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


@pytest.mark.parametrize("backward,matmuls", [
    ("fused", {"flash_bwd": 5}),
    ("split", {"flash_bwd_dq": 3, "flash_bwd_dkv": 4})])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_kernel_matmuls_take_operands_as_given(monkeypatch, dtype, backward,
                                               matmuls):
    """Every matmul of the training kernels multiplies the dtype the
    caller passed and accumulates in float32: bf16 tensors reach the MXU
    as bf16 (no cast to float32 comes back), and float32 tensors still
    multiply in float32 — which is what keeps the float32 numerics tests
    above meaning what they say. The backward is ONE kernel of five
    matmuls: each tile pair's p and ds are formed once; the split pair
    that a sequence too long for it runs forms them twice (seven). The
    statistics' kernel multiplies nothing. Each kernel holds those
    matmuls once a body, and a causal sequence has three: the interior
    tiles' and the two pieces of the diagonal tile (`ft._tile_classes`)."""
    if backward == "split":
        monkeypatch.setattr(ft, "fused_bwd_fits", lambda *a: False)
    q, k, v = (x.astype(dtype) for x in _qkv(s=256, hq=2, hkv=1, d=64))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_mha(q, k, v, block_q=128, block_k=128)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    dots = list(_kernel_dots(jaxpr.jaxpr))
    per_kernel = {}
    for name, _, _ in dots:
        per_kernel[name] = per_kernel.get(name, 0) + 1
    (pieces,) = ft._tile_classes(2, 2, 128, 128, True, None, None).values()
    bodies = 1 + len(pieces)
    assert bodies == 3
    assert per_kernel == {name: n * bodies for name, n in
                          {"flash_fwd": 2, **matmuls}.items()}, dots
    for kernel, operands, result in dots:
        assert operands == (dtype, dtype), (kernel, operands)
        assert result == jnp.float32, (kernel, result)


@pytest.mark.parametrize("block", [64, 128, 256])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_row_statistics_kernel_packs_lse_and_delta(dtype, block):
    """`flash_bwd_stats` spreads the two compact statistics over the
    lanes and changes no bit: lanes [0, 64) of a row hold its
    log-sum-exp as given, the rest hold rowsum(do * o), summed in
    float32 from the tensors' own dtype by XLA as before."""
    _, _, o, do = _bhsd_case(dtype, heads=3)
    lse = jnp.asarray(np.random.default_rng(5).standard_normal((1, 3, 256)),
                      jnp.float32) * 3 + 7
    stats = np.asarray(ft._bwd_stats(lse, o, do, block))
    assert stats.shape == (1, 3, 256, 128) and stats.dtype == np.float32
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    for lanes, want in ((slice(0, ft._DELTA_LANE), lse),
                        (slice(ft._DELTA_LANE, 128), delta)):
        np.testing.assert_array_equal(
            stats[..., lanes],
            np.broadcast_to(np.asarray(want)[..., None], (1, 3, 256, 64)))


# ---------------------------------------------------------------------------
# the fused backward against the split pair it replaced (which sequences
# too long for the fused kernel's VMEM footprint still run) and against
# the XLA gradient
# ---------------------------------------------------------------------------

# (mask, (causal, window)) over S = 256: the window smaller than the
# sequence leaves dead tiles on both sides of the band at either tile
_MASKS = {"causal": (True, None), "window64": (True, 64),
          "window_s": (True, 256), "bidirectional": (False, None)}
# offset of the q rows' global positions against the keys' (a ring
# stripe): aligned, a stripe wholly in the keys' future (every pair
# visible under `causal`), one in their past (no q row sees a kv tile
# under `causal`: dq must come out zero), and a band cut askew
_OFFSETS = {"aligned": None, "future": 256, "past": -256, "askew": 96}


def _bhsd_case(dtype, heads, seed=3, s=256, d=64):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((1, heads, s, d)), dtype)
                 for _ in range(4))


def _backwards(q, k, v, do, causal, window, block, offset):
    """(fused, split): (dq, dk, dv) of the two backwards from the same
    forward results."""
    scale = float(1.0 / q.shape[-1] ** 0.5)
    o, lse = ft._fwd(q, k, v, scale, causal, window, block, block,
                     delta=offset)
    stats = ft._bwd_stats(lse, o, do, block)
    args = (q, k, v, do, stats, scale, causal, window, block, block, offset)
    return ft._bwd_fused(*args), ft._bwd_split(*args)


@pytest.mark.parametrize("block", [64, 128])
@pytest.mark.parametrize("offset", list(_OFFSETS))
@pytest.mark.parametrize("mask", list(_MASKS))
def test_fused_backward_equals_the_split_pair_bit_for_bit(mask, offset,
                                                          block):
    """float32 under the interpreter: every sum of the fused kernel
    takes the pair's terms in the pair's order, so no bit differs."""
    causal, window = _MASKS[mask]
    fused, split = _backwards(*_bhsd_case(jnp.float32, 2), causal, window,
                              block, _OFFSETS[offset])
    for name, got, want in zip(("dq", "dk", "dv"), fused, split):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)
    if causal and offset == "past":
        assert not np.asarray(fused[0]).any()    # no visible pair at all


@pytest.mark.parametrize("offset", ["aligned", "askew"])
@pytest.mark.parametrize("mask", list(_MASKS))
def test_fused_backward_equals_the_split_pair_in_bf16(mask, offset):
    causal, window = _MASKS[mask]
    fused, split = _backwards(*_bhsd_case(jnp.bfloat16, 2), causal, window,
                              128, _OFFSETS[offset])
    for name, got, want in zip(("dq", "dk", "dv"), fused, split):
        assert got.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=name)


@pytest.mark.parametrize("block", [64, 128])
@pytest.mark.parametrize("hkv", [1, 2], ids=["gqa", "mha"])
@pytest.mark.parametrize("mask", list(_MASKS))
def test_fused_backward_matches_xla_gradient(mask, hkv, block):
    """Through `flash_mha` (transposes; under GQA the kernels' reads by
    KV head and the backward's sum over the group) against the gradient
    of the XLA attention."""
    causal, window = _MASKS[mask]
    q, k, v = _qkv(s=256, hq=2, hkv=hkv, d=64)

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

    got = jax.grad(loss(functools.partial(
        flash_mha, sliding_window=window, causal=causal, block_q=block,
        block_k=block)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(functools.partial(
        attention, sliding_window=window,
        mask_type="causal" if causal else "bidirectional")),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert _share_of_range(a, b) <= 2e-3, f"d{name}"


@pytest.mark.parametrize("sq,d,dtype,fused", [
    (1024, 128, jnp.bfloat16, True), (4096, 128, jnp.bfloat16, True),
    (16384, 128, jnp.bfloat16, True), (65536, 128, jnp.bfloat16, True),
    (131072, 128, jnp.bfloat16, False), (32768, 128, jnp.float32, True),
    (65536, 128, jnp.float32, False), (16384, 256, jnp.bfloat16, True),
    (65536, 256, jnp.bfloat16, False)])
def test_which_backward_a_shape_takes(monkeypatch, sq, d, dtype, fused):
    """A function of the sequence, the row and the dtype alone: the
    fused kernel while dq of a whole sequence fits in VMEM beside the
    tiles `pick_blocks` gives, the split pair beyond. `_bwd` asks it and
    nothing else."""
    blocks = ft.pick_blocks(sq, d, dtype)
    assert ft.fused_bwd_fits(sq, d, dtype, *blocks) is fused
    item = jnp.dtype(dtype).itemsize
    assert (ft._fused_bwd_vmem_bytes(sq, *blocks, d, item)
            - ft._bwd_vmem_bytes(*blocks, d, item)) == sq * d * (4 + 2 * item)
    taken = []
    for name in ("_bwd_fused", "_bwd_split"):
        monkeypatch.setattr(
            ft, name, lambda *a, name=name: taken.append(name) or (None,) * 3)
    monkeypatch.setattr(ft, "_bwd_stats", lambda lse, o, do, block_q: None)
    x = jax.ShapeDtypeStruct((1, 1, sq, d), dtype)
    ft._bwd(x, x, x, x, None, x, 1.0, True, None, *blocks)
    assert taken == ["_bwd_fused" if fused else "_bwd_split"]


# ---------------------------------------------------------------------------
# tile classes: each live tile runs the body of its class (interior: no
# mask arithmetic; band edge: its live half-tile pieces), chosen from the
# call's static shape, window and offset (`ft._tile_classes`)
# ---------------------------------------------------------------------------

# case: (window, kv heads of 2 query heads, traced offset, the distances
# under the diagonal at which S 256 in tiles of 64 has edge tiles, each
# with its number of pieces; None: the shape falls back to the one masked
# body)
_CLASS_CASES = {
    "causal": (None, 2, False, {0: 2}),
    "window_is_the_tile": (64, 2, False, {0: 2, 1: 2}),
    "window_is_two_tiles": (128, 2, False, {0: 2, 2: 2}),
    # an odd number of half tiles: the edge crosses a whole tile's lower
    # left quarter (one masked piece) and the next tile's upper right
    "window_is_three_halves": (96, 2, False, {0: 2, 1: 1, 2: 1}),
    # shorter than the tile: the diagonal tile holds both edges
    "window_is_the_half": (32, 2, False, {0: 2, 1: 1}),
    "window_off_the_half": (40, 2, False, None),
    "gqa": (64, 1, False, {0: 2, 1: 2}),
    "traced_offset": (64, 2, True, None),
}


def _reference_bhsd(q, k, v, window):
    """The XLA attention on [B, H, S, D] tensors."""
    t = lambda x: jnp.transpose(x, (0, 2, 1, 3))  # noqa: E731
    return t(attention(t(q), t(k), t(v), sliding_window=window))


@pytest.mark.parametrize("case", list(_CLASS_CASES))
def test_every_tile_class_matches_the_plain_reference(case):
    """Forward and backward at S 256 in tiles of 64 (halves of 32)
    against the XLA attention and its gradient, at this file's
    tolerances, for each class of tile and its neighbours; and the
    classes are the ones the shape should have. The traced offset goes
    through the ring's entries (`stripe_fwd` / `stripe_bwd`), offset 0."""
    window, hkv, traced, want = _CLASS_CASES[case]
    got = ft._tile_classes(4, 4, 64, 64, True, window, 0 if traced else None)
    assert (got and {d: len(p) for d, p in got.items()}) == want
    if traced:
        q, k, v, do = _bhsd_case(jnp.float32, 2)
        scale = float(1.0 / q.shape[-1] ** 0.5)

        @jax.jit
        def stripes(delta):
            o, lse = ft.stripe_fwd(q, k, v, delta, window, scale, 64)
            return o, ft.stripe_bwd(q, k, v, o, lse, do, delta, window,
                                    scale, 64)

        o, grads = stripes(jnp.int32(0))
        want_o, vjp = jax.vjp(
            lambda *a: _reference_bhsd(*a, window), q, k, v)
        want_grads = vjp(do)
    else:
        q, k, v = _qkv(s=256, hq=2, hkv=hkv, d=64)
        flash = functools.partial(flash_mha, sliding_window=window,
                                  block_q=64, block_k=64)
        ref = functools.partial(attention, sliding_window=window)
        loss = lambda fn: (lambda *a: jnp.sum(jnp.square(fn(*a))))  # noqa: E731
        o, want_o = flash(q, k, v), ref(q, k, v)
        grads = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
        want_grads = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=2e-3, atol=2e-3)
    for name, a, b in zip("qkv", grads, want_grads):
        assert _share_of_range(a, b) <= 2e-3, f"d{name}"


def test_interior_body_equals_the_masked_body_bit_for_bit():
    """A bidirectional sequence has interior tiles only: the body without
    positions, mask and selects gives the bits of the masked body (which
    a traced offset of 0 still runs on every tile), forward and backward."""
    q, k, v, do = _bhsd_case(jnp.float32, 2)
    scale = float(1.0 / q.shape[-1] ** 0.5)
    assert ft._tile_classes(4, 4, 64, 64, False, None, None) == {}

    def both(offset):
        o, lse = ft._fwd(q, k, v, scale, False, None, 64, 64, delta=offset)
        return (o, lse) + tuple(ft._bwd(q, k, v, o, lse, do, scale, False,
                                        None, 64, 64, offset=offset))

    for name, got, want in zip(("o", "lse", "dq", "dk", "dv"), both(None),
                               jax.jit(both)(jnp.int32(0))):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


@pytest.mark.parametrize("s,block,window,tiles,computed,ratio,by_class", [
    # a window-1024 layer at 8192: edge tiles only, 8 diagonal + 7 window
    (8192, 1024, 1024, (0, 8, 7, 0), 11.25, 1.50, True),
    (8192, 1024, None, (28, 8, 0, 0), 34.0, 1.06, True),
    (4096, 1024, None, (6, 4, 0, 0), 9.0, 1.12, True),
    (4096, 1024, 4096, (6, 4, 0, 0), 9.0, 1.12, True),
    # shapes that fall back compute every live tile whole, as before
    (8192, 1024, 1000, (0, 0, 7, 8), 15.0, 2.04, False),
    (4096, 1024, 640, (0, 0, 3, 4), 7.0, 3.04, False),
    # one tile a sequence; a window shorter than the tile
    (1024, 1024, None, (0, 1, 0, 0), 0.75, 1.50, True),
    (4096, 1024, 512, (0, 0, 3, 4), 3.75, 2.00, True),
])
def test_tile_counts_arithmetic(monkeypatch, s, block, window, tiles,
                                computed, ratio, by_class):
    """`tile_counts`: a head's live tiles by class, the tiles' worth of
    score elements the kernels compute and that over the visible pairs,
    on hardware's rule for the half tile (a multiple of 128)."""
    monkeypatch.setattr(ft, "_interpret", lambda: False)
    got = ft.tile_counts(s, block, True, window)
    assert (got["interior"], got["causal_edge"], got["window_edge"],
            got["both"]) == tiles
    assert got["tiles"] == sum(tiles) and got["by_class"] is by_class
    assert got["tiles_computed"] == computed
    assert round(got["computed_over_visible"], 2) == ratio
    rows = np.arange(s)
    visible = np.minimum(rows + 1, window or s).sum()
    assert got["computed_over_visible"] == pytest.approx(
        computed * block * block / visible)


def test_a_half_tile_hardware_cannot_slice_falls_back(monkeypatch):
    """Tiles of 128 (serving prefill buckets) have halves of 64: the
    interpreter slices them, hardware runs the one masked body."""
    assert ft._tile_classes(2, 2, 128, 128, True, None, None)
    monkeypatch.setattr(ft, "_interpret", lambda: False)
    assert ft._tile_classes(2, 2, 128, 128, True, None, None) is None
    assert ft._tile_classes(2, 2, 256, 256, True, None, None)
    assert ft._tile_classes(2, 2, 256, 512, True, None, None) is None


# ---------------------------------------------------------------------------
# GQA: the kernels read K and V by KV head (`ft._kv_head` in their index
# maps) and the backward sums dk and dv over a KV head's query heads in its
# float32 scratch; nothing of the query heads' shape is made of K or V
# ---------------------------------------------------------------------------

_GQA_HEADS = 8


@pytest.mark.parametrize("backward", ["fused", "split"])
@pytest.mark.parametrize("window", [None, 64])
@pytest.mark.parametrize("groups", [1, 2, 4, 8])
def test_compact_kv_matches_the_dense_path(monkeypatch, groups, window,
                                           backward):
    """`flash_mha` on K and V as they lie, 8 query heads over 8 / 4 / 2 / 1
    KV heads, S 256 in tiles of 64 (a window smaller than the sequence
    leaves dead tiles), through either backward: o, dq, dk, dv against
    the dense XLA path of ops/attention.py."""
    if backward == "split":
        monkeypatch.setattr(ft, "fused_bwd_fits", lambda *a, **k: False)
    q, k, v = _qkv(s=256, hq=_GQA_HEADS, hkv=_GQA_HEADS // groups, d=64)
    do = jnp.asarray(RNG.standard_normal(q.shape), jnp.float32)
    flash = functools.partial(flash_mha, sliding_window=window, block_q=64,
                              block_k=64)
    o, vjp = jax.vjp(flash, q, k, v)
    want_o, want_vjp = jax.vjp(
        functools.partial(attention, sliding_window=window), q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                               rtol=2e-3, atol=2e-3)
    for name, a, b in zip(("dq", "dk", "dv"), vjp(do), want_vjp(do)):
        assert a.shape == b.shape, name
        assert _share_of_range(a, b) <= 2e-3, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("mask", ["causal", "window64"])
@pytest.mark.parametrize("groups", [2, 4])
def test_grouped_fused_backward_equals_the_split_pair_bit_for_bit(
        groups, mask, dtype):
    """Under GQA too each of dk's and dv's sums takes its terms in the
    split pair's order (a kv tile's: query heads ascending, q tiles
    ascending) into float32 and is rounded once, so no bit differs
    between the fused kernel's whole-sequence accumulators and the
    pair's tile."""
    causal, window = _MASKS[mask]
    q, _, _, do = _bhsd_case(dtype, 4)
    _, k, v, _ = _bhsd_case(dtype, 4 // groups, seed=5)
    fused, split = _backwards(q, k, v, do, causal, window, 64, None)
    for name, got, want in zip(("dq", "dk", "dv"), fused, split):
        assert got.shape == (k.shape if name != "dq" else q.shape)
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=name)


def test_the_group_sum_is_float32_with_one_rounding():
    """bf16, 8 query heads over one KV head: dk and dv are the float32
    sum over the group rounded once: closer in the mean to the float32
    reference than the sum of the eight heads' gradients each rounded to
    bf16 first (what the broadcast's own vjp gave; 0.00070 against
    0.00085 of the unit normal inputs' scale, whatever the seed)."""
    rng = np.random.default_rng(13)
    q, do = (jnp.asarray(rng.standard_normal((1, 8, 256, 64)), jnp.bfloat16)
             for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((1, 1, 256, 64)), jnp.bfloat16)
            for _ in range(2))
    scale = float(1.0 / 64 ** 0.5)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

    def grads(q, k, v):
        o, lse = ft._fwd(q, k, v, scale, True, None, 128, 128)
        return ft._bwd(q, k, v, o, lse, do.astype(q.dtype), scale, True,
                       None, 128, 128)

    _, dk, dv = grads(q, k, v)
    rep = lambda x: jnp.repeat(x, 8, axis=1)  # noqa: E731
    _, dk_heads, dv_heads = grads(q, rep(k), rep(v))
    _, want_dk, want_dv = grads(f32(q), f32(k), f32(v))
    for got, heads, want in ((dk, dk_heads, want_dk),
                             (dv, dv_heads, want_dv)):
        assert got.dtype == jnp.bfloat16 and got.shape == (1, 1, 256, 64)
        summed = heads.sum(axis=1, keepdims=True)    # bf16 terms, bf16 sum
        off = lambda x: float(jnp.abs(f32(x) - want).mean())  # noqa: E731
        assert off(got) < 0.9 * off(summed)
        assert _share_of_range(got, want) <= 4e-3


# the four training cells' flash calls: (batch, query heads, kv heads,
# sequence), and two shapes past the fused kernel's footprint under GQA
_CELL_SHAPES = {
    "mellum": (2, 32, 4, 8192, True), "mistral_seq4k": (1, 32, 8, 4096, True),
    "mistral_tp2dp2": (8, 16, 4, 4096, True),
    "olmoe": (1, 16, 16, 4096, True),
    # dk and dv of a KV head's whole sequence beside dq: to 16k rows
    "gqa_16k": (1, 8, 2, 16384, True), "gqa_32k": (1, 8, 2, 32768, False),
    "mha_32k": (1, 8, 8, 32768, True), "gqa_64k": (1, 8, 1, 65536, False)}


@pytest.mark.parametrize("case", list(_CELL_SHAPES))
def test_which_backward_a_gqa_shape_takes(monkeypatch, case):
    """`fused_bwd_fits` with the group's size, which `_bwd` reads off the
    operands' shapes: every training cell's call takes the fused kernel;
    where several query heads share a KV head the kernel holds dk and dv
    of the KV head's whole sequence beside dq, three such sums for one,
    and a sequence past that footprint takes the split pair."""
    b, hq, hkv, s, fused = _CELL_SHAPES[case]
    d, dtype = 128, jnp.bfloat16
    blocks = ft.pick_blocks(s, d, dtype)
    groups = hq // hkv
    assert ft.fused_bwd_fits(s, d, dtype, *blocks, groups) is fused
    item = jnp.dtype(dtype).itemsize
    assert (ft._fused_bwd_vmem_bytes(s, *blocks, d, item, groups)
            - ft._bwd_vmem_bytes(*blocks, d, item)
            ) == (3 if groups > 1 else 1) * s * d * (4 + 2 * item)
    taken = []
    for name in ("_bwd_fused", "_bwd_split"):
        monkeypatch.setattr(
            ft, name, lambda *a, name=name: taken.append(name) or (None,) * 3)
    monkeypatch.setattr(ft, "_bwd_stats", lambda lse, o, do, block_q: None)
    x = jax.ShapeDtypeStruct((b, hq, s, d), dtype)
    kv = jax.ShapeDtypeStruct((b, hkv, s, d), dtype)
    ft._bwd(x, kv, kv, x, None, x, 1.0, True, None, *blocks)
    assert taken == ["_bwd_fused" if fused else "_bwd_split"]


def _pallas_calls(jaxpr):
    """Every `pallas_call` equation of a jaxpr, those inside nested
    jaxprs (jit, custom_vjp) too, in order."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
            continue
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                found += _pallas_calls(inner)
    return found


def _fwd_and_vjp(q, k, v, **kwargs):
    """(jaxpr of the forward, jaxpr of forward + vjp) of `flash_mha`."""
    flash = functools.partial(flash_mha, **kwargs)

    def both(q, k, v, do):
        o, vjp = jax.vjp(flash, q, k, v)
        return (o,) + vjp(do)

    return (jax.make_jaxpr(flash)(q, k, v).jaxpr,
            jax.make_jaxpr(both)(q, k, v, q).jaxpr)


@pytest.mark.parametrize("backward", ["fused", "split"])
def test_one_query_head_a_kv_head_is_the_program_it_was(monkeypatch,
                                                        backward):
    """At groups 1 (MHA; the ring's stripes, which arrive broadcast)
    every call is what it was before the kernels read K and V by KV head:
    the grids, the operands' and results' shapes, dk's and dv's tile-sized
    output blocks and accumulators, heads a parallel axis, and index maps
    that name the grid's own head. (Character for character the parent's
    jaxpr when this was written: PERF.md section 6, PR 70.)"""
    if backward == "split":
        monkeypatch.setattr(ft, "fused_bwd_fits", lambda *a, **k: False)
    b, s, h, d, blk = 1, 256, 2, 64, 64
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.float32)
    _, both = _fwd_and_vjp(x, x, x, sliding_window=128, block_q=blk,
                           block_k=blk)
    calls = {eqn.params["name"]: eqn for eqn in _pallas_calls(both)}
    bwd = (["flash_bwd"] if backward == "fused"
           else ["flash_bwd_dq", "flash_bwd_dkv"])
    assert list(calls) == ["flash_fwd", "flash_bwd_stats"] + bwd
    head, lanes, tile = (b, h, s, d), (b, h, s, 128), (1, 1, blk, d)
    n = s // blk
    want = {  # name: (operands behind the offset, results, blocks, scratch)
        "flash_fwd": ([head] * 3, [head, lanes],
                      [tile] * 4 + [(1, 1, blk, 128)],
                      [(blk, 1), (blk, 1), (blk, d)]),
        "flash_bwd": ([head] * 4 + [lanes], [head] * 3,
                      [tile] * 4 + [(1, 1, blk, 128), (1, 1, s, d), tile,
                                    tile],
                      [(n, blk, d), (blk, d), (blk, d)]),
        "flash_bwd_dq": ([head] * 4 + [lanes], [head],
                         [tile] * 4 + [(1, 1, blk, 128), tile], [(blk, d)]),
        "flash_bwd_dkv": ([head] * 4 + [lanes], [head] * 2,
                          [tile] * 4 + [(1, 1, blk, 128), tile, tile],
                          [(blk, d), (blk, d)]),
    }
    for name in ["flash_fwd"] + bwd:
        eqn = calls[name]
        operands, results, blocks, scratch = want[name]
        mapping = eqn.params["grid_mapping"]
        assert mapping.grid == (b, h, n, n), name
        assert [v.aval.shape for v in eqn.invars[1:]] == operands, name
        assert [v.aval.shape for v in eqn.outvars] == results, name
        assert [tuple(getattr(dim, "block_size", dim)
                      for dim in bm.block_shape)
                for bm in mapping.block_mappings] == blocks, name
        assert [a.shape for a in mapping.scratch_avals] == scratch, name
        semantics = eqn.params["compiler_params"][
            "mosaic_tpu"].dimension_semantics
        assert semantics[:2] == ("parallel", "parallel"), name
        for bm in mapping.block_mappings:
            # batch and head of every block are the grid's own: no
            # division stands between a head and the block it names
            index = bm.index_map_jaxpr.jaxpr
            assert index.outvars[:2] == index.invars[:2], name


def _made_of_kv(jaxpr, tainted):
    """(shapes, results): the shapes of every value a jaxpr computes from
    its tainted inputs outside the Pallas calls (a kernel's results are
    its own: o, dq and the gradients are meant to depend on K and V), and
    which of its results are such values."""
    tainted = {v for v, t in zip(jaxpr.invars, tainted) if t}
    shapes = []
    for eqn in jaxpr.eqns:
        ins = [isinstance(v, Var) and v in tainted
               for v in eqn.invars]
        if eqn.primitive.name == "pallas_call" or not any(ins):
            continue
        inner = [getattr(p, "jaxpr", p) for p in eqn.params.values()
                 if hasattr(getattr(p, "jaxpr", p), "eqns")]
        outs = [True] * len(eqn.outvars)
        if inner and len(inner[0].invars) == len(ins):
            found, outs = _made_of_kv(inner[0], ins)
            shapes += found
        for v, t in zip(eqn.outvars, outs):
            if t:
                tainted.add(v)
                shapes.append(v.aval.shape)
    return shapes, [isinstance(v, Var) and v in tainted
                    for v in jaxpr.outvars]


@pytest.mark.parametrize("backward", ["fused", "split"])
@pytest.mark.parametrize("groups", [2, 8])
def test_nothing_of_the_query_heads_shape_is_made_of_k_or_v(monkeypatch,
                                                            groups, backward):
    """At groups > 1 neither the forward nor its vjp holds a value of
    shape [B, Hq, Skv, D] (in either layout) derived from K or V outside
    the kernels: no broadcast in front of them, no query-head-shaped dk or
    dv behind them. The kernels take K and V as [B, Hkv, Skv, D] and the
    backward's dk and dv leave it in that shape; dq stays its FIRST
    result (benchmark/kernel_costs/flash_bwd.py counts over it)."""
    if backward == "split":
        monkeypatch.setattr(ft, "fused_bwd_fits", lambda *a, **k: False)
    b, s, hq, d = 2, 256, 8, 64
    hkv = hq // groups
    q = jax.ShapeDtypeStruct((b, s, hq, d), jnp.float32)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.float32)
    fwd, both = _fwd_and_vjp(q, kv, kv, block_q=64, block_k=64)
    for jaxpr in (fwd, both):
        shapes, _ = _made_of_kv(jaxpr, [False, True, True, False][
            :len(jaxpr.invars)])
        assert shapes, "K and V are at least transposed"
        assert not {(b, hq, s, d), (b, s, hq, d)} & set(shapes)
        assert set(shapes) <= {(b, hkv, s, d), (b, s, hkv, d)}
    compact, head = (b, hkv, s, d), (b, hq, s, d)
    calls = {eqn.params["name"]: eqn for eqn in _pallas_calls(both)}
    for name, eqn in calls.items():
        if name == "flash_bwd_stats":
            continue
        assert [v.aval.shape for v in eqn.invars[2:4]] == [compact] * 2
        if name != "flash_bwd_dkv":     # whose grid walks the KV heads
            # K's and V's blocks: the query head over the group's size,
            # one truncating division (heads are never negative)
            for bm in eqn.params["grid_mapping"].block_mappings[1:3]:
                index = bm.index_map_jaxpr.jaxpr
                made_by = [e for e in index.eqns
                           if index.outvars[1] in e.outvars]
                assert [e.primitive.name for e in made_by] == ["div"], name
                assert made_by[0].invars[0] == index.invars[1], name
    results = {name: [v.aval.shape for v in eqn.outvars]
               for name, eqn in calls.items()}
    if backward == "fused":
        assert results["flash_bwd"] == [head, compact, compact]
    else:
        assert results["flash_bwd_dq"] == [head]
        assert results["flash_bwd_dkv"] == [compact, compact]
