"""The program's grouped-matmul kernels (ops/pallas/grouped_matmul.py)
against `jax.lax.ragged_dot`, on the CPU with the kernels in interpret
mode at small shapes: value and both gradients over every kind of group
layout, the tile rule, and the rule that picks kernel or `ragged_dot`;
and the experts' two products with the activation inside the kernels
(`grouped_mlp`) against `ragged_dot`, `apply_activation`, `ragged_dot`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.ops.activations import apply_activation
from megatron_tpu.ops.pallas import grouped_matmul as gm

M, K, N = 512, 256, 384

# group sizes summing to M; the row tile at these shapes is 128
LAYOUTS = {
    "uniform": [128, 128, 128, 128],
    "skewed": [300, 12, 150, 50],
    "empty_group": [200, 0, 56, 256],
    "empty_first_and_last": [0, 256, 256, 0],
    "boundaries_off_the_tile": [1, 130, 127, 254],
    "one_group_holds_every_row": [0, 0, 512, 0],
    "many_small_groups": [40] * 12 + [32],
}


def _operands(sizes, dtype=jnp.float32, k=K, n=N):
    keys = jax.random.split(jax.random.PRNGKey(len(sizes)), 3)
    lhs = jax.random.normal(keys[0], (M, k), dtype)
    rhs = jax.random.normal(keys[1], (len(sizes), k, n), dtype)
    weight = jax.random.normal(keys[2], (M, n), dtype)
    return lhs, rhs, weight, jnp.asarray(sizes, jnp.int32)


@pytest.fixture
def as_on_one_tpu(monkeypatch):
    """`grouped_matmul` takes its kernels (interpret mode: the backend is
    still the CPU) as it would on one TPU."""
    monkeypatch.setattr(gm, "_one_tpu", lambda: True)


def _value_and_grads(fn, lhs, rhs, weight):
    """fn's value and the gradients of a scalar of it in lhs and rhs."""
    loss = lambda a, b: jnp.sum(fn(a, b) * weight)  # noqa: E731
    return (fn(lhs, rhs),) + jax.grad(loss, argnums=(0, 1))(lhs, rhs)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernels_equal_ragged_dot_in_value_and_gradients(as_on_one_tpu,
                                                         layout):
    """`moe_gmm` forward, `moe_gmm` on the weights' other axis (the
    gradient of the rows) and `moe_tgmm` (the gradient of the weights)
    give what `lax.ragged_dot` and its own gradients give."""
    sizes = LAYOUTS[layout]
    lhs, rhs, weight, gs = _operands(sizes)
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda a, b: gm.grouped_matmul(a, b, gs))(lhs, rhs))
    got = _value_and_grads(lambda a, b: gm.grouped_matmul(a, b, gs),
                           lhs, rhs, weight)
    want = _value_and_grads(lambda a, b: jax.lax.ragged_dot(a, b, gs),
                            lhs, rhs, weight)
    for g, w, what in zip(got, want, ("value", "d lhs", "d rhs")):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-4, err_msg=what)
    for e, size in enumerate(sizes):
        if size == 0:   # no row, no gradient: exactly zero, not nearly
            assert not np.any(np.asarray(got[2][e])), e


@pytest.mark.parametrize("layout", ["skewed", "empty_group"])
def test_bf16_operands_give_bf16_results(as_on_one_tpu, layout):
    """bf16 in, float32 accumulation, bf16 out, for the value and both
    gradients, as `lax.ragged_dot` gives them."""
    lhs, rhs, weight, gs = _operands(LAYOUTS[layout], jnp.bfloat16)
    got = _value_and_grads(lambda a, b: gm.grouped_matmul(a, b, gs),
                           lhs, rhs, weight)
    want = _value_and_grads(lambda a, b: jax.lax.ragged_dot(a, b, gs),
                            lhs, rhs, weight)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype == jnp.bfloat16
        np.testing.assert_allclose(g.astype(jnp.float32),
                                   w.astype(jnp.float32),
                                   rtol=2e-2, atol=0.25)


@pytest.mark.parametrize("tiles", [(128, 128, 128), (256, 128, 384),
                                   (512, 256, 128), (128, 256, 384)])
@pytest.mark.parametrize("kernel", ["gmm", "gmm_transposed", "tgmm",
                                    "tgmm_into"])
def test_each_kernel_at_tiles_of_several_steps(kernel, tiles):
    """Every kernel at tiles that cut k and n into several steps (the
    accumulator across k tiles, the row tile's visits across n tiles) and
    at row tiles of one to four visits a group; `moe_tgmm` also in the
    form that sums into an accumulator's blocks."""
    sizes = LAYOUTS["boundaries_off_the_tile"]
    lhs, rhs, weight, gs = _operands(sizes)
    visits = gm.group_visits(gs, M, tiles[0])
    if kernel == "gmm":
        got = gm._gmm(lhs, rhs, visits, tiles, False)
        want = jax.lax.ragged_dot(lhs, rhs, gs)
    elif kernel == "gmm_transposed":
        # weight [M, N] through rhs [E, K, N] on its last axis -> [M, K]
        tm, tk, tn = tiles
        got = gm._gmm(weight, rhs, visits, (tm, tn, tk), True)
        want = jax.lax.ragged_dot(weight, jnp.swapaxes(rhs, 1, 2), gs)
    else:
        want = jax.grad(lambda r: jnp.sum(
            jax.lax.ragged_dot(lhs, r, gs) * weight))(rhs)
        if kernel == "tgmm":
            got = gm._tgmm(lhs, weight, visits, tiles)
        else:
            got, = gm._tgmm(lhs, weight, visits, tiles,
                            into=(rhs[None], jnp.int32(0)))
            want = rhs + want
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


def _sunk(lhs, rhs, weight, gs, stack, layer):
    """Value, d lhs, rhs's own cotangent and the stack's, of
    grouped_matmul with `stack` as the sink and as its cotangent."""
    def fn(a, b, c):
        out, c = gm.grouped_matmul(a, b, gs, sink=(c, layer))
        return jnp.sum(out * weight, dtype=jnp.float32), (c, out)

    (_, (through, out)), vjp = jax.vjp(fn, lhs, rhs, stack)
    dlhs, drhs, summed = vjp((jnp.ones((), jnp.float32),
                              (stack, jnp.zeros_like(out))))
    return out, dlhs, drhs, summed, through


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_sink_receives_its_cotangent_plus_the_weight_gradient(
        as_on_one_tpu, layout):
    """With a sink, the stack goes through the function untouched and the
    gradient rule answers its cotangent (a running sum) with that sum plus
    the float32 `lax.ragged_dot` weight gradient at the named layer; the
    value and the rows' gradient are those without a sink, and rhs's own
    cotangent is zero: the gradient exists once."""
    sizes = LAYOUTS[layout]
    lhs, rhs, weight, gs = _operands(sizes)
    stack = jax.random.normal(jax.random.PRNGKey(7), (1,) + rhs.shape)
    out, dlhs, drhs, summed, through = _sunk(lhs, rhs, weight, gs, stack,
                                             jnp.int32(0))
    want = _value_and_grads(lambda a, b: jax.lax.ragged_dot(a, b, gs),
                            lhs, rhs, weight)
    np.testing.assert_array_equal(through, stack)
    np.testing.assert_allclose(out, want[0], rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(dlhs, want[1], rtol=2e-5, atol=2e-4)
    np.testing.assert_allclose(summed[0], stack[0] + want[2],
                               rtol=2e-5, atol=2e-4)
    assert not np.any(np.asarray(drhs))
    for e, size in enumerate(sizes):
        if size == 0:   # an empty group adds zero, exactly
            np.testing.assert_array_equal(summed[0, e], stack[0, e])


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_only_the_named_layer_of_a_stack_changes(as_on_one_tpu, layer):
    """In a stack of three layers' accumulators the kernel reads and
    writes the named layer's blocks; the other two come back bit-equal."""
    lhs, rhs, weight, gs = _operands(LAYOUTS["empty_group"])
    stack = jax.random.normal(jax.random.PRNGKey(8), (3,) + rhs.shape)
    summed = _sunk(lhs, rhs, weight, gs, stack, jnp.int32(layer))[3]
    want = jax.grad(lambda r: jnp.sum(
        jax.lax.ragged_dot(lhs, r, gs) * weight))(rhs)
    for at in range(3):
        if at == layer:
            np.testing.assert_allclose(summed[at], stack[at] + want,
                                       rtol=2e-5, atol=2e-4)
        else:
            np.testing.assert_array_equal(summed[at], stack[at])


@pytest.mark.parametrize("layout", ["skewed", "empty_group"])
def test_bf16_operands_sum_in_float32(as_on_one_tpu, layout):
    """bf16 operands, a float32 sum: the product reaches the accumulator
    unrounded, so the sum is nearer the float32 gradient than the sum of
    the rounded gradient that the form without a sink gives."""
    lhs, rhs, weight, gs = _operands(LAYOUTS[layout], jnp.bfloat16)
    stack = jax.random.normal(jax.random.PRNGKey(9), (1,) + rhs.shape)
    summed = _sunk(lhs, rhs, weight, gs, stack, jnp.int32(0))[3]
    assert summed.dtype == jnp.float32
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    exact = stack[0] + jax.grad(lambda r: jnp.sum(
        jax.lax.ragged_dot(f32(lhs), r, gs) * f32(weight)))(f32(rhs))
    rounded = stack[0] + f32(_value_and_grads(
        lambda a, b: gm.grouped_matmul(a, b, gs), lhs, rhs, weight)[2])
    np.testing.assert_allclose(summed[0], exact, rtol=1e-5, atol=1e-3)
    assert (np.abs(summed[0] - exact).max()
            < 0.1 * np.abs(rounded - exact).max())


def test_without_a_sink_the_kernel_is_the_one_it_was(as_on_one_tpu):
    """No sink: `moe_tgmm` takes the visit table's four scalar operands,
    aliases nothing and gives the operands' dtype; with one it takes the
    layer as a fifth and its float32 result is its last operand's
    buffer."""
    lhs, rhs, weight, gs = _operands(LAYOUTS["skewed"], jnp.bfloat16)
    stack = jnp.zeros((2,) + rhs.shape, jnp.float32)

    def tgmm_call(fn, *args):
        eqns = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
                if e.primitive.name == "pallas_call"
                and "moe_tgmm" in str(e.params["name"])]
        assert len(eqns) == 1
        return eqns[0]

    plain = tgmm_call(jax.grad(lambda b: jnp.sum(
        gm.grouped_matmul(lhs, b, gs) * weight, dtype=jnp.float32)), rhs)
    assert plain.params["grid_mapping"].num_index_operands == 4
    assert not plain.params["input_output_aliases"]
    assert [v.aval.dtype for v in plain.outvars] == [jnp.bfloat16]

    into = tgmm_call(lambda c: _sunk(lhs, rhs, weight, gs, c,
                                     jnp.int32(1))[3], stack)
    assert into.params["grid_mapping"].num_index_operands == 5
    assert tuple(into.params["input_output_aliases"]) == ((7, 0),)
    assert [v.aval.dtype for v in into.outvars] == [jnp.float32]
    assert into.outvars[0].aval.shape == stack.shape


def test_a_sink_where_the_products_are_not_the_kernels_is_refused():
    """`takes_sink` is the predicate the products go by: off the TPU (this
    backend) it is false, and a sink handed in all the same raises."""
    lhs, rhs, _, gs = _operands(LAYOUTS["skewed"])
    assert not gm.takes_sink(M, K, N, len(gs))
    with pytest.raises(ValueError, match="takes_sink"):
        gm.grouped_matmul(lhs, rhs, gs, sink=(
            jnp.zeros((1,) + rhs.shape), jnp.int32(0)))


def test_takes_sink_follows_the_tiles(as_on_one_tpu):
    assert gm.takes_sink(M, K, N, 4)
    assert gm.takes_sink(32768, 2048, 2048, 64)
    assert not gm.takes_sink(8, 2048, 2048, 4)       # a decode batch
    assert not gm.takes_sink(512, 200, 384, 4)       # lanes off 128


# ---------------------------------------------------------------------------
# the pair of products with the activation inside the kernels
# ---------------------------------------------------------------------------

H, F = 256, 128     # the experts' hidden width and inner width
ACTIVATIONS = ("swiglu", "gelu_tanh", "squared_relu")
PAIR_LAYOUTS = ("skewed", "empty_group", "boundaries_off_the_tile")


def _pair_operands(sizes, activation, dtype=jnp.float32, h=H, f=F):
    """Rows, the experts' two matrices (the first twice as wide for a
    GLU), the weight of the scalar and the group sizes."""
    keys = jax.random.split(jax.random.PRNGKey(len(sizes)), 4)
    fin = gm._act_parts(activation) * f
    xs = jax.random.normal(keys[0], (M, h), dtype)
    w_in = (jax.random.normal(keys[1], (len(sizes), h, fin)) / 8).astype(dtype)
    w_out = (jax.random.normal(keys[2], (len(sizes), f, h)) / 8).astype(dtype)
    weight = jax.random.normal(keys[3], (M, h), dtype)
    return xs, w_in, w_out, weight, jnp.asarray(sizes, jnp.int32)


def _dense_pair(xs, w_in, w_out, weight, gs, activation):
    """The scalar's value parts (result, d rows, d w_in, d w_out) by
    `lax.ragged_dot`, `apply_activation` and `lax.ragged_dot`, over the
    rows the groups hold (the operands' first sum(gs))."""
    rows = int(gs.sum())

    def fn(a, b, c):
        mid = apply_activation(activation, jax.lax.ragged_dot(a, b, gs))
        return jax.lax.ragged_dot(mid, c, gs)

    loss = lambda a, b, c: jnp.sum(  # noqa: E731
        fn(a, b, c) * weight[:rows], dtype=jnp.float32)
    return (fn(xs[:rows], w_in, w_out),) + jax.grad(loss, argnums=(0, 1, 2))(
        xs[:rows], w_in, w_out)


def _fused_pair(xs, w_in, w_out, weight, gs, activation, stacks=(None, None),
                ragged=False):
    """The same parts by `grouped_mlp` (over the groups' rows, the others
    kept out of the scalar as a caller's `kept` keeps them out), and what
    the stacks' cotangents come back as, each handed in as its stack's."""
    held = (jnp.arange(xs.shape[0]) < gs.sum())[:, None]

    def fn(a, b, c, sunk):
        out, through = gm.grouped_mlp(a, b, c, gs, activation, ragged=ragged,
                                      sinks=(*sunk, jnp.int32(0)))
        loss = jnp.sum(jnp.where(held, out * weight, 0.0), dtype=jnp.float32)
        return loss, through, out

    (_, through, out), vjp = jax.vjp(fn, xs, w_in, w_out, stacks)
    dxs, dw_in, dw_out, summed = vjp((jnp.ones((), jnp.float32), stacks,
                                      jnp.zeros_like(out)))
    for stack, same in zip(stacks, through):
        assert (stack is None) == (same is None)
        if stack is not None:
            np.testing.assert_array_equal(same, stack)
    rows = int(gs.sum())
    return (out[:rows], dxs[:rows], dw_in, dw_out), summed


def _made_outside_kernels(jaxpr):
    """The primitives, outside the Pallas calls' bodies, whose result has
    the rows' leading dimension: the arithmetic that stands between the
    kernels."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            continue
        inner = [getattr(v, "jaxpr", v) for v in eqn.params.values()
                 if hasattr(getattr(v, "jaxpr", v), "eqns")]
        for sub in inner:
            found += _made_outside_kernels(sub)
        if not inner and any(getattr(v.aval, "shape", ())[:1] == (M,)
                             for v in eqn.outvars):
            found.append(eqn.primitive.name)
    return found


def _assert_pair(got, want, rtol, atol, dtype=None):
    for g, w, what in zip(got, want, ("value", "d rows", "d w_in",
                                      "d w_out")):
        if dtype is not None:
            assert g.dtype == w.dtype == dtype, what
        np.testing.assert_allclose(g.astype(jnp.float32),
                                   w.astype(jnp.float32),
                                   rtol=rtol, atol=atol, err_msg=what)


@pytest.mark.parametrize("sunk", [False, True], ids=["plain", "sinks"])
@pytest.mark.parametrize("activation", ACTIVATIONS)
@pytest.mark.parametrize("layout", PAIR_LAYOUTS)
def test_fused_pair_equals_ragged_dot_and_activation(as_on_one_tpu, layout,
                                                     activation, sunk):
    """`grouped_mlp`: the activation made inside `moe_gmm` (second
    product) and `moe_tgmm` (its matrix's gradient), its backward inside
    the `moe_gmm` that takes the cotangent back: value and every gradient
    are those of `ragged_dot`, `apply_activation`, `ragged_dot`; with
    sinks each matrix's gradient is added to its stack's cotangent in
    float32 and the matrix's own cotangent is zero."""
    sizes = LAYOUTS[layout]
    xs, w_in, w_out, weight, gs = _pair_operands(sizes, activation)
    assert _made_outside_kernels(jax.make_jaxpr(jax.grad(
        lambda a, b, c: jnp.sum(gm.grouped_mlp(a, b, c, gs, activation)[0]),
        argnums=(0, 1, 2)))(xs, w_in, w_out).jaxpr) == ["broadcast_in_dim"]
    stacks = (None, None)
    if sunk:
        stacks = tuple(jax.random.normal(jax.random.PRNGKey(7 + i),
                                         (1,) + w.shape)
                       for i, w in enumerate((w_in, w_out)))
    got, summed = _fused_pair(xs, w_in, w_out, weight, gs, activation,
                              stacks)
    want = _dense_pair(xs, w_in, w_out, weight, gs, activation)
    if sunk:
        for own, total, stack, grad in zip(got[2:], summed[:2], stacks,
                                           want[2:]):
            assert not np.any(np.asarray(own))
            np.testing.assert_allclose(total[0], stack[0] + grad,
                                       rtol=2e-5, atol=2e-4)
        got, want = got[:2], want[:2]
    else:
        assert summed[:2] == (None, None)
    _assert_pair(got, want, rtol=2e-5, atol=2e-4)
    for e, size in enumerate(sizes):
        if size == 0 and not sunk:   # no row, no gradient: exactly zero
            assert not np.any(np.asarray(got[2][e])), e
            assert not np.any(np.asarray(got[3][e])), e


@pytest.mark.parametrize("activation", ACTIVATIONS)
@pytest.mark.parametrize("layout", ["skewed", "empty_group"])
def test_fused_pair_bf16_operands_give_bf16_results(as_on_one_tpu, layout,
                                                    activation):
    """bf16 in, the first product kept in bf16, the activation rounded to
    bf16 in front of the MXU, float32 accumulation, bf16 out: the dense
    form's rounding points (the activation itself in float32 and rounded
    once, where the dense form rounds after each of its operations), so
    each part is as near the float32 result as the dense bf16 form's."""
    operands = _pair_operands(LAYOUTS[layout], activation, jnp.bfloat16)
    got, _ = _fused_pair(*operands, activation)
    want = _dense_pair(*operands, activation)
    exact = _dense_pair(*(a.astype(jnp.float32) for a in operands[:4]),
                        operands[4], activation)
    for g, w, e, what in zip(got, want, exact, ("value", "d rows", "d w_in",
                                                "d w_out")):
        assert g.dtype == w.dtype == jnp.bfloat16, what
        off = lambda a: np.abs(np.asarray(a, np.float32) - e)  # noqa: E731
        assert off(g).max() <= 2 * off(w).max(), what
        assert off(g).mean() <= 1.25 * off(w).mean(), what


@pytest.mark.parametrize("activation", ["swiglu", "gelu_tanh"])
@pytest.mark.parametrize("kernel", ["gmm_act", "gmm_act_vjp", "tgmm_act",
                                    "tgmm_act_into"])
def test_each_activation_kernel_at_tiles_of_several_steps(kernel,
                                                          activation):
    """The kernels that hold the activation at tiles that cut the experts'
    inner width into several steps, so that a GLU's gate and up blocks are
    two windows F columns apart that move together: the contraction of
    `moe_gmm` over F, the k tiles of `moe_tgmm`; the backward's n tiles
    (one for a GLU, which writes both cotangents side by side; several for
    a plain activation)."""
    f = 256
    sizes = LAYOUTS["boundaries_off_the_tile"]
    xs, w_in, w_out, weight, gs = _pair_operands(sizes, activation, f=f)
    visits = gm.group_visits(gs, M, 128)
    hmid = jax.lax.ragged_dot(xs, w_in, gs)
    act = lambda a: apply_activation(activation, a)  # noqa: E731
    if kernel == "gmm_act":
        got = gm._gmm(hmid, w_out, visits, (128, 128, 128), False,
                      act=activation)
        want = jax.lax.ragged_dot(act(hmid), w_out, gs)
    elif kernel == "gmm_act_vjp":
        tn = f if gm._act_parts(activation) > 1 else 128
        got = gm._gmm(weight, w_out, visits, (128, 128, tn), True,
                      act_vjp=(activation, hmid))
        want = jax.grad(lambda a: jnp.sum(
            jax.lax.ragged_dot(act(a), w_out, gs) * weight))(hmid)
    else:
        want = jax.grad(lambda w: jnp.sum(
            jax.lax.ragged_dot(act(hmid), w, gs) * weight))(w_out)
        if kernel == "tgmm_act":
            got = gm._tgmm(hmid, weight, visits, (128, 128, 128),
                           act=activation)
        else:
            got, = gm._tgmm(hmid, weight, visits, (128, 128, 128),
                            act=activation, into=(w_out[None], jnp.int32(0)))
            want = w_out + want
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


def test_a_glu_wider_than_its_n_tile_is_refused_by_the_kernel():
    lhs, rhs, _, gs = _operands(LAYOUTS["skewed"])
    with pytest.raises(ValueError, match="one n tile"):
        gm._gmm(lhs, rhs, gm.group_visits(gs, M, 128), (128, 128, 128),
                False, act_vjp=("swiglu", jnp.zeros((M, 2 * N))))


@pytest.mark.parametrize("activation", ACTIVATIONS)
def test_rows_behind_the_last_group_reach_nothing(as_on_one_tpu, activation):
    """A share of the experts: the groups end before the rows. The rows
    behind them hold NaN on the way in, and no kernel writes the first
    product's (the interpreter leaves NaN there, the chip whatever the
    buffer held): every result row of a group, both matrices' gradients
    and the groups' rows of `d xs` are finite and equal the dense form's
    over the groups' rows, the last group's boundary window of `moe_tgmm`
    included."""
    sizes = [200, 0, 56, 100]                  # 356 of the 512 rows
    xs, w_in, w_out, weight, gs = _pair_operands(sizes, activation)
    behind = (jnp.arange(M) >= sum(sizes))[:, None]
    xs = jnp.where(behind, jnp.nan, xs)
    got, _ = _fused_pair(xs, w_in, w_out, weight, gs, activation,
                         ragged=True)
    for part in got:
        assert np.isfinite(np.asarray(part)).all()
    _assert_pair(got, _dense_pair(xs, w_in, w_out, weight, gs, activation),
                 rtol=2e-5, atol=2e-4)
    # without the call's word that rows stand behind the groups, one
    # operand of `moe_tgmm`'s window keeps what those rows hold
    unmasked, _ = _fused_pair(xs, w_in, w_out, weight, gs, activation)
    assert not np.isfinite(np.asarray(unmasked[2])).all()


@pytest.mark.parametrize("why,kwargs", [
    ("exact gelu: erfc does not lower", dict(activation="gelu")),
    ("exact gelu: erfc does not lower", dict(activation="geglu")),
    ("a GLU wider than one n tile", dict(activation="swiglu", f=4096)),
    ("rows the tiles do not divide", dict(activation="swiglu", m=8)),
    ("widths that do not fit the activation", dict(activation="relu",
                                                   fin=2 * F)),
])
def test_where_the_fused_pair_does_not_serve(as_on_one_tpu, why, kwargs):
    """`grouped_mlp` answers None, and the caller runs the products apart
    with the activation between them."""
    activation = kwargs["activation"]
    m, f = kwargs.get("m", M), kwargs.get("f", F)
    fin = kwargs.get("fin", gm._act_parts(activation) * f)
    shape = jax.ShapeDtypeStruct
    served = jax.eval_shape(
        lambda a, b, c: gm.grouped_mlp(a, b, c, jnp.zeros((4,), jnp.int32),
                                       activation),
        shape((m, H), jnp.float32), shape((4, H, fin), jnp.float32),
        shape((4, f, H), jnp.float32))
    assert served is None, why


def test_off_the_tpu_there_is_no_fused_pair():
    xs, w_in, w_out, _, gs = _pair_operands(LAYOUTS["skewed"], "swiglu")
    assert gm.grouped_mlp(xs, w_in, w_out, gs, "swiglu") is None


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("tm", [128, 256, 512])
def test_visit_table_covers_every_row_once(layout, tm):
    """Each row is inside exactly one visit's (tile, group) intersection,
    visits walk the tiles and the groups in order, every group (an empty
    one too) has a visit, and the entries past `count` repeat the last."""
    sizes = np.asarray(LAYOUTS[layout])
    offsets, gids, tids, count = (np.asarray(a) for a in gm.group_visits(
        jnp.asarray(sizes, jnp.int32), M, tm)[:4])
    count = int(count[0])
    assert len(gids) == len(tids) == M // tm + len(sizes) - 1
    assert count <= len(gids)
    np.testing.assert_array_equal(offsets, np.concatenate([[0],
                                                           sizes.cumsum()]))
    covered = np.zeros(M, np.int32)
    for g, t in zip(gids[:count], tids[:count]):
        lo = max(offsets[g], t * tm)
        hi = min(offsets[g + 1], (t + 1) * tm)
        assert hi > lo or sizes[g] == 0, (g, t)
        covered[lo:max(lo, hi)] += 1
    assert (covered == 1).all()
    assert (np.diff(gids) >= 0).all() and (np.diff(tids) >= 0).all()
    assert set(gids[:count]) == set(range(len(sizes)))
    assert (gids[count:] == gids[count - 1]).all()
    assert (tids[count:] == tids[count - 1]).all()


@pytest.mark.parametrize("m,k,n,groups", [
    (32768, 2048, 2048, 64), (32768, 1024, 2048, 64),   # the OLMoE cell
    (32768, 2048, 1024, 64),
    (8192, 4096, 28672, 8), (8192, 14336, 4096, 8),     # Mixtral-shaped
    (512, 256, 384, 4), (128, 128, 128, 1), (4096, 640, 1152, 16),
])
@pytest.mark.parametrize("tgmm", [False, True], ids=["gmm", "tgmm"])
def test_picked_tiles_divide_the_shape(m, k, n, groups, tgmm):
    tm, tk, tn = gm.pick_gmm_tiles(m, k, n, groups, tgmm=tgmm)
    assert m % tm == 0 and k % tk == 0 and n % tn == 0, (tm, tk, tn)
    assert tm % 128 == 0 and tk % 128 == 0 and tn % 128 == 0, (tm, tk, tn)
    # the row tile follows the rows and the groups alone: one visit table
    # serves every product over the same groups
    assert tm == gm.pick_row_tile(m, groups)


@pytest.mark.parametrize("shape,gmm,tgmm", [
    # the OLMoE cell's products (PERF.md, PR 27): one k tile, widest tn
    ((32768, 2048, 2048, 64), (512, 2048, 2048), (512, 1024, 2048)),
    ((32768, 1024, 2048, 64), (512, 1024, 2048), (512, 1024, 2048)),
    ((32768, 2048, 1024, 64), (512, 2048, 1024), (512, 2048, 1024)),
    # Mixtral-shaped: k tiles accumulate, so half the slab
    ((8192, 4096, 28672, 8), (512, 1024, 2048), (512, 1024, 2048)),
    ((8192, 14336, 4096, 8), (512, 1024, 2048), (512, 1024, 2048)),
    # groups smaller than a tile in the mean: the smallest row tile
    ((4096, 2048, 2048, 64), (128, 2048, 2048), (128, 2048, 2048)),
    ((16384, 2048, 2048, 64), (256, 2048, 2048), (256, 2048, 2048)),
])
def test_picked_tiles_are_the_swept_rule(shape, gmm, tgmm):
    assert gm.pick_gmm_tiles(*shape) == gmm
    assert gm.pick_gmm_tiles(*shape, tgmm=True) == tgmm


@pytest.mark.parametrize("m,k,n", [
    (1, 2048, 2048),        # single-row decode through an MoE layer
    (8, 2048, 2048),        # a decode batch
    (4095 * 8, 2048, 2048),  # an odd row count
    (512, 200, 384), (512, 256, 100),   # lanes that 128 does not divide
])
def test_declined_shapes_are_ragged_dot(as_on_one_tpu, m, k, n):
    """Shapes the tiles do not divide are declined, and the function is
    then `lax.ragged_dot` itself, on a TPU too."""
    assert gm.pick_gmm_tiles(m, k, n, 4) is None
    assert gm.pick_gmm_tiles(m, k, n, 4, tgmm=True) is None
    lhs = jnp.zeros((m, k), jnp.float32)
    rhs = jnp.zeros((4, k, n), jnp.float32)
    gs = jnp.asarray([m, 0, 0, 0], jnp.int32)
    text = str(jax.make_jaxpr(
        lambda a, b: gm.grouped_matmul(a, b, gs))(lhs, rhs))
    assert "ragged_dot" in text and "pallas_call" not in text


def test_off_the_tpu_the_function_is_ragged_dot():
    """On this backend (the CPU) nothing of the kernels is traced: same
    primitive, same result, no visit table."""
    lhs, rhs, _, gs = _operands(LAYOUTS["skewed"])
    assert gm.visits_for(gs, M) is None
    ours = jax.make_jaxpr(lambda a, b: gm.grouped_matmul(a, b, gs))(lhs, rhs)
    plain = jax.make_jaxpr(lambda a, b: jax.lax.ragged_dot(a, b, gs))(
        lhs, rhs)
    assert str(ours) == str(plain)
    assert "ragged_dot" in str(ours)


def test_under_a_mesh_of_several_devices_the_function_is_ragged_dot(
        monkeypatch):
    """GSPMD cannot partition a Mosaic call: with more than one device in
    the ambient mesh the products stay `lax.ragged_dot` on a TPU too."""
    from jax.sharding import Mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gm._one_tpu()
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    with jax.sharding.set_mesh(mesh):
        assert not gm._one_tpu()


def test_one_visit_table_serves_both_products(as_on_one_tpu, monkeypatch):
    """`visits_for` builds the layer's one table; handed to both products,
    neither builds its own."""
    lhs, rhs, _, gs = _operands(LAYOUTS["skewed"])
    back = jnp.swapaxes(rhs, 1, 2)
    built = []
    build = gm.group_visits
    monkeypatch.setattr(
        gm, "group_visits",
        lambda sizes, m, tm: built.append(tm) or build(sizes, m, tm))

    def layer(shared):
        del built[:]
        visits = gm.visits_for(gs, M) if shared else None
        mid = gm.grouped_matmul(lhs, rhs, gs, visits=visits)
        return gm.grouped_matmul(mid, back, gs, visits=visits), list(built)

    shared, once = layer(True)
    apart, twice = layer(False)
    assert once == [gm.pick_row_tile(M, len(gs))] and twice == 2 * once
    np.testing.assert_allclose(shared, apart)


# ---------------------------------------------------------------------------
# dead steps: a visit without rows names the blocks already in VMEM
# ---------------------------------------------------------------------------

# group sizes over the 512 rows that leave empty groups in front, in the
# middle and behind, and (a share of the experts, a serving step's rows
# nobody reads) rows behind the last group
DEAD_LAYOUTS = {
    "empty_in_front": [0, 0, 200, 312],
    "empty_in_the_middle": [130, 0, 0, 0, 254, 128],
    "empty_behind": [256, 256, 0, 0, 0],
    "empty_everywhere": [0, 100, 0, 0, 28, 0, 384, 0],
    "rows_behind_the_groups": [100, 0, 56, 0, 100, 0],
    "one_row_groups_a_few": [0, 1, 0, 0, 1, 1, 0, 0, 0, 1, 0, 0],
}


def _grid_blocks(sizes, tm, nk, nj=2, transpose=False):
    """Each `moe_gmm` index map's block indices over the grid (n tile,
    visit, k tile), k innermost, with which steps are live."""
    visits = gm.group_visits(jnp.asarray(sizes, jnp.int32), M, tm)
    table = [np.asarray(a) for a in visits]
    count = int(visits.count[0])
    lhs_map, rhs_map, out_map = gm._gmm_index_maps(nk, transpose)
    steps = []
    for j in range(nj):
        for v in range(len(visits.group_ids)):
            live = v < count and sizes[int(visits.group_ids[v])] > 0
            for ki in range(nk):
                steps.append((live, (j, v, ki), tuple(
                    tuple(int(i) for i in m(j, v, ki, *table))
                    for m in (lhs_map, rhs_map, out_map))))
    return steps, table


@pytest.mark.parametrize("nk", [1, 3])
@pytest.mark.parametrize("tm", [128, 256])
@pytest.mark.parametrize("layout", sorted(DEAD_LAYOUTS))
def test_a_dead_step_names_the_blocks_of_the_step_before(layout, tm, nk):
    """Over the grid no index map names an empty group's rhs block, a
    live step names its own visit's blocks at its own k tile, and no
    block index changes across a dead step (a visit past `count`, or of
    an empty group), whichever k tile it stands at: the pipeline copies
    nothing for it. Dead steps in front of a pass's first live one name
    what that one will."""
    sizes = DEAD_LAYOUTS[layout]
    steps, (_, gids, tids, _, _) = _grid_blocks(sizes, tm, nk)
    assert any(live for live, _, _ in steps)
    assert not all(live for live, _, _ in steps)
    for at, (live, (j, v, ki), (lhs, rhs, out)) in enumerate(steps):
        assert sizes[rhs[0]] > 0, (v, ki, rhs)
        if live:
            assert lhs == (tids[v], ki) and out == (tids[v], j)
            assert rhs == (gids[v], ki, j)
            continue
        before = steps[at - 1] if at else None
        if before is not None and before[1][0] == j and (
                before[0] or before[2] == (lhs, rhs, out)):
            assert before[2] == (lhs, rhs, out), (v, ki)
        else:
            # in front of the pass's first live step: that step's blocks
            ahead = next(s for s in steps[at:] if s[0])
            assert ahead[1][0] == j and ahead[1][2] == 0
            assert ahead[2] == (lhs, rhs, out), (v, ki)


def test_the_transposed_product_names_the_same_steps():
    """rhs on its last axis (the rows' gradient): the same visits and k
    tiles, the block's two last indices exchanged."""
    sizes = DEAD_LAYOUTS["empty_everywhere"]
    plain, _ = _grid_blocks(sizes, 128, 3)
    back, _ = _grid_blocks(sizes, 128, 3, transpose=True)
    for (_, _, (lhs, rhs, out)), (_, _, (lhs_t, rhs_t, out_t)) in zip(
            plain, back):
        assert (lhs, out) == (lhs_t, out_t)
        assert rhs == (rhs_t[0], rhs_t[2], rhs_t[1])


def test_where_no_group_holds_a_row_every_step_names_one_block():
    steps, _ = _grid_blocks([0, 0, 0, 0], 128, 3, nj=1)
    assert len({blocks for _, _, blocks in steps[3:]}) == 1


@pytest.mark.parametrize("nk", [1, 3])
@pytest.mark.parametrize("kernel", ["gmm", "gmm_transposed"])
@pytest.mark.parametrize("layout", sorted(DEAD_LAYOUTS))
def test_gmm_over_dead_visits_equals_ragged_dot(kernel, layout, nk):
    """`moe_gmm` both ways round over tables with dead visits in every
    place, at one k tile and at three: the groups' rows are
    `lax.ragged_dot`'s (rows behind the last group are nobody's)."""
    sizes = DEAD_LAYOUTS[layout]
    rows = sum(sizes)
    lhs, rhs, weight, gs = _operands(sizes, k=128 * nk, n=256)
    visits = gm.group_visits(gs, M, 128)
    if kernel == "gmm":
        got = gm._gmm(lhs, rhs, visits, (128, 128, 128), False)
        want = jax.lax.ragged_dot(lhs[:rows], rhs, gs)
    else:
        back = jnp.swapaxes(rhs, 1, 2)           # [E, n, k]: contract k
        got = gm._gmm(lhs, back, visits, (128, 128, 128), True)
        want = jax.lax.ragged_dot(lhs[:rows], rhs, gs)
    np.testing.assert_allclose(got[:rows], want, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("layout", sorted(
    name for name, sizes in DEAD_LAYOUTS.items() if sum(sizes) == M))
def test_grouped_matmul_over_dead_visits_in_value_and_gradients(
        as_on_one_tpu, layout):
    """`grouped_matmul` (forward, the rows' gradient, the weights') over
    those of the tables whose groups hold every row, against
    `lax.ragged_dot` and its gradients; an empty group's gradient is
    exactly zero."""
    sizes = DEAD_LAYOUTS[layout]
    lhs, rhs, weight, gs = _operands(sizes)
    got = _value_and_grads(lambda a, b: gm.grouped_matmul(a, b, gs),
                           lhs, rhs, weight)
    want = _value_and_grads(lambda a, b: jax.lax.ragged_dot(a, b, gs),
                            lhs, rhs, weight)
    for g, w, what in zip(got, want, ("value", "d lhs", "d rhs")):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-4, err_msg=what)
    for e, size in enumerate(sizes):
        if size == 0:
            assert not np.any(np.asarray(got[2][e])), e


@pytest.mark.parametrize("activation", ["swiglu", "squared_relu"])
@pytest.mark.parametrize("layout", sorted(DEAD_LAYOUTS))
def test_fused_pair_over_dead_visits_in_value_and_gradients(
        as_on_one_tpu, layout, activation):
    """`grouped_mlp` over the same tables (rows behind the last group
    where the layout leaves some), forward and the three gradients,
    against `ragged_dot`, the activation, `ragged_dot`."""
    sizes = DEAD_LAYOUTS[layout]
    xs, w_in, w_out, weight, gs = _pair_operands(sizes, activation)
    got, _ = _fused_pair(xs, w_in, w_out, weight, gs, activation,
                         ragged=sum(sizes) != M)
    _assert_pair(got, _dense_pair(xs, w_in, w_out, weight, gs, activation),
                 rtol=2e-5, atol=5e-4)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("layout", ["empty_everywhere",
                                    "rows_behind_the_groups"])
def test_a_layer_of_the_stacks_over_dead_visits(as_on_one_tpu, layout,
                                                layer):
    """`grouped_mlp_of_layer`: the layer's matrices read in the stacks
    through the shifted table give, bit for bit, what the same kernels
    give over the layer's own matrices, and `ragged_dot`'s values."""
    sizes = DEAD_LAYOUTS[layout]
    rows = sum(sizes)
    xs, w_in, w_out, weight, gs = _pair_operands(sizes, "swiglu")
    stack_in = jnp.stack([w_in * (i + 1) for i in range(3)])
    stack_out = jnp.stack([w_out / (i + 1) for i in range(3)])
    got = gm.grouped_mlp_of_layer(xs, stack_in, stack_out,
                                  jnp.int32(layer), gs, "swiglu")
    alone, _ = gm.grouped_mlp(xs, stack_in[layer], stack_out[layer], gs,
                              "swiglu", ragged=True)
    np.testing.assert_array_equal(got[:rows], alone[:rows])
    want = _dense_pair(xs, stack_in[layer], stack_out[layer], weight, gs,
                       "swiglu")[0]
    np.testing.assert_allclose(got[:rows], want, rtol=2e-5, atol=2e-4)
