"""The row movements of a call that hands `kept` walk the live rows
(ops/moe.py `_walk_blocks`): block by block over the held rows of expert
order and over the kept (token, choice) pairs of token order, with the
bits of the one pass over all N*k rows, which stays here as the reference
(as tests/test_moe.py keeps the scatter forms the gathers replaced)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.models import presets
from megatron_tpu.models.params import init_params
from megatron_tpu.ops import moe

N, K, H, EXPERTS, HELD = 64, 4, 16, 16, 4
# rows a trip on the expert side, tokens a trip on the token side: small
# enough that the toy call has several blocks and a boundary inside one
BLOCKS = (16, 8)


@pytest.fixture
def walked(monkeypatch):
    """The toy shapes take the walked passes, at the toy's blocks:
    `_walk_blocks` is the one place the passes ask."""
    monkeypatch.setattr(moe, "_walk_blocks", lambda n, k: BLOCKS)


# --- the one pass over all N*k rows: what the walked passes replace ---------

def _one_pass_sum(rows, inv, topw=None, kept=None):
    total = None
    for j in range(inv.shape[1]):
        term = rows[inv[:, j]].astype(jnp.float32)
        if topw is not None:
            term = term * topw[:, j, None]
        term = jnp.where(kept[:, j, None], term, 0.0)
        total = term if total is None else total + term
    return total


def _one_pass(xf, topw, order, inv, kept, dy, product):
    """(y, d xf, d out, d topw) of dispatch -> `product` -> combine with
    every pass over all N*k rows; the rows behind the kept ones read
    through `kept` alone, as the walked form's callers read them."""
    k = inv.shape[1]
    tokens = order // k
    xs = xf[tokens]
    out = product(xs)
    y = _one_pass_sum(out, inv, topw, kept).astype(xf.dtype)
    gates = jnp.where(kept, topw, 0.0)
    w = jnp.zeros(order.shape, topw.dtype).at[inv.reshape(-1)].set(
        gates.reshape(-1))
    dy_rows = dy[tokens].astype(jnp.float32)
    d_out = (dy_rows * w[:, None]).astype(out.dtype)
    d_w = jnp.sum(out.astype(jnp.float32) * dy_rows, axis=-1)
    d_topw = jnp.where(kept, d_w[inv], 0.0)
    d_xs = jax.vjp(product, xs)[1](d_out)[0]
    d_xf = _one_pass_sum(d_xs, inv, kept=kept).astype(xf.dtype)
    return y, d_xf, d_out, d_topw


def _walked_passes(xf, topw, order, inv, kept, dy, product):
    """The same through rows_to_expert_order / rows_to_token_order."""
    def layer(xf, topw):
        xs = moe.rows_to_expert_order(xf, order, inv, kept)
        out = product(xs)
        return moe.rows_to_token_order(out, topw, order, inv, xf.dtype,
                                       kept), out

    (y, out), back = jax.vjp(layer, xf, topw)
    d_xf, d_topw = back((dy, jnp.zeros_like(out)))
    d_out = jax.vjp(
        lambda o: moe.rows_to_token_order(o, topw, order, inv, xf.dtype,
                                          kept), out)[1](dy)[0]
    return y, d_xf, d_out, d_topw


def _choices(case, key):
    """(topi [N, K] over EXPERTS experts, kept [N, K]) of a routing."""
    first = jnp.arange(K)
    held = lambda topi: topi < HELD
    if case == "collapsed":
        # every token the same experts, two of them held
        topi = jnp.broadcast_to(jnp.array([1, HELD + 3, 2, HELD])[:K], (N, K))
        return topi, held(topi)
    if case in ("uniform", "rows_read", "share_and_rows_read"):
        topi = jax.vmap(lambda k: jax.random.permutation(k, EXPERTS)[:K])(
            jax.random.split(key, N))
        read = (jnp.arange(N) % 16 < 11)[:, None]
        kept = {"uniform": held(topi),
                "rows_read": jnp.broadcast_to(read, topi.shape),
                "share_and_rows_read": held(topi) & read}[case]
        return topi, kept
    if case == "none_held":
        topi = jnp.broadcast_to(HELD + first, (N, K))
        return topi, held(topi)
    if case == "all_held":
        topi = jax.vmap(lambda k: jax.random.permutation(k, HELD)[:K])(
            jax.random.split(key, N))
        return topi, held(topi)
    if case == "ragged_boundary":
        # 3 * 13 = 39 held rows: no multiple of either block
        topi = jnp.broadcast_to(HELD + first, (N, K))
        topi = topi.at[:13, :3].set(jnp.array([0, 2, 3]))
        return topi, held(topi)
    raise ValueError(case)


def _coarse(a):
    """On bfloat16's grid, whatever the dtype that holds it: the product
    of two such numbers is exact in float32, so a compiler that folds a
    multiply and an add into one rounding in one form and not in the other
    (XLA's CPU backend does) changes no bit, and what is compared is the
    order of the sums."""
    return a.astype(jnp.bfloat16).astype(jnp.float32)


CASES = ["collapsed", "uniform", "none_held", "all_held", "ragged_boundary",
         "rows_read", "share_and_rows_read"]


def _setup(case, dtype):
    key = jax.random.PRNGKey(CASES.index(case))
    kx, kw, kd, kp, kc = jax.random.split(key, 5)
    topi, kept = _choices(case, kc)
    xf = _coarse(jax.random.normal(kx, (N, H))).astype(dtype)
    topw = _coarse(jax.nn.softmax(jax.random.normal(kw, (N, K)), axis=-1))
    dy = _coarse(jax.random.normal(kd, (N, H))).astype(dtype)
    mat = _coarse(jax.random.normal(kp, (H, H))).astype(dtype)
    # the rows whose expert is held elsewhere sort behind the held ones
    order, inv = moe.sort_by_expert(jnp.where(kept, topi, EXPERTS))
    return (xf, topw, order, inv, kept, dy,
            lambda rows: _coarse(jnp.tanh(rows @ mat)).astype(dtype))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_the_walked_passes_keep_the_one_pass_bits(walked, case, dtype):
    args = _setup(case, jnp.dtype(dtype))
    kept, order = args[4], args[2]
    want = jax.jit(_one_pass, static_argnums=6)(*args)
    got = jax.jit(_walked_passes, static_argnums=6)(*args)
    live = np.arange(order.shape[0]) < int(kept.sum())
    for name, a, b in zip(("y", "d_xf", "d_out", "d_topw"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if name == "d_out":
            # expert order: the rows behind the kept ones are nobody's
            a, b = a[live], b[live]
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert np.all(np.isfinite(np.asarray(got[2], np.float32)))


# --- what stands behind the live rows is nobody's, whatever it is ------------

def _held_experts(held):
    """(cfg, the matrices) of a toy layer that holds `held` experts."""
    cfg = _toy_cfg(moe_experts_held=None if held == EXPERTS else held,
                   params_dtype="bfloat16")
    layer = _toy_layer(cfg)
    return cfg, {name: layer[name] for name in moe.EXPERT_MATRICES}


def _as_the_chip_leaves_it(product, live):
    """`product(p, xs)` with what the chip's kernels leave behind the
    live rows of their result and of the rows' gradient: they visit no
    tile there, so it is whatever the buffer held. Here: NaN."""
    poison = lambda a: jnp.where(live[:, None], a, jnp.nan)

    @jax.custom_vjp
    def left(p, xs):
        return poison(product(p, xs))

    def fwd(p, xs):
        out, back = jax.vjp(product, p, xs)
        return poison(out), back

    def bwd(back, d_out):
        d_p, d_xs = back(d_out)
        return d_p, poison(d_xs)

    left.defvjp(fwd, bwd)
    return left


def _layer_and_gradients(p, xf, topw, order, inv, kept, dy, product):
    """(y, d xf, d topw, the matrices' gradients, d out) of dispatch ->
    product -> combine through the module's two functions."""
    def layer(p, xf, topw):
        out = product(p, moe.rows_to_expert_order(xf, order, inv, kept))
        return moe.rows_to_token_order(out, topw, order, inv, xf.dtype,
                                       kept), out

    (y, out), back = jax.vjp(layer, p, xf, topw)
    d_p, d_xf, d_topw = back((dy, jnp.zeros_like(out)))
    d_out = jax.vjp(
        lambda o: moe.rows_to_token_order(o, topw, order, inv, xf.dtype,
                                          kept), out)[1](dy)[0]
    return y, d_xf, d_topw, d_p, d_out


@pytest.mark.parametrize("case", CASES)
def test_garbage_behind_the_live_rows_reaches_no_result(monkeypatch, case):
    """On the chip the walked dispatch writes into a buffer nobody has
    written (`unwritten_rows`), the kernels leave what they found behind
    the held groups, and the combine's backward writes `d out` over `out`:
    behind the live rows stands whatever the memory held. Off the chip
    that buffer is zeros, so here it is NaN, in the buffer, in the
    products' result and in the rows' gradient: every reader has to leave
    those rows out by a `where`, never by a multiply, and what comes of
    the layer (`y`, `d xf`, `d topw`, the kept rows of `d out`, the
    experts' matrices' gradients through `experts_mlp`, ragged) is finite
    and the one pass's to the last bit."""
    from megatron_tpu.ops.pallas import grouped_matmul

    # `rows_read` alone: every expert is held, and a row counts or not
    held = EXPERTS if case == "rows_read" else HELD
    cfg, p = _held_experts(held)
    xf, topw, order, inv, kept, dy, _ = _setup(case, jnp.bfloat16)
    live = jnp.arange(order.shape[0]) < kept.sum()
    # the groups of expert order as `_setup` sorted it: a kept row's own
    # expert, the rows of no held expert behind them
    topi = _choices(case, jax.random.split(
        jax.random.PRNGKey(CASES.index(case)), 5)[4])[0]
    sizes = jnp.bincount(jnp.where(kept, topi, EXPERTS).reshape(-1),
                         length=EXPERTS + 1)[:held].astype(jnp.int32)
    assert int(sizes.sum()) == int(kept.sum())

    def product(p, xs):
        return moe.experts_mlp(cfg, p, xs, sizes, None, xs.dtype,
                               ragged=True)[0]

    args = (p, xf, topw, order, inv, kept, dy)
    monkeypatch.setattr(moe, "_walk_blocks", lambda n, k: None)
    want = jax.jit(_layer_and_gradients, static_argnums=7)(*args, product)
    monkeypatch.setattr(moe, "_walk_blocks", lambda n, k: BLOCKS)
    monkeypatch.setattr(
        grouped_matmul, "unwritten_rows",
        lambda shape, dtype, after: jnp.full(shape, jnp.nan, dtype))
    got = jax.jit(_layer_and_gradients, static_argnums=7)(
        *args, _as_the_chip_leaves_it(product, live))
    # the poison is there: behind the blocks that hold kept rows the
    # walked d out is what out was
    walked_over = -(-int(kept.sum()) // BLOCKS[0]) * BLOCKS[0]
    assert np.all(np.isnan(np.asarray(got[4], np.float32)[walked_over:]))
    got, want = (
        (*r[:3], r[3]["w_in"], r[3]["w_out"], r[4][np.asarray(live)])
        for r in (got, want))
    for name, a, b in zip(("y", "d_xf", "d_topw", "d_w_in", "d_w_out",
                           "d_out"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.all(np.isfinite(a)), name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("case", CASES)
def test_the_walked_dispatch_fills_the_live_rows_and_no_others(walked, case):
    xf, _, order, inv, kept, _, _ = _setup(case, jnp.float32)
    xs = jax.jit(moe.rows_to_expert_order)(xf, order, inv, kept)
    held = int(kept.sum())
    np.testing.assert_array_equal(xs[:held], xf[order[:held] // K])
    # whole blocks are filled; behind them the buffer's zeros
    filled = -(-held // BLOCKS[0]) * BLOCKS[0]
    assert not np.any(np.asarray(xs[filled:]))


def test_the_moved_rows_follow_the_held_rows_of_a_uniform_router(walked):
    """A router whose tokens all choose differently: the passes still move
    the held rows and one row a token, plus half a block a boundary. A
    form that saved only where tokens choose alike would read ~1 here."""
    _, _, _, _, kept, _, _ = _setup("uniform", jnp.float32)
    share = float(kept.mean())
    assert 0.15 < share < 0.35
    slack = (BLOCKS[0] + K * BLOCKS[1]) / (N * K)
    moved = float(jax.jit(moe.moved_rows_share)(kept))
    assert share < moved <= share + 1 / K + slack
    assert moved < 0.75


@pytest.mark.parametrize("case,moved", [
    ("none_held", N / (2 * N * K)),
    ("all_held", (2 * N * K + N) / (2 * N * K)),
    ("ragged_boundary", (48 + 3 * 16 + N) / (2 * N * K)),
])
def test_the_moved_rows_are_counted_by_the_block(walked, case, moved):
    kept = _setup(case, jnp.float32)[4]
    assert float(moe.moved_rows_share(kept)) == pytest.approx(moved)


def test_the_one_pass_stays_under_the_row_count_and_reads_one():
    """At the toy's row count nothing walks: the shapes decide."""
    assert moe._walk_blocks(N, K) is None
    own = (moe._EXPERT_BLOCK, moe._TOKEN_BLOCK)
    assert moe._walk_blocks(16384, 8) == own          # the Mellum call
    assert moe._walk_blocks(8192, 8) == own
    assert moe._walk_blocks(512, 22) is None          # a served chunk
    assert moe._walk_blocks(64, 22) is None           # a served tick
    # a token count the blocks do not divide: no block hangs over the end
    assert moe._walk_blocks(16384 + 8, 8) is None
    kept = _setup("uniform", jnp.float32)[4]
    assert float(moe.moved_rows_share(kept)) == 1.0


def _loops(jaxpr, names=("while", "cond")):
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in names:
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _loops(sub, names)
    return found


def _toy_cfg(**kw):
    return presets.tiny(**{**dict(
        vocab_size=64, seq_length=N // 2, hidden_size=H,
        num_attention_heads=2, ffn_hidden_size=32, num_experts=EXPERTS,
        num_layers=1, moe_top_k=K, moe_dispatch="dropless",
        params_dtype="float32"), **kw})


def _toy_layer(cfg):
    layers = init_params(cfg, jax.random.PRNGKey(3))["layers"]
    return jax.tree.map(lambda a: a[0], layers["moe"])


def test_without_kept_the_block_holds_no_loop(walked):
    """The dense-MoE call (no share, no rows_read) is the one pass even
    where a share would walk: its traced program, forward and backward,
    holds no `while` and no `cond`."""
    cfg = _toy_cfg()
    layer = _toy_layer(cfg)
    x = jnp.ones((2, N // 2, H))

    def loss(p, x):
        return jnp.sum(moe.moe_block_dropless(cfg, p, x)[0])

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(layer, x)
    assert _loops(jaxpr.jaxpr) == []


def test_a_share_walks_and_journals_how_far(walked):
    """The share's program does hold the loops, and its load vector ends
    with the moved rows' share."""
    cfg = _toy_cfg(moe_experts_held=HELD)
    layer = _toy_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, N // 2, H))

    def loss(p, x):
        return jnp.sum(moe.moe_block_dropless(cfg, p, x)[0])

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(layer, x)
    assert "while" in _loops(jaxpr.jaxpr)
    _, _, load = moe.moe_block_dropless(cfg, layer, x)
    assert load.shape == (4,) and float(load[2]) == 0.0
    assert float(load[1]) < float(load[3]) <= 1.0
    _, _, read = moe.moe_block_dropless(
        cfg, layer, x, rows_read=jnp.array([N // 2, 5], jnp.int32))
    # the same places with the word: the served counters read the second
    # and the third by their place, the moved rows' share stands last
    assert read.shape == (4,) and float(read[2]) >= 1.0
    assert float(read[1]) < float(load[1])
    assert float(read[1]) < float(read[3]) < float(load[3])
    assert moe.moe_stats_zero(cfg).shape == (5,)
