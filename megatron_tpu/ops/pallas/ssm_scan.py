"""`ssm_scan`: the selective scan of ops/ssm.py as one Pallas kernel.

The recurrence is sequential in time and elementwise in (state, channel):
nothing for the matrix unit, and as XLA's `while` over time every step is
a handful of small fusions that read and write `h` in HBM (a 512-token
chunk of the benchmark's typed configuration: 512 x 26 such steps). Here
the grid is (sequence, blocks of the inner width, blocks of time), the time
loop runs inside the kernel, and `h` [N, block] stays in VMEM (in vector
registers within a block of time) from the chunk's first position to its
last: per step the discretisation (exp(delta A), delta B x), the
contraction with C, the D skip and the silu(z) gate, all float32 on the
vector unit. The valid length is a scalar in SMEM: positions past it leave
`h` as it was. State in, state out.

Layout: the inner width runs along the lanes, the state N along the
sublanes, so `h` of one lane tile is [N, 128]: two vector registers at
N = 16. B_t and C_t have to multiply ROWS of it, one number a sublane; they
come in with each number repeated along a lane tile ([T, N, 128], 4 MB a
sequence of 512), which a load then hands over in the right shape. Time
goes in groups of 8 positions, a sublane tile of x, delta, z and y.

The gradient rule is `jax.vjp` of the plain form, recomputed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_tpu.ops.pallas.flash_template import (
    _interpret, _named_pallas_call,
)

F32 = jnp.float32
_GROUP = 8   # positions a step of the time loop: one sublane tile


def _kernel(valid_ref, x_ref, dt_ref, z_ref, a_ref, b_ref, c_ref, d_ref,
            h0_ref, y_ref, h_ref, h_scr, *, block_t: int, lane: int):
    row, ti = pl.program_id(0), pl.program_id(2)

    @pl.when(ti == 0)
    def _():
        h_scr[...] = h0_ref[0]

    valid = valid_ref[row]
    tiles = a_ref.shape[1] // lane   # lane tiles of this block of channels
    cols = [slice(j * lane, (j + 1) * lane) for j in range(tiles)]
    a = [a_ref[:, c] for c in cols]                      # [N, lane] each
    d = [d_ref[:, c] for c in cols]                      # [1, lane]

    def group(g, h):
        t0 = pl.multiple_of(g * _GROUP, _GROUP)
        at = pl.ds(t0, _GROUP)
        x = [x_ref[0, at, c] for c in cols]              # [8, lane] each
        dt = [dt_ref[0, at, c] for c in cols]
        h, rows = list(h), [[] for _ in cols]
        for i in range(_GROUP):
            live = ti * block_t + t0 + i < valid
            b_t, c_t = b_ref[0, t0 + i], c_ref[0, t0 + i]   # [N, lane]
            for j in range(tiles):
                x_t, dt_t = x[j][i:i + 1], dt[j][i:i + 1]   # [1, lane]
                new = jnp.exp(dt_t * a[j]) * h[j] + (dt_t * x_t) * b_t
                h[j] = jnp.where(live, new, h[j])
                rows[j].append(jnp.sum(h[j] * c_t, axis=0, keepdims=True))
        for j, c in enumerate(cols):
            z = z_ref[0, at, c]
            y = jnp.concatenate(rows[j], axis=0) + d[j] * x[j]
            y_ref[0, at, c] = y * (z * jax.nn.sigmoid(z))
        return tuple(h)

    h = jax.lax.fori_loop(0, block_t // _GROUP, group,
                          tuple(h_scr[:, c] for c in cols))
    for j, c in enumerate(cols):
        h_scr[:, c] = h[j]

    @pl.when(ti == pl.num_programs(2) - 1)
    def _():
        h_ref[0] = h_scr[...]


def _largest_dividing(n: int, candidates) -> int:
    return next((c for c in candidates if n % c == 0), n)


def _forward(x, delta, a, b, c, d_skip, z, h0, valid):
    B, T, di = x.shape
    N = a.shape[0]
    # time in whole groups: the positions added lie past every valid length
    Tp = -(-T // _GROUP) * _GROUP
    if Tp != T:
        pad = lambda t: jnp.pad(  # noqa: E731
            t, ((0, 0), (0, Tp - T)) + ((0, 0),) * (t.ndim - 2))
        x, delta, z, b, c = map(pad, (x, delta, z, b, c))
    block_t = _largest_dividing(Tp, (128, 64, 32, 16, 8))
    block_d = _largest_dividing(di, (512, 256, 128))
    lane = min(128, block_d)
    repeated = lambda t: jnp.broadcast_to(  # noqa: E731
        t[..., None], t.shape + (lane,))

    chunk = pl.BlockSpec((1, block_t, block_d),
                         lambda r, j, t, valid: (r, t, j))
    per_step = pl.BlockSpec((1, block_t, N, lane),
                            lambda r, j, t, valid: (r, t, 0, 0))
    state = pl.BlockSpec((1, N, block_d), lambda r, j, t, valid: (r, 0, j))
    y, h = _named_pallas_call(
        "ssm_scan",
        functools.partial(_kernel, block_t=block_t, lane=lane),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, di // block_d, Tp // block_t),
            in_specs=[
                chunk, chunk, chunk,
                pl.BlockSpec((N, block_d), lambda r, j, t, valid: (0, j)),
                per_step, per_step,
                pl.BlockSpec((1, block_d), lambda r, j, t, valid: (0, j)),
                state,
            ],
            out_specs=[chunk, state],
            scratch_shapes=[pltpu.VMEM((N, block_d), F32)]),
        out_shape=[jax.ShapeDtypeStruct((B, Tp, di), F32),
                   jax.ShapeDtypeStruct((B, N, di), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(valid.astype(jnp.int32), x.astype(F32), delta.astype(F32),
      z.astype(F32), a.astype(F32), repeated(b.astype(F32)),
      repeated(c.astype(F32)), d_skip.astype(F32)[None], h0.astype(F32))
    return y[:, :T], h


@jax.custom_vjp
def ssm_scan(x, delta, a, b, c, d_skip, z, h0, valid):
    """ops/ssm.py `selective_scan`, same operands and results: (y [B, T,
    d_i] float32, h [B, N, d_i] after each row's last valid position)."""
    return _forward(x, delta, a, b, c, d_skip, z, h0, valid)


def _ssm_scan_fwd(*operands):
    return _forward(*operands), operands


def _ssm_scan_bwd(operands, cotangents):
    from megatron_tpu.ops.ssm import selective_scan

    *floats, valid = operands
    _, vjp = jax.vjp(lambda *f: selective_scan(*f, valid), *floats)
    return vjp(cotangents) + (None,)


ssm_scan.defvjp(_ssm_scan_fwd, _ssm_scan_bwd)
