"""`ssd_step`: one position of the Mamba-2 recurrence for every row of the
state store, in place (ops/ssm.py has the equations).

A decode tick advances the state of every slot by one position: each
element is read once, multiplied by its head's decay, given its head's
dt x times its group's B, written back, and summed against its group's C
into y. As XLA's fusion over a layer's rows sliced out of the store the
step read and wrote the rows several times over (the slice, B and C spread
over the inner width as arrays of the state's size, the update written
back into the store: 10 ms a layer of the benchmark's Nemotron share, where
the state's 268 MB read and written once are 0.66 ms at the HBM's peak).
Here the store [layers, rows, N, d_i] is the kernel's operand and, aliased,
its result: the grid is (rows, groups), a step holds one row's state of one
group, [N, d_i / G], the layer comes as a scalar the index maps read, and
nothing of the store but the layer's blocks is touched.

Layout: the inner width runs along the lanes, the state N along the
sublanes. A head's decay and dt x are per channel ([1, lanes] rows that
broadcast down the sublanes); a group's B and C are per state row, one
number a sublane: they come in as [1, N] rows and are turned inside the
kernel (spread down 128 sublanes, then transposed) into [N, 128] tiles
whose every column is the vector. All float32, all on the vector unit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (`ft._interpret` is read at the call: a test that steers it may be the
# first to import this module)
from megatron_tpu.ops.pallas import flash_template as ft

F32 = jnp.float32
_LANE = 128


def serves(n_state: int, group_width: int) -> bool:
    """The shapes the kernel tiles: the state a whole number of lane rows
    (its B and C are turned through a [128, N] tile), a group's channels
    too."""
    return n_state % _LANE == 0 and group_width % _LANE == 0


def _kernel(layer_ref, s_ref, decay_ref, dtx_ref, b_ref, c_ref, y_ref,
            out_ref):
    del layer_ref  # read by the index maps
    n = s_ref.shape[0]
    # [1, N] along the lanes -> [N, 128], the vector down every column
    down = lambda ref: jnp.broadcast_to(ref[...], (_LANE, n)).T  # noqa: E731
    b, c = down(b_ref), down(c_ref)
    for j in range(s_ref.shape[1] // _LANE):
        cols = slice(j * _LANE, (j + 1) * _LANE)
        s = decay_ref[:, cols] * s_ref[:, cols] + dtx_ref[:, cols] * b
        out_ref[:, cols] = s
        y_ref[:, cols] = jnp.sum(s * c, axis=0, keepdims=True)


def ssd_step(store, layer, decay, dtx, b, c):
    """store [L, R, N, d_i] float32: layer `layer`'s rows advance one
    position, in place (donate it). decay, dtx [R, d_i]: each channel's
    factor exp(dt A) and input dt x (a row that does not decode: 1 and 0,
    and its state stays). b, c [R, G, N]. Returns (y [R, d_i], the
    store)."""
    _, rows, n, di = store.shape
    groups = b.shape[1]
    width = di // groups
    per_channel = pl.BlockSpec((None, 1, width), lambda r, g, layer: (r, 0, g))
    per_state = pl.BlockSpec((None, None, 1, n),
                             lambda r, g, layer: (r, g, 0, 0))
    state = pl.BlockSpec((None, None, n, width),
                         lambda r, g, layer: (layer[0], r, 0, g))
    y, store = ft._named_pallas_call(
        "ssd_step", _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows, groups),
            in_specs=[state, per_channel, per_channel, per_state, per_state],
            out_specs=[per_channel, state]),
        out_shape=[jax.ShapeDtypeStruct((rows, 1, di), F32),
                   jax.ShapeDtypeStruct(store.shape, F32)],
        # the store (operand 1, behind the scalar) is the second result
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=ft._interpret(),
    )(jnp.asarray(layer, jnp.int32).reshape(1), store,
      decay[:, None], dtx[:, None], b[:, :, None], c[:, :, None])
    return y[:, 0], store
