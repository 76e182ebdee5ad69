"""Grouped matmul over ragged row groups: the dropless experts' product.

    grouped_matmul(lhs [m, k], rhs [E, k, n], group_sizes [E]) -> [m, n]

Row r of `lhs` belongs to group g when offsets[g] <= r < offsets[g + 1]
(offsets = the running sum of `group_sizes`, which must sum to m: every
row has an expert, the dropless dispatch's invariant) and is multiplied
by rhs[g]. That is `jax.lax.ragged_dot`, and on every backend but a TPU,
under a mesh of several devices, or at shapes the tiles do not divide,
this function IS `jax.lax.ragged_dot`. On one TPU it is Pallas kernels of
the program's own under one `jax.custom_vjp`:

  kernel      | product                          | grid
  ------------|----------------------------------|--------------------------
  `moe_gmm`   | out[rows of g] = lhs · rhs[g]    | (n tiles, visits, k tiles)
  `moe_gmm`   | dlhs = dout · rhs[g]ᵀ: the same  | the same; rhs's block is
              | kernel contracting rhs's last    | taken [tn, tk] from the
              | axis, no transposed copy in HBM  | stored [E, k, n]
  `moe_tgmm`  | drhs[g] = lhs[rows of g]ᵀ · dout | (n tiles, k tiles, visits)
  `moe_tgmm`  | acc[layer, g] += the same, in    | the same; the block of the
  (`sink=`)   | float32, unrounded, in place     | stacked [L, E, k, n] acc is
              |                                  | read, added to and written

The last row is for a step that accumulates rhs's gradient over several
calls (micro-batches): handed the float32 accumulator of every layer's
rhs as `sink=(stack, layer)`, the function sums the gradient where it is
made. A backward rule is handed nothing but cotangents, so the stack goes
through the function untouched and its COTANGENT is the accumulator: the
rule answers it with `moe_tgmm(..., into=that cotangent)`, whose result is
the operand's own buffer (`input_output_aliases`) with the layer's blocks
updated. No [E, k, n] gradient exists then, in any dtype, and no pass of
its own adds it (that pass took 2.2 times the kernel's time; PERF.md,
PR 33).

The algorithm is MegaBlocks' as jax ships it
(jax/experimental/pallas/ops/tpu/megablox): rows are cut into tiles of tm
aligned to the array, not to the groups, and a VISIT is one (row tile,
group) pair whose rows intersect. A tile wholly inside one group is
visited once; a tile that holds a group boundary once per group it
touches, keeping only that group's rows. So there are m / tm +
(groups - 1) visits at most. The visit table (`group_visits`) rides in as
scalar-prefetch operands and the BlockSpec index maps read it, so
consecutive visits of one group name the same rhs block and its [tk, tn]
slab stays in VMEM while the group's row tiles stream past.

The grid is the table's full length whatever the rows do, and a step
whose visit holds no row (an empty group's, or one past the visits there
are) is DEAD: in `moe_gmm` it computes nothing and, since a step's copies
are decided by its block indices alone, it also names, for every operand
and at every k tile, the blocks the step in front of it named
(`GroupVisits.fetch`, `_gmm_index_maps`), so nothing is copied for it. A
call's HBM traffic is the matrices of the groups that hold a row, each
once an n tile, and no others. (Before PR 62 an empty group cost its
whole matrix, and with several k tiles every visit past the table's end
cost the last group's once more: a third of the bytes of a served chunk's
second product.) `moe_tgmm` keeps the plain table: it writes an empty
group's zeros, and its visits are the innermost axis.

What differs from jax's copy, and why the kernels live here:
  * a name (`pallas_call(name=)` under `jax.named_scope`, flash_template's
    `_named_pallas_call`), so that a device trace books them under
    `mlp`/`moe_experts`; tiles chosen in code from the shapes
    (`pick_gmm_tiles`, swept on the chip) and a VMEM limit to match;
  * a boundary visit does a boundary's work, not a tile's: `moe_gmm`
    takes it in 128-row spans and skips those the group does not reach,
    `moe_tgmm` contracts over the smallest 128, 256, ... row window that
    holds the group's rows. With 64 groups of 512 rows in the mean nearly
    every tile holds a boundary, and a whole-tile product per visit is
    what kept the compiler's kernel at a third of the MXU's peak;
  * the store is masked only where a span holds a boundary; `moe_tgmm`
    zeroes one operand, not both; where one k tile spans the contraction
    there is no accumulator round trip;
  * one row tile for the three kernels, so one visit table for a layer,
    built once and shared by every product over the same groups
    (`visits=`).

Precision: operands reach the MXU in the dtype they arrive in, every
product accumulates in float32, results are cast to the operands' dtype on
the way out, as `lax.ragged_dot` and its gradients give them; the sum into
a sink stays float32 all the way.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from megatron_tpu.ops.activations import (
    apply_activation, glu_activation, mlp_input_width_factor,
)
from megatron_tpu.ops.pallas import flash_template as ft
from megatron_tpu.ops.pallas.flash_template import _NN, _NT, _TN, _dot

Tiles = Tuple[int, int, int]          # (tm, tk, tn)


class GroupVisits(NamedTuple):
    """The visit table of one row-tile size (all int32, scalar-prefetched).
    offsets [E + 1]: first row of each group, m last. group_ids, tile_ids
    [m / tm + E - 1]: the group and the row tile of each visit, in row
    order; entries past `count` repeat the last visit. count [1]: the
    visits there are. An empty group has one visit (`moe_tgmm` writes the
    group's zeros there, and its repeated entries, on its innermost axis,
    name the blocks the step before them named).

    A visit is DEAD in `moe_gmm` where it stands past `count` or its
    group is empty: its steps compute nothing, and they copy nothing
    either, because `moe_gmm`'s index maps do not read a dead visit's own
    entries. fetch [as group_ids]: the visit whose blocks step v names. A
    live visit's is v; a dead one's is the last live visit in front of it,
    at that visit's LAST k tile, which is the block every operand's window
    already holds (`_gmm_index_maps`); the dead visits in front of the first
    live one name that one's first blocks, which it then finds fetched.
    (All 0 where no group holds a row.) So neither an empty group's
    matrix nor, past the table's end, the last group's once more per
    visit, crosses from HBM."""
    offsets: jnp.ndarray
    group_ids: jnp.ndarray
    tile_ids: jnp.ndarray
    count: jnp.ndarray
    fetch: jnp.ndarray


def group_visits(group_sizes: jnp.ndarray, m: int, tm: int) -> GroupVisits:
    """The visit table for rows cut into m / tm tiles.

    `lax.div`, not `//`: the rows are not negative, so truncating floors,
    and jnp's floor_divide would put its sign correction on every index."""
    E = group_sizes.shape[0]
    n_tiles = m // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(jax.lax.div(starts, tm), n_tiles - 1)
    # tiles a group touches; an empty group is visited once
    n_visits = jnp.where(sizes > 0,
                         jax.lax.div(ends - 1, tm) - first + 1, 1)
    visit_ends = jnp.cumsum(n_visits)
    count = visit_ends[E - 1:]
    steps = jnp.arange(n_tiles + E - 1, dtype=jnp.int32)
    v = jnp.minimum(steps, count - 1)
    group_ids = jnp.sum(v[:, None] >= visit_ends[None, :], axis=1,
                        dtype=jnp.int32)
    # visit v of group g is tile first[g] + (v - the visits before g)
    tile_ids = (jnp.take(first, group_ids)
                + v - jnp.take(visit_ends - n_visits, group_ids))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    # the visit a step's blocks are named after: the last live one at or
    # in front of it, else the first live one there is
    live = (steps < count) & (jnp.take(sizes, group_ids) > 0)
    behind = jax.lax.cummax(jnp.where(live, steps, -1))
    ahead = jnp.min(jnp.where(live, steps, steps.shape[0]))
    fetch = jnp.where(behind >= 0, behind,
                      jnp.where(ahead < steps.shape[0], ahead, 0))
    return GroupVisits(offsets, group_ids, tile_ids, count, fetch)


# ---------------------------------------------------------------------------
# tiles
# ---------------------------------------------------------------------------

_SLAB = 2048 * 2048


def _largest_tile(dim: int, cap: int) -> Optional[int]:
    """The largest multiple of 128 that divides dim and is at most cap."""
    for t in range(min(dim, cap) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return None


def pick_row_tile(m: int, num_groups: int) -> Optional[int]:
    """tm of every kernel over m rows in num_groups groups: the largest of
    512, 256, 128 that divides m and is no larger than the mean group.
    None where 128 does not divide m (decode, odd batches). One row tile
    for the three kernels is one visit table for a layer. A boundary tile
    is worked in 128-row spans (`moe_gmm`) or in the smallest 128, 256,
    ... row window that holds the group's rows (`moe_tgmm`), so a larger
    tile wastes no more MXU time at boundaries than a smaller one and
    takes fewer grid steps and accumulator passes (PERF.md, PR 27: 512
    over 256 over 128 at a mean group of 512 rows and of 1024)."""
    if m % 128 or num_groups < 1:
        return None
    for tm in (512, 256):
        if m % tm == 0 and tm <= m // num_groups:
            return tm
    return 128


def pick_gmm_tiles(m: int, k: int, n: int, num_groups: int, *,
                   tgmm: bool = False) -> Optional[Tiles]:
    """(tm, tk, tn) for the product of m rows in num_groups groups over a
    contraction of k into n columns (`moe_gmm`; for the rows' gradient
    call it with k and n exchanged), or with tgmm=True for the [k, n]
    products of `moe_tgmm`. None where the kernels do not tile the shape:
    rows that `pick_row_tile` declines, k or n that 128 does not divide.

    The rule the sweeps on the v5e left (PERF.md, PR 27): the widest tn
    up to 2048, then the longest tk the slab a kernel keeps in VMEM
    allows. `moe_gmm` keeps rhs's [tk, tn], which stays while a group's
    row tiles stream past: 2048 x 2048 where that is the whole contraction
    (no accumulator, each product stored as it is made), half of it where
    k tiles accumulate (at k 4096 the larger slab took 1.8 times as
    long). `moe_tgmm` keeps the float32 [tk, tn] accumulator: 2048 x 2048
    at row tiles up to 256, half of it above (512 rows against the larger
    one took 2.4 times as long)."""
    tm = pick_row_tile(m, num_groups)
    if tm is None or k % 128 or n % 128:
        return None
    tn = _largest_tile(n, 2048)
    if tgmm:
        slab = _SLAB // 2 if tm > 256 else _SLAB
    else:
        slab = _SLAB if k * tn <= _SLAB else _SLAB // 2
    tk = _largest_tile(k, min(2048, slab // tn))
    return tm, tk, tn


def _vmem_limit(nbytes: int) -> Optional[int]:
    """The scoped-VMEM limit for a kernel whose blocks and temporaries
    count nbytes: a quarter and 4 MiB more for what the compiler adds
    (the count came 2 % short at [512, 2048] x [2048, 1024] tiles), left
    alone where the default already holds it."""
    nbytes = nbytes * 5 // 4 + (4 << 20)
    if nbytes <= ft._DEFAULT_SCOPED_VMEM:
        return None
    return min(nbytes, ft._MAX_SCOPED_VMEM)


# ---------------------------------------------------------------------------
# moe_gmm: out[rows of g] = lhs[rows of g] · rhs[g]  (or · rhs[g]ᵀ)
# ---------------------------------------------------------------------------


# rows of a boundary tile's spans: one pass of the 128 x 128 MXU
_SPAN = 128
# rows a dynamic slice of a tile may start at: bf16 packs 16 to a sublane tile
_SUBLANES = 16


def _visit(offs_ref, gids_ref, tids_ref, v, tm: int):
    """(first row, end row) of the visit's group, first row of its tile."""
    g = gids_ref[v]
    return offs_ref[g], offs_ref[g + 1], tids_ref[v] * tm


def _row_mask(lo, hi, row0, tm: int):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    return (rows >= lo) & (rows < hi)


def _act_parts(name: Optional[str]) -> int:
    """The blocks of its argument that one block of the activation's result
    reads: a GLU's gate and up, F columns apart; else (and of no
    activation) the block itself."""
    return mlp_input_width_factor(name)


def _activation(name: str, *parts):
    """`apply_activation`'s arithmetic on the blocks of `_act_parts`."""
    if len(parts) == 2:
        return glu_activation(name, *parts)
    return apply_activation(name, *parts)


def _act_tile(name: str, parts, dtype):
    """The activation of a tile inside a kernel: of the first product's
    values as they are stored, in float32, rounded once to the operands'
    dtype, which is what XLA's fusion of the same arithmetic gives."""
    return _activation(
        name, *(p.astype(jnp.float32) for p in parts)).astype(dtype)


def _act_vjp_tile(name: str, parts, dact, dtype):
    """The cotangents of the activation's argument blocks for the float32
    product `dact`, the cotangent of its result, which is rounded to the
    operands' dtype first, as it was when it was an array of its own. The
    formula is autodiff's of `_activation`, traced in the kernel's body."""
    _, vjp = jax.vjp(functools.partial(_activation, name),
                     *(p.astype(jnp.float32) for p in parts))
    return [d.astype(dtype)
            for d in vjp(dact.astype(dtype).astype(jnp.float32))]


def _gmm_kernel(offs_ref, gids_ref, tids_ref, count_ref, fetch_ref, *refs,
                tm: int, dims, act: Optional[str], act_vjp: Optional[str]):
    """refs: lhs, rhs, out and, where k tiles accumulate, the float32
    scratch. With `act`, lhs is the `_act_parts` blocks of the activation's
    argument and the rows that meet rhs are the activation of them. With
    `act_vjp`, the block(s) of the activation's argument stand behind rhs,
    side by side in one, and out holds, side by side too, their cotangents
    for the product as the cotangent of the activation's result."""
    n_lhs = _act_parts(act)
    lhs_refs, (rhs_ref, *refs) = refs[:n_lhs], refs[n_lhs:]
    arg_ref = refs.pop(0) if act_vjp else None
    out_ref, *scratch = refs
    v = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    lo, hi, row0 = _visit(offs_ref, gids_ref, tids_ref, v, tm)
    live = (v < count_ref[0]) & (hi > lo)
    whole = (lo <= row0) & (hi >= row0 + tm)

    def span(start: int, size: int, inside):
        """The product of the tile's rows [start, start + size), kept
        where they are the group's. inside: the span holds no boundary
        (True where the caller knows, else traced)."""
        rows = pl.ds(start, size)
        first_row = row0 + start

        def results(acc):
            """(columns of out, their values) for the finished product."""
            if act_vjp is None:
                return [(slice(None), acc)]
            tn = acc.shape[1]
            blocks = [slice(i * tn, (i + 1) * tn)
                      for i in range(_act_parts(act_vjp))]
            return list(zip(blocks, _act_vjp_tile(
                act_vjp, [arg_ref[rows, b] for b in blocks], acc,
                out_ref.dtype)))

        def keep(acc):
            def all_rows():
                for cols, val in results(acc):
                    out_ref[rows, cols] = val.astype(out_ref.dtype)

            def the_groups_rows():
                # the other rows belong to the visits before and after
                # this one, which hold the same output block
                mask = _row_mask(lo, hi, first_row, size)
                for cols, val in results(acc):
                    out_ref[rows, cols] = jnp.where(
                        mask, val.astype(jnp.float32),
                        out_ref[rows, cols].astype(jnp.float32)
                    ).astype(out_ref.dtype)

            if inside is True:
                all_rows()
            else:
                pl.when(inside)(all_rows)
                pl.when(jnp.logical_not(inside))(the_groups_rows)

        if act is None:
            lhs = lhs_refs[0][rows, :]
        else:
            lhs = _act_tile(act, [r[rows, :] for r in lhs_refs],
                            rhs_ref.dtype)
        prod = _dot(lhs, rhs_ref[...], dims)
        if not scratch:      # one k tile spans the contraction
            keep(prod)
            return
        acc_ref, = scratch

        @pl.when(ki == 0)
        def _first():
            acc_ref[rows, :] = prod

        @pl.when(ki > 0)
        def _later():
            acc_ref[rows, :] += prod

        @pl.when(ki == nk - 1)
        def _emit():
            keep(acc_ref[rows, :])

    # a tile inside one group is one product; a tile that holds a boundary
    # is taken in spans of _SPAN rows, and only those the group reaches
    pl.when(live & whole)(lambda: span(0, tm, True))
    for start in range(0, tm, _SPAN):
        first_row = row0 + start
        reached = (hi > first_row) & (lo < first_row + _SPAN)
        inside = (lo <= first_row) & (hi >= first_row + _SPAN)
        pl.when(live & jnp.logical_not(whole) & reached)(
            functools.partial(span, start, _SPAN, inside))


def _gmm_index_maps(nk: int, transpose_rhs: bool):
    """`moe_gmm`'s index maps over its grid (n tile j, visit v, k tile ki)
    and the visit table: (of an lhs part, of rhs, of out and of what
    stands beside it). A dead visit's steps name what the step in front
    of them named (GroupVisits), so the pipeline copies nothing for
    them."""

    def named(v, ki, fetch):
        """(visit, k tile) whose blocks step (v, ki) names: its own where
        visit v is live."""
        u = fetch[v]
        return u, jnp.where(u == v, ki, jnp.where(u < v, nk - 1, 0))

    def lhs_map(j, v, ki, offs, gids, tids, count, fetch, part=0):
        u, ki = named(v, ki, fetch)
        return tids[u], ki + part * nk

    def rhs_map(j, v, ki, offs, gids, tids, count, fetch):
        u, ki = named(v, ki, fetch)
        return (gids[u], j, ki) if transpose_rhs else (gids[u], ki, j)

    def out_map(j, v, ki, offs, gids, tids, count, fetch):
        return tids[fetch[v]], j

    return lhs_map, rhs_map, out_map


def _gmm(lhs, rhs, visits: GroupVisits, tiles: Tiles, transpose_rhs: bool,
         act: Optional[str] = None, act_vjp=None):
    """lhs [m, k] · rhs[g] with rhs [E, k, n], or with transpose_rhs
    lhs [m, k] · rhs[g]ᵀ with rhs [E, n, k]. Returns [m, n].

    act: lhs is the argument [m, k or 2k] of the activation of that name,
    and the rows that meet rhs are the activation of it, made of the tile
    in front of each product (a GLU's gate and up blocks are two windows
    onto the one array). act_vjp = (name, arg [m, n or 2n]): the result is
    the cotangent of `arg` for the product as the cotangent of the
    activation of `arg`, [m, n or 2n], made of the finished tile: no
    [m, n] array is written. A GLU's two halves are written side by side
    by the one n tile that spans n."""
    m = lhs.shape[0]
    k, n = rhs.shape[:0:-1] if transpose_rhs else rhs.shape[1:]
    tm, tk, tn = tiles
    nk = k // tk
    dtype = jnp.result_type(lhs.dtype, rhs.dtype)
    item = jnp.dtype(dtype).itemsize

    lhs_map, rhs_map, out_map = _gmm_index_maps(nk, transpose_rhs)
    rhs_spec = pl.BlockSpec((None, tn, tk) if transpose_rhs
                            else (None, tk, tn), rhs_map)

    n_lhs = _act_parts(act)
    in_specs = [pl.BlockSpec((tm, tk), functools.partial(lhs_map, part=i))
                for i in range(n_lhs)] + [rhs_spec]
    operands = [lhs.astype(dtype)] * n_lhs + [rhs.astype(dtype)]
    out_parts = 1
    if act_vjp is not None:
        act_vjp, arg = act_vjp
        out_parts = _act_parts(act_vjp)
        if out_parts > 1 and tn != n:
            raise ValueError(f"moe_gmm writes a GLU's two cotangents from "
                             f"one n tile: {tn} of {n} columns")
        in_specs.append(pl.BlockSpec((tm, out_parts * tn), out_map))
        operands.append(arg.astype(dtype))

    # lhs, rhs and out blocks double-buffered, the float32 product and (for
    # several k tiles) the accumulator; an activation's float32 values
    # (argument, result and what stands between) beside them
    vmem = (2 * (n_lhs * tm * tk + tk * tn + tm * out_parts * tn) * item
            + (1 if nk == 1 else 2) * tm * tn * 4)
    if act is not None:
        vmem += (n_lhs + 2) * tm * tk * 4
    if act_vjp is not None:
        vmem += 2 * tm * out_parts * tn * item + 3 * out_parts * tm * tn * 4
    return ft._named_pallas_call(
        "moe_gmm",
        functools.partial(_gmm_kernel, tm=tm,
                          dims=_NT if transpose_rhs else _NN,
                          act=act, act_vjp=act_vjp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(visits),
            grid=(n // tn, visits.group_ids.shape[0], nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tm, out_parts * tn), out_map),
            scratch_shapes=([] if nk == 1
                            else [pltpu.VMEM((tm, tn), jnp.float32)])),
        out_shape=jax.ShapeDtypeStruct((m, out_parts * n), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(vmem)),
        interpret=ft._interpret(),
    )(*visits, *operands)


# ---------------------------------------------------------------------------
# moe_tgmm: out[g] = lhs[rows of g]ᵀ · dout[rows of g]
# ---------------------------------------------------------------------------


def _tgmm_kernel(offs_ref, gids_ref, tids_ref, count_ref, *refs, tm: int,
                 into: bool, act: Optional[str], ragged: bool):
    """refs: lhs, dout, out and the float32 scratch a group's product is
    summed in; with `into`, the layer's index (read by the index maps
    alone) in front, the accumulator's block after dout, and no scratch:
    the float32 output block is where the product is summed, on top of
    the accumulator's block. With `act`, lhs is the `_act_parts` blocks of
    the activation's argument, and the rows contracted are the activation
    of them. ragged: rows may stand behind the last group, where either
    operand may hold anything."""
    if into:
        refs = refs[1:]
    n_lhs = _act_parts(act)
    lhs_refs, (dout_ref, *refs) = refs[:n_lhs], refs[n_lhs:]
    if into:
        into_ref, out_ref = refs
        acc_ref = out_ref
    else:
        out_ref, acc_ref = refs
    v = pl.program_id(2)
    last_step = pl.num_programs(2) - 1
    count = count_ref[0]
    g = gids_ref[v]
    lo, hi, row0 = _visit(offs_ref, gids_ref, tids_ref, v, tm)
    live = v < count
    first = (v == 0) | (gids_ref[jnp.maximum(v - 1, 0)] != g)
    last = (v == count - 1) | (gids_ref[jnp.minimum(v + 1, last_step)] != g)
    whole = (lo <= row0) & (hi >= row0 + tm)

    def lhs_rows(rows):
        if act is None:
            return lhs_refs[0][rows, :]
        return _act_tile(act, [r[rows, :] for r in lhs_refs],
                         dout_ref.dtype)

    def accumulate(prod):
        @pl.when(first)
        def _first():
            acc_ref[...] = into_ref[...] + prod if into else prod

        @pl.when(jnp.logical_not(first))
        def _later():
            acc_ref[...] += prod

    @pl.when(live & whole)
    def _all_rows():
        accumulate(_dot(lhs_rows(slice(None)), dout_ref[...], _TN))

    # A tile that holds a boundary: the group's rows are [a, b) of it, and
    # the product is taken over the smallest window of 128, 256, ... rows
    # that holds them (from a sublane-aligned start), the other groups'
    # rows in it zeroed in the narrower operand: a zero row on one side is
    # a zero term of the sum, and the MXU's time follows the window's rows.
    # Not where rows stand behind the last group: those a kernel never
    # wrote, a zero times what they hold is not a zero, and both operands'
    # are zeroed.
    a = jnp.maximum(lo - row0, 0)
    b = jnp.minimum(hi - row0, tm)
    smaller_fits = jnp.bool_(False)
    size = _SPAN
    while size <= tm:
        start = jnp.minimum(a // _SUBLANES * _SUBLANES, tm - size)
        fits = b <= start + size

        def window(start=start, size=size):
            rows = pl.ds(pl.multiple_of(start, _SUBLANES), size)
            mask = _row_mask(lo, hi, row0 + start, size)
            lhs, dout = lhs_rows(rows), dout_ref[rows, :]
            if ragged or lhs.shape[1] <= dout.shape[1]:
                lhs = jnp.where(mask, lhs, jnp.zeros_like(lhs))
            if ragged or lhs.shape[1] > dout.shape[1]:
                dout = jnp.where(mask, dout, jnp.zeros_like(dout))
            accumulate(_dot(lhs, dout, _TN))

        pl.when(live & jnp.logical_not(whole) & (hi > lo) & fits
                & jnp.logical_not(smaller_fits))(window)
        smaller_fits = smaller_fits | fits
        size *= 2

    @pl.when(live & (hi == lo))
    def _no_rows():
        acc_ref[...] = into_ref[...] if into else jnp.zeros_like(acc_ref)

    if not into:
        @pl.when(live & last)
        def _emit():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _tgmm(lhs, dout, visits: GroupVisits, tiles: Tiles, into=None,
          act: Optional[str] = None, ragged: bool = False):
    """lhs [m, k], dout [m, n] -> [E, k, n], one product per group, in
    the operands' dtype. With into = (stack float32 [L, E, k, n], layer
    int32 scalar): the stack with stack[layer] + the products in place of
    stack[layer], in float32 and unrounded. The stack is aliased to the
    result, so only the layer's blocks are read and written and the rest
    of the buffer is never touched.

    act: lhs is the argument [m, k or 2k] of the activation of that name,
    and what is contracted is the activation of it, made of each row
    window in front of its product. ragged: the groups may end before the
    rows, and what the rows behind them hold (in either operand) is kept
    out of the last group's boundary window."""
    m = lhs.shape[0]
    n_lhs = _act_parts(act)
    k = lhs.shape[1] // n_lhs
    n = dout.shape[1]
    E = visits.offsets.shape[0] - 1
    tm, tk, tn = tiles
    dtype = jnp.result_type(lhs.dtype, dout.dtype)
    item = jnp.dtype(dtype).itemsize

    # the index maps' trailing arguments are the scalar-prefetch operands:
    # the visit table, and behind it the layer where there is a stack
    def lhs_map(j, i, v, offs, gids, tids, *_, part=0):
        return tids[v], i + part * (k // tk)

    def dout_map(j, i, v, offs, gids, tids, *_):
        return tids[v], j

    def out_map(j, i, v, offs, gids, tids, count):
        return gids[v], i, j

    def stack_map(j, i, v, offs, gids, tids, count, layer):
        return layer[0], gids[v], i, j

    in_specs = [pl.BlockSpec((tm, tk), functools.partial(lhs_map, part=p))
                for p in range(n_lhs)] + [pl.BlockSpec((tm, tn), dout_map)]
    # (its own four entries of the table: `fetch` is `moe_gmm`'s)
    operands = [*visits[:4], *[lhs.astype(dtype)] * n_lhs,
                dout.astype(dtype)]
    # an activation's float32 values beside the blocks
    act_vmem = (n_lhs + 2) * tm * tk * 4 if act else 0
    if into is None:
        out_shape = jax.ShapeDtypeStruct((E, k, n), dtype)
        out_spec = pl.BlockSpec((None, tk, tn), out_map)
        scratch, aliases = [pltpu.VMEM((tk, tn), jnp.float32)], {}
        # row tiles and the output block double-buffered, the float32
        # accumulator and one product beside it
        vmem = (2 * (n_lhs * tm * tk + tm * tn + tk * tn) * item
                + 2 * tk * tn * 4)
    else:
        stack, layer = into
        if stack.dtype != jnp.float32 or stack.shape[1:] != (E, k, n):
            raise ValueError(
                f"moe_tgmm sums into a float32 [layers, {E}, {k}, {n}] "
                f"stack, not {stack.dtype}{list(stack.shape)}")
        out_shape = jax.ShapeDtypeStruct(stack.shape, jnp.float32)
        out_spec = pl.BlockSpec((None, None, tk, tn), stack_map)
        in_specs.append(out_spec)
        operands.insert(4, jnp.asarray(layer, jnp.int32).reshape(1))
        operands.append(stack)
        scratch, aliases = [], {len(operands) - 1: 0}
        # row tiles double-buffered; the accumulator's block in and the
        # block out, both float32 and double-buffered; one product
        vmem = 2 * (n_lhs * tm * tk + tm * tn) * item + 5 * tk * tn * 4
    return ft._named_pallas_call(
        "moe_tgmm",
        functools.partial(_tgmm_kernel, tm=tm, into=into is not None,
                          act=act, ragged=ragged),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4 if into is None else 5,
            grid=(n // tn, k // tk, visits.group_ids.shape[0]),
            in_specs=in_specs, out_specs=out_spec, scratch_shapes=scratch),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(vmem + act_vmem)),
        interpret=ft._interpret(),
    )(*operands)


# ---------------------------------------------------------------------------
# the function and its gradient
# ---------------------------------------------------------------------------


class _Plan(NamedTuple):
    """Tiles of the three products of one grouped_matmul (one tm)."""
    fwd: Tiles       # lhs · rhs[g]
    drows: Tiles     # dout · rhs[g]ᵀ
    tgmm: Tiles      # lhs ᵀ · dout per group


def _plan(m: int, k: int, n: int, num_groups: int) -> Optional[_Plan]:
    tiles = (pick_gmm_tiles(m, k, n, num_groups),
             pick_gmm_tiles(m, n, k, num_groups),
             pick_gmm_tiles(m, k, n, num_groups, tgmm=True))
    return None if None in tiles else _Plan(*tiles)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _grouped_matmul_kernels(lhs, rhs, visits: GroupVisits, sink, plan: _Plan):
    out = _gmm(lhs, rhs, visits, plan.fwd, False)
    # the sink's stack is handed through unread: it is there for its
    # cotangent, which the backward rule answers
    return out if sink is None else (out, sink[0])


def _kernels_fwd(lhs, rhs, visits, sink, plan: _Plan):
    layer = None if sink is None else sink[1]
    return (_grouped_matmul_kernels(lhs, rhs, visits, sink, plan),
            (lhs, rhs, visits, layer))


def _kernels_bwd(plan: _Plan, res, ct):
    lhs, rhs, visits, layer = res
    dout, dstack = (ct, None) if layer is None else ct
    dout = dout.astype(jnp.result_type(lhs.dtype, rhs.dtype))
    dlhs = _gmm(dout, rhs, visits, plan.drows, True).astype(lhs.dtype)
    if layer is None:
        drhs = _tgmm(lhs, dout, visits, plan.tgmm).astype(rhs.dtype)
        return dlhs, drhs, None, None
    # rhs's gradient goes into the stack's cotangent and nowhere else:
    # rhs's own cotangent is zero (dead code for a caller that does not
    # differentiate in rhs, as the train step does not)
    dstack = _tgmm(lhs, dout, visits, plan.tgmm, into=(dstack, layer))
    return dlhs, jnp.zeros_like(rhs), None, (dstack, None)


_grouped_matmul_kernels.defvjp(_kernels_fwd, _kernels_bwd)


class _MlpPlan(NamedTuple):
    """The experts' two products with the activation between them."""
    first: _Plan             # rows · w_in
    second: _Plan            # activation(that) · w_out
    activation: str
    ragged: bool             # rows may stand behind the last group
    save_as: Optional[str]   # the first product's `checkpoint_name`


@functools.partial(jax.jit, static_argnames=("plan",))
def _mlp_products(xs, w_in, w_out, visits: GroupVisits, plan: _MlpPlan):
    """(activation(xs · w_in[g]) · w_out[g], xs · w_in[g]), the first
    product under its name for a `jax.checkpoint` policy.

    A function of its own (an inner `jit`) for that name's sake: the
    second product has to read the NAMED value, so that a backward pass
    that makes the second product again (a share of the experts, for the
    gates' gradient: ops/moe.py `_to_token_fwd`) reads the saved first
    product and does not make that again too. But `jax.checkpoint` rounds
    every saved value that the forward pass reads as well (a
    `reduce_precision`, which this chip's compiler keeps as a pass over
    the array where nothing but kernels stands around it), unless the
    reader stands inside a call, as here."""
    hmid = _gmm(xs, w_in, visits, plan.first.fwd, False)
    if plan.save_as is not None:
        hmid = checkpoint_name(hmid, plan.save_as)
    return _gmm(hmid, w_out, visits, plan.second.fwd, False,
                act=plan.activation), hmid


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _grouped_mlp_kernels(xs, w_in, w_out, visits: GroupVisits, sinks,
                         plan: _MlpPlan):
    """activation(xs · w_in[g]) · w_out[g], the activation made of the
    first product's tiles inside the second's kernel. sinks = (w_in's
    stack, w_out's stack, layer), a matrix without one None: the stacks are
    handed through unread behind the result, for their cotangents."""
    return _mlp_products(xs, w_in, w_out, visits, plan=plan)[0], sinks[:2]


def _mlp_fwd(xs, w_in, w_out, visits, sinks, plan: _MlpPlan):
    out, hmid = _mlp_products(xs, w_in, w_out, visits, plan=plan)
    return (out, sinks[:2]), (xs, hmid, w_in, w_out, visits, sinks[2])


def _mlp_bwd(plan: _MlpPlan, res, ct):
    xs, hmid, w_in, w_out, visits, layer = res
    dout, dstacks = ct
    dout = dout.astype(hmid.dtype)
    # the rows' gradient through w_out, turned into the first product's
    # cotangent by the activation's vjp as each tile is finished
    dhmid = _gmm(dout, w_out, visits, plan.second.drows, True,
                 act_vjp=(plan.activation, hmid))
    dxs = _gmm(dhmid, w_in, visits, plan.first.drows, True).astype(xs.dtype)

    def matrix_grad(w, dstack, lhs, d, tiles, act=None):
        """(w's cotangent, its stack's): the gradient goes into the stack
        where there is one, and w's own is then zero (`_kernels_bwd`)."""
        if dstack is None:
            return _tgmm(lhs, d, visits, tiles, act=act,
                         ragged=plan.ragged).astype(w.dtype), None
        return jnp.zeros_like(w), _tgmm(lhs, d, visits, tiles, act=act,
                                        ragged=plan.ragged,
                                        into=(dstack, layer))

    dw_in, dstack_in = matrix_grad(w_in, dstacks[0], xs, dhmid,
                                   plan.first.tgmm)
    dw_out, dstack_out = matrix_grad(w_out, dstacks[1], hmid, dout,
                                     plan.second.tgmm, plan.activation)
    return dxs, dw_in, dw_out, None, (dstack_in, dstack_out, None)


_grouped_mlp_kernels.defvjp(_mlp_fwd, _mlp_bwd)


# activations whose arithmetic the chip's kernel compiler does not lower
# (the exact gelu's `erfc`): their experts keep the activation between the
# kernels
_NOT_IN_KERNEL = ("gelu", "geglu")


def _visits_of_layer(visits: GroupVisits, layer, layers: int) -> GroupVisits:
    """The visit table of E groups as one over layers * E groups, of which
    layer `layer`'s are these: group g becomes group layer * E + g, and
    the offsets stand where the kernels look them up (`_visit`: entries
    g and g + 1 of the visit's group)."""
    E = visits.offsets.shape[0] - 1
    first = jnp.asarray(layer, jnp.int32) * E
    at = jnp.clip(jnp.arange(layers * E + 1, dtype=jnp.int32) - first, 0, E)
    return visits._replace(offsets=jnp.take(visits.offsets, at),
                           group_ids=visits.group_ids + first)


def grouped_mlp_of_layer(xs: jnp.ndarray, w_in: jnp.ndarray,
                         w_out: jnp.ndarray, layer,
                         group_sizes: jnp.ndarray, activation: str, *,
                         visits: Optional[GroupVisits] = None):
    """`grouped_mlp`'s result for layer `layer` (static or traced) of the
    STACKED matrices w_in [L, E, h, f or 2f] and w_out [L, E, f, h], read
    where they lie. A kernel's operand is a whole array: a layer's matrices
    sliced out of their stacks in front of the call are a copy of every
    expert's matrices, every call (two of 0.7 GB a layer in a decode tick
    of the benchmark's Nemotron share, as much again as the tick needs to
    read). The stacks go in as [L * E, ...], which is no copy, and the
    visit table names the layer's group g as group layer * E + g
    (`_visits_of_layer`); the kernels are `moe_gmm` as they stand.

    The forward products alone, with no gradient rule: for the serving
    steps, which nothing differentiates. None where `grouped_mlp` gives
    None."""
    L, E = w_out.shape[:2]
    plans = _mlp_plans(xs, w_in, w_out, activation)
    if plans is None:
        return None
    if visits is None:
        visits = visits_for(group_sizes, xs.shape[0])
    return _mlp_products(
        xs, w_in.reshape((L * E,) + w_in.shape[2:]),
        w_out.reshape((L * E,) + w_out.shape[2:]),
        _visits_of_layer(visits, layer, L),
        plan=_MlpPlan(*plans, activation, False, None))[0]


def unwritten_rows(shape, dtype, after: jnp.ndarray) -> jnp.ndarray:
    """An array of `shape` that holds whatever its memory held: a buffer
    for a caller that writes the rows somebody reads and leaves the rest
    (ops/moe.py rows_to_expert_order, of a share of the experts: the rows
    behind the held groups, which no kernel here visits and whose part of
    a boundary window `moe_tgmm` masks on both sides, `ragged`). On one
    TPU a kernel that writes nothing: XLA has no such array, its `empty`
    is a broadcast of zero, and a fill of the Mellum cell's 604 MB buffer
    is 0.85 ms, twice a layer. Zeros elsewhere.

    after: an array the buffer is wanted behind (the rows it will take).
    The kernel names it as an operand and reads nothing of it: a producer
    without operands stands nowhere in the step's order, and with four
    such buffers afloat the chip's scheduler left the flash forward's
    lane-padded log-sum-exp (268 MB a layer) lying until the backward
    pass: 6.67 GB of temporaries in the Mellum step against 6.07 with the
    operand (the described-v5e compile, PR 69)."""
    if not _one_tpu():
        return jnp.zeros(shape, dtype)
    return pl.pallas_call(
        lambda _, out: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name="moe_unwritten_rows")(after)


def _one_tpu() -> bool:
    """The kernels serve one TPU: GSPMD cannot partition a Mosaic call, and
    under a mesh of several devices the products stay `lax.ragged_dot`,
    which it can."""
    if jax.default_backend() != "tpu":
        return False
    from megatron_tpu.parallel.mesh import ambient_mesh_shape

    return math.prod(ambient_mesh_shape().values()) == 1


def _mlp_plans(xs, w_in, w_out, activation: str):
    """The plans of the experts' two products, (rows · w_in, activation ·
    w_out), where the kernels hold both and the activation between them;
    else None. w_in [.., E, h, f or 2f], w_out [.., E, f, h]: the last
    three axes are read (a layer's matrices, or the stacked layers')."""
    m, h = xs.shape
    E, f, _ = w_out.shape[-3:]
    # (the first product, the activation and the result all in the rows'
    # dtype, as the products apart have them from such operands)
    if (not _one_tpu() or activation in _NOT_IN_KERNEL
            or w_in.shape[-1] != _act_parts(activation) * f
            or not xs.dtype == w_in.dtype == w_out.dtype):
        return None
    plans = _plan(m, h, w_in.shape[-1], E), _plan(m, f, h, E)
    return None if None in plans else plans


def visits_for(group_sizes: jnp.ndarray, m: int) -> Optional[GroupVisits]:
    """The visit table of m rows in these groups for
    `grouped_matmul(visits=)`: built once, shared by every product over
    the same groups. None where the kernels do not serve."""
    tm = pick_row_tile(m, group_sizes.shape[0]) if _one_tpu() else None
    return None if tm is None else group_visits(group_sizes, m, tm)


def takes_sink(m: int, k: int, n: int, num_groups: int) -> bool:
    """Whether `grouped_matmul` of these shapes, traced here, can sum rhs's
    gradient into a `sink`: where its products are the kernels."""
    return _one_tpu() and _plan(m, k, n, num_groups) is not None


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, *,
                   visits: Optional[GroupVisits] = None,
                   sink: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None):
    """lhs [m, k] times rhs[g] [k, n] for the rows of each group g of
    group_sizes [E] (which sum to m); differentiable in lhs and rhs.
    `visits` is `visits_for(group_sizes, m)` from a caller that runs
    several products over the same groups.

    `sink` = (stack float32 [L, E, k, n], layer) is where rhs's gradient
    is to be summed, for a caller that accumulates it over several calls
    (training/train_step.py; only where `takes_sink` holds). The result is
    then (product, stack): the stack comes back as it went in, and the
    gradient rule answers ITS cotangent c with c, c[layer] replaced by
    c[layer] + lhs[rows of g]ᵀ · dout[rows of g] in float32, unrounded,
    written in place by `moe_tgmm`; rhs's own cotangent is zero. So the
    vector-Jacobian product with the running sum as the stack's cotangent
    gives, for the stack, the new running sum: the gradient is added where
    it is made and exists nowhere in rhs's dtype or shape."""
    m, k = lhs.shape
    E, _, n = rhs.shape
    plan = _plan(m, k, n, E) if _one_tpu() else None
    if plan is None:
        if sink is not None:
            raise ValueError("grouped_matmul: a gradient sink where the "
                             "products are not the kernels (takes_sink)")
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    if visits is None:
        visits = visits_for(group_sizes, m)
    return _grouped_matmul_kernels(lhs, rhs, visits, sink, plan)


def grouped_mlp(xs: jnp.ndarray, w_in: jnp.ndarray, w_out: jnp.ndarray,
                group_sizes: jnp.ndarray, activation: str, *,
                visits: Optional[GroupVisits] = None,
                sinks=(None, None, None), ragged: bool = False,
                save_as: Optional[str] = None):
    """activation(xs [m, h] · w_in[g] [h, f or 2f]) · w_out[g] [f, h] for
    the rows of each group, as one function with one gradient rule, the
    activation and its backward inside the kernels: `moe_gmm` over w_out
    and `moe_tgmm` for w_out's gradient make it of the first product's
    tiles in front of their products, and the `moe_gmm` that takes the
    result's cotangent back through w_out turns each finished tile into
    the first product's cotangent. No array of the activation's shape
    exists and nothing but the kernels touches the first product.
    Returns (result [m, h], (w_in's stack, w_out's stack)), or None where
    this form does not serve and the caller runs the products apart
    (`grouped_matmul`): where the products are not the kernels, operands
    of several dtypes, an activation the kernels' compiler does not
    lower, a GLU wider than the one n tile that writes its two cotangents
    side by side.

    sinks = (w_in's stack, w_out's stack, layer) as `grouped_matmul(sink=)`
    has one. ragged: the groups may end before the rows; a result row
    behind them, and its gradient, holds whatever the buffer held.
    save_as: the `checkpoint_name` of the first product, the one value
    between the kernels, for a caller's `jax.checkpoint` policy."""
    plans = _mlp_plans(xs, w_in, w_out, activation)
    if plans is None or (_act_parts(activation) > 1
                         and plans[1].drows[2] != w_out.shape[1]):
        return None
    if visits is None:
        visits = visits_for(group_sizes, xs.shape[0])
    return _grouped_mlp_kernels(
        xs, w_in, w_out, visits, tuple(sinks),
        _MlpPlan(*plans, activation, ragged, save_as))
