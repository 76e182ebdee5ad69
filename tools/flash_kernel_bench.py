"""Time the training flash kernels alone on the chip, by class of layer.

    chiprun -- python tools/flash_kernel_bench.py
    chiprun -- python tools/flash_kernel_bench.py --tree archive_check/parent

Runs `flash_template._fwd` (kernel `flash_fwd`) and `_bwd_fused` (kernel
`flash_bwd`) at the shapes the training cells call them with, q
[B, Hq, S, D] and k, v [B, Hkv, S, D] bf16 at the tiles `pick_blocks`
gives: Mellum's window-1024 and full layers at 8192 (32 query heads over
4), Mistral's window-4096 layer at 4096 on one chip (32 over 8) and as the
TP 2 x DP 2 cell's shard (16 over 4), OLMoE's causal layer (16 over 16).
One JSON line a case, two forms of the same layer side by side:

  `compact`    the kernels on K and V as they lie, a KV head read by each
               query head of its group through the index maps, dk and dv
               summed over the group inside `flash_bwd` (a tree whose
               kernels want K and V at the query heads' shape has no such
               form, and the line leaves it out);
  `broadcast`  the form before it: K and V repeated to the query heads
               outside (`broadcast_ms`, XLA's pass), the kernels on the
               repeated tensors, dk and dv at the query heads' shape summed
               over the group by the repeat's own vjp (`group_sum_ms`).

Each form: ms a call of each kernel (REPS calls queued back to back, one
wait) and of the layer's whole forward and backward as one jitted function
(`fwd_layer_ms`, `bwd_layer_ms`: kernel and XLA's passes around it). Also
the tiles a head visits by class and `computed_over_visible` where the
tree counts them (`tile_counts`), and the largest difference of the output
and of the three gradients of `flash_mha` from the XLA attention's on two
query heads of the same sequence (one KV head's under GQA), as a share of
the reference's range. --tree points at another checkout of the repo (an
unpacked parent), for a comparison in one call. Needs a TPU.
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import json
import os
import sys
import time

_ARGS = argparse.ArgumentParser()
_ARGS.add_argument("--tree", default=os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
_ARGS.add_argument("--seed", type=int, default=1)
_ARGS.add_argument("--reps", type=int, default=20)
_ARGS.add_argument("--cases", nargs="*")

D = 128
# case: (batch, query heads, kv heads, sequence, window)
CASES = {
    "mellum_sliding": (2, 32, 4, 8192, 1024),
    "mellum_full": (2, 32, 4, 8192, None),
    "mistral_seq4k": (1, 32, 8, 4096, 4096),
    "mistral_tp2dp2": (8, 16, 4, 4096, 4096),
    "olmoe_seq4k": (1, 16, 16, 4096, None),
}


def _timed(fn, args, reps):
    fn(*args)[0].block_until_ready()
    start = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out[0].block_until_ready()
    return (time.perf_counter() - start) / reps * 1e3


def _share_of_range(got, want):
    import numpy as np

    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / np.abs(want).max())


def _reads_kv_heads(ft) -> bool:
    """Whether the tree's training kernels address K and V by KV head
    (`fused_bwd_fits` then takes the group's size)."""
    return "groups" in inspect.signature(ft.fused_bwd_fits).parameters


def _forms(ft, groups):
    """{form: (spread, gather)}: what a form does to K or V in front of
    the kernels, and to dk or dv behind them."""
    import jax.numpy as jnp

    def repeat(x):
        return jnp.repeat(x, groups, axis=1)

    def group_sum(dx):
        # what the repeat's own vjp does: the query heads' gradients, in
        # the dtype the kernel rounded them to, summed over the group
        b, h, s, d = dx.shape
        return dx.reshape(b, h // groups, groups, s, d).sum(axis=2)

    forms = {"broadcast": (repeat, group_sum)}
    if _reads_kv_heads(ft):
        forms = {"compact": (lambda x: x, lambda dx: dx), **forms}
    return forms


def run(ft, attention, name, seed, reps):
    import jax
    import jax.numpy as jnp

    b, hq, hkv, s, window = CASES[name]
    groups = hq // hkv
    scale = float(1.0 / D ** 0.5)
    blocks = ft.pick_blocks(s, D, jnp.bfloat16)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, do = (jax.random.normal(key, (b, hq, s, D), jnp.bfloat16)
             for key in keys[:2])
    k, v = (jax.random.normal(key, (b, hkv, s, D), jnp.bfloat16)
            for key in keys[2:])
    line = {"case": name, "q": [b, hq, s, D], "kv": [b, hkv, s, D],
            "window": window, "blocks": list(blocks)}

    def fwd(q, k, v):
        return ft._fwd(q, k, v, scale, True, window, *blocks)

    def bwd(q, k, v, do, stats):
        return ft._bwd_fused(q, k, v, do, stats, scale, True, window,
                             *blocks)

    for form, (spread, gather) in _forms(ft, groups).items():
        if groups == 1 and form == "broadcast" and "compact" in line:
            continue     # nothing to repeat: the two forms are one
        kk, vv = jax.jit(lambda k, v: (spread(k), spread(v)))(k, v)
        o, lse = jax.jit(fwd)(q, kk, vv)
        stats = jax.jit(lambda lse, o, do: ft._bwd_stats(
            lse, o, do, blocks[0]))(lse, o, do)

        def fwd_layer(q, k, v):
            return fwd(q, spread(k), spread(v))

        def bwd_layer(q, k, v, do, stats):
            dq, dk, dv = bwd(q, spread(k), spread(v), do, stats)
            return dq, gather(dk), gather(dv)

        got = {"flash_fwd_ms": _timed(jax.jit(fwd), (q, kk, vv), reps),
               "flash_bwd_ms": _timed(jax.jit(bwd), (q, kk, vv, do, stats),
                                      reps),
               "fwd_layer_ms": _timed(jax.jit(fwd_layer), (q, k, v), reps),
               "bwd_layer_ms": _timed(jax.jit(bwd_layer),
                                      (q, k, v, do, stats), reps)}
        if form == "broadcast" and groups > 1:
            dkv = jax.jit(bwd)(q, kk, vv, do, stats)[1:]
            got["broadcast_ms"] = _timed(
                jax.jit(lambda k, v: (spread(k), spread(v))), (k, v), reps)
            got["group_sum_ms"] = _timed(
                jax.jit(lambda dk, dv: (gather(dk), gather(dv))), dkv, reps)
            del dkv
        line[form] = got
        del kk, vv, o, lse, stats
    if hasattr(ft, "tile_counts"):
        line["tiles"] = ft.tile_counts(s, blocks[0], True, window)

    # two query heads of the first sequence (one KV head's, under GQA)
    # against the XLA attention, whose scores of the whole case would not
    # fit the chip
    t = lambda x, heads: jnp.transpose(x[:1, :heads], (0, 2, 1, 3))  # noqa: E731
    kv_heads = 2 if groups == 1 else 1
    cut = (t(q, 2), t(k, kv_heads), t(v, kv_heads), t(do, 2))

    def out_and_grads(fn):
        o, vjp = jax.vjp(fn, *cut[:3])
        return (o, *vjp(cut[3].astype(o.dtype)))

    got = out_and_grads(lambda q, k, v: ft.flash_mha(
        q, k, v, sliding_window=window))
    want = out_and_grads(lambda q, k, v: attention(
        q, k, v, sliding_window=window, impl="xla"))
    line["share_of_range"] = {
        name: _share_of_range(a, b)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want)}
    print(json.dumps(line), flush=True)


def main():
    args = _ARGS.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))
    import jax

    from megatron_tpu.ops.pallas import flash_template as ft

    attention = importlib.import_module("megatron_tpu.ops.attention").attention
    kind = jax.devices()[0].device_kind
    if "TPU" not in kind:
        sys.exit(f"needs a TPU, found {kind}")
    print(json.dumps({"tree": os.path.abspath(args.tree),
                      "device_kind": kind,
                      "reads_kv_heads": _reads_kv_heads(ft)}), flush=True)
    for name in args.cases or CASES:
        run(ft, attention, name, args.seed, args.reps)


if __name__ == "__main__":
    main()
