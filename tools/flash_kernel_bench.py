"""Time the training flash kernels alone on the chip, by class of layer.

    chiprun -- python tools/flash_kernel_bench.py
    chiprun -- python tools/flash_kernel_bench.py --tree archive_check/parent

Runs `flash_template._fwd` (kernel `flash_fwd`) and `_bwd_fused` (kernel
`flash_bwd`) at the shapes the training cells call them with, [B, H, S, D]
bf16 at the tiles `pick_blocks` gives: Mellum's window-1024 and full
layers at 8192, Mistral's window-4096 layer at 4096 on one chip and as
the TP 2 x DP 2 cell's shard, OLMoE's causal layer. One JSON line a case:
ms a call of each kernel (REPS calls queued back to back, one wait), the
tiles a head visits by class and `computed_over_visible` where the tree
counts them (`tile_counts`), and the largest difference of the output and
of the three gradients from the XLA attention's on two heads of the same
sequence, as a share of the reference's range. --tree points at another
checkout of the repo (an unpacked parent), for a comparison in one call.
Needs a TPU.
"""

from __future__ import annotations

import argparse
import importlib
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
# case: (batch, heads, sequence, window)
CASES = {
    "mellum_sliding": (2, 32, 8192, 1024),
    "mellum_full": (2, 32, 8192, None),
    "mistral_seq4k": (1, 32, 4096, 4096),
    "mistral_tp2dp2": (8, 16, 4096, 4096),
    "olmoe_seq4k": (1, 16, 4096, None),
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


def run(ft, attention, name, seed, reps):
    import jax
    import jax.numpy as jnp

    b, h, s, window = CASES[name]
    scale = float(1.0 / D ** 0.5)
    blocks = ft.pick_blocks(s, D, jnp.bfloat16)
    q, k, v, do = (jax.random.normal(key, (b, h, s, D), jnp.bfloat16)
                   for key in jax.random.split(jax.random.PRNGKey(seed), 4))
    fwd = jax.jit(lambda q, k, v: ft._fwd(q, k, v, scale, True, window,
                                          *blocks))
    bwd = jax.jit(lambda q, k, v, do, stats: ft._bwd_fused(
        q, k, v, do, stats, scale, True, window, *blocks))
    o, lse = fwd(q, k, v)
    stats = jax.jit(lambda lse, o, do: ft._bwd_stats(lse, o, do, blocks[0]))(
        lse, o, do)
    line = {"case": name, "shape": [b, h, s, D], "window": window,
            "blocks": list(blocks),
            "flash_fwd_ms": _timed(fwd, (q, k, v), reps),
            "flash_bwd_ms": _timed(bwd, (q, k, v, do, stats), reps)}
    if hasattr(ft, "tile_counts"):
        line["tiles"] = ft.tile_counts(s, blocks[0], True, window)

    # two heads of the first sequence against the XLA attention, whose
    # scores of the whole case would not fit the chip
    cut = tuple(jnp.transpose(x[:1, :2], (0, 2, 1, 3)) for x in (q, k, v, do))

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
                      "device_kind": kind}), flush=True)
    for name in args.cases or CASES:
        run(ft, attention, name, args.seed, args.reps)


if __name__ == "__main__":
    main()
