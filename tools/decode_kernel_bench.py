"""Time one call of the paged decode kernel on the chip.

    chiprun -- python tools/decode_kernel_bench.py
    chiprun -- python tools/decode_kernel_bench.py --tree archive_check/parent

Runs `flash_template.paged_flash_decode` (`_mq` at --sq > 1) alone at the
two served cells' shapes (benchmark/configs/mistral-7b-d8-serve.json:
64 slots x 528 table entries of 16, 8 of 32 heads, window 4096;
jamba2-3b-serve.json: 64 x 256, 1 of 20 heads, no window) over one
layer's pool, for rows as the cells' loads leave them: some slots in use
at drawn lengths, the others at length 0 (what the kernel could skip) or
at 1 to `drift` (what the engines hand it for an idle slot today: the
layer passes `cache_index + 1`, and the decode step adds 1 to every row's
length on the device between two uploads of the carry). One JSON line a
case: ms a call (eight chained calls a dispatch, as a step of eight
layers makes them), the largest difference from the gather reference on
the rows in use, and the blocks visited over the blocks the table holds
(`decode_blocks_visited`, where the tree has it). --tree points at
another checkout of the repo (an unpacked parent), for a comparison in
one call. Needs a TPU.
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
_ARGS.add_argument("--sq", type=int, default=1)
ARGS = _ARGS.parse_args()
sys.path.insert(0, os.path.abspath(ARGS.tree))

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.ops.pallas import flash_template as ft

attention = importlib.import_module("megatron_tpu.ops.attention").attention

D, CALLS, REPS = 128, 8, 30
# (slots, table entries, page, query heads, kv heads, window, pool pages)
SHAPES = {
    "instruct": (64, 528, 16, 32, 8, 4096, 17000),
    "reasoning": (64, 256, 16, 20, 1, None, 16640),
}
# (shape, slots in use, their shortest and longest row, idle rows' drift)
CASES = [
    ("instruct", 38, 64, 700, 0), ("instruct", 38, 64, 700, 5),
    ("instruct", 0, 1, 1, 0), ("instruct", 64, 1500, 2500, 0),
    ("instruct", 8, 5000, 8000, 0),
    ("reasoning", 53, 100, 1800, 0), ("reasoning", 53, 100, 1800, 5),
    ("reasoning", 64, 3800, 4000, 0),
]


def run(shape, in_use, lo, hi, drift, seed, sq):
    slots, entries, ps, hq, hkv, window, pages = SHAPES[shape]
    rng = np.random.default_rng(seed)
    lens = np.zeros(slots, np.int32)
    rows = rng.permutation(slots)[:in_use]
    lens[rows] = rng.integers(lo, hi + 1, in_use)
    if drift:
        lens[lens == 0] = rng.integers(1, drift + 1, slots - in_use)
    # a row's pages scattered over the pool; what lies behind its window
    # and past its end parks on scratch (page 0), as the engine's does
    table = np.zeros((slots, entries), np.int32)
    free = iter(rng.permutation(np.arange(1, pages)))
    for r in rows:
        first = max(0, (int(lens[r]) - window) // ps) if window else 0
        for e in range(first, -(-(int(lens[r]) + sq) // ps)):
            table[r, e] = next(free)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    k = jax.random.normal(keys[0], (pages, ps, hkv, D), jnp.bfloat16)
    v = jax.random.normal(keys[1], (pages, ps, hkv, D), jnp.bfloat16)
    q = jax.random.normal(keys[2], (slots, sq, hq, D), jnp.bfloat16)
    kernel = ft.paged_flash_decode if sq == 1 else ft.paged_flash_decode_mq

    @jax.jit
    def chained(q, k, v, t, n):
        for _ in range(CALLS):
            o = kernel(q, k, v, t, n, sliding_window=window)
            q = q + (o * 1e-3).astype(q.dtype)
        return o

    one = jax.jit(lambda q, k, v, t, n: kernel(
        q, k, v, t, n, sliding_window=window))
    ref = jax.jit(lambda q, k, v, t, n: attention(
        q, k, v, sliding_window=window, impl="xla", kv_lengths=n,
        page_table=t))
    t, n = jnp.asarray(table), jnp.asarray(lens)
    used = np.zeros(slots, bool)
    used[rows] = True
    got = np.asarray(one(q, k, v, t, n).astype(jnp.float32))
    want = np.asarray(ref(q, k, v, t, n).astype(jnp.float32))
    chained(q, k, v, t, n).block_until_ready()
    start = time.perf_counter()
    for _ in range(REPS):
        o = chained(q, k, v, t, n)
    o.block_until_ready()
    line = {"shape": shape, "sq": sq, "in_use": in_use, "tokens": int(
        lens[rows].sum()), "drift": drift,
        "ms_a_call": (time.perf_counter() - start) / REPS / CALLS * 1e3,
        "max_difference": float(np.abs(got[used] - want[used]).max())
        if in_use else 0.0}
    if hasattr(ft, "decode_blocks_visited"):
        line["blocks_visited_of_held"] = ft.decode_blocks_visited(
            lens, entries, ps, hkv, sq, window)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    kind = jax.devices()[0].device_kind
    if "TPU" not in kind:
        sys.exit(f"needs a TPU, found {kind}")
    print(json.dumps({"tree": os.path.abspath(ARGS.tree),
                      "device_kind": kind}), flush=True)
    for case in CASES:
        run(*case, seed=ARGS.seed, sq=ARGS.sq)
