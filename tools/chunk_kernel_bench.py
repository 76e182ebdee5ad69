"""Time one call of the prefill chunk's attention kernel on the chip.

    chiprun -- python tools/chunk_kernel_bench.py

Runs `flash_template.paged_flash_chunk` alone at the two served cells'
chunk shapes (benchmark/configs/mistral-7b-d8-serve.json: one chunk of
512 under a table of 528 entries of 16, 8 of 32 heads, window 4096;
jamba2-3b-serve.json: 256 entries, 1 of 20 heads, no window) over one
layer's pool, for prompts as the cells' traffic and the queued long-prompt
candidate make them: a chunk at offset `off` of a prompt of `total`
tokens (the chunk's tail behind `total` is padding). One JSON line a
case: ms a call of the kernel (eight chained calls a dispatch, as a step
of eight layers makes them) and of the dense path it replaces (the gather
of the table's every page and the masked einsum: `attention(impl="xla",
page_table=...)`), the largest difference between the two on the
prompt's rows, and the blocks visited over the blocks the table holds a
query tile (`chunk_blocks_visited`). Needs a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.ops.attention import attention
from megatron_tpu.ops.pallas import flash_template as ft

D, CHUNK, CALLS, REPS = 128, 512, 8, 20
# (table entries, page, query heads, kv heads, window, pool pages)
SHAPES = {
    "instruct": (528, 16, 32, 8, 4096, 17000),
    "reasoning": (256, 16, 20, 1, None, 16640),
}
# (shape, the chunk's offset, the prompt's length)
CASES = [
    ("instruct", 0, 16), ("instruct", 0, 64), ("instruct", 0, 300),
    ("instruct", 0, 512), ("instruct", 3584, 4096), ("instruct", 4096, 4608),
    ("instruct", 7680, 8192),
    ("reasoning", 0, 100), ("reasoning", 0, 512), ("reasoning", 1536, 2048),
    ("reasoning", 3584, 4000),
]


def _timed(fn, args, reps):
    fn(*args).block_until_ready()
    start = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - start) / reps * 1e3


def run(shape, off, total, seed):
    entries, ps, hq, hkv, window, pages = SHAPES[shape]
    rng = np.random.default_rng(seed)
    # the row's pages scattered over the pool; what lies behind its
    # window and past its end parks on scratch (page 0), as the engine's
    table = np.zeros((1, entries), np.int32)
    first = max(0, (off - window) // ps) if window else 0
    live = np.arange(first, -(-total // ps))
    table[0, live] = rng.permutation(np.arange(1, pages))[:live.size]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    k = jax.random.normal(keys[0], (pages, ps, hkv, D), jnp.bfloat16)
    v = jax.random.normal(keys[1], (pages, ps, hkv, D), jnp.bfloat16)
    q = jax.random.normal(keys[2], (1, CHUNK, hq, D), jnp.bfloat16)
    offs, ends = jnp.full((1,), off, jnp.int32), jnp.full((1,), total,
                                                          jnp.int32)

    def kernel(q, k, v, t):
        return ft.paged_flash_chunk(q, k, v, t, offs, ends,
                                    sliding_window=window)

    def dense(q, k, v, t):
        return attention(q, k, v, sliding_window=window, impl="xla",
                         q_offset=offs[0], page_table=t)

    def chained(fn):
        def run(q, k, v, t):
            for _ in range(CALLS):
                o = fn(q, k, v, t)
                q = q + (o * 1e-3).astype(q.dtype)
            return o
        return jax.jit(run)

    t = jnp.asarray(table)
    rows = min(CHUNK, total - off)
    got = np.asarray(jax.jit(kernel)(q, k, v, t).astype(jnp.float32))
    want = np.asarray(jax.jit(dense)(q, k, v, t).astype(jnp.float32))
    print(json.dumps({
        "shape": shape, "off": off, "total": total,
        "kernel_ms_a_call": _timed(chained(kernel), (q, k, v, t),
                                   REPS) / CALLS,
        "dense_ms_a_call": _timed(chained(dense), (q, k, v, t), 3) / CALLS,
        "max_difference": float(np.abs(got[0, :rows]
                                       - want[0, :rows]).max()),
        "finite": bool(np.isfinite(got).all()),
        "blocks_visited_of_held": ft.chunk_blocks_visited(
            off, CHUNK, total, hq // hkv, entries, ps, hkv, window)}),
        flush=True)


if __name__ == "__main__":
    args = argparse.ArgumentParser()
    args.add_argument("--seed", type=int, default=1)
    seed = args.parse_args().seed
    kind = jax.devices()[0].device_kind
    if "TPU" not in kind:
        sys.exit(f"needs a TPU, found {kind}")
    print(json.dumps({"device_kind": kind}), flush=True)
    for case in CASES:
        run(*case, seed=seed)
