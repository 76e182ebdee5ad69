"""On the chip, do the walked row movements give the one pass's bits?

    chiprun -- python tools/moe_rows_bits.py

The four passes of `ops/moe.py` (`rows_to_expert_order`,
`rows_to_token_order`, each one's backward) at the Mellum cell's call
(16,384 tokens of 8 choices, width 2,304, bfloat16, 0.44 of the rows
kept), once as the one pass over all N*k rows and once walked at the
module's own blocks, under both routers of tools/moe_rows_bench.py. One
JSON line a router: for `y`, `d_xf`, `d_topw` and the kept rows of `d_out`
and `xs`, how many elements differ, of how many, and by how much at most;
`finite` says that no result but the rows behind the kept ones holds a NaN
or an Inf, `walked` that the walked form did leave the buffer's last rows
alone (the two forms are traced apart). On the chip those rows hold whatever the memory held
(`grouped_matmul.unwritten_rows`), which no CPU run shows: the CPU's case
is tests/test_moe_live_rows.py
`test_garbage_behind_the_live_rows_reaches_no_result`. The lines also go
to chiprun_out/<--out>/moe_rows_bits.jsonl. Needs a TPU.

What to expect (PR 69, PERF.md section 6): every result equal to the last
bit but `d_topw`, whose row sums over 2,304 columns the chip's compiler
orders by the fusion's shape (a block of rows against all of them): a
third of its elements apart by at most 3.1e-5.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.ops import moe
from moe_rows_bench import CASES, routing

NAMES = ("y", "d_xf", "d_out", "d_topw", "xs")


def passes(order, inv, kept, xf, big, topw):
    dtype = xf.dtype
    return (moe.rows_to_token_order(big, topw, order, inv, dtype, kept),
            moe._to_expert_bwd((inv, kept), big)[0],
            *moe._to_token_bwd(dtype, (big, topw, order, inv, kept), xf)[:2],
            moe.rows_to_expert_order(xf, order, inv, kept))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", default="mellum", choices=list(CASES))
    ap.add_argument("--share", type=float, default=0.4375)
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--out", default="pr69")
    args = ap.parse_args()
    assert jax.devices()[0].platform == "tpu", jax.devices()
    n, k, h = CASES[args.case]
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    xf = jax.random.normal(k1, (n, h), jnp.bfloat16)
    big = jax.random.normal(k2, (n * k, h), jnp.bfloat16)
    topw = jax.nn.softmax(jax.random.normal(k3, (n, k)), axis=-1)
    own = (moe._EXPERT_BLOCK, moe._TOKEN_BLOCK)
    os.makedirs(os.path.join("chiprun_out", args.out), exist_ok=True)
    sink = open(os.path.join("chiprun_out", args.out, "moe_rows_bits.jsonl"),
                "a")
    for router in ("uniform", "collapsed"):
        order, inv, kept = routing(router, n, k, args.share, args.seed)
        held = int(kept.sum())
        got = {}
        # the walk first: what its buffers hold behind the kept rows is
        # then not what the one pass left in the same memory
        for form, blocks in (("walk", own), ("one_pass", None)):
            # the passes ask `_walk_blocks` alone: a form is its answer
            moe._walk_blocks = lambda n, k, blocks=blocks: blocks
            # a function of its own a form: `jit` keeps what it traced of
            # one function, whatever `_walk_blocks` says by then
            traced = jax.jit(lambda *args: passes(*args))
            got[form] = [np.asarray(a.astype(jnp.float32)) for a in
                         traced(order, inv, kept, xf, big, topw)]
        moe._walk_blocks = lambda n, k: own
        line = {"case": args.case, "router": router, "held_rows": held,
                "blocks": list(own), "finite": True,
                "moved_rows_share": round(
                    float(moe.moved_rows_share(kept)), 4),
                # the walk did walk: behind the blocks it filled, its `xs`
                # is not the one pass's
                "walked": bool(np.any(
                    got["one_pass"][4][-own[0]:] != got["walk"][4][-own[0]:]))}
        for name, a, b in zip(NAMES, got["one_pass"], got["walk"]):
            if name in ("d_out", "xs"):      # expert order: the kept rows
                a, b = a[:held], b[:held]
            line["finite"] &= bool(np.all(np.isfinite(b)))
            line[name] = {"differ": int((a != b).sum()), "of": int(a.size),
                          "max_abs": float(np.abs(a - b).max())}
        print(json.dumps(line), flush=True)
        sink.write(json.dumps(line) + "\n")
        sink.flush()


if __name__ == "__main__":
    main()
