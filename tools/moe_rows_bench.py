"""Time the MoE block's four row movements on the chip, one pass over all
N*k rows against the walk over the live rows.

    chiprun -- python tools/moe_rows_bench.py
    chiprun -- python tools/moe_rows_bench.py --cases mellum --blocks

The passes (ops/moe.py): `dispatch` (rows_to_expert_order), `dispatch_bwd`,
`combine` (rows_to_token_order), `combine_bwd`, each alone at a call's
shapes (N tokens, k choices, width h, bfloat16) under a routing that
keeps `share` of the (token, choice) rows: `uniform` (every token draws
its own experts) or `collapsed` (every token the same). One JSON line a
(case, pass, form): ms a call (REPS calls chained inside one jit, the best
of three), and for the walked forms the rows a trip moves. `--blocks`
sweeps the trips' sizes; without it the forms are the one pass and the
walk at the module's own blocks (`--forms`, `--passes` keep some). The lines also go to
chiprun_out/<--out>/moe_rows.jsonl. Needs a TPU.

What `ops/moe.py` `_walk_blocks` rests on (PERF.md section 6, PR 69).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.ops import moe

REPS = 10
# name: (tokens, choices, width): the Mellum cell's micro-batch, the
# Nemotron cell's chunk and tick, and sizes between them
CASES = {
    "mellum": (16384, 8, 2304),
    "mellum_half": (8192, 8, 2304), "mellum_4th": (4096, 8, 2304),
    "mellum_8th": (2048, 8, 2304), "mellum_16th": (1024, 8, 2304),
    "nemotron_chunk": (512, 22, 1024), "nemotron_tick": (64, 22, 1024),
    "nemotron_x2": (1024, 22, 1024), "nemotron_x4": (2048, 22, 1024),
    "nemotron_x8": (4096, 22, 1024), "nemotron_x16": (8192, 22, 1024),
}
EXPERTS = 64


def routing(kind: str, n: int, k: int, share: float, seed: int):
    """(order, inv, kept) of a router that keeps `share` of the rows."""
    rng = np.random.default_rng(seed)
    held = round(share * EXPERTS)
    if kind == "collapsed":
        kept_k = round(share * k)
        row = np.concatenate([np.arange(kept_k), held + np.arange(k - kept_k)])
        topi = np.broadcast_to(rng.permutation(row), (n, k)).copy()
    else:
        topi = np.argsort(rng.random((n, EXPERTS)), axis=1)[:, :k]
    kept = jnp.asarray(topi < held)
    order, inv = moe.sort_by_expert(
        jnp.where(kept, jnp.asarray(topi, jnp.int32), held))
    return order, inv, kept


def chained(fn):
    """fn(*ints, *arrays) REPS times inside one jit, the index arrays made
    to depend on the call before so that no call is hoisted or merged."""
    def run(ints, arrays):
        def body(carry, _):
            moved = [a + jnp.minimum(carry, 0).astype(a.dtype) for a in ints]
            out = fn(*moved, *arrays)
            first = jax.tree.leaves(out)[0]
            seen = first.reshape(-1)[0]
            return carry + (seen != seen).astype(jnp.int32), None
        return jax.lax.scan(body, jnp.zeros((), jnp.int32), None,
                            length=REPS)[0]
    return jax.jit(run)


def time_ms(fn, ints, arrays):
    run = chained(fn)
    run(ints, arrays).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        run(ints, arrays).block_until_ready()
        best = min(best, time.perf_counter() - t)
    return best * 1e3 / REPS


def passes(dtype):
    return {
        "dispatch": lambda order, inv, kept, xf, big, topw:
            moe.rows_to_expert_order(xf, order, inv, kept),
        "dispatch_bwd": lambda order, inv, kept, xf, big, topw:
            moe._to_expert_bwd((inv, kept), big)[0],
        "combine": lambda order, inv, kept, xf, big, topw:
            moe.rows_to_token_order(big, topw, order, inv, dtype, kept),
        "combine_bwd": lambda order, inv, kept, xf, big, topw:
            moe._to_token_bwd(dtype, (big, topw, order, inv, kept), xf)[:2],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cases", nargs="*", default=list(CASES))
    ap.add_argument("--routers", nargs="*", default=["uniform", "collapsed"])
    ap.add_argument("--share", type=float, default=0.4375)
    ap.add_argument("--blocks", action="store_true")
    ap.add_argument("--passes", nargs="*", default=None)
    ap.add_argument("--forms", nargs="*", default=["one_pass", "walk"])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="pr69")
    args = ap.parse_args()
    assert jax.devices()[0].platform == "tpu", jax.devices()
    own = (moe._EXPERT_BLOCK, moe._TOKEN_BLOCK)
    if args.blocks:
        forms = [("walk", e, t) for e, t in (
            (512, 128), (1024, 256), (2048, 512), (4096, 1024),
            (8192, 2048), (16384, 4096))]
    else:
        forms = [("walk", *own)]
    forms = [f for f in [("one_pass", None, None)] + forms
             if f[0] in args.forms]
    dtype = jnp.bfloat16
    os.makedirs(os.path.join("chiprun_out", args.out), exist_ok=True)
    sink = open(os.path.join("chiprun_out", args.out, "moe_rows.jsonl"), "a")
    for case in args.cases:
        n, k, h = CASES[case]
        key = jax.random.PRNGKey(args.seed)
        xf = jax.random.normal(key, (n, h), dtype)
        big = jax.random.normal(key, (n * k, h), dtype)
        topw = jax.nn.softmax(jax.random.normal(key, (n, k)), axis=-1)
        for router in args.routers:
            order, inv, kept = routing(router, n, k, args.share, args.seed)
            for form, expert, token in forms:
                # the passes ask `_walk_blocks` alone: a form is its answer
                # (no block may hang over the buffer's end: the largest
                # power of two under the asked one that divides it)
                blocks = ((math.gcd(n * k, expert), math.gcd(n, token))
                          if form == "walk" else None)
                moe._walk_blocks = lambda n, k, blocks=blocks: blocks
                for name, fn in passes(dtype).items():
                    if args.passes and name not in args.passes:
                        continue
                    ms = time_ms(fn, (order, inv), (kept, xf, big, topw))
                    line = {"case": case, "tokens": n, "k": k, "h": h,
                            "rows": n * k, "router": router,
                            "kept_share": round(float(kept.mean()), 4),
                            "pass": name, "form": form, "ms": round(ms, 4)}
                    if form == "walk":
                        line["blocks"] = list(blocks)
                        line["moved_rows_share"] = round(
                            float(moe.moved_rows_share(kept)), 4)
                    print(json.dumps(line), flush=True)
                    sink.write(json.dumps(line) + "\n")
                    sink.flush()


if __name__ == "__main__":
    main()
