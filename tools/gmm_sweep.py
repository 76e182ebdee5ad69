"""Sweep the grouped-matmul kernels' tiles on the chip.

    chiprun -- python tools/gmm_sweep.py --shape olmoe
    chiprun -- python tools/gmm_sweep.py --shape mixtral --tm 256 512

Times each of the six products of one dropless MoE layer's experts
(megatron_tpu/ops/pallas/grouped_matmul.py: forward and the two backward
products of the gate-up and of the down matrix; `moe_tgmm` also in the
form that sums into a float32 accumulator, `ms_into`) at every tile of the grid
below, against `lax.ragged_dot` on the same rows, and prints one JSON line
a measurement (also written under --out). `pick_gmm_tiles` keeps the rule
this table teaches; PERF.md keeps the table.

The groups are the model's own: `--shape olmoe` runs the benchmark cell's
model (OLMoE-1B-7B widths, one layer, freshly initialised from --seed) over
one 4096-token sequence of the benchmark's corpus and takes the router's
group sizes; `--shape mixtral` has no cell, so its 8 groups are a fixed
skewed split. A tile the compiler refuses is a line with "error".
Needs a TPU.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.ops.pallas import grouped_matmul as gm


def olmoe_group_sizes(seed: int) -> np.ndarray:
    """The router's rows per expert for one sequence of the benchmark's
    corpus (benchmark/harness/train_child.py build_corpus: documents that
    walk one cycle of 512 token ids) under initial weights."""
    from megatron_tpu.models import presets
    from megatron_tpu.models.language_model import lm_forward
    from megatron_tpu.models.params import init_params

    cfg = dataclasses.replace(
        presets.olmoe(seq_length=4096), num_layers=1,
        params_dtype="bfloat16", attention_impl="pallas").validate()
    rng = np.random.default_rng(seed)
    eod = cfg.vocab_size - 1
    cycle = rng.choice(eod, size=512, replace=False)
    tokens = []
    while len(tokens) < cfg.seq_length:
        n = int(np.clip(np.exp(rng.normal(np.log(600), 1.0)), 32, 16384))
        at = rng.integers(0, 512)
        tokens += list(cycle[(at + np.arange(n)) % 512]) + [eod]
    tokens = jnp.asarray(tokens[:cfg.seq_length], jnp.int32)[None]

    seen = []
    real = gm.visits_for

    def spy(group_sizes, m):
        jax.debug.callback(lambda g: seen.append(np.asarray(g)), group_sizes)
        return real(group_sizes, m)

    gm.visits_for = spy
    try:
        params = init_params(cfg, jax.random.PRNGKey(seed))
        jax.block_until_ready(
            jax.jit(lambda p, t: lm_forward(cfg, p, t))(params, tokens))
    finally:
        gm.visits_for = real
    return seen[0]


def mixtral_group_sizes(m: int) -> np.ndarray:
    share = np.array([0.22, 0.17, 0.14, 0.12, 0.11, 0.09, 0.08, 0.07])
    sizes = np.floor(share * m).astype(np.int32)
    sizes[0] += m - sizes.sum()
    return sizes


def time_ms(fn, args, carried=None, reps: int = 3, calls: int = 10) -> float:
    """Best mean of `calls` calls of fn(*args), or with `carried` of
    carried = fn(*args, carried): the last argument donated and the result
    fed back, as a step's accumulator is."""
    if carried is None:
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*args))
    else:
        fn = jax.jit(fn, donate_argnums=len(args))
        carried = jax.block_until_ready(fn(*args, carried))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = None
        for _ in range(calls):
            if carried is None:
                out = fn(*args)
            else:
                out = carried = fn(*args, carried)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return 1e3 * best


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", choices=("olmoe", "mixtral"), default="olmoe")
    ap.add_argument("--seed", type=int, default=2700000001)
    ap.add_argument("--tm", type=int, nargs="+", default=[128, 256, 512])
    ap.add_argument("--tk", type=int, nargs="+", default=[512, 1024, 0],
                    help="0 = the whole contraction")
    ap.add_argument("--tn", type=int, nargs="+", default=[512, 1024, 2048])
    ap.add_argument("--products", nargs="+", default=None,
                    help="only these (fwd_in, ..., tgmm_out)")
    ap.add_argument("--out", default="chiprun_out/gmm_sweep")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("tools/gmm_sweep.py measures on a TPU")

    if args.shape == "olmoe":
        m, h, f2, f = 32768, 2048, 2048, 1024     # f2 = gate and up fused
        sizes = olmoe_group_sizes(args.seed)
    else:
        m, h, f2, f = 8192, 4096, 28672, 14336
        sizes = mixtral_group_sizes(m)
    E = len(sizes)
    assert sizes.sum() == m, (sizes.sum(), m)
    os.makedirs(args.out, exist_ok=True)
    log = open(os.path.join(args.out, f"{args.shape}.jsonl"), "w")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        log.write(line + "\n")
        log.flush()

    emit({"shape": args.shape, "m": m, "groups": E,
          "max_over_mean": float(sizes.max() * E / m),
          "group_sizes": sizes.tolist(),
          "device": jax.devices()[0].device_kind})
    gs = jnp.asarray(sizes, jnp.int32)
    key = jax.random.PRNGKey(0)
    bf16 = jnp.bfloat16

    def rand(i, shape):
        return (jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * 0.05).astype(bf16)

    # (product, contraction, result columns): lhs [m, k]; rhs as stored
    products = [("fwd_in", "nn", h, f2), ("fwd_out", "nn", f, h),
                ("drows_in", "nt", f2, h), ("drows_out", "nt", h, f),
                ("tgmm_in", "t", h, f2), ("tgmm_out", "t", f, h)]
    for name, kind, k, n in products:
        if args.products and name not in args.products:
            continue
        lhs = rand(1, (m, k))
        if kind == "t":
            other = rand(2, (m, n))
        else:
            other = rand(2, (E, n, k) if kind == "nt" else (E, k, n))
        if kind == "nn":
            # the product alone, and its two gradients alone (the sum's gradient
            # needs no forward result, so XLA drops the forward), the plain way
            # and the kernels' way at the tiles `pick_gmm_tiles` gives
            plan = gm._plan(m, k, n, E)
            table = gm.group_visits(gs, m, plan.fwd[0])
            ways = {"ragged_dot": lambda a, b: jax.lax.ragged_dot(a, b, gs),
                    "picked": lambda a, b: gm._grouped_matmul_kernels(
                        a, b, table, None, plan)}
            for way, fn in ways.items():
                both = jax.grad(lambda a, b, fn=fn: jnp.sum(
                    fn(a, b).astype(jnp.float32)), argnums=(0, 1))
                rec = {"product": name, "k": k, "n": n, "kernel": way,
                       "tiles": None if way == "ragged_dot" else list(plan)}
                try:
                    rec["ms"] = time_ms(fn, (lhs, other))
                    rec["ms_both_gradients"] = time_ms(both, (lhs, other))
                except Exception as e:  # noqa: BLE001 - as below
                    rec["error"] = str(e).splitlines()[0][:160]
                emit(rec)
        for tm, tk, tn in itertools.product(args.tm, args.tk, args.tn):
            tk = tk or k
            if tk > k or tn > n or k % tk or n % tn:
                continue
            rec = {"product": name, "k": k, "n": n, "tiles": [tm, tk, tn]}
            visits = gm.group_visits(gs, m, tm)
            try:
                if kind == "t":
                    fn = lambda a, b: gm._tgmm(a, b, visits, (tm, tk, tn))
                else:
                    fn = lambda a, b: gm._gmm(a, b, visits, (tm, tk, tn),
                                              kind == "nt")
                rec["ms"] = time_ms(fn, (lhs, other))
                if kind == "t":
                    # the form that sums into a float32 accumulator of the
                    # result's shape, in place (a stack of one layer)
                    rec["ms_into"] = time_ms(
                        lambda a, b, c: gm._tgmm(
                            a, b, visits, (tm, tk, tn),
                            into=(c, jnp.int32(0))),
                        (lhs, other), jnp.zeros((1, E, k, n), jnp.float32))
            except Exception as e:  # noqa: BLE001 - a tile the compiler
                # refuses is a row of the table, not the end of the sweep
                rec["error"] = str(e).splitlines()[0][:160]
            emit(rec)
    return 0


if __name__ == "__main__":
    sys.exit(main())
