"""The two readings behind a training cell's `first_loss_tolerance`, on
the device this runs on (one chip; `chiprun -- python3
benchmark/tools/first_loss_control.py --workload <cell> --seed <n>`):

- `gap_program`: the program's first loss (the cell's flags and dtype)
  against the cell's float32 reference at `highest` matmul precision,
  which is what `correct` compares;
- `gap_lower_precision_reference`: that reference computed a precision
  lower (the device's default for float32 matmuls: on a TPU one bf16 pass)
  against itself at `highest`. A limit that holds a lower precision lies
  between the two.

Sequences of the cell's length, drawn as the harness's corpus draws them (a
seeded cycle of the mix's `cycle` ids). Prints one JSON line."""
import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.harness import spec  # noqa: E402
from megatron_tpu.arguments import args_to_run_config, parse_args  # noqa: E402
from megatron_tpu.models.language_model import lm_loss  # noqa: E402
from megatron_tpu.models.params import init_params  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    cell = spec.Cell(os.path.join(ROOT, "BENCHMARK.json"), args.workload)
    config, mix = cell.config, cell.traffic
    ref = spec.load_module(cell.reference_path())
    seq, rows = mix["seq_length"], mix["micro_batch_size"]
    cfg = args_to_run_config(parse_args(
        ref.program_flags(config, seq) + config["program"]["flags"]
        + list(mix["flags"])
        + ["--micro_batch_size", str(rows),
           "--global_batch_size", str(rows)])).model
    params = init_params(cfg, jax.random.PRNGKey(args.seed % (2 ** 31)))

    rng = np.random.default_rng(args.seed)
    ids = mix["corpus"]["cycle"]
    cycle = rng.choice(config["vocab_size"] - 1, size=ids, replace=False)
    starts = rng.integers(0, ids, size=rows)
    toks = np.stack([cycle[(s + np.arange(seq + 1)) % ids] for s in starts])
    batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
             "labels": jnp.asarray(toks[:, 1:], jnp.int32),
             "loss_mask": jnp.ones((rows, seq), jnp.float32)}
    program = float(jax.jit(
        lambda p, b: lm_loss(cfg, p, b, recompute="selective")[0])(
            params, batch))

    def reference_loss():
        return float(jax.jit(
            lambda w, t, y, m: ref.lm_loss(w, t, y, m, config))(
                ref.from_program_params(params), batch["tokens"],
                batch["labels"], batch["loss_mask"]))

    highest = reference_loss()
    # the reference asks for `highest` itself: take its request away
    asked = jax.default_matmul_precision
    jax.default_matmul_precision = lambda name: contextlib.nullcontext()
    try:
        lower = reference_loss()
    finally:
        jax.default_matmul_precision = asked
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": jax.devices()[0].device_kind,
        "program_first_loss": program, "reference_highest": highest,
        "reference_default_precision": lower,
        "gap_program": abs(program - highest),
        "gap_lower_precision_reference": abs(lower - highest),
        "first_loss_tolerance": mix.get("first_loss_tolerance")}))


if __name__ == "__main__":
    main()
