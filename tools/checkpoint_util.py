#!/usr/bin/env python
"""Checkpoint copy / dtype-cast / verify / prune utility.

The reference's tools/checkpoint_util.py + loader/saver plugins (907 LoC)
exist to reshard checkpoints between tensor/pipeline layouts. Here that
job is free — checkpoints are one logical orbax tree with sharding
metadata and load at ANY topology (tests/test_checkpoint.py) — so this
tool keeps the remaining real uses: copying a checkpoint to a new
directory, picking a specific iteration, casting parameter dtype
(e.g. fp32 masters -> bf16 serving weights), and the crash-safety
subcommands built on the manifest API (docs/fault_tolerance.md):

  # copy/cast (default mode, no subcommand)
  python tools/checkpoint_util.py --load ckpts/run --save ckpts/export \
      [--load_iters N] [--target_params_dtype bfloat16] [--params_only]

  # verify manifests (existence+size; --deep adds crc32): exits non-zero
  # if any checked checkpoint is invalid
  python tools/checkpoint_util.py verify --load ckpts/run [--load_iters N] [--deep]

  # retention: prune all but the newest K committed checkpoints, and
  # uncommitted staging dirs left by crashes
  python tools/checkpoint_util.py prune --load ckpts/run --keep_latest_k 3 \
      [--dry_run]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def verify_main(argv=None):
    """`verify` subcommand: manifest-check one or all checkpoints in a run
    dir. Pure file I/O — never builds a model or touches devices."""
    p = argparse.ArgumentParser(prog="checkpoint_util.py verify")
    p.add_argument("--load", required=True)
    p.add_argument("--load_iters", type=int, default=None,
                   help="verify only this iteration (default: all found)")
    p.add_argument("--deep", action="store_true",
                   help="also verify crc32 checksums (reads every byte)")
    args = p.parse_args(argv)

    from megatron_tpu.training import checkpointing

    iters = ([args.load_iters] if args.load_iters is not None
             else checkpointing.committed_iterations(args.load))
    if not iters:
        raise SystemExit(f"no checkpoints found in {args.load}")
    results = []
    for it in iters:
        path = checkpointing.checkpoint_dir(args.load, it)
        ok, detail = checkpointing.verify_checkpoint(path, deep=args.deep)
        results.append((it, ok))
        tags = checkpointing.checkpoint_tags(path)
        print(f"iter {it:7d}: {'OK     ' if ok else 'INVALID'} {detail}"
              + (f" [tags: {','.join(tags)}]" if tags else ""))
    tracked = checkpointing.read_tracker(args.load)
    print(f"tracker: {tracked}; newest valid: "
          f"{max((i for i, ok in results if ok), default=None)}")
    if not all(ok for _, ok in results):
        raise SystemExit(1)
    return results


def prune_main(argv=None):
    """`prune` subcommand: keep_latest_k retention + stale staging
    cleanup, driven by the same manifest API the train loop uses."""
    p = argparse.ArgumentParser(prog="checkpoint_util.py prune")
    p.add_argument("--load", required=True)
    p.add_argument("--keep_latest_k", type=int, required=True)
    p.add_argument("--dry_run", action="store_true")
    p.add_argument("--staging_age_mins", type=float, default=60.0,
                   help="only remove staging dirs idle this long — a LIVE "
                        "training run's async save writes into a .tmp dir "
                        "and must not be pruned from under it")
    args = p.parse_args(argv)

    from megatron_tpu.training import checkpointing

    pruned = checkpointing.prune_checkpoints(
        args.load, args.keep_latest_k, dry_run=args.dry_run)
    stale = ([] if args.dry_run
             else checkpointing.cleanup_staging(
                 args.load, min_age_seconds=args.staging_age_mins * 60))
    verb = "would prune" if args.dry_run else "pruned"
    print(f"{verb} iterations {pruned}; removed staging dirs {stale}; "
          f"kept {checkpointing.list_valid_checkpoints(args.load)}")
    return pruned


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "verify":
        return verify_main(argv[1:])
    if argv and argv[0] == "prune":
        return prune_main(argv[1:])
    p = argparse.ArgumentParser()
    p.add_argument("--load", required=True)
    p.add_argument("--save", required=True)
    p.add_argument("--load_iters", type=int, default=None)
    p.add_argument("--target_params_dtype", default=None,
                   choices=["bfloat16", "float16", "float32"])
    p.add_argument("--params_only", action="store_true",
                   help="drop optimizer state (a serving/export copy)")
    args = p.parse_args(argv)

    import json

    import jax
    import jax.numpy as jnp

    from megatron_tpu.config import RunConfig
    from megatron_tpu.models.params import init_params
    from megatron_tpu.training import checkpointing
    from megatron_tpu.training.optimizer import init_train_state

    it = (args.load_iters if args.load_iters is not None
          else checkpointing.read_tracker(args.load))
    if it is None:
        raise SystemExit(f"no checkpoint tracker in {args.load}")
    meta_path = os.path.join(
        checkpointing.checkpoint_dir(args.load, it), "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    saved_cfg = meta.get("config") or {}
    if "model" not in saved_cfg:
        raise SystemExit(f"{meta_path} has no saved model config")
    cfg = RunConfig.from_dict(saved_cfg)

    params = init_params(cfg.model, jax.random.PRNGKey(0))
    state = init_train_state(cfg.optimizer, params)
    state, it, consumed = checkpointing.load_checkpoint(
        args.load, state, iteration=it,
        no_load_optim=args.params_only)
    if args.params_only:
        import dataclasses

        zeroed = jax.tree.map(jnp.zeros_like, state.mu)
        state = dataclasses.replace(state, mu=zeroed,
                                    nu=jax.tree.map(jnp.zeros_like, state.nu))
    if args.target_params_dtype:
        import dataclasses

        dt = jnp.dtype(args.target_params_dtype)
        cast = lambda t: jax.tree.map(lambda x: x.astype(dt), t)
        state = dataclasses.replace(state, params=cast(state.params))
        saved_cfg["model"]["params_dtype"] = args.target_params_dtype

    path = checkpointing.save_checkpoint(args.save, state, it, consumed,
                                         config=saved_cfg)
    print(f"wrote checkpoint (iteration {it}"
          + (", params-only" if args.params_only else "")
          + (f", params {args.target_params_dtype}"
             if args.target_params_dtype else "")
          + f") to {path}")
    return path


if __name__ == "__main__":
    main()
