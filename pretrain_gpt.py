#!/usr/bin/env python
"""GPT-family pretraining entry point.

Equivalent of the reference's pretrain.py path for GPT/Llama/Falcon/Mistral
(finetune.py with --model_name, or pretrain_gpt upstream): parses reference-
style flags, builds datasets from --data_path, runs the training loop.

Example (tiny smoke run):
  python pretrain_gpt.py --model_name llama2-7B --data_path /data/corpus \
      --train_iters 1000 --micro_batch_size 1 --global_batch_size 128 \
      --tensor_model_parallel_size 8 --sequence_parallel --bf16 \
      --save ckpts --save_interval 500
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from megatron_tpu.parallel.distributed import initialize_distributed

initialize_distributed()

from megatron_tpu.arguments import args_to_run_config, parse_args
from megatron_tpu.data.gpt_dataset import build_gpt_datasets
from megatron_tpu.data.samplers import (
    PretrainingRandomSampler, PretrainingSampler, build_data_loader,
)
from megatron_tpu.training.pretrain import gpt_collate, pretrain


def main(argv=None):
    args = parse_args(argv)
    cfg = args_to_run_config(args)
    if not args.data_path:
        raise SystemExit("--data_path is required")
    t = cfg.training
    train_iters = t.train_iters or (t.train_samples // t.global_batch_size)

    n_train = train_iters * t.global_batch_size
    n_valid = (train_iters // max(t.eval_interval, 1) + 1) * t.eval_iters \
        * t.global_batch_size
    train_ds, valid_ds, test_ds = build_gpt_datasets(
        args.data_path, args.split, cfg.model.seq_length,
        (n_train, n_valid, t.eval_iters * t.global_batch_size),
        seed=t.seed, cache_dir=args.data_cache_dir)

    eod = args.eod_token_id
    if (args.eod_mask_loss or args.reset_position_ids) and eod is None:
        raise SystemExit(
            "--eod_mask_loss/--reset_position_ids need --eod_token_id "
            "(the data is pre-tokenized; there is no tokenizer to ask)")
    collate = lambda items: gpt_collate(
        items, eod_token=eod, eod_mask_loss=args.eod_mask_loss,
        reset_position_ids=args.reset_position_ids)

    def train_iter_factory(consumed, gbs):
        if args.dataloader_type == "cyclic":
            # epoch-seeded random order (ref MegatronPretrainingRandomSampler)
            sampler = PretrainingRandomSampler(
                total_samples=len(train_ds), consumed_samples=consumed,
                micro_batch_size=gbs, data_parallel_rank=0,
                data_parallel_size=1, seed=t.seed)
        else:
            sampler = PretrainingSampler(
                total_samples=len(train_ds), consumed_samples=consumed,
                micro_batch_size=gbs, data_parallel_rank=0,
                data_parallel_size=1)
        return build_data_loader(train_ds, sampler, collate_fn=collate,
                                 prefetch=args.num_workers)

    def valid_iter_factory():
        if valid_ds is None:
            return iter(())
        sampler = PretrainingSampler(
            total_samples=len(valid_ds), consumed_samples=0,
            micro_batch_size=t.global_batch_size, data_parallel_rank=0,
            data_parallel_size=1)
        return build_data_loader(valid_ds, sampler, collate_fn=collate,
                                 prefetch=args.num_workers)

    return pretrain(cfg, train_iter_factory, valid_iter_factory)


if __name__ == "__main__":
    main()
