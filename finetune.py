#!/usr/bin/env python
"""Instruction finetuning entry point.

Equivalent of the reference's finetune.py (257 LoC): loads a converted
checkpoint (--load, typically produced by tools/hf_to_native.py), trains on
either packed GPT data (--data_type gpt) or paired text/role instruction
data (--data_type instruction) with assistant-token loss masking.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from megatron_tpu.parallel.distributed import initialize_distributed

initialize_distributed()

from megatron_tpu.arguments import args_to_run_config, parse_args
from megatron_tpu.data.instruction_dataset import (
    InstructionDataset, instruction_collator,
)
from megatron_tpu.data.samplers import PretrainingRandomSampler, build_data_loader
from megatron_tpu.training.pretrain import pretrain


def extra_args(parser):
    g = parser.add_argument_group("finetuning")
    g.add_argument("--data_type", default="instruction",
                   choices=["gpt", "instruction"])
    g.add_argument("--pad_token_id", type=int, default=0)
    return parser


def main(argv=None):
    args = parse_args(argv, extra_args_provider=extra_args)
    cfg = args_to_run_config(args)
    if not args.data_path:
        raise SystemExit("--data_path is required")

    if args.data_type == "gpt":
        import pretrain_gpt

        return pretrain_gpt.main(argv)

    t = cfg.training
    prefix = args.data_path[0]
    train_ds = InstructionDataset(prefix, seed=t.seed)

    def collate(items):
        return instruction_collator(
            items, seq_length=cfg.model.seq_length,
            pad_token=args.pad_token_id,
            scalar_loss_mask=args.scalar_loss_mask,
            variable_seq_lengths=False)

    def train_iter_factory(consumed, gbs):
        sampler = PretrainingRandomSampler(
            total_samples=len(train_ds), consumed_samples=consumed,
            micro_batch_size=gbs, data_parallel_rank=0,
            data_parallel_size=1, seed=t.seed)
        return build_data_loader(train_ds, sampler, collate_fn=collate,
                                 prefetch=args.num_workers)

    pretrain(cfg, train_iter_factory)


if __name__ == "__main__":
    main()
