#!/usr/bin/env python
"""Task finetune/eval harness (ref: tasks/main.py, 96 LoC).

  python -m tasks.main --task MNLI --train_data train.tsv \
      --valid_data dev.tsv --epochs 3 --pretrained_checkpoint ckpt/ \
      --num_layers 12 ... --tokenizer_type HF --tokenizer_model bert-base-...

Tasks: MNLI, QQP (sentence-pair classification), RACE (multiple choice).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.parallel.distributed import initialize_distributed

initialize_distributed()

from megatron_tpu.arguments import args_to_run_config, parse_args


def extra_args(p):
    g = p.add_argument_group("tasks")
    g.add_argument("--task", required=True,
                   choices=["MNLI", "QQP", "RACE", "RET-FINETUNE-NQ"])
    g.add_argument("--train_data", nargs="+", required=True)
    g.add_argument("--valid_data", nargs="+", required=True)
    g.add_argument("--epochs", type=int, default=3)
    g.add_argument("--pretrained_checkpoint", type=str, default=None)
    g.add_argument("--cls_token_id", type=int, default=101)
    g.add_argument("--sep_token_id", type=int, default=102)
    g.add_argument("--pad_token_id", type=int, default=0)
    # ORQA retriever finetuning (ref tasks/main.py:57-69 + arguments.py:954)
    g.add_argument("--retriever_seq_length", type=int, default=256)
    g.add_argument("--train_with_neg", action="store_true")
    g.add_argument("--train_hard_neg", type=int, default=0)
    g.add_argument("--val_av_rank_hard_neg", type=int, default=30)
    g.add_argument("--val_av_rank_other_neg", type=int, default=30)
    g.add_argument("--sample_rate", type=float, default=1.0)
    g.add_argument("--ict_head_size", type=int, default=128)
    g.add_argument("--biencoder_shared_query_context_model",
                   action="store_true")
    g.add_argument("--retriever_score_scaling", action="store_true")
    g.add_argument("--retriever_report_topk_accuracies", nargs="*",
                   type=int, default=[1, 5, 20])
    return p


def _build_task_tokenizer(args, vocab_size):
    from megatron_tpu.tokenizer.tokenizer import build_tokenizer

    return build_tokenizer(args.tokenizer_type, vocab_size=vocab_size,
                           tokenizer_model=getattr(args, "tokenizer_model",
                                                   None),
                           vocab_extra_ids=args.vocab_extra_ids or 0,
                           new_tokens=args.new_tokens)


def _finetune_cfg(args, cfg, n_train):
    """train_iters from epochs + pretrained-checkpoint load/finetune flags
    — shared by every finetune task."""
    import dataclasses

    t = cfg.training
    iters = max(1, args.epochs * n_train // t.global_batch_size)
    training = dataclasses.replace(
        t, train_iters=iters,
        load=args.pretrained_checkpoint or t.load,
        finetune=bool(args.pretrained_checkpoint) or t.finetune)
    return dataclasses.replace(cfg, training=training), iters


def run_orqa(args, cfg):
    """RET-FINETUNE-NQ: supervised DPR-style retriever finetuning."""
    import dataclasses

    import numpy as np

    from megatron_tpu.models.biencoder import biencoder_config
    from tasks.orqa_finetune import (
        NQSupervisedDataset, finetune_orqa, load_dpr_json,
    )

    model = biencoder_config(
        num_layers=cfg.model.num_layers,
        hidden_size=cfg.model.hidden_size,
        num_attention_heads=cfg.model.num_attention_heads,
        vocab_size=cfg.model.vocab_size,
        seq_length=args.retriever_seq_length,
        params_dtype=cfg.model.params_dtype,
        hidden_dropout=cfg.model.hidden_dropout,
        attention_dropout=cfg.model.attention_dropout,
    )
    cfg = dataclasses.replace(cfg, model=model)

    tok = _build_task_tokenizer(args, model.vocab_size)
    ids = dict(cls_id=args.cls_token_id, sep_id=args.sep_token_id,
               pad_id=args.pad_token_id, seed=cfg.training.seed)
    train_raw = [s for p in args.train_data for s in load_dpr_json(p)]
    if args.sample_rate < 1.0:  # ref data.py:161-164
        rng = np.random.RandomState(cfg.training.seed)
        keep = rng.permutation(len(train_raw))[
            : int(len(train_raw) * args.sample_rate)]
        train_raw = [train_raw[i] for i in sorted(keep)]
    valid_raw = [s for p in args.valid_data for s in load_dpr_json(p)]
    num_neg = args.train_hard_neg if args.train_with_neg else 0
    train_ds = NQSupervisedDataset(train_raw, tok.tokenize, model.seq_length,
                                   evaluate=False, num_neg=num_neg, **ids)
    valid_ds = NQSupervisedDataset(valid_raw, tok.tokenize, model.seq_length,
                                   evaluate=True,
                                   val_hard_neg=args.val_av_rank_hard_neg,
                                   val_other_neg=args.val_av_rank_other_neg,
                                   **ids)

    cfg, iters = _finetune_cfg(args, cfg, len(train_ds))
    print(f"RET-FINETUNE-NQ: {len(train_ds)} train / {len(valid_ds)} valid, "
          f"{num_neg} hard negatives/sample, {iters} iterations")
    finetune_orqa(cfg, train_ds, valid_ds,
                  ict_head_size=args.ict_head_size,
                  shared=args.biencoder_shared_query_context_model,
                  score_scaling=args.retriever_score_scaling,
                  topk=tuple(args.retriever_report_topk_accuracies))


def main(argv=None):
    import dataclasses

    from megatron_tpu.models.classification import classification_config
    from tasks.finetune_utils import finetune_classification
    from tasks.glue import GlueDataset, load_mnli, load_qqp
    from tasks.race import RaceDataset, load_race

    args = parse_args(argv, extra_args_provider=extra_args)
    cfg = args_to_run_config(args)
    if args.task == "RET-FINETUNE-NQ":
        return run_orqa(args, cfg)
    model = classification_config(
        num_layers=cfg.model.num_layers,
        hidden_size=cfg.model.hidden_size,
        num_attention_heads=cfg.model.num_attention_heads,
        vocab_size=cfg.model.vocab_size,
        seq_length=cfg.model.seq_length,
        params_dtype=cfg.model.params_dtype,
    )
    cfg = dataclasses.replace(cfg, model=model)

    tok = _build_task_tokenizer(args, cfg.model.vocab_size)
    ids = dict(cls_id=args.cls_token_id, sep_id=args.sep_token_id,
               pad_id=args.pad_token_id)

    if args.task == "RACE":
        num_classes = 1  # per-choice score head [H, 1] (ref multiple_choice.py:46)
        train_raw = [s for p in args.train_data for s in load_race(p)]
        valid_raw = [s for p in args.valid_data for s in load_race(p)]
        train_ds = RaceDataset(train_raw, tok.tokenize, cfg.model.seq_length, **ids)
        valid_ds = RaceDataset(valid_raw, tok.tokenize, cfg.model.seq_length, **ids)
    else:
        loader = load_mnli if args.task == "MNLI" else load_qqp
        num_classes = 3 if args.task == "MNLI" else 2
        train_raw = [s for p in args.train_data for s in loader(p)]
        valid_raw = [s for p in args.valid_data for s in loader(p)]
        train_ds = GlueDataset(train_raw, tok.tokenize, cfg.model.seq_length, **ids)
        valid_ds = GlueDataset(valid_raw, tok.tokenize, cfg.model.seq_length, **ids)

    cfg, iters = _finetune_cfg(args, cfg, len(train_ds))

    print(f"{args.task}: {len(train_ds)} train / {len(valid_ds)} valid "
          f"samples, {num_classes} classes, {iters} iterations")
    finetune_classification(cfg, num_classes, train_ds, valid_ds)


if __name__ == "__main__":
    main()
