#!/usr/bin/env python
"""BERT pretraining entry point (ref: pretrain_bert.py).

Data: a sentence-level indexed dataset (one sequence per sentence, document
boundaries preserved — produce with tools/preprocess_data.py and a sentence
splitter upstream).

  python pretrain_bert.py --num_layers 12 --hidden_size 768 \
      --num_attention_heads 12 --seq_length 512 --vocab_size 30592 \
      --data_path data/sents --mask_token_id 103 --cls_token_id 101 \
      --sep_token_id 102 --pad_token_id 0 --train_iters 10000 ...
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from megatron_tpu.parallel.distributed import initialize_distributed

initialize_distributed()

from megatron_tpu.arguments import args_to_run_config, parse_args


def extra_args(p):
    g = p.add_argument_group("bert")
    g.add_argument("--mask_token_id", type=int, default=103)
    g.add_argument("--cls_token_id", type=int, default=101)
    g.add_argument("--sep_token_id", type=int, default=102)
    g.add_argument("--pad_token_id", type=int, default=0)
    g.add_argument("--no_binary_head", action="store_true")
    return p


def main(argv=None):
    import dataclasses

    import numpy as np

    from megatron_tpu.data.bert_dataset import BertDataset
    from megatron_tpu.data.indexed_dataset import make_dataset
    from megatron_tpu.data.samplers import PretrainingSampler, build_data_loader
    from megatron_tpu.models.bert import bert_loss
    from megatron_tpu.training.pretrain import TrainLoop

    args = parse_args(argv, extra_args_provider=extra_args)
    cfg = args_to_run_config(args)
    # BERT-ify the model config (ref: BertModel flags)
    cfg = dataclasses.replace(
        cfg,
        model=dataclasses.replace(
            cfg.model,
            attn_mask_type="padding",
            num_tokentypes=2,
            bert_binary_head=not args.no_binary_head,
            tie_embed_logits=True,
            position_embedding_type="absolute",
            max_position_embeddings=cfg.model.max_position_embeddings
            or cfg.model.seq_length,
        ).validate())
    if not args.data_path:
        raise SystemExit("--data_path is required")

    t = cfg.training
    indexed = make_dataset(args.data_path[0])
    n_train = (t.train_iters or 1000) * t.global_batch_size
    train_ds = BertDataset(
        indexed, num_samples=n_train, max_seq_length=cfg.model.seq_length,
        mask_token=args.mask_token_id, cls_token=args.cls_token_id,
        sep_token=args.sep_token_id, pad_token=args.pad_token_id,
        vocab_size=cfg.model.vocab_size, seed=t.seed,
        masked_lm_prob=args.mask_prob,
        short_seq_prob=args.short_seq_prob,
        binary_head=not args.no_binary_head)

    def train_iter_factory(consumed, gbs):
        sampler = PretrainingSampler(len(train_ds), consumed, gbs, 0, 1)
        return build_data_loader(train_ds, sampler,
                                 prefetch=args.num_workers)

    def bert_loss_fn(model_cfg, p, b, key, sharder=None):
        kw = {"sharder": sharder} if sharder is not None else {}
        return bert_loss(model_cfg, p, b, dropout_key=key, **kw)

    loop = TrainLoop(cfg, loss_fn=bert_loss_fn)
    loop.train(train_iter_factory)


if __name__ == "__main__":
    main()
