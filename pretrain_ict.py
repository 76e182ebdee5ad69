#!/usr/bin/env python
"""ICT (inverse cloze task) biencoder pretraining entry point
(ref: pretrain_ict.py, 165 LoC).

Data: a sentence-level indexed dataset for blocks, plus (optionally) a
title dataset with one title sequence per document
(--titles_data_path, like the reference).

  python pretrain_ict.py --num_layers 12 --hidden_size 768 \
      --num_attention_heads 12 --seq_length 256 --vocab_size 30592 \
      --data_path data/sents --titles_data_path data/titles \
      --ict_head_size 128 --train_iters 10000 ...
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from megatron_tpu.parallel.distributed import initialize_distributed

initialize_distributed()

from megatron_tpu.arguments import args_to_run_config, parse_args


def extra_args(p):
    g = p.add_argument_group("ict")
    g.add_argument("--titles_data_path", type=str, default=None)
    g.add_argument("--ict_head_size", type=int, default=128)
    g.add_argument("--biencoder_shared_query_context_model",
                   action="store_true")
    g.add_argument("--retriever_score_scaling", action="store_true")
    g.add_argument("--retriever_report_topk_accuracies", nargs="*",
                   type=int, default=[1, 5])
    g.add_argument("--query_in_block_prob", type=float, default=0.1)
    g.add_argument("--use_one_sent_docs", action="store_true")
    g.add_argument("--cls_token_id", type=int, default=101)
    g.add_argument("--sep_token_id", type=int, default=102)
    g.add_argument("--pad_token_id", type=int, default=0)
    return p


def main(argv=None):
    import dataclasses
    import functools

    from megatron_tpu.data.ict_dataset import ICTDataset
    from megatron_tpu.data.indexed_dataset import make_dataset
    from megatron_tpu.data.samplers import PretrainingSampler, build_data_loader
    from megatron_tpu.models.biencoder import (
        biencoder_config, biencoder_init_params, biencoder_loss,
        biencoder_param_specs,
    )
    from megatron_tpu.training.pretrain import TrainLoop

    args = parse_args(argv, extra_args_provider=extra_args)
    cfg = args_to_run_config(args)
    model = biencoder_config(
        num_layers=cfg.model.num_layers,
        hidden_size=cfg.model.hidden_size,
        num_attention_heads=cfg.model.num_attention_heads,
        vocab_size=cfg.model.vocab_size,
        seq_length=cfg.model.seq_length,
        params_dtype=cfg.model.params_dtype,
    )
    cfg = dataclasses.replace(cfg, model=model)
    if not args.data_path:
        raise SystemExit("--data_path is required")

    t = cfg.training
    shared = args.biencoder_shared_query_context_model
    blocks = make_dataset(args.data_path[0])
    titles = make_dataset(args.titles_data_path) if args.titles_data_path else None
    n_train = (t.train_iters or 1000) * t.global_batch_size
    train_ds = ICTDataset(
        blocks, titles, num_samples=n_train,
        max_seq_length=cfg.model.seq_length,
        cls_token=args.cls_token_id, sep_token=args.sep_token_id,
        pad_token=args.pad_token_id, seed=t.seed,
        query_in_block_prob=args.query_in_block_prob,
        use_titles=titles is not None,
        use_one_sent_docs=args.use_one_sent_docs)

    def collate(items):
        import numpy as np

        keys = [k for k in items[0] if k != "block_data"]
        return {k: np.stack([it[k] for it in items]) for k in keys}

    def train_iter_factory(consumed, gbs):
        sampler = PretrainingSampler(len(train_ds), consumed, gbs, 0, 1)
        return build_data_loader(train_ds, sampler, collate_fn=collate,
                                 prefetch=args.num_workers)

    def loss_fn(model_cfg, p, b, key):
        return biencoder_loss(model_cfg, p, b, dropout_key=key,
                              score_scaling=args.retriever_score_scaling,
                              topk=tuple(args.retriever_report_topk_accuracies))

    # fixed_num_microbatches=1: the in-batch softmax needs the WHOLE global
    # batch as negatives (the reference all-gathers embeddings across DP for
    # exactly this, pretrain_ict.py:86-133); a microbatch loop would shrink
    # the candidate set — with micro_batch_size*dp == 1 the loss would be
    # identically log(1) = 0.
    loop = TrainLoop(
        cfg,
        init_params_fn=functools.partial(
            biencoder_init_params, ict_head_size=args.ict_head_size,
            shared=shared),
        param_specs_fn=functools.partial(biencoder_param_specs, shared=shared),
        loss_fn=loss_fn,
        fixed_num_microbatches=1)
    loop.train(train_iter_factory)


if __name__ == "__main__":
    main()
