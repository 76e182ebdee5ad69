#!/usr/bin/env python
"""T5 pretraining entry point (ref: pretrain_t5.py, 171 LoC).

Data: a sentence-level indexed dataset (produce with
tools/preprocess_data.py --split_sentences); samples are span-corrupted
T5-style with sentinel tokens from the top of the vocabulary (the
reference's --vocab_extra_ids 100 reserves tokenizer extra ids;
here --vocab_extra_ids carves the same count from the top of vocab_size
unless explicit sentinel ids are given).

  python pretrain_t5.py --num_layers 12 --hidden_size 768 \
      --num_attention_heads 12 --seq_length 512 --decoder_seq_length 128 \
      --vocab_size 30592 --vocab_extra_ids 100 --data_path data/sents \
      --train_iters 10000 ...
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from megatron_tpu.parallel.distributed import initialize_distributed

initialize_distributed()

from megatron_tpu.arguments import args_to_run_config, parse_args


def extra_args(p):
    g = p.add_argument_group("t5")
    g.add_argument("--decoder_seq_length", type=int, default=128)
    g.add_argument("--encoder_num_layers", type=int, default=None,
                   help="encoder depth (default: --num_layers)")
    g.add_argument("--decoder_num_layers", type=int, default=None,
                   help="decoder depth (default: --num_layers)")
    g.add_argument("--bos_token_id", type=int, default=101)
    g.add_argument("--eos_token_id", type=int, default=102)
    g.add_argument("--pad_token_id", type=int, default=0)
    return p


def main(argv=None):
    import dataclasses

    from megatron_tpu.data.indexed_dataset import make_dataset
    from megatron_tpu.data.samplers import PretrainingSampler, build_data_loader
    from megatron_tpu.data.t5_dataset import T5Dataset
    from megatron_tpu.models.t5 import (
        t5_config, t5_init_params, t5_loss, t5_param_specs,
    )
    from megatron_tpu.training.pretrain import TrainLoop

    args = parse_args(argv, extra_args_provider=extra_args)
    cfg = args_to_run_config(args)
    model = t5_config(
        num_layers=cfg.model.num_layers,
        hidden_size=cfg.model.hidden_size,
        num_attention_heads=cfg.model.num_attention_heads,
        vocab_size=cfg.model.vocab_size,
        seq_length=cfg.model.seq_length,
        decoder_seq_length=args.decoder_seq_length,
        encoder_num_layers=args.encoder_num_layers,
        decoder_num_layers=args.decoder_num_layers,
        params_dtype=cfg.model.params_dtype,
    )
    cfg = dataclasses.replace(cfg, model=model)
    if not args.data_path:
        raise SystemExit("--data_path is required")

    # sentinels from the top of the padded vocab (ref: tokenizer
    # additional_special_tokens via --vocab_extra_ids)
    v = cfg.model.vocab_size
    n_extra = 100 if args.vocab_extra_ids is None else args.vocab_extra_ids
    if n_extra <= 0:
        raise SystemExit("T5 span corruption needs sentinel ids: pass "
                         "--vocab_extra_ids N (the reference uses 100)")
    sentinels = list(range(v - n_extra, v))

    t = cfg.training
    indexed = make_dataset(args.data_path[0])
    n_train = (t.train_iters or 1000) * t.global_batch_size
    train_ds = T5Dataset(
        indexed, num_samples=n_train,
        max_seq_length=cfg.model.seq_length,
        max_seq_length_dec=args.decoder_seq_length,
        bos_token=args.bos_token_id, eos_token=args.eos_token_id,
        pad_token=args.pad_token_id, sentinel_tokens=sentinels,
        seed=t.seed, masked_lm_prob=args.mask_prob,
        short_seq_prob=args.short_seq_prob)

    def train_iter_factory(consumed, gbs):
        sampler = PretrainingSampler(len(train_ds), consumed, gbs, 0, 1)
        return build_data_loader(train_ds, sampler,
                                 prefetch=args.num_workers)

    def t5_loss_fn(model_cfg, p, b, key):
        return t5_loss(model_cfg, p, b)

    pp_factory = None
    if cfg.parallel.pipeline_parallel > 1:
        from megatron_tpu.training.t5_pipeline import make_t5_pipeline_loss_fn

        if (cfg.parallel.virtual_pipeline_parallel or 1) > 1:
            raise SystemExit(
                "T5 pp>1 is already interleaved (encoder+decoder chunks "
                "per stage); --num_layers_per_virtual_pipeline_stage "
                "doesn't apply")
        pp_factory = make_t5_pipeline_loss_fn

    loop = TrainLoop(cfg, init_params_fn=t5_init_params,
                     param_specs_fn=t5_param_specs, loss_fn=t5_loss_fn,
                     pipeline_loss_factory=pp_factory)
    loop.train(train_iter_factory)


if __name__ == "__main__":
    main()
