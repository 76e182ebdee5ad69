#!/usr/bin/env python
"""Build a dense retrieval index of evidence blocks with the biencoder's
context tower.

Equivalent of megatron/indexer.py (123 LoC) + data/realm_index.py's
OpenRetreivalDataStore: one pass over the block dataset, context-tower
embeddings written as block_index.npy [N, D] + block_meta.npy [N, 4]
(start, end, doc, block id). Query-side search is a jitted dot-product
top-k (the reference brute-forces the same way via FAISS flat).

  python tools/build_retrieval_index.py --load ckpts/ict \
      --data_path data/blocks --titles_data_path data/titles \
      --output index_dir --num_layers 12 ...
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import numpy as np


def build_index(cfg, tower, dataset, batch_size: int = 64,
                log=print, log_interval: int = 50):
    """Embed every block with the context tower. Returns (emb [N,D],
    meta [N,4])."""
    import jax
    import jax.numpy as jnp

    from megatron_tpu.models.biencoder import embed_text

    @jax.jit
    def step(params, tokens, mask):
        return embed_text(cfg, params, tokens, mask > 0)

    embs, metas = [], []
    n = len(dataset)
    if n == 0:
        raise SystemExit("no blocks to index (empty block dataset/mapping)")
    for i in range(0, n, batch_size):
        rows = [dataset[j] for j in range(i, min(i + batch_size, n))]
        pad = batch_size - len(rows)
        rows_p = rows + [rows[0]] * pad  # fixed shapes; padded rows dropped
        toks = jnp.asarray(np.stack([r["context_tokens"] for r in rows_p]))
        mask = jnp.asarray(np.stack([r["context_pad_mask"] for r in rows_p]))
        # fp32 on the host: numpy has no bf16 matmul for search()
        out = np.asarray(step(tower, toks, mask),
                         dtype=np.float32)[: len(rows)]
        embs.append(out)
        metas.extend(r["block_data"] for r in rows)
        if (i // batch_size) % log_interval == 0:
            log(f"indexed {min(i + batch_size, n)}/{n} blocks")
    return np.concatenate(embs), np.stack(metas)


def search(index: np.ndarray, query_emb: np.ndarray, topk: int = 5):
    """Brute-force dot-product top-k (ref realm FAISS flat index).
    query_emb [B, D] -> (scores [B, topk], ids [B, topk])."""
    scores = query_emb @ index.T
    ids = np.argsort(-scores, axis=1)[:, :topk]
    return np.take_along_axis(scores, ids, axis=1), ids


def main(argv=None):
    from megatron_tpu.arguments import args_to_run_config, parse_args

    def extra(p):
        g = p.add_argument_group("indexer")
        g.add_argument("--titles_data_path", type=str, default=None)
        g.add_argument("--output", required=True)
        g.add_argument("--ict_head_size", type=int, default=128)
        g.add_argument("--biencoder_shared_query_context_model",
                       action="store_true")
        g.add_argument("--indexer_batch_size", type=int, default=64)
        g.add_argument("--indexer_log_interval", type=int, default=50)
        g.add_argument("--cls_token_id", type=int, default=101)
        g.add_argument("--sep_token_id", type=int, default=102)
        g.add_argument("--pad_token_id", type=int, default=0)
        return p

    import dataclasses

    import jax

    from megatron_tpu.data.ict_dataset import ICTDataset
    from megatron_tpu.data.indexed_dataset import make_dataset
    from megatron_tpu.models.biencoder import (
        biencoder_config, load_biencoder_params,
    )

    args = parse_args(argv, extra_args_provider=extra)
    if not args.data_path:
        raise SystemExit("--data_path is required")
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

    shared = args.biencoder_shared_query_context_model
    params = load_biencoder_params(model, cfg.optimizer, cfg.training.load,
                                   args.ict_head_size, shared)
    tower = params.get("shared", params.get("context"))

    blocks = make_dataset(args.data_path[0])
    titles = (make_dataset(args.titles_data_path)
              if args.titles_data_path else None)
    ds = ICTDataset(blocks, titles, num_samples=None,
                    max_seq_length=model.seq_length,
                    cls_token=args.cls_token_id, sep_token=args.sep_token_id,
                    pad_token=args.pad_token_id, query_in_block_prob=1.0,
                    use_titles=titles is not None)

    emb, meta = build_index(model, tower, ds,
                            batch_size=args.indexer_batch_size,
                            log_interval=args.indexer_log_interval)
    os.makedirs(args.output, exist_ok=True)
    np.save(os.path.join(args.output, "block_index.npy"), emb)
    np.save(os.path.join(args.output, "block_meta.npy"), meta)
    print(f"wrote {emb.shape[0]} block embeddings (dim {emb.shape[1]}) "
          f"to {args.output}")


if __name__ == "__main__":
    main()
