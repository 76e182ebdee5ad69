#!/usr/bin/env python
"""ORQA-style retriever evaluation: embed questions with the query tower,
search the block index, report top-k answer-hit rates.

Equivalent of tasks/orqa/evaluate_orqa.py + evaluate_utils.py (the
reference's unsupervised NQ evaluation): questions come from a tsv
(question \t answer), blocks from the index built by
tools/build_retrieval_index.py; a retrieval counts as a hit when the
answer token sequence appears inside the retrieved block (the reference's
string-match criterion, qa_utils.calculate_matches, applied at the token
level since this stack evaluates on tokenized blocks).

  python -m tasks.orqa --index_dir index/ --questions nq_dev.tsv \
      --load ckpts/ict --data_path data/blocks ... --topk 1 5 20
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from typing import Callable, List, Optional, Sequence

import numpy as np


def _contains(haystack: np.ndarray, needle: Sequence[int]) -> bool:
    n, m = len(haystack), len(needle)
    if m == 0 or m > n:
        return False
    needle = np.asarray(needle, haystack.dtype)
    windows = np.lib.stride_tricks.sliding_window_view(haystack, m)
    return bool((windows == needle).all(axis=1).any())


def evaluate_retriever(
    questions: List[str],
    answers: List,                # str or List[str] per question
    tokenize: Callable[[str], List[int]],
    query_embed: Callable[[np.ndarray, np.ndarray], np.ndarray],
    index: np.ndarray,           # [N, D]
    get_block_tokens: Callable[[int], np.ndarray],
    max_query_len: int,
    cls_id: int,
    sep_id: int,
    pad_id: int,
    topk: Sequence[int] = (1, 5, 20),
    batch_size: int = 32,
    match: str = "token",
    detokenize: Optional[Callable[[Sequence[int]], str]] = None,
):
    """Returns {f"top{k}": hit_rate}.

    match="token": answer token sequence must appear in the block's tokens
    (this stack's native criterion — no detokenizer required).
    match="string"/"regex": DPR's text-level criteria
    (tasks/qa_utils.has_answer, ref qa_utils.py:112-140) over the
    detokenized block; requires `detokenize`."""
    from tools.build_retrieval_index import search

    if not questions:
        raise SystemExit("no questions parsed (expected question<TAB>answer "
                         "lines)")
    if not topk:
        raise SystemExit("--topk needs at least one value")
    if match != "token" and detokenize is None:
        raise SystemExit(f"--match {match} needs a detokenizing tokenizer")
    toks = np.full((len(questions), max_query_len), pad_id, np.int64)
    mask = np.zeros((len(questions), max_query_len), np.float32)
    for i, q in enumerate(questions):
        ids = [cls_id] + tokenize(q)[: max_query_len - 2] + [sep_id]
        toks[i, : len(ids)] = ids
        mask[i, : len(ids)] = 1.0

    embs = []
    n = len(questions)
    for i in range(0, n, batch_size):
        j = min(i + batch_size, n)
        pad = batch_size - (j - i)
        t = np.concatenate([toks[i:j], np.tile(toks[i:i + 1], (pad, 1))]) \
            if pad else toks[i:j]
        m = np.concatenate([mask[i:j], np.tile(mask[i:i + 1], (pad, 1))]) \
            if pad else mask[i:j]
        embs.append(np.asarray(query_embed(t, m), np.float32)[: j - i])
    q_emb = np.concatenate(embs)

    kmax = max(topk)
    _, ids = search(index, q_emb, topk=kmax)
    hits = np.zeros((n, kmax), bool)
    for qi in range(n):
        ans_list = (answers[qi] if isinstance(answers[qi], (list, tuple))
                    else [answers[qi]])
        if match == "token":
            ans_toks = [tokenize(a) for a in ans_list]
            found = lambda block: any(
                _contains(block, t) for t in ans_toks if t)
            get = lambda bid: np.asarray(get_block_tokens(bid), np.int64)
        else:
            from tasks.qa_utils import has_answer

            found = lambda text: has_answer(ans_list, text, match)
            get = lambda bid: detokenize(
                [int(t) for t in get_block_tokens(bid)])
        for rank, bid in enumerate(ids[qi]):
            if found(get(int(bid))):
                hits[qi, rank:] = True
                break
    return {f"top{k}": float(hits[:, k - 1].mean()) for k in topk}


def main(argv=None):
    import jax

    from megatron_tpu.arguments import args_to_run_config, parse_args
    from megatron_tpu.data.indexed_dataset import make_dataset
    from megatron_tpu.models.biencoder import (
        biencoder_config, embed_text, load_biencoder_params,
    )
    from megatron_tpu.tokenizer.tokenizer import build_tokenizer

    def extra(p):
        g = p.add_argument_group("orqa")
        g.add_argument("--index_dir", required=True)
        g.add_argument("--questions", required=True,
                       help="tsv: question<TAB>answer per line")
        g.add_argument("--titles_data_path", type=str, default=None)
        g.add_argument("--ict_head_size", type=int, default=128)
        g.add_argument("--biencoder_shared_query_context_model",
                       action="store_true")
        g.add_argument("--topk", nargs="*", type=int, default=[1, 5, 20])
        g.add_argument("--match", choices=["token", "string", "regex"],
                       default="token",
                       help="answer-match criterion (string/regex are "
                            "DPR's, ref tasks/main.py --faiss_match)")
        g.add_argument("--cls_token_id", type=int, default=101)
        g.add_argument("--sep_token_id", type=int, default=102)
        g.add_argument("--pad_token_id", type=int, default=0)
        return p

    import dataclasses

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
    qtower = params.get("shared", params.get("query"))

    tok = build_tokenizer(args.tokenizer_type, vocab_size=model.vocab_size,
                          tokenizer_model=args.tokenizer_model,
                          vocab_file=args.vocab_file,
                          vocab_extra_ids=args.vocab_extra_ids or 0,
                          new_tokens=args.new_tokens)

    index = np.load(os.path.join(args.index_dir, "block_index.npy"))
    meta = np.load(os.path.join(args.index_dir, "block_meta.npy"))
    blocks_ds = make_dataset(args.data_path[0])

    _cache = {}

    def get_block_tokens(bid: int) -> np.ndarray:
        # lazy: only retrieved blocks are ever token-checked — the full
        # corpus never materializes (reference scale: millions of blocks)
        if bid not in _cache:
            s, e = int(meta[bid][0]), int(meta[bid][1])
            _cache[bid] = np.concatenate(
                [np.asarray(blocks_ds[i], np.int64) for i in range(s, e)])
        return _cache[bid]

    import ast

    questions, answers = [], []
    with open(args.questions) as f:
        for line in f:
            parts = line.rstrip("\n").split("\t")
            if len(parts) >= 2:
                questions.append(parts[0])
                a = parts[1]
                # NQ-format answer lists ("['a', 'b']", ref nq.py:205 uses
                # eval; literal_eval here) or a plain string
                if a.startswith("[") and a.endswith("]"):
                    try:
                        a = list(ast.literal_eval(a))
                    except (ValueError, SyntaxError):
                        pass
                answers.append(a)

    import jax.numpy as jnp

    @jax.jit
    def query_embed(toks, mask):
        return embed_text(model, qtower, jnp.asarray(toks),
                          jnp.asarray(mask) > 0)

    out = evaluate_retriever(
        questions, answers, tok.tokenize, query_embed, index,
        get_block_tokens,
        max_query_len=model.seq_length, cls_id=args.cls_token_id,
        sep_id=args.sep_token_id, pad_id=args.pad_token_id, topk=args.topk,
        match=args.match, detokenize=tok.detokenize)
    for k, v in out.items():
        print(f"{k} retrieval hit rate: {v:.4f} ({len(questions)} questions)")
    return out


if __name__ == "__main__":
    main()
