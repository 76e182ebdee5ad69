#!/usr/bin/env python
"""Zero-shot evaluation: perplexity and LAMBADA-style cloze accuracy.

Equivalent of the reference's tasks/zeroshot_gpt harness (tasks/main.py
--task WIKITEXT103 / LAMBADA): teacher-forced perplexity over a text or
indexed dataset, and last-word cloze accuracy for LAMBADA-format jsonl.

  # perplexity over raw text (tokenized on the fly)
  python tools/evaluate_zeroshot.py --task wikitext --load ckpt \
      --model_name llama2-7B --tokenizer_type SentencePieceTokenizer \
      --tokenizer_model tok.model --text wiki.test.txt

  # LAMBADA cloze accuracy ({"text": "..."} jsonl, last word is the target)
  python tools/evaluate_zeroshot.py --task lambada --load ckpt ... \
      --jsonl lambada_test.jsonl
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load_model(args):
    import jax

    from megatron_tpu.arguments import args_to_run_config
    from megatron_tpu.models.params import init_params
    from megatron_tpu.training import checkpointing

    cfg = args_to_run_config(args)
    params = init_params(cfg.model, jax.random.PRNGKey(0))
    if cfg.training.load:
        params = checkpointing.load_params_only(cfg.training.load, params)
        print(f"loaded checkpoint at iteration "
              f"{checkpointing.read_tracker(cfg.training.load)}",
              file=sys.stderr)
    return cfg.model, params


def eval_perplexity(model_cfg, params, token_stream, batch=8):
    """Strided teacher-forced ppl over a long token stream
    (ref: tasks/zeroshot_gpt, overlapping eval disabled — plain strides)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatron_tpu.models.language_model import lm_loss

    import numpy as _np

    hi = int(_np.max(token_stream))
    if hi >= model_cfg.vocab_size:
        raise SystemExit(
            f"token id {hi} >= model vocab_size {model_cfg.vocab_size} — "
            "tokenizer/model vocab mismatch (note: NullTokenizer's eod is "
            "its vocab_size argument, so its effective vocab is N+1)")
    S = model_cfg.seq_length
    n = (len(token_stream) - 1) // S
    total_loss, total_tokens = 0.0, 0
    loss_fn = jax.jit(lambda p, b: lm_loss(model_cfg, p, b)[0])
    for i in range(0, n, batch):
        rows = []
        for j in range(i, min(i + batch, n)):
            rows.append(token_stream[j * S: j * S + S + 1])
        arr = np.stack(rows).astype(np.int64)
        b = {"tokens": jnp.asarray(arr[:, :-1], jnp.int32),
             "labels": jnp.asarray(arr[:, 1:], jnp.int32),
             "loss_mask": jnp.ones((len(rows), S), jnp.float32)}
        loss = float(loss_fn(params, b))
        total_loss += loss * len(rows) * S
        total_tokens += len(rows) * S
    import math

    mean = total_loss / max(total_tokens, 1)
    return {"lm_loss": mean, "ppl": math.exp(min(mean, 20.0)),
            "tokens": total_tokens}


def eval_lambada(model_cfg, params, tokenizer, examples):
    """Cloze accuracy: greedy-decode the final word's tokens
    (ref: tasks/zeroshot_gpt LAMBADA accuracy)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatron_tpu.models.language_model import lm_forward

    fwd = None
    correct = total = 0
    for text in examples:
        words = text.rstrip().rsplit(" ", 1)
        if len(words) != 2:
            continue
        context, target = words
        ctx_ids = tokenizer.tokenize(context)
        tgt_ids = tokenizer.tokenize(" " + target)
        if not ctx_ids or not tgt_ids:
            continue
        ids = np.asarray([ctx_ids + tgt_ids], np.int32)
        logits = lm_forward(model_cfg, params, jnp.asarray(ids))
        pred = np.asarray(jnp.argmax(logits[0], axis=-1))
        # every target token must be greedily predicted
        ok = all(pred[len(ctx_ids) - 1 + i] == tgt_ids[i]
                 for i in range(len(tgt_ids)))
        correct += int(ok)
        total += 1
    return {"accuracy": correct / max(total, 1), "examples": total}


def main(argv=None):
    from megatron_tpu.arguments import build_parser
    from megatron_tpu.tokenizer import build_tokenizer

    def extra(parser):
        g = parser.add_argument_group("zeroshot")
        g.add_argument("--task", required=True,
                       choices=["wikitext", "ppl", "lambada"])
        g.add_argument("--text", default=None, help="raw text file (ppl)")
        g.add_argument("--jsonl", default=None, help="jsonl with 'text' keys")
        g.add_argument("--tokens", default=None, help=".npy token stream")
        g.add_argument("--eval_batch", type=int, default=8)
        return parser

    args = build_parser(extra).parse_args(argv)
    tokenizer = build_tokenizer(
        args.tokenizer_type, vocab_file=args.vocab_file,
        merges_file=args.merges_file, tokenizer_model=args.tokenizer_model,
        vocab_size=args.vocab_size,
        vocab_extra_ids=args.vocab_extra_ids or 0,
        new_tokens=args.new_tokens)
    model_cfg, params = _load_model(args)

    if args.task in ("wikitext", "ppl"):
        import math

        import numpy as np

        num_original_tokens = None
        if args.tokens:
            stream = np.load(args.tokens)
        elif args.text:
            with open(args.text, encoding="utf-8") as f:
                raw = f.read()
            stream = np.asarray(tokenizer.tokenize(raw))
            num_original_tokens = len(raw.split())
        elif args.jsonl:
            parts = []
            num_original_tokens = 0
            with open(args.jsonl, encoding="utf-8") as f:
                for line in f:
                    if line.strip():
                        text = json.loads(line)["text"]
                        parts.extend(tokenizer.tokenize(text))
                        parts.append(tokenizer.eod)
                        num_original_tokens += len(text.split())
            stream = np.asarray(parts)
        else:
            raise SystemExit("need --text, --jsonl or --tokens")
        out = eval_perplexity(model_cfg, params, stream, batch=args.eval_batch)
        if args.task == "wikitext" and num_original_tokens:
            # word-level adjusted ppl: exp(loss * tokenized/original ratio)
            # (ref tasks/zeroshot_gpt/evaluate.py:152-160). The full-stream
            # ratio stays correct even though eval drops the sub-stride
            # tail: evaluated nats (loss * N_eval) over evaluated words
            # (W * N_eval / N_stream) reduces to loss * N_stream / W.
            ratio = (len(stream) - 1) / max(num_original_tokens - 1, 1)
            out["adjusted_ppl"] = math.exp(
                min(out["lm_loss"] * ratio, 20.0))
            out["token_ratio"] = ratio
    else:
        if not args.jsonl:
            raise SystemExit("lambada needs --jsonl")
        with open(args.jsonl, encoding="utf-8") as f:
            examples = [json.loads(l)["text"] for l in f if l.strip()]
        out = eval_lambada(model_cfg, params, tokenizer, examples)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
