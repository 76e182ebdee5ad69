#!/usr/bin/env python
"""Push a converted HF-format model (or a native checkpoint, converting it
first) to the HuggingFace Hub.

Equivalent of tools/push_to_hub.py (161 LoC) in the reference: wraps the
native->HF conversion and the hub upload in one command.

  # HF-format directory, straight upload:
  python tools/push_to_hub.py hf_out --hub_repo me/my-model

  # native checkpoint: convert, then upload
  python tools/push_to_hub.py ckpts/llama7b --from_native \
      --model_type llama --hub_repo me/my-model

--dry_run stops after conversion/validation and prints what would be
uploaded (also the testable path in offline environments).
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("path", help="HF model dir, or native ckpt with --from_native")
    p.add_argument("--hub_repo", required=True,
                   help="hub repo id, e.g. org/model-name")
    p.add_argument("--from_native", action="store_true",
                   help="path is a native checkpoint; convert first")
    p.add_argument("--model_type", default=None)
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float16", "float32"])
    p.add_argument("--private", action="store_true")
    p.add_argument("--commit_message", default="upload model")
    p.add_argument("--dry_run", action="store_true")
    args = p.parse_args(argv)

    path = args.path
    tmp = None
    if args.from_native:
        from tools import native_to_hf

        tmp = tempfile.mkdtemp(prefix="push_to_hub_")
        conv = ["--load", path, "--output", tmp, "--dtype", args.dtype]
        if args.model_type:
            conv += ["--model_type", args.model_type]
        native_to_hf.main(conv)
        path = tmp
    try:
        return _validate_and_upload(args, path)
    finally:
        if tmp is not None and not args.dry_run:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)


def _validate_and_upload(args, path):

    # validate: the directory must look like an HF model
    needed = ["config.json"]
    have = set(os.listdir(path))
    missing = [n for n in needed if n not in have]
    weights = [f for f in have if f.endswith((".bin", ".safetensors"))]
    if missing or not weights:
        raise SystemExit(
            f"{path} does not look like an HF model dir "
            f"(missing {missing or 'weight files'})")

    files = sorted(os.listdir(path))
    total = sum(os.path.getsize(os.path.join(path, f)) for f in files)
    print(f"uploading {len(files)} files ({total / 1e6:.1f} MB) "
          f"from {path} -> {args.hub_repo}")
    for f in files:
        print(f"  {f}")
    if args.dry_run:
        print("dry run: skipping upload")
        return path

    from huggingface_hub import HfApi

    api = HfApi()
    api.create_repo(args.hub_repo, private=args.private, exist_ok=True)
    api.upload_folder(folder_path=path, repo_id=args.hub_repo,
                      commit_message=args.commit_message)
    print(f"pushed to https://huggingface.co/{args.hub_repo}")
    return path


if __name__ == "__main__":
    main()
