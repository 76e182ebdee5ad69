#!/usr/bin/env python
"""Convert a native checkpoint back to HuggingFace format.

Equivalent of weights_conversion/megatron_to_hf.py (621 LoC):

  python tools/native_to_hf.py --load ckpts/llama7b --output hf_out \
      --model_type llama
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--load", required=True, help="native checkpoint dir")
    p.add_argument("--output", required=True, help="HF output dir")
    p.add_argument("--model_type", default=None,
                   help="llama|mistral|falcon|gpt2 (default: from checkpoint)")
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float16", "float32"])
    args = p.parse_args(argv)

    import jax
    import torch

    from megatron_tpu.config import model_config_from_saved
    from megatron_tpu.interop.hf import hf_config_from_native, params_to_hf_state_dict
    from megatron_tpu.models.params import init_params
    from megatron_tpu.training import checkpointing

    it = checkpointing.read_tracker(args.load)
    if it is None:
        raise SystemExit(f"no checkpoint tracker in {args.load}")
    with open(os.path.join(checkpointing.checkpoint_dir(args.load, it),
                           "meta.json")) as f:
        meta = json.load(f)
    model_dict = meta["config"]["model"]
    cfg = model_config_from_saved(model_dict).validate()
    model_type = args.model_type or meta["config"].get("hf_model_type")
    if not model_type:
        raise SystemExit("--model_type required (not recorded in checkpoint)")

    template = init_params(cfg, jax.random.PRNGKey(0))
    params = checkpointing.load_params_only(args.load, template)

    sd = params_to_hf_state_dict(jax.device_get(params), cfg, model_type)
    torch_dtype = {"bfloat16": torch.bfloat16, "float16": torch.float16,
                   "float32": torch.float32}[args.dtype]
    torch_sd = {k: torch.from_numpy(
        v.astype("float32")).to(torch_dtype) for k, v in sd.items()}

    from transformers import AutoModelForCausalLM

    hf_config = hf_config_from_native(cfg, model_type)
    hf_config.torch_dtype = torch_dtype
    model = AutoModelForCausalLM.from_config(hf_config)
    model = model.to(torch_dtype)
    missing, unexpected = model.load_state_dict(torch_sd, strict=False)
    allowed_missing = {"lm_head.weight"} if getattr(
        hf_config, "tie_word_embeddings", False) else set()
    bad_missing = set(missing) - allowed_missing
    if bad_missing or unexpected:
        raise SystemExit(f"state dict mismatch: missing={bad_missing} "
                         f"unexpected={unexpected}")
    if hasattr(model, "tie_weights"):
        model.tie_weights()
    os.makedirs(args.output, exist_ok=True)
    model.save_pretrained(args.output)
    print(f"wrote HF checkpoint to {args.output}")


if __name__ == "__main__":
    main()
