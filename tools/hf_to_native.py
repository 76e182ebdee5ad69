#!/usr/bin/env python
"""Convert a HuggingFace checkpoint to a native training checkpoint.

Equivalent of weights_conversion/hf_to_megatron.py (449 LoC). The output is
a normal framework checkpoint (orbax, iteration 0, fresh optimizer state)
that loads at ANY parallel topology — no per-rank shard layout to choose at
conversion time, unlike the reference which bakes tp=pp=1 and needs
tools/checkpoint_util.py to reshard.

  python tools/hf_to_native.py --model /path/or/hub-id --output ckpts/llama7b
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model", required=True,
                   help="HF checkpoint directory or hub id")
    p.add_argument("--output", required=True, help="native checkpoint dir")
    p.add_argument("--dtype", default="bfloat16",
                   choices=["bfloat16", "float16", "float32"])
    p.add_argument("--seq_length", type=int, default=None)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from transformers import AutoConfig, AutoModelForCausalLM

    from megatron_tpu.config import OptimizerConfig, RunConfig
    from megatron_tpu.interop.hf import config_from_hf, hf_state_dict_to_params
    from megatron_tpu.training import checkpointing
    from megatron_tpu.training.optimizer import init_train_state

    hf_config = AutoConfig.from_pretrained(args.model)
    cfg = config_from_hf(hf_config, seq_length=args.seq_length)
    cfg = cfg.__class__(**{**cfg.__dict__, "params_dtype": args.dtype})
    model_type = hf_config.model_type
    print(f"converting {model_type} model: {cfg.num_layers} layers, "
          f"hidden {cfg.hidden_size}, vocab {cfg.vocab_size}")

    hf_model = AutoModelForCausalLM.from_pretrained(args.model)
    params = hf_state_dict_to_params(hf_model.state_dict(), cfg, model_type,
                                     dtype=cfg.dtype)
    del hf_model
    params = jax.tree.map(jnp.asarray, params)

    state = init_train_state(OptimizerConfig(), params)
    run_cfg = RunConfig(model=cfg)
    path = checkpointing.save_checkpoint(
        args.output, state, iteration=0, consumed_samples=0,
        config={**run_cfg.to_dict(), "hf_model_type": model_type})
    print(f"wrote native checkpoint to {path}")


if __name__ == "__main__":
    main()
