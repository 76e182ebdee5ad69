"""finetune.py CLI end-to-end on instruction data + tensor-parallel
generation parity (previously untested surfaces)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_finetune_cli_instruction_data(tmp_path):
    # ~35s: finetune.py subprocess with a cold jax start + fresh compile
    # (deselectable with -m 'not slow', conftest marker doc)
    """preprocess_instruct_data -> finetune.py --data_type instruction:
    the reference's instruction-tuning recipe as a hermetic test."""
    rng = np.random.default_rng(0)
    jsonl = tmp_path / "chats.jsonl"
    with open(jsonl, "w") as f:
        for _ in range(40):
            conv = [
                {"role": "prompter",
                 "text": " ".join(str(int(x)) for x in rng.integers(0, 80, 8))},
                {"role": "assistant",
                 "text": " ".join(str(int(x)) for x in rng.integers(0, 80, 10))},
            ]
            f.write(json.dumps({"conversation": conv}) + "\n")

    env = {k: v for k, v in os.environ.items()}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    prefix = str(tmp_path / "instr")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools/preprocess_instruct_data.py"),
         "--input", str(jsonl), "--output_prefix", prefix,
         "--tokenizer_type", "null", "--vocab_size", "97"],
        env=env, capture_output=True, text=True, cwd=REPO, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "finetune.py"),
         "--num_layers", "2", "--hidden_size", "32",
         "--num_attention_heads", "4", "--seq_length", "64",
         "--vocab_size", "128", "--fp32",
         "--data_path", prefix, "--data_type", "instruction",
         "--micro_batch_size", "1", "--global_batch_size", "8",
         "--train_iters", "4", "--log_interval", "2",
         "--lr", "1e-3", "--lr_decay_style", "constant",
         "--eval_interval", "100"],
        env=env, capture_output=True, text=True, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "lm loss" in out.stdout


def test_generation_parity_under_tensor_parallel():
    """generate_tokens with tp=2-sharded params must emit the same tokens
    as the unsharded model (greedy)."""
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.inference.generation import generate_tokens
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import init_params, param_specs
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import shard_tree

    cfg = presets.tiny(vocab_size=64, seq_length=32, num_layers=2,
                       hidden_size=32, num_attention_heads=4, num_kv_heads=2,
                       ffn_hidden_size=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompts = np.asarray([[5, 11, 3], [9, 2, 0]], np.int32)
    lengths = np.asarray([3, 2], np.int32)
    base = generate_tokens(cfg, params, prompts, lengths, max_new_tokens=6,
                           top_k=1, eod=63, want_logprobs=False)

    rt = build_mesh(ParallelConfig(tensor_parallel=2))
    sharded = shard_tree(rt, params, param_specs(cfg))
    with jax.sharding.set_mesh(rt.mesh):
        got = generate_tokens(cfg, sharded, prompts, lengths,
                              max_new_tokens=6, top_k=1, eod=63,
                              want_logprobs=False)
    np.testing.assert_array_equal(base.tokens, got.tokens)
    np.testing.assert_array_equal(base.lengths, got.lengths)
