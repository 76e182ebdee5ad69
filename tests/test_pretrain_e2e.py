"""End-to-end slice: preprocess -> datasets -> train loop -> checkpoint ->
resume (the reference's 'getting started' path as a hermetic test)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_corpus(tmp_path, n_docs=200, vocab=97):
    rng = np.random.default_rng(0)
    jsonl = tmp_path / "docs.jsonl"
    with open(jsonl, "w") as f:
        for _ in range(n_docs):
            n = int(rng.integers(20, 60))
            f.write(json.dumps(
                {"text": " ".join(str(int(x)) for x in rng.integers(0, vocab, n))}
            ) + "\n")
    return str(jsonl)


def test_preprocess_and_train_and_resume(tmp_path):
    from tools import preprocess_data
    from megatron_tpu.config import (
        ModelConfig, OptimizerConfig, ParallelConfig, RunConfig, TrainingConfig,
    )
    from megatron_tpu.data.gpt_dataset import build_gpt_datasets
    from megatron_tpu.data.samplers import PretrainingSampler, build_data_loader
    from megatron_tpu.training.pretrain import TrainLoop, gpt_collate

    jsonl = _make_corpus(tmp_path)
    prefix = str(tmp_path / "corpus")
    preprocess_data.main([
        "--input", jsonl, "--output_prefix", prefix,
        "--tokenizer_type", "null", "--vocab_size", "97", "--append_eod"])

    model = ModelConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4, num_kv_heads=2,
        ffn_hidden_size=64, vocab_size=128, seq_length=32,
        params_dtype="float32").validate()
    save_dir = str(tmp_path / "ckpt")
    cfg = RunConfig(
        model=model,
        parallel=ParallelConfig(),
        optimizer=OptimizerConfig(lr=5e-3, lr_decay_style="constant"),
        training=TrainingConfig(
            micro_batch_size=2, global_batch_size=16, train_iters=12,
            log_interval=4, save=save_dir, save_interval=6,
            eval_interval=8, eval_iters=2, seed=1),
    )

    train_ds, valid_ds, _ = build_gpt_datasets(
        [prefix], "90,10,0", 32, (12 * 16 + 64, 64, 0), seed=1)

    def train_iter_factory(consumed, gbs):
        sampler = PretrainingSampler(len(train_ds), consumed, gbs, 0, 1)
        return build_data_loader(train_ds, sampler,
                                 collate_fn=lambda it: gpt_collate(it, 97))

    def valid_iter_factory():
        sampler = PretrainingSampler(len(valid_ds), 0, 16, 0, 1)
        return build_data_loader(valid_ds, sampler,
                                 collate_fn=lambda it: gpt_collate(it, 97))

    logs = []
    loop = TrainLoop(cfg, log=logs.append)
    loop.train(train_iter_factory, valid_iter_factory)
    assert loop.iteration == 12
    assert loop.consumed_samples == 12 * 16
    # checkpoints at 6 and 12 exist; tracker points at 12
    from megatron_tpu.training import checkpointing
    assert checkpointing.read_tracker(save_dir) == 12
    assert any("validation" in l for l in logs)
    assert any("tokens/sec" in l for l in logs)

    # resume: new loop continues from iteration 12 with exact data order
    cfg2 = RunConfig(
        model=model, parallel=cfg.parallel, optimizer=cfg.optimizer,
        training=TrainingConfig(
            micro_batch_size=2, global_batch_size=16, train_iters=16,
            log_interval=4, save=save_dir, load=save_dir, seed=1),
    )
    logs2 = []
    loop2 = TrainLoop(cfg2, log=logs2.append)
    assert loop2.iteration == 12
    assert loop2.consumed_samples == 12 * 16
    loop2.train(train_iter_factory)
    assert loop2.iteration == 16


@pytest.mark.slow  # 20s subprocess measured cacheless (PR 4 re-budget);
# the in-process preprocess->train->resume e2e above stays tier-1
def test_pretrain_gpt_cli(tmp_path):
    """Drive the actual CLI entry point as a subprocess (CPU mesh)."""
    jsonl = _make_corpus(tmp_path, n_docs=120)
    prefix = str(tmp_path / "corpus")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = env.get("XLA_FLAGS", "") + \
        " --xla_force_host_platform_device_count=8"
    subprocess.run([
        sys.executable, os.path.join(REPO, "tools", "preprocess_data.py"),
        "--input", jsonl, "--output_prefix", prefix,
        "--tokenizer_type", "null", "--vocab_size", "97", "--append_eod"],
        check=True, env=env, capture_output=True)
    out = subprocess.run([
        sys.executable, os.path.join(REPO, "pretrain_gpt.py"),
        "--num_layers", "2", "--hidden_size", "32",
        "--num_attention_heads", "4", "--vocab_size", "128",
        "--seq_length", "32", "--use_rms_norm", "--glu_activation", "swiglu",
        "--fp32",
        "--micro_batch_size", "2", "--global_batch_size", "8",
        "--train_iters", "6", "--log_interval", "2",
        "--lr", "1e-3", "--lr_decay_style", "constant",
        "--data_path", prefix, "--split", "95,5,0",
        "--tensor_model_parallel_size", "2", "--sequence_parallel",
        "--eval_interval", "100"],
        env=env, capture_output=True, text=True, cwd=REPO, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "iteration 6/6" in out.stdout
    assert "lm loss" in out.stdout
