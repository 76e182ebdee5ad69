"""CLI flag-parity behaviors (counterpart: reference megatron/arguments.py
defaults that scripts rely on)."""

import os

import pytest

from megatron_tpu.arguments import args_to_run_config, parse_args

BASE = ["--num_layers", "2", "--hidden_size", "32",
        "--num_attention_heads", "4", "--seq_length", "32",
        "--vocab_size", "128", "--micro_batch_size", "1",
        "--global_batch_size", "1"]


def test_tie_embed_logits_defaults_tied_like_reference():
    cfg = args_to_run_config(parse_args(BASE))
    assert cfg.model.tie_embed_logits is True


def test_no_tie_embed_logits_unties():
    cfg = args_to_run_config(parse_args(BASE + ["--no_tie_embed_logits"]))
    assert cfg.model.tie_embed_logits is False


def test_tie_embed_logits_explicit_flag_still_ties():
    cfg = args_to_run_config(parse_args(BASE + ["--tie_embed_logits"]))
    assert cfg.model.tie_embed_logits is True


def test_ddp_impl_accepted_for_script_compat():
    args = parse_args(BASE + ["--DDP_impl", "local"])
    assert args.DDP_impl == "local"
    args_to_run_config(args)  # no error; reduction is XLA either way


def test_no_new_tokens_parsed():
    args = parse_args(BASE + ["--no_new_tokens"])
    assert args.new_tokens is False
    assert parse_args(BASE).new_tokens is True


def test_wandb_api_key_exported(monkeypatch):
    monkeypatch.delenv("WANDB_API_KEY", raising=False)
    args_to_run_config(parse_args(BASE + ["--wandb_api_key", "k-test"]))
    assert os.environ.get("WANDB_API_KEY") == "k-test"
    monkeypatch.setenv("WANDB_API_KEY", "preexisting")
    args_to_run_config(parse_args(BASE + ["--wandb_api_key", "k-other"]))
    assert os.environ["WANDB_API_KEY"] == "preexisting"


def _saved_run_config(tmp_path, **extra_model_keys):
    """(run-config dict, checkpoint root) as a run before PR 28 left them:
    meta.json's "config" with extra keys in its "model" dict."""
    import json

    from megatron_tpu.training.checkpointing import checkpoint_dir

    saved = args_to_run_config(parse_args(BASE)).to_dict()
    saved["model"].update(extra_model_keys)
    ckpt = checkpoint_dir(str(tmp_path), 1)
    os.makedirs(ckpt)
    with open(os.path.join(ckpt, "meta.json"), "w") as f:
        json.dump({"config": saved}, f)
    with open(os.path.join(str(tmp_path),
                           "latest_checkpointed_iteration.txt"), "w") as f:
        f.write("1")
    return saved, str(tmp_path)


def _load_run_config(saved, root):
    from megatron_tpu.config import RunConfig

    return RunConfig.from_dict(saved).model


def _load_checkpoint_args(saved, root):
    from megatron_tpu.arguments import _model_config_from_checkpoint

    return _model_config_from_checkpoint(root)


@pytest.mark.parametrize("load, extra, ok", [
    (_load_run_config, {"flash_bwd": False}, True),
    (_load_checkpoint_args, {"flash_bwd": True}, True),
    (_load_run_config, {"flash_fwd": True}, False),
    (_load_checkpoint_args, {"flash_fwd": True}, False),
], ids=["from_dict-retired", "use_checkpoint_args-retired",
        "from_dict-unknown", "use_checkpoint_args-unknown"])
def test_saved_model_config_loads_without_retired_fields(tmp_path, load,
                                                         extra, ok):
    """A meta.json written while ModelConfig still had `flash_bwd` loads
    through both loaders; a key that was never a field still raises."""
    saved, root = _saved_run_config(tmp_path, **extra)
    if ok:
        assert load(saved, root) == args_to_run_config(parse_args(BASE)).model
    else:
        with pytest.raises(TypeError, match="flash_fwd"):
            load(saved, root)


def test_help_renders_and_says_what_selective_keeps():
    """`--help` formats (argparse takes a bare `%` in a help string for a
    format: one stood in --expert_model_parallel_size's and broke the
    whole page), and --recompute_granularity says what each policy keeps
    of the flash kernel."""
    from megatron_tpu.arguments import build_parser

    page = " ".join(build_parser().format_help().split())
    at = page.index("--recompute_granularity {none,selective,full} ")
    told = page[at:at + 900]
    assert "keeps the flash kernel's output and log-sum-exp" in told
    assert "full keeps the layer's input only" in told
