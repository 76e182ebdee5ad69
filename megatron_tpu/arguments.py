"""CLI argument parsing with reference flag-name parity.

Equivalent of megatron/arguments.py (1,103 LoC): the same flag names
(underscored, like the reference's fork) parsed into typed RunConfig
dataclasses instead of a mutable global namespace. validate_args'
cross-flag invariants live in the dataclasses' validate() methods; the
derivations (dp size, microbatches, params dtype) happen in build_mesh /
MicroBatchCalculator at use sites.
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Optional, Sequence

from megatron_tpu.config import (
    AttentionKind, ModelConfig, OptimizerConfig, ParallelConfig, RunConfig,
    TrainingConfig,
    model_config_from_saved,
)


def _attention_pattern(text: str):
    """--attention_pattern's JSON list as a tuple of AttentionKind."""
    try:
        return tuple(AttentionKind(**kind) for kind in json.loads(text))
    except (TypeError, ValueError) as e:
        raise argparse.ArgumentTypeError(
            f"not a JSON list of attention kinds: {e}") from e


def _layer_pattern(text: str):
    """--layer_pattern's JSON list of layer types as a tuple."""
    try:
        types = json.loads(text)
        if not isinstance(types, list) or not all(
                isinstance(t, str) for t in types):
            raise ValueError("expected a list of strings")
        return tuple(types)
    except ValueError as e:
        raise argparse.ArgumentTypeError(
            f"not a JSON list of layer types: {e}") from e


def build_parser(extra_args_provider=None) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="megatron_tpu",
                                allow_abbrev=False)

    g = p.add_argument_group("network size")
    g.add_argument("--num_layers", type=int, default=None)
    g.add_argument("--hidden_size", type=int, default=None)
    g.add_argument("--num_attention_heads", type=int, default=None)
    g.add_argument("--num_attention_heads_kv", type=int, default=None)
    g.add_argument("--kv_channels", type=int, default=None)
    g.add_argument("--ffn_hidden_size", type=int, default=None)
    g.add_argument("--seq_length", type=int, default=2048)
    g.add_argument("--max_position_embeddings", type=int, default=None)
    g.add_argument("--vocab_size", type=int, default=32000)
    g.add_argument("--make_vocab_size_divisible_by", type=int, default=128)
    g.add_argument("--position_embedding_type", default="rotary",
                   choices=["rotary", "absolute", "none"])
    g.add_argument("--rope_theta", type=float, default=10000.0)
    g.add_argument("--rope_scaling_factor", type=float, default=1.0)
    g.add_argument("--layernorm_epsilon", type=float, default=1e-5)
    g.add_argument("--use_rms_norm", action="store_true")
    g.add_argument("--use_post_ln", action="store_true",
                   help="post-LN layer convention (no pre-norm; per-layer "
                        "output norm; no final stack norm)")
    g.add_argument("--apply_residual_connection_post_layernorm",
                   action="store_true",
                   help="take residuals from the LN output (ref semantics)")
    g.add_argument("--glu_activation", default=None,
                   choices=["swiglu", "geglu", "reglu", "liglu"])
    g.add_argument("--activation", default=None,
                   choices=["gelu", "gelu_tanh", "relu", "squared_relu"],
                   help="the FFN's activation where it is no GLU (default "
                        "gelu; --glu_activation states a GLU)")
    g.add_argument("--parallel_attn", action="store_true")
    g.add_argument("--parallel_layernorm", action="store_true")
    g.add_argument("--use_bias", action="store_true")
    # ref polarity: tied is the default, --no_tie_embed_logits unties
    # (llama presets set their own untied value regardless)
    g.add_argument("--tie_embed_logits", action="store_true", default=None)
    g.add_argument("--no_tie_embed_logits", action="store_false",
                   dest="tie_embed_logits",
                   help="untie the word embedding and lm head (ref default "
                        "is tied)")
    g.add_argument("--sliding_window_size", type=int, default=None)
    g.add_argument("--attention_pattern", type=_attention_pattern,
                   default=None,
                   help="layers of several attention kinds in one stack: "
                        "a JSON list with one object a layer of one period "
                        "of the pattern, its keys config.AttentionKind's "
                        "(name, sliding_window_size, rope_theta, "
                        "rope_scaling_factor, rope_type linear|yarn, "
                        "yarn_*); the stack repeats it over --num_layers. "
                        "In place of --sliding_window_size, --rope_theta "
                        "and --rope_scaling_factor, which state one kind "
                        "(layers that are all alike are said with those)")
    g.add_argument("--layer_pattern", type=_layer_pattern, default=None,
                   help="layers of several TYPES in one stack: a JSON list "
                        "with one type a layer of one period of the "
                        "pattern; the stack repeats it over --num_layers. "
                        "Mixers: \"attention\", \"mamba\" (Mamba-1) and "
                        "\"mamba2\" (Mamba-2 / SSD), both sized by "
                        "--ssm_*; each layer then holds an FFN too. With a "
                        "feed-forward type in it, \"mlp\" (dense) or "
                        "\"moe\" (experts, --num_experts ...), every layer "
                        "is ONE block alone: a mixer or an FFN, behind one "
                        "norm and one residual add")
    g.add_argument("--ssm_d_state", type=int, default=16,
                   help="the state a channel of a state-space layer")
    g.add_argument("--ssm_d_conv", type=int, default=4,
                   help="the width of its causal convolution")
    g.add_argument("--ssm_expand", type=int, default=2,
                   help="its inner width over the hidden size")
    g.add_argument("--ssm_dt_rank", type=int, default=None,
                   help="the rank of its step size's projection (default: "
                        "ceil(hidden_size / 16))")
    g.add_argument("--ssm_inner_norms", action="store_true",
                   help="an RMSNorm with a learned scale on dt, B and C "
                        "(Jamba)")
    g.add_argument("--ssm_num_heads", type=int, default=None,
                   help="Mamba-2: the heads of the inner width (one step "
                        "size and one scalar decay a head)")
    g.add_argument("--ssm_n_groups", type=int, default=1,
                   help="Mamba-2: the groups of heads that share B and C "
                        "(and of the gated norm)")
    g.add_argument("--ssm_chunk_size", type=int, default=128,
                   help="Mamba-2: the positions a chunk of the chunked scan")
    g.add_argument("--qk_norm", action="store_true", default=None,
                   help="RMSNorm with a learned scale over the whole q and "
                        "the whole k projection, before the head split and "
                        "the rotary (OLMoE)")
    # MoE (beyond the reference; see ops/moe.py). Defaults are None so an
    # explicitly-passed knob overrides a preset's value but an unpassed
    # knob never clobbers it (the mixtral preset carries its own values).
    g.add_argument("--num_experts", type=int, default=None)
    g.add_argument("--moe_experts_held", type=int, default=None,
                   help="one chip's share of an expert-parallel layer, run "
                        "alone: the weights of this many of the router's "
                        "--num_experts experts exist here, and the layer "
                        "returns the part of the result they give")
    g.add_argument("--moe_expert_share", type=int, default=None,
                   help="which share: experts share * held up to the next "
                        "share's first (default 0)")
    g.add_argument("--moe_top_k", type=int, default=None)
    g.add_argument("--moe_capacity_factor", type=float, default=None)
    g.add_argument("--moe_aux_loss_coeff", type=float, default=None)
    g.add_argument("--moe_z_loss_coeff", type=float, default=None)
    g.add_argument("--moe_group_size", type=int, default=None,
                   help="GShard dispatch group size (tokens); 0 = auto "
                        "(largest divisor of seq_length <= 2048)")
    g.add_argument("--moe_dispatch", choices=["capacity", "dropless"],
                   default=None,
                   help="capacity: GShard einsum dispatch (EP-shardable); "
                        "dropless: sort + lax.ragged_dot grouped GEMMs, "
                        "no token drops (under ep>1: explicit expert-axis "
                        "all-to-all dispatch)")
    g.add_argument("--moe_ep_buffer_factor", type=float, default=None,
                   help="dropless-EP receive buffer = n*top_k*factor rows "
                        "per expert shard (default: ep, exact dropless; "
                        "smaller scales FLOPs/memory at the cost of "
                        "greedy drops under routing imbalance)")
    g.add_argument("--moe_renorm_gates", action="store_true", default=None)
    g.add_argument("--no_moe_renorm_gates", action="store_false",
                   dest="moe_renorm_gates",
                   help="use raw softmax gate values (GShard) instead of "
                        "renormalized top-k weights (Mixtral)")
    g.add_argument("--moe_router_score", choices=["softmax", "sigmoid"],
                   default=None,
                   help="sigmoid: a score an expert on its own, the choice "
                        "by score + a learned selection bias, gates the "
                        "chosen scores over their sum, no load-balance "
                        "loss (ops/moe.py)")
    g.add_argument("--moe_route_scale", type=float, default=None,
                   help="the sigmoid form's gates times this")
    g.add_argument("--moe_latent_size", type=int, default=None,
                   help="the routed experts work in this narrower width, "
                        "between a projection down in front of the "
                        "dispatch and one up behind the weighted sum")
    g.add_argument("--moe_shared_ffn_size", type=int, default=None,
                   help="a shared expert of this width beside the routed "
                        "ones: every token's, added to their result")
    g.add_argument("--moe_router_form", choices=["linear", "mlp"],
                   default=None,
                   help="mlp: the layer's input projected down to "
                        "--moe_router_hidden_size, plus a learned scale "
                        "times the previous layer's such state, through a "
                        "three-layer gelu MLP to the logits (ops/moe.py)")
    g.add_argument("--moe_router_hidden_size", type=int, default=None)
    g.add_argument("--moe_bias_update_rate", type=float, default=None,
                   help="balance by a selection bias and no auxiliary "
                        "loss: after each step bias_e += rate * sign(1/E "
                        "- load_e); the bias is read for the choice alone "
                        "and trained by no gradient")
    g.add_argument("--attention_form", choices=["plain", "cca"],
                   default="plain",
                   help="cca: compressed convolutional attention "
                        "(ops/cca.py): two causal convolutions over the "
                        "(q, k) latent, the q-k mean, unit-norm q and k "
                        "with a temperature, half of v shifted")
    g.add_argument("--cca_conv_kernels", type=int, nargs=2, default=(2, 2),
                   help="the taps of cca's depthwise convolution and of "
                        "the one grouped by head")
    g.add_argument("--rotary_percent", type=float, default=1.0,
                   help="the share of a head's channels that rotary "
                        "turns (the first ones; the rest pass)")
    g.add_argument("--residual_scale", action="store_true",
                   help="each residual add as (s_x * x + b_x) + (s_o * "
                        "out + b_o), four learned vectors a sub-layer")
    g.add_argument("--lima_dropout", action="store_true")
    g.add_argument("--encoder_seq_length", type=int, default=None,
                   help="alias of --seq_length (ref derives one from the other)")
    g.add_argument("--attention_softmax_in_fp32", action="store_true",
                   default=True,
                   help="always on here (the TPU path computes softmax in "
                        "fp32 by default); flag kept for CLI parity")
    g.add_argument("--model_name", default=None,
                   help="preset: llama/llama2/codellama/falcon/mistral/mixtral/"
                        "olmoe/gpt2"
                        " (optionally 'name-SIZE', e.g. llama2-7B)")
    g.add_argument("--model_size", default=None)

    g = p.add_argument_group("regularization")
    g.add_argument("--hidden_dropout", type=float, default=0.0)
    g.add_argument("--attention_dropout", type=float, default=0.0)
    g.add_argument("--weight_decay", type=float, default=0.01)
    g.add_argument("--start_weight_decay", type=float, default=None)
    g.add_argument("--end_weight_decay", type=float, default=None)
    g.add_argument("--weight_decay_incr_style", default="constant")
    g.add_argument("--clip_grad", type=float, default=1.0)
    g.add_argument("--head_lr_mult", type=float, default=1.0,
                   help="LR multiplier for task-head params during "
                        "finetuning (ref --head_lr_mult)")

    g = p.add_argument_group("training")
    g.add_argument("--micro_batch_size", type=int, default=1)
    g.add_argument("--global_batch_size", type=int, default=None)
    g.add_argument("--rampup_batch_size", nargs=3, type=int, default=None)
    g.add_argument("--train_iters", type=int, default=None)
    g.add_argument("--train_samples", type=int, default=None)
    g.add_argument("--exit_interval", type=int, default=None)
    g.add_argument("--exit_duration_in_mins", type=int, default=None)
    g.add_argument("--seed", type=int, default=1234)
    g.add_argument("--init_method_std", type=float, default=0.02)
    g.add_argument("--recompute_granularity", default="none",
                   choices=["none", "selective", "full"],
                   help="what a layer keeps for its backward pass. "
                        "selective keeps the weight matmuls' outputs "
                        "(the rotated q and k in place of the projected "
                        "ones) and computes norms, activations and the "
                        "dense core attention again; with --attention_impl "
                        "pallas it also keeps the flash kernel's output "
                        "and log-sum-exp (one hidden-state-sized tensor "
                        "and seq_length floats a head, a layer, a "
                        "sequence), so the forward kernel runs once. full "
                        "keeps the layer's input only and runs the whole "
                        "forward, the kernel included, a second time")
    g.add_argument("--recompute_activations", action="store_true",
                   help="ref alias for --recompute_granularity selective")
    g.add_argument("--recompute_method", default="uniform",
                   choices=["uniform", "block"],
                   help="with --recompute_granularity full: uniform remats "
                        "in chunks of --recompute_num_layers (sqrt-remat "
                        "carry storage when N ~ sqrt(L)); block remats only "
                        "the first N layers per stack/pipeline-chunk "
                        "(ref transformer.py:1110-1172)")
    g.add_argument("--recompute_num_layers", type=int, default=1,
                   help="layer budget/chunk for --recompute_method")
    g.add_argument("--optimizer", default="adam", choices=["adam", "sgd"])
    g.add_argument("--sgd_momentum", type=float, default=0.9)
    g.add_argument("--attention_impl", default="xla",
                   choices=["xla", "pallas", "ring", "ulysses"])
    g.add_argument("--ce_chunk_size", type=int, default=0,
                   help="compute LM head + cross-entropy over sequence "
                        "chunks of this many tokens, a chunk's gradient "
                        "formed beside its logits "
                        "(0 = unchunked full [B,S,V] logits)")
    g.add_argument("--use_flash_attn", action="store_true",
                   help="ref alias for --attention_impl pallas")
    g.add_argument("--exit_signal_handler", action="store_true",
                   default=True,
                   help="SIGTERM checkpoint-and-exit is always enabled here")
    g.add_argument("--eval_only", action="store_true")
    g.add_argument("--skip_iters", nargs="*", type=int, default=[],
                   help="skip the update on these iterations (ref fault "
                        "injection, training.py:397-425)")

    g = p.add_argument_group("learning rate")
    g.add_argument("--lr", type=float, default=3e-4)
    g.add_argument("--min_lr", type=float, default=0.0)
    g.add_argument("--lr_decay_style", default="cosine",
                   choices=["constant", "linear", "cosine",
                            "inverse-square-root"])
    g.add_argument("--lr_decay_iters", type=int, default=None)
    g.add_argument("--lr_warmup_iters", type=int, default=0)
    g.add_argument("--lr_warmup_fraction", type=float, default=None)
    g.add_argument("--lr_decay_samples", type=int, default=None,
                   help="converted to iters via global_batch_size")
    g.add_argument("--lr_warmup_samples", type=int, default=None,
                   help="converted to iters via global_batch_size")
    g.add_argument("--override_opt_param_scheduler", action="store_true",
                   default=True,
                   help="always effectively on: schedules here are pure "
                        "functions of (config, step), never checkpointed "
                        "state, so CLI values always apply")
    g.add_argument("--adam_beta1", type=float, default=0.9)
    g.add_argument("--adam_beta2", type=float, default=0.999)
    g.add_argument("--adam_eps", type=float, default=1e-8)

    g = p.add_argument_group("checkpointing")
    g.add_argument("--save", default=None)
    g.add_argument("--load", default=None)
    g.add_argument("--save_interval", default=None,
                   help="checkpoint every N steps, or 'auto' to derive the"
                        " cadence from measured commit latency against the"
                        " --preempt_save_timeout grace window (journaled "
                        "as cadence_retune on every change)")
    g.add_argument("--save_interval_floor", type=int, default=25,
                   help="lower clamp (steps) on the '--save_interval auto'"
                        " cadence")
    g.add_argument("--load_iters", type=int, default=None)
    g.add_argument("--finetune", action="store_true")
    g.add_argument("--no_load_optim", action="store_true")
    g.add_argument("--no_load_rng", action="store_true")
    g.add_argument("--use_checkpoint_args", action="store_true",
                   help="read model-architecture args from the checkpoint's "
                        "saved config (ref load_args_from_checkpoint)")
    g.add_argument("--no_initialization", action="store_true",
                   default=True,
                   help="accepted for parity; params are always initialized "
                        "lazily/jitted here, there is no slow eager init to skip")
    g.add_argument("--no_async_save", action="store_false", dest="async_save",
                   default=True,
                   help="block the train loop on each checkpoint write "
                        "instead of overlapping it with compute")
    g.add_argument("--keep_latest_k", type=int, default=None,
                   help="retention: prune all but the newest K committed "
                        "checkpoints after each save (default: keep all)")

    g = p.add_argument_group("async loop")
    g.add_argument("--no_async_loop", action="store_false", dest="async_loop",
                   default=True,
                   help="run the fully synchronous train loop (blocking "
                        "data fetch, transfer, and metrics read each "
                        "step) — the differential-test oracle; the async "
                        "loop is bitwise-identical and the default")
    g.add_argument("--prefetch_depth", type=int, default=2,
                   help="device-side double-buffer depth of the "
                        "background batch prefetcher (0 keeps placement "
                        "on the critical path)")
    g.add_argument("--metrics_lag", type=int, default=1,
                   help="fetch step metrics K steps late so the next "
                        "dispatch overlaps the current step; sentinel/"
                        "logger/heartbeat see steps K late (bounded — "
                        "docs/fault_tolerance.md)")
    g.add_argument("--compilation_cache_dir", default=None,
                   help="persistent XLA compilation cache dir: restarts "
                        "pay the goodput `compile` bucket once (cache "
                        "hits land in telemetry step records)")

    g = p.add_argument_group("fault tolerance")
    g.add_argument("--divergence_patience", type=int, default=100,
                   help="trip the divergence sentinel after this many "
                        "CONSECUTIVE non-finite/skipped optimizer steps "
                        "(0 disables; isolated fp16 loss-scale skips never "
                        "accumulate)")
    g.add_argument("--loss_spike_factor", type=float, default=0.0,
                   help="trip when loss > factor * EMA(loss) for "
                        "--loss_spike_patience consecutive steps "
                        "(0 disables)")
    g.add_argument("--loss_spike_patience", type=int, default=5)
    g.add_argument("--rollback_on_divergence", action="store_true",
                   help="on sentinel trip: reload the newest valid "
                        "checkpoint and fast-forward the data past the "
                        "poison window instead of aborting")
    g.add_argument("--max_rollbacks", type=int, default=3,
                   help="abort anyway after this many divergence rollbacks")
    g.add_argument("--preempt_save_timeout", type=float, default=600.0,
                   help="deadline (seconds) on the expedited checkpoint a "
                        "SIGTERM preemption notice forces; past it the "
                        "process force-exits instead of overstaying the "
                        "notice window (0 disables the deadline)")
    g.add_argument("--step_timeout_s", type=float, default=0.0,
                   help="hang watchdog: if no step completes for this many "
                        "seconds, dump a flight bundle, journal "
                        "hang_detected, and abort cleanly instead of "
                        "hanging forever (0 disables; must exceed the "
                        "longest legitimate step + eval/save stall)")
    g.add_argument("--replay_check_interval", type=int, default=0,
                   help="every N steps re-run the jitted step on the "
                        "retained batch and compare outputs BITWISE — "
                        "silent-data-corruption sentinel; a mismatch "
                        "journals sdc_detected and aborts (0 disables)")
    g.add_argument("--log_data_fingerprint", action="store_true",
                   help="journal a crc32 of every host batch as data_crc "
                        "on step records (sample-exactness evidence for "
                        "elastic resume)")
    g.add_argument("--coordination_dir", default=None,
                   help="shared directory for the file-backed multi-host "
                        "agreement seam (signal agreement, peer-death "
                        "poison records, two-phase checkpoint commit, "
                        "restart barrier); unset, a jax.process_count()>1 "
                        "run uses the jax.distributed KV store instead "
                        "(docs/fault_tolerance.md)")
    g.add_argument("--peer_death_timeout_s", type=float, default=60.0,
                   help="declare a peer host dead after this many seconds "
                        "without a heartbeat; survivors journal "
                        "peer_abort and exit code 76 instead of wedging "
                        "in the next collective (0 disables heartbeat "
                        "detection; poison records still observed)")

    g = p.add_argument_group("mixed precision")
    g.add_argument("--bf16", action="store_true")
    g.add_argument("--fp16", action="store_true")
    g.add_argument("--fp32", action="store_true")
    g.add_argument("--loss_scale", type=float, default=None)
    g.add_argument("--initial_loss_scale", type=float, default=2.0**32)
    g.add_argument("--min_loss_scale", type=float, default=1.0)
    g.add_argument("--loss_scale_window", type=int, default=1000)
    g.add_argument("--hysteresis", type=int, default=2)
    g.add_argument("--fp8_e4m3", action="store_true",
                   help="fp8 training GEMMs, everything e4m3 "
                        "(ref TransformerEngine Format.E4M3)")
    g.add_argument("--fp8_hybrid", action="store_true",
                   help="fp8 training GEMMs, e4m3 forward / e5m2 grads "
                        "(ref TransformerEngine Format.HYBRID)")
    # None sentinels (like the MoE knobs): an unpassed flag must never
    # clobber a preset's fp8_margin/fp8_wgrad (ADVICE r5 low #1)
    g.add_argument("--fp8_margin", type=int, default=None,
                   help="back quantization scales off by 2^-margin")
    g.add_argument("--no_fp8_wgrad", action="store_false", dest="fp8_wgrad",
                   default=None,
                   help="run the wgrad GEMM in higher precision")

    g = p.add_argument_group("distributed")
    g.add_argument("--tensor_model_parallel_size", type=int, default=1)
    g.add_argument("--pipeline_model_parallel_size", type=int, default=1)
    g.add_argument("--expert_model_parallel_size", type=int, default=1,
                   help="MoE expert-parallel degree (dedicated mesh axis; "
                        "E %% ep == 0, dp unconstrained)")
    g.add_argument("--context_parallel_size", type=int, default=1)
    g.add_argument("--num_layers_per_virtual_pipeline_stage", type=int,
                   default=None,
                   help="enables the interleaved schedule "
                        "(ref schedules.py:253-502)")
    g.add_argument("--sequence_parallel", action="store_true")
    g.add_argument("--use_distributed_optimizer", action="store_true")
    g.add_argument("--distributed_backend", default="xla",
                   choices=["xla", "nccl", "gloo"],
                   help="collectives are always XLA on this stack; "
                        "nccl/gloo accepted for script compat and ignored")
    g.add_argument("--local_rank", type=int, default=None,
                   help="accepted for torchrun-script compat; process "
                        "identity comes from jax.distributed here")
    g.add_argument("--DDP_impl", default="local", choices=["local", "torch"],
                   help="accepted for script compat; gradient reduction is "
                        "XLA data sharding either way")

    g = p.add_argument_group("validation")
    g.add_argument("--eval_interval", type=int, default=1000)
    g.add_argument("--eval_iters", type=int, default=100)
    g.add_argument("--metrics", nargs="*", default=[])

    g = p.add_argument_group("data")
    g.add_argument("--data_path", nargs="*", default=None)
    g.add_argument("--split", default="969,30,1")
    g.add_argument("--data_impl", default="mmap", choices=["mmap", "infer"],
                   help="only the mmap format exists here (the ref's "
                        "lazy/cached impls are legacy)")
    g.add_argument("--mmap_warmup", action="store_true",
                   help="accepted for parity; the OS page cache handles it")
    g.add_argument("--dataloader_type", default="single",
                   choices=["single", "cyclic"],
                   help="single = sequential deterministic resume; cyclic = "
                        "epoch-seeded random order (ref data_samplers.py)")
    g.add_argument("--num_workers", type=int, default=2,
                   help="prefetch depth of the threaded batch loader "
                        "(0 = synchronous)")
    g.add_argument("--tokenizer_type", default="SentencePieceTokenizer")
    g.add_argument("--vocab_file", default=None)
    g.add_argument("--merges_file", default=None)
    g.add_argument("--merge_file", dest="merges_file", default=None,
                   help="ref spelling of --merges_file")
    g.add_argument("--tokenizer_model", default=None)
    g.add_argument("--vocab_extra_ids", type=int, default=None)
    g.add_argument("--no_new_tokens", action="store_false", dest="new_tokens",
                   help="do not add special/extra-id tokens in the "
                        "sentencepiece tokenizer")
    g.add_argument("--data_cache_dir", default=None)
    g.add_argument("--scalar_loss_mask", type=float, default=0.0)
    g.add_argument("--variable_seq_lengths", action="store_true")
    g.add_argument("--eod_mask_loss", action="store_true")
    g.add_argument("--eod_token_id", type=int, default=None,
                   help="EOD id for --eod_mask_loss/--reset_position_ids "
                        "when no tokenizer is built (the reference reads it "
                        "from the tokenizer)")
    g.add_argument("--reset_position_ids", action="store_true",
                   help="restart position ids after each EOD")
    g.add_argument("--reset_attention_mask", action="store_true",
                   help="accepted with --reset_position_ids: EOD isolation "
                        "is carried by packed position ids + causal masking "
                        "(no materialized [S,S] mask on this stack)")
    g.add_argument("--mask_prob", type=float, default=0.15)
    g.add_argument("--short_seq_prob", type=float, default=0.1)

    g = p.add_argument_group("logging")
    g.add_argument("--log_interval", type=int, default=100)
    g.add_argument("--tensorboard_dir", default=None)
    g.add_argument("--wandb_logger", action="store_true")
    g.add_argument("--wandb_project", default="megatron_tpu")
    g.add_argument("--wandb_name", default=None)
    g.add_argument("--wandb_api_key", default=None,
                   help="exported as WANDB_API_KEY if not already set")
    g.add_argument("--timing_log_level", type=int, default=0)
    g.add_argument("--log_num_zeros_in_grad", action="store_true")
    g.add_argument("--log_params_norm", action="store_true")
    g.add_argument("--log_memory_to_tensorboard", action="store_true")
    g.add_argument("--log_batch_size_to_tensorboard", action="store_true")
    g.add_argument("--log_world_size_to_tensorboard", action="store_true")
    g.add_argument("--log_validation_ppl_to_tensorboard", action="store_true",
                   default=True,
                   help="validation ppl always goes to the writer here")
    g.add_argument("--log_timers_to_tensorboard", action="store_true",
                   help="per-span timer scalars each log_interval "
                        "(also raises --timing_log_level to 1)")
    g.add_argument("--profile", action="store_true",
                   help="jax.profiler trace window (TPU-native nsys "
                        "equivalent) for steps [start, end)")
    g.add_argument("--profile_step_start", type=int, default=10)
    g.add_argument("--profile_step_end", type=int, default=12)
    g.add_argument("--profile_signal_steps", type=int, default=2,
                   help="steps traced when SIGUSR1 arms an on-demand "
                        "profile window mid-run (no --profile needed)")
    g.add_argument("--profile_dir", default=None,
                   help="trace output dir (default: --tensorboard_dir)")

    g = p.add_argument_group("telemetry")
    g.add_argument("--telemetry_dir", default=None,
                   help="write the structured event journal (per-step "
                        "records, goodput ledger, checkpoint/rollback/"
                        "fault events) as rotating JSONL under this dir "
                        "(docs/observability.md; summarize with "
                        "tools/telemetry_report.py)")
    g.add_argument("--journal_max_mb", type=float, default=64.0,
                   help="rotate the journal past this size (disk stays "
                        "bounded on unbounded runs); 0 disables rotation")
    g.add_argument("--metrics_port", type=int, default=None,
                   help="sidecar Prometheus /metrics listener for the "
                        "train loop (0 binds a free port; the serving "
                        "server exposes /metrics on its own port)")
    g.add_argument("--flight_recorder", action="store_true",
                   help="arm the stall watchdog: no step heartbeat for "
                        "--flight_recorder_deadline_s dumps all-thread "
                        "stacks + the journal tail to a bundle dir")
    g.add_argument("--flight_recorder_deadline_s", type=float, default=600.0)
    g.add_argument("--flight_recorder_abort", action="store_true",
                   help="after dumping the stall bundle, SIGABRT so the "
                        "supervisor restarts the process with the "
                        "evidence on disk")

    if extra_args_provider is not None:
        extra_args_provider(p)
    return p


def _fp8_overrides(args) -> dict:
    """ref --fp8_e4m3/--fp8_hybrid are mutually exclusive store_true flags
    (megatron/arguments.py:313). Like _moe_overrides, only explicitly
    passed knobs are emitted (None = flag absent, keep the preset's or
    ModelConfig's value) — ADVICE r5 low #1."""
    if getattr(args, "fp8_e4m3", False) and getattr(args, "fp8_hybrid", False):
        raise ValueError("cannot train with both fp8 e4m3 and hybrid "
                         "formatting (pick --fp8_e4m3 or --fp8_hybrid)")
    out = {}
    for name in ("fp8_margin", "fp8_wgrad"):
        v = getattr(args, name, None)
        if v is not None:
            out[name] = v
    if getattr(args, "fp8_e4m3", False):
        out["fp8_format"] = "e4m3"
    elif getattr(args, "fp8_hybrid", False):
        out["fp8_format"] = "hybrid"
    return out


def _moe_overrides(args) -> dict:
    """MoE knobs that were explicitly passed (None = flag absent, keep the
    preset's or ModelConfig's value)."""
    out = {}
    for name in ("num_experts", "moe_experts_held", "moe_expert_share",
                 "moe_top_k", "moe_capacity_factor",
                 "moe_aux_loss_coeff", "moe_z_loss_coeff",
                 "moe_renorm_gates", "moe_group_size", "moe_dispatch",
                 "moe_ep_buffer_factor", "moe_router_score",
                 "moe_route_scale", "moe_latent_size",
                 "moe_shared_ffn_size", "moe_router_form",
                 "moe_router_hidden_size", "moe_bias_update_rate"):
        v = getattr(args, name, None)
        if v is not None:
            out[name] = v
    return out


def _parse_save_interval(value):
    """--save_interval takes an int or the literal 'auto' (the autotuned
    cadence, TrainingConfig.save_interval_auto); anything else is the
    argparse-grade error the old type=int gave."""
    if value is None or str(value).lower() == "auto":
        return None
    try:
        return int(value)
    except ValueError:
        raise SystemExit(
            f"--save_interval must be an integer or 'auto' (got {value!r})")


def args_to_run_config(args) -> RunConfig:
    from megatron_tpu.models import presets
    from megatron_tpu.tokenizer import pad_vocab_size

    # reference aliases resolved up front
    if getattr(args, "encoder_seq_length", None):
        args.seq_length = args.encoder_seq_length
    if getattr(args, "use_flash_attn", False):
        args.attention_impl = "pallas"
    if getattr(args, "recompute_activations", False) \
            and args.recompute_granularity == "none":
        args.recompute_granularity = "selective"
    method = getattr(args, "recompute_method", "uniform")
    n_rc = getattr(args, "recompute_num_layers", 1)
    if method == "block" or (method == "uniform" and n_rc > 1):
        if args.recompute_granularity != "full":
            raise ValueError(
                f"--recompute_method {method} with --recompute_num_layers "
                "needs --recompute_granularity full (they allocate a "
                "FULL-remat layer budget; selective already bounds memory "
                "per layer)")
        args.recompute_granularity = f"{method}:{n_rc}"
    if getattr(args, "log_timers_to_tensorboard", False):
        args.timing_log_level = max(args.timing_log_level, 1)
    gbs = args.global_batch_size or args.micro_batch_size
    if getattr(args, "dataloader_type", "single") == "cyclic" \
            and args.rampup_batch_size:
        raise ValueError(
            "--dataloader_type cyclic resumes by consumed-samples modulo a "
            "FIXED batch size and breaks under --rampup_batch_size; use the "
            "default sequential loader with rampup")
    if getattr(args, "lr_decay_samples", None) or getattr(
            args, "lr_warmup_samples", None):
        if args.rampup_batch_size:
            raise ValueError(
                "--lr_{decay,warmup}_samples are converted to iterations "
                "via the final global batch size, which is wrong under "
                "--rampup_batch_size; use --lr_{decay,warmup}_iters")
        if args.lr_decay_samples and not args.lr_decay_iters:
            args.lr_decay_iters = args.lr_decay_samples // gbs
        if args.lr_warmup_samples and not args.lr_warmup_iters:
            args.lr_warmup_iters = args.lr_warmup_samples // gbs

    ckpt_model = None
    if getattr(args, "use_checkpoint_args", False) and args.load:
        ckpt_model = _model_config_from_checkpoint(
            args.load, getattr(args, "load_iters", None))

    if ckpt_model is not None:
        model = ckpt_model
    elif args.model_name:
        name = args.model_name
        size = args.model_size
        if "-" in name and size is None:
            name, size = name.split("-", 1)
        kw = {}
        if size:
            kw["size"] = size
        model = presets.PRESETS[name](**kw)
        # CLI overrides on top of the preset
        overrides = {}
        if args.seq_length and args.seq_length != 2048:
            overrides["seq_length"] = args.seq_length
        if args.rope_scaling_factor != 1.0:
            overrides["rope_scaling_factor"] = args.rope_scaling_factor
        overrides["hidden_dropout"] = args.hidden_dropout
        overrides["attention_dropout"] = args.attention_dropout
        overrides["lima_dropout"] = args.lima_dropout
        overrides["attention_impl"] = args.attention_impl
        overrides["ce_chunk_size"] = args.ce_chunk_size
        overrides["params_dtype"] = _dtype_name(args)
        overrides.update(_fp8_overrides(args))
        if args.tie_embed_logits is not None:  # explicit (no_)tie flag
            overrides["tie_embed_logits"] = args.tie_embed_logits
        if args.qk_norm is not None:
            overrides["qk_norm"] = args.qk_norm
        overrides.update(_moe_overrides(args))
        model = ModelConfig(**{**model.__dict__, **overrides}).validate()
    else:
        required = ["num_layers", "hidden_size", "num_attention_heads"]
        missing = [r for r in required if getattr(args, r) is None]
        if missing:
            raise ValueError(f"missing required model args: {missing} "
                             "(or use --model_name)")
        vocab = pad_vocab_size(args.vocab_size,
                               args.make_vocab_size_divisible_by,
                               args.tensor_model_parallel_size)
        model = ModelConfig(
            num_layers=args.num_layers,
            hidden_size=args.hidden_size,
            num_attention_heads=args.num_attention_heads,
            num_kv_heads=args.num_attention_heads_kv,
            kv_channels=args.kv_channels,
            ffn_hidden_size=args.ffn_hidden_size,
            vocab_size=vocab,
            seq_length=args.seq_length,
            max_position_embeddings=args.max_position_embeddings,
            position_embedding_type=args.position_embedding_type,
            rope_theta=args.rope_theta,
            rope_scaling_factor=args.rope_scaling_factor,
            rotary_percent=args.rotary_percent,
            attention_form=args.attention_form,
            cca_conv_kernels=tuple(args.cca_conv_kernels),
            residual_scale=args.residual_scale,
            normalization="rmsnorm" if args.use_rms_norm else "layernorm",
            layernorm_epsilon=args.layernorm_epsilon,
            activation=args.glu_activation or args.activation or "gelu",
            parallel_attn=args.parallel_attn,
            parallel_layernorm=args.parallel_layernorm,
            use_bias_linear=args.use_bias,
            use_bias_qkv=args.use_bias,
            # ref default is tied (untie with --no_tie_embed_logits)
            tie_embed_logits=(True if args.tie_embed_logits is None
                              else args.tie_embed_logits),
            **_moe_overrides(args),
            sliding_window_size=args.sliding_window_size,
            attention_pattern=args.attention_pattern,
            layer_pattern=args.layer_pattern,
            ssm_d_state=args.ssm_d_state,
            ssm_d_conv=args.ssm_d_conv,
            ssm_expand=args.ssm_expand,
            ssm_dt_rank=args.ssm_dt_rank,
            ssm_inner_norms=args.ssm_inner_norms,
            ssm_num_heads=args.ssm_num_heads,
            ssm_n_groups=args.ssm_n_groups,
            ssm_chunk_size=args.ssm_chunk_size,
            qk_norm=bool(args.qk_norm),
            use_post_ln=args.use_post_ln,
            apply_residual_post_ln=args.apply_residual_connection_post_layernorm,
            hidden_dropout=args.hidden_dropout,
            attention_dropout=args.attention_dropout,
            lima_dropout=args.lima_dropout,
            init_method_std=args.init_method_std,
            params_dtype=_dtype_name(args),
            attention_impl=args.attention_impl,
            ce_chunk_size=args.ce_chunk_size,
            **_fp8_overrides(args),
        ).validate()

    vpp = None
    per_stage = getattr(args, "num_layers_per_virtual_pipeline_stage", None)
    if per_stage:
        pp = args.pipeline_model_parallel_size
        vpp = model.num_layers // (pp * per_stage)
        if vpp * pp * per_stage != model.num_layers:
            raise ValueError(
                f"num_layers={model.num_layers} not divisible by "
                f"pp*per_stage={pp}*{per_stage}")
    parallel = ParallelConfig(
        tensor_parallel=args.tensor_model_parallel_size,
        pipeline_parallel=args.pipeline_model_parallel_size,
        context_parallel=args.context_parallel_size,
        expert_parallel=getattr(args, "expert_model_parallel_size", 1),
        sequence_parallel=args.sequence_parallel,
        virtual_pipeline_parallel=vpp if (vpp or 0) > 1 else None,
    ).validate()

    optimizer = OptimizerConfig(
        optimizer=args.optimizer,
        sgd_momentum=args.sgd_momentum,
        log_num_zeros_in_grad=getattr(args, "log_num_zeros_in_grad", False),
        lr=args.lr, min_lr=args.min_lr,
        lr_decay_style=args.lr_decay_style,
        lr_decay_iters=args.lr_decay_iters,
        lr_warmup_iters=args.lr_warmup_iters,
        lr_warmup_fraction=args.lr_warmup_fraction,
        adam_beta1=args.adam_beta1, adam_beta2=args.adam_beta2,
        adam_eps=args.adam_eps,
        weight_decay=args.weight_decay,
        start_weight_decay=args.start_weight_decay,
        end_weight_decay=args.end_weight_decay,
        weight_decay_incr_style=args.weight_decay_incr_style,
        clip_grad=args.clip_grad,
        # task heads: classification_head (GLUE and RACE — multichoice
        # reuses the same param name), the ICT/DPR retrieval heads, and
        # BERT's binary head — the param-path form of the reference's
        # scale_lr_cond param groups
        param_group_mults=(
            (("(^|/)(classification_head|ict_head|binary_head)(/|$)",
              args.head_lr_mult, 1.0),)
            if getattr(args, "head_lr_mult", 1.0) != 1.0 else ()),
        use_distributed_optimizer=args.use_distributed_optimizer,
        loss_scale=args.loss_scale,
        initial_loss_scale=args.initial_loss_scale,
        min_loss_scale=args.min_loss_scale,
        loss_scale_window=args.loss_scale_window,
        hysteresis=args.hysteresis,
    )

    if getattr(args, "wandb_api_key", None) and "WANDB_API_KEY" not in os.environ:
        os.environ["WANDB_API_KEY"] = args.wandb_api_key

    training = TrainingConfig(
        micro_batch_size=args.micro_batch_size,
        global_batch_size=args.global_batch_size or args.micro_batch_size,
        rampup_batch_size=tuple(args.rampup_batch_size)
        if args.rampup_batch_size else None,
        train_iters=args.train_iters,
        train_samples=args.train_samples,
        eval_interval=args.eval_interval,
        eval_iters=args.eval_iters,
        seed=args.seed,
        recompute_granularity=args.recompute_granularity,
        save=args.save, load=args.load,
        save_interval=_parse_save_interval(args.save_interval),
        save_interval_auto=(str(args.save_interval).lower() == "auto"),
        save_interval_floor=getattr(args, "save_interval_floor", 25),
        exit_interval=args.exit_interval,
        exit_duration_in_mins=args.exit_duration_in_mins,
        finetune=args.finetune,
        no_load_optim=args.no_load_optim,
        no_load_rng=args.no_load_rng,
        async_save=getattr(args, "async_save", True),
        keep_latest_k=getattr(args, "keep_latest_k", None),
        async_loop=getattr(args, "async_loop", True),
        prefetch_depth=getattr(args, "prefetch_depth", 2),
        metrics_lag=getattr(args, "metrics_lag", 1),
        compilation_cache_dir=getattr(args, "compilation_cache_dir", None),
        divergence_patience=getattr(args, "divergence_patience", 100),
        loss_spike_factor=getattr(args, "loss_spike_factor", 0.0),
        loss_spike_patience=getattr(args, "loss_spike_patience", 5),
        rollback_on_divergence=getattr(args, "rollback_on_divergence", False),
        max_rollbacks=getattr(args, "max_rollbacks", 3),
        preempt_save_timeout=getattr(args, "preempt_save_timeout", 600.0),
        step_timeout_s=getattr(args, "step_timeout_s", 0.0),
        replay_check_interval=getattr(args, "replay_check_interval", 0),
        log_data_fingerprint=getattr(args, "log_data_fingerprint", False),
        coordination_dir=getattr(args, "coordination_dir", None),
        peer_death_timeout_s=getattr(args, "peer_death_timeout_s", 60.0),
        log_interval=args.log_interval,
        tensorboard_dir=args.tensorboard_dir,
        wandb_logger=args.wandb_logger,
        wandb_project=getattr(args, "wandb_project", "megatron_tpu"),
        wandb_name=getattr(args, "wandb_name", None),
        timing_log_level=args.timing_log_level,
        log_timers_to_tensorboard=getattr(args, "log_timers_to_tensorboard",
                                          False),
        profile=getattr(args, "profile", False),
        profile_step_start=getattr(args, "profile_step_start", 10),
        profile_step_end=getattr(args, "profile_step_end", 12),
        profile_signal_steps=getattr(args, "profile_signal_steps", 2),
        profile_dir=getattr(args, "profile_dir", None),
        telemetry_dir=getattr(args, "telemetry_dir", None),
        journal_max_mb=getattr(args, "journal_max_mb", 64.0),
        metrics_port=getattr(args, "metrics_port", None),
        flight_recorder=getattr(args, "flight_recorder", False),
        flight_recorder_deadline_s=getattr(args, "flight_recorder_deadline_s",
                                           600.0),
        flight_recorder_abort=getattr(args, "flight_recorder_abort", False),
        eval_only=getattr(args, "eval_only", False),
        skip_iters=tuple(getattr(args, "skip_iters", []) or []),
        log_params_norm=getattr(args, "log_params_norm", False),
        log_memory=getattr(args, "log_memory_to_tensorboard", False),
        log_batch_size=getattr(args, "log_batch_size_to_tensorboard", False),
        log_world_size=getattr(args, "log_world_size_to_tensorboard", False),
        scalar_loss_mask=args.scalar_loss_mask,
        variable_seq_lengths=args.variable_seq_lengths,
        metrics=tuple(args.metrics),
    ).validate()

    return RunConfig(model=model, parallel=parallel, optimizer=optimizer,
                     training=training).validate()


def _model_config_from_checkpoint(load: str, iteration=None):
    """ModelConfig from a checkpoint's saved run config
    (ref: load_args_from_checkpoint, checkpointing.py:482-567)."""
    import json
    import os

    from megatron_tpu.training.checkpointing import checkpoint_dir, read_tracker

    it = iteration if iteration is not None else read_tracker(load)
    if it is None:
        return None
    meta_path = os.path.join(checkpoint_dir(load, it), "meta.json")
    if not os.path.exists(meta_path):
        return None
    with open(meta_path) as f:
        saved = json.load(f).get("config", {})
    if "model" not in saved:
        return None
    return model_config_from_saved(saved["model"]).validate()


def _dtype_name(args) -> str:
    if getattr(args, "fp16", False):
        return "float16"
    if getattr(args, "fp32", False):
        return "float32"
    return "bfloat16"


def parse_args(argv: Optional[Sequence[str]] = None, extra_args_provider=None):
    parser = build_parser(extra_args_provider)
    return parser.parse_args(argv)
