"""Architecture presets.

Replaces the reference's assertion-shell model subclasses
(megatron/model/llama_model.py, falcon_model.py, mistral_model.py,
gpt_model.py — each just asserts/forces flag values) with config
constructors. Size tables mirror weights_conversion/hf_to_megatron.py:53-57
and the public model cards.

Vocab sizes here are the raw tokenizer sizes; pad_vocab() applies the
reference's padding rule (make_vocab_size_divisible_by x tensor_parallel,
ref: megatron/tokenizer/tokenizer.py:45-62).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from megatron_tpu.config import ModelConfig


def pad_vocab(vocab_size: int, divisible_by: int = 128, tensor_parallel: int = 1) -> int:
    mult = divisible_by * tensor_parallel
    return mult * ((vocab_size + mult - 1) // mult)


def _llama_base(**kw) -> ModelConfig:
    base = dict(
        normalization="rmsnorm",
        activation="swiglu",
        position_embedding_type="rotary",
        use_bias_linear=False,
        use_bias_qkv=False,
        tie_embed_logits=False,
        layernorm_epsilon=1e-5,
        vocab_size=32000,
        # the flash kernel family on the training path, like the reference's
        # recommended --use_flash_attn configs; the XLA path serves what the
        # kernel does not cover by a condition known before the call
        # (padding masks, dropout, q_len != kv_len: ops/attention.py)
        attention_impl="pallas",
    )
    base.update(kw)
    return ModelConfig(**base).validate()


# (hidden, layers, heads, kv_heads, ffn)
_LLAMA_SIZES = {
    "7B": (4096, 32, 32, None, 11008),
    "13B": (5120, 40, 40, None, 13824),
    "30B": (6656, 60, 52, None, 17920),
    "65B": (8192, 80, 64, None, 22016),
}
_LLAMA2_SIZES = {
    "7B": (4096, 32, 32, None, 11008),
    "13B": (5120, 40, 40, None, 13824),
    "70B": (8192, 80, 64, 8, 28672),
}
_CODELLAMA_SIZES = {
    "7B": (4096, 32, 32, None, 11008),
    "13B": (5120, 40, 40, None, 13824),
    "34B": (8192, 48, 64, 8, 22016),
}


def llama(size: str = "7B", version: int = 2, seq_length: Optional[int] = None,
          rope_scaling_factor: float = 1.0) -> ModelConfig:
    """Llama v1 (seq 2048, eps 1e-6) / v2 (seq 4096, eps 1e-5)
    (ref: megatron/model/llama_model.py version flags)."""
    table = _LLAMA2_SIZES if version == 2 else _LLAMA_SIZES
    h, L, nh, nkv, ffn = table[size]
    return _llama_base(
        hidden_size=h, num_layers=L, num_attention_heads=nh, num_kv_heads=nkv,
        ffn_hidden_size=ffn,
        seq_length=seq_length or (4096 if version == 2 else 2048),
        layernorm_epsilon=1e-5 if version == 2 else 1e-6,
        rope_scaling_factor=rope_scaling_factor,
    )


def codellama(size: str = "7B", seq_length: int = 16384) -> ModelConfig:
    """CodeLlama: llama-2 geometry + rope theta 1e6 + 32016-token vocab
    (ref: arguments.py:466-469 --rope_theta)."""
    h, L, nh, nkv, ffn = _CODELLAMA_SIZES[size]
    return _llama_base(
        hidden_size=h, num_layers=L, num_attention_heads=nh, num_kv_heads=nkv,
        ffn_hidden_size=ffn, seq_length=seq_length, vocab_size=32016,
        rope_theta=1e6,
    )


def mistral(size: str = "7B", seq_length: int = 8192) -> ModelConfig:
    """Mistral-7B: llama flags + GQA(8) + sliding window 4096
    (ref: megatron/model/mistral_model.py)."""
    assert size == "7B"
    return _llama_base(
        hidden_size=4096, num_layers=32, num_attention_heads=32, num_kv_heads=8,
        ffn_hidden_size=14336, seq_length=seq_length,
        sliding_window_size=4096,
    )


def mixtral(size: str = "8x7B", seq_length: int = 8192) -> ModelConfig:
    """Mixtral-8x7B: Mistral geometry with 8 experts / top-2 renormalized
    routing per layer (beyond the reference — no MoE upstream; routing
    semantics match HF MixtralSparseMoeBlock when capacity is ample)."""
    assert size == "8x7B"
    return _llama_base(
        hidden_size=4096, num_layers=32, num_attention_heads=32,
        num_kv_heads=8, ffn_hidden_size=14336, seq_length=seq_length,
        num_experts=8, moe_top_k=2, moe_renorm_gates=True,
        rope_theta=1e6,  # Mixtral-8x7B config (vs llama/mistral 1e4)
    )


def olmoe(size: str = "1B-7B", seq_length: int = 4096) -> ModelConfig:
    """OLMoE-1B-7B (arXiv:2409.02060; allenai/OLMoE-1B-7B-0125-Instruct
    config.json): 16 layers, hidden 2048, 16 heads (MHA), 64 SwiGLU
    experts of width 1024 with 8 a token, raw softmax gates
    (`norm_topk_prob: false`), no shared expert, QK-norm over the whole
    projections, untied head, vocab 50304. Dropless dispatch, as the
    paper trains; load-balance coefficient 0.01, router z-loss 0.001."""
    assert size == "1B-7B"
    return _llama_base(
        hidden_size=2048, num_layers=16, num_attention_heads=16,
        ffn_hidden_size=1024, vocab_size=50304, seq_length=seq_length,
        qk_norm=True, num_experts=64, moe_top_k=8, moe_renorm_gates=False,
        moe_dispatch="dropless", moe_aux_loss_coeff=0.01,
        moe_z_loss_coeff=0.001,
    )


def falcon(size: str = "7B", seq_length: int = 2048) -> ModelConfig:
    """Falcon 7B/40B: rotary, MQA/GQA, parallel attention, layernorm, gelu,
    tied embeddings, no linear biases (ref: megatron/model/falcon_model.py)."""
    if size == "7B":
        h, L, nh, nkv, parallel_ln = 4544, 32, 71, 1, False
    elif size == "40B":
        h, L, nh, nkv, parallel_ln = 8192, 60, 128, 8, True
    else:
        raise ValueError(f"unknown falcon size {size}")
    return ModelConfig(
        hidden_size=h, num_layers=L, num_attention_heads=nh, num_kv_heads=nkv,
        ffn_hidden_size=4 * h, vocab_size=65024, seq_length=seq_length,
        normalization="layernorm", activation="gelu",
        position_embedding_type="rotary",
        parallel_attn=True, parallel_layernorm=parallel_ln,
        use_bias_linear=False, use_bias_qkv=False,
        tie_embed_logits=True, layernorm_epsilon=1e-5,
        attention_impl="pallas",
    ).validate()


def gpt2(size: str = "124M", seq_length: int = 1024) -> ModelConfig:
    """GPT-2-style model (ref: megatron/model/gpt_model.py GPTModel with
    absolute pos-emb, gelu, layernorm, biases, tied embeddings)."""
    sizes = {
        "124M": (768, 12, 12),
        "355M": (1024, 24, 16),
        "760M": (1536, 24, 16),
        "1.3B": (2048, 24, 32),
    }
    h, L, nh = sizes[size]
    return ModelConfig(
        hidden_size=h, num_layers=L, num_attention_heads=nh,
        vocab_size=50304,  # 50257 padded
        seq_length=seq_length, max_position_embeddings=seq_length,
        normalization="layernorm", activation="gelu",
        position_embedding_type="absolute",
        use_bias_linear=True, use_bias_qkv=True,
        tie_embed_logits=True, layernorm_epsilon=1e-5,
        init_method_std=0.02,
    ).validate()


def jamba(size: str = "2-3B", seq_length: int = 4096) -> ModelConfig:
    """AI21 Jamba with a dense FFN in every layer (`num_experts` 1): in
    each period of 14 layers the one at offset 7 is an attention layer
    (20 heads over one KV head), the others Mamba-1 layers with Jamba's
    norms on dt, B and C; no positional encoding; tied head
    (https://huggingface.co/ai21labs/AI21-Jamba2-3B config.json)."""
    if size != "2-3B":
        raise ValueError(f"unknown jamba size {size}")
    return ModelConfig(
        hidden_size=2560, num_layers=28, num_attention_heads=20,
        num_kv_heads=1, kv_channels=128, ffn_hidden_size=8192,
        vocab_size=65536, seq_length=seq_length,
        normalization="rmsnorm", activation="swiglu",
        position_embedding_type="none", tie_embed_logits=True,
        layernorm_epsilon=1e-6, init_method_std=0.02,
        layer_pattern=("mamba",) * 7 + ("attention",) + ("mamba",) * 6,
        ssm_d_state=16, ssm_d_conv=4, ssm_expand=2, ssm_dt_rank=160,
        ssm_inner_norms=True, attention_impl="pallas",
    ).validate()


def tiny(vocab_size: int = 256, seq_length: int = 128, **kw) -> ModelConfig:
    """Small config for tests/CI."""
    base = dict(
        hidden_size=64, num_layers=2, num_attention_heads=4, num_kv_heads=2,
        ffn_hidden_size=128, vocab_size=vocab_size, seq_length=seq_length,
        normalization="rmsnorm", activation="swiglu",
        position_embedding_type="rotary", tie_embed_logits=False,
        params_dtype="float32",
    )
    base.update(kw)
    return ModelConfig(**base).validate()


PRESETS = {
    "llama": llama,
    "llama2": lambda **kw: llama(version=2, **kw),
    "codellama": codellama,
    "mistral": mistral,
    "mixtral": mixtral,
    "olmoe": olmoe,
    "falcon": falcon,
    "gpt2": gpt2,
    "jamba": jamba,
    "tiny": tiny,
}
