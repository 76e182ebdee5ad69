"""Typed configuration for megatron_tpu.

Replaces the reference's argparse god-namespace (megatron/arguments.py, 1,103
LoC; megatron/global_vars.py get_args()) with frozen dataclasses. The CLI
layer in megatron_tpu/arguments.py maps reference flag names onto these, so
flag-level parity is preserved without mutable global state.

Field names deliberately follow the reference flags (hidden_size,
num_attention_heads, ...) so that configs can round-trip through checkpoints
the way the reference pickles its args namespace
(ref: megatron/checkpointing.py:267-285).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence, Tuple

import jax.numpy as jnp

# ---------------------------------------------------------------------------
# enums (ref: megatron/model/enums.py)
# ---------------------------------------------------------------------------

# "none": no positional encoding at all (a stack whose state-space layers
# carry the order of the sequence)
POSITION_EMBEDDING_TYPES = ("rotary", "absolute", "none")
# What a layer can be (`ModelConfig.layer_pattern`). A sequence mixer:
# softmax attention, a Mamba-1 selective state-space mixer or a Mamba-2
# (SSD) one (ops/ssm.py has both forms). Or a feed-forward block ALONE: a
# dense MLP, or an expert layer (ops/moe.py). Types differ in their
# parameters' shapes; attention KINDS share them.
MIXER_TYPES = ("attention", "mamba", "mamba2")
SSM_TYPES = ("mamba", "mamba2")
FFN_TYPES = ("mlp", "moe")
LAYER_TYPES = MIXER_TYPES + FFN_TYPES
MOE_ROUTER_SCORES = ("softmax", "sigmoid")
# What stands between the expert layer's input and the router's logits
# (ops/moe.py `_route`, `router_mlp`): one matrix, or a down projection, a state
# carried from layer to layer and an MLP.
MOE_ROUTER_FORMS = ("linear", "mlp")
# How a layer makes q, k and v of its normed input (models/transformer.py
# attention_block): three projections, or compressed convolutional
# attention (ops/cca.py).
ATTENTION_FORMS = ("plain", "cca")
NORMALIZATION_TYPES = ("layernorm", "rmsnorm")
# GLU family per ref megatron/model/glu_activations.py plus plain variants.
ACTIVATION_TYPES = ("gelu", "gelu_tanh", "geglu", "swiglu", "reglu", "liglu", "relu", "squared_relu")
GLU_ACTIVATIONS = ("geglu", "swiglu", "reglu", "liglu")
# "padding": bidirectional with a per-row key padding mask (BERT-style
# encoders); requires an attention_mask input end-to-end.
ATTN_MASK_TYPES = ("causal", "bidirectional", "padding")
ATTENTION_IMPLS = ("xla", "pallas", "ring", "ulysses")
RECOMPUTE_POLICIES = ("none", "selective", "full")
DTYPES = {"bfloat16": jnp.bfloat16, "float16": jnp.float16, "float32": jnp.float32}


def _resolve_dtype(name: str):
    if name not in DTYPES:
        raise ValueError(f"unknown dtype {name!r}; one of {sorted(DTYPES)}")
    return DTYPES[name]


# ---------------------------------------------------------------------------
# model architecture
# ---------------------------------------------------------------------------

ROPE_TYPES = ("linear", "yarn")


@dataclass(frozen=True)
class AttentionKind:
    """What may differ between the attention layers of one stack and is
    static where a layer calls its kernels: the window and the rotary
    table. Layers of one kind compare equal; a model's layers are of the
    kinds of `ModelConfig.attention_period`, in that order, over and over.

    rope_type "linear": positions divided by rope_scaling_factor (1.0: the
    plain table). "yarn" (arXiv:2309.00071, as Hugging Face's
    `rope_parameters` state it): each frequency blended between itself and
    itself / rope_scaling_factor by a linear ramp over the dimensions,
    from the one that turns yarn_beta_fast times over
    yarn_original_max_positions (and faster: left alone) to the one that
    turns yarn_beta_slow times (and slower: interpolated); cos and sin
    scaled by yarn_attention_factor (None: 0.1 ln(factor) + 1)."""

    name: str = "full"     # the layer's scope in a trace: attn_<name>
    sliding_window_size: Optional[int] = None
    rope_theta: float = 10000.0
    rope_scaling_factor: float = 1.0
    rope_type: str = "linear"
    yarn_original_max_positions: Optional[int] = None
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: Optional[float] = None

    def validate(self) -> "AttentionKind":
        if self.rope_type not in ROPE_TYPES:
            raise ValueError(f"bad rope_type {self.rope_type!r}; one of "
                             f"{ROPE_TYPES}")
        if self.rope_type == "yarn" and not self.yarn_original_max_positions:
            raise ValueError("rope_type 'yarn' needs "
                             "yarn_original_max_positions")
        if (self.sliding_window_size is not None
                and self.sliding_window_size < 1):
            raise ValueError("sliding_window_size must be >= 1")
        return self



@dataclass(frozen=True)
class ModelConfig:
    """Architecture of one decoder-only (or encoder) transformer LM.

    One configurable block covers the union of the reference's model zoo
    (GPT/Llama/Falcon/Mistral assertion-shell subclasses,
    ref: megatron/model/{gpt_model,llama_model,falcon_model,mistral_model}.py).
    Presets live in megatron_tpu/models/presets.py.
    """

    num_layers: int
    hidden_size: int
    num_attention_heads: int
    vocab_size: int
    seq_length: int

    # encoder-decoder models (T5) may give the two stacks different
    # depths (ref: --encoder_num_layers / --decoder_num_layers,
    # megatron/arguments.py); None = num_layers. Decoder-only models
    # ignore both.
    encoder_num_layers: Optional[int] = None
    decoder_num_layers: Optional[int] = None

    # grouped-/multi-query attention (ref: transformer.py:450-465
    # num_attention_heads_kv broadcast trick). None => MHA.
    num_kv_heads: Optional[int] = None
    # head dim override (defaults to hidden_size // num_attention_heads)
    kv_channels: Optional[int] = None
    # MLP width. None => 4*hidden for non-GLU, (8/3)*hidden rounded for GLU
    # presets set it explicitly (e.g. llama-2 7B: 11008).
    ffn_hidden_size: Optional[int] = None

    # position embeddings (ref: megatron/model/positional_embeddings.py)
    position_embedding_type: str = "rotary"
    rope_theta: float = 10000.0
    # linear position-interpolation RoPE scaling (ref --rope_scaling_factor)
    rope_scaling_factor: float = 1.0
    # the share of a head's channels that rotary turns: the first
    # rotary_percent * head_dim, rotate-half inside them, the rest passed
    # (Hugging Face's `partial_rotary_factor`)
    rotary_percent: float = 1.0
    max_position_embeddings: Optional[int] = None  # for absolute pos-emb

    # norms / activations
    normalization: str = "rmsnorm"
    layernorm_epsilon: float = 1e-5
    activation: str = "swiglu"
    # Falcon-style parallel attention: mlp(ln(x)) + attn(ln(x)) in one
    # residual add (ref: transformer.py parallel_attn), optionally with a
    # second dedicated mlp layernorm (Falcon-40B parallel_layernorm).
    parallel_attn: bool = False
    parallel_layernorm: bool = False
    # post-LN layer convention (ref --use_post_ln): no pre-norm, each layer
    # ends with its own LN (reusing the ln1 slot), no final stack norm
    use_post_ln: bool = False
    # residual taken from the LN output instead of the LN input
    # (ref --apply_residual_connection_post_layernorm)
    apply_residual_post_ln: bool = False
    # post-attention norm applied before mlp (standard pre-LN stack)

    # biases (llama/falcon: none; gpt: all)
    use_bias_linear: bool = False
    use_bias_qkv: bool = False

    # tied input/output embeddings (gpt/falcon: tied; llama/mistral: untied)
    tie_embed_logits: bool = False

    # Mistral sliding-window attention (ref: transformer.py:528-536)
    sliding_window_size: Optional[int] = None
    # Layers of several attention kinds in one stack (window and full
    # layers mixed, a rotary table a kind): one period of the layers'
    # pattern, which the stack repeats over its depth (num_layers is a
    # multiple of its length). None: every layer is of the one kind that
    # rope_theta, rope_scaling_factor and sliding_window_size above state;
    # given, those three stay at their defaults (a kind states its own),
    # and its layers are not all alike: a model has one spelling (a
    # pattern of one layer only for a table the scalars cannot state).
    # Read through `attention_period`, never directly.
    attention_pattern: Optional[Tuple[AttentionKind, ...]] = None

    # Layers of several TYPES in one stack (LAYER_TYPES): one period of the
    # layers' types, which the stack repeats over its depth. A type has its
    # own parameter leaves, stacked over THAT type's layers alone
    # (models/params.py: layers/ssm/* over the state-space layers,
    # layers/attn/* over the "attention" ones, layers/mlp/* and
    # layers/moe/* over the layers of those types). What a layer is
    # follows from the pattern. Mixer types alone (MIXER_TYPES): every
    # layer is a mixer AND a feed-forward block, two norms and two
    # residual adds (norms and the FFN stacked over all layers). Where the
    # pattern names a feed-forward type (FFN_TYPES), every layer is ONE
    # block, a mixer alone or a feed-forward block alone, behind one norm
    # (`ln1`, stacked over all layers) and one residual add: no `ln2`, and
    # no FFN in a mixer's layer (`single_block_layers`). None: every layer
    # is an attention layer and an FFN. Its attention layers are of one
    # kind (no attention_pattern beside it), its state-space layers of one
    # form. Read through `layer_period`.
    layer_pattern: Optional[Tuple[str, ...]] = None
    # The state-space mixers' sizes: the state N (a channel of Mamba-1,
    # arXiv:2312.00752; a head's [P, N] of Mamba-2, arXiv:2405.21060), the
    # causal convolution's width K, the inner width over the hidden size.
    # Mamba-1's own: the rank R of the step size's projection (None:
    # ceil(hidden_size / 16)); ssm_inner_norms: an RMSNorm with a learned
    # scale on each of dt, B and C before they are used (Jamba's own).
    # Mamba-2's own: the heads H (of inner width / H channels each, one
    # step size and one scalar decay a head), the groups G that share B
    # and C (H / G heads a group; also the groups of the gated norm), and
    # the positions a chunk of the chunked scan.
    ssm_d_state: int = 16
    ssm_d_conv: int = 4
    ssm_expand: int = 2
    ssm_dt_rank: Optional[int] = None
    ssm_inner_norms: bool = False
    ssm_num_heads: Optional[int] = None
    ssm_n_groups: int = 1
    ssm_chunk_size: int = 128

    # "cca": compressed convolutional attention (arXiv:2510.04476;
    # ops/cca.py has the equations): q and k of the latent (heads x head
    # size wide, never projected up) go through a depthwise causal
    # convolution of cca_conv_kernels[0] taps and one grouped by head of
    # cca_conv_kernels[1] taps, the mean of the pre-convolution q and k of
    # a group is added, both are brought to unit norm (k times a learned
    # temperature a KV head), and half of v's channels read the previous
    # position. Training alone: a cache would hold the convolutions' tail.
    attention_form: str = "plain"
    cca_conv_kernels: Tuple[int, int] = (2, 2)
    # Each of a layer's two residual adds as (s_x * x + b_x) + (s_o * out
    # + b_o), four learned vectors of hidden_size a sub-layer
    # (layers/res1, layers/res2; s at 1 and b at 0 is the plain add).
    residual_scale: bool = False

    # OLMoE QK-norm: RMSNorm with a learned scale over the WHOLE q and the
    # whole k projection (all heads at once), before the head split and
    # the rotary (HF modeling_olmoe.py q_norm / k_norm)
    qk_norm: bool = False

    # Mixture-of-Experts (beyond the reference): GShard/Switch einsum
    # dispatch with capacity; Mixtral-style renormalized top-k gates.
    # None = dense MLP. See ops/moe.py.
    num_experts: Optional[int] = None
    # One chip's share of an expert-parallel layer, run alone: the router
    # stays num_experts wide, the weights of moe_experts_held experts
    # exist here (experts moe_expert_share * held up to the next share's
    # first), and the layer returns the part of the result they give
    # (ops/moe.py moe_block_dropless). None: every expert is held.
    moe_experts_held: Optional[int] = None
    moe_expert_share: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coeff: float = 1e-2
    moe_z_loss_coeff: float = 0.0
    moe_renorm_gates: bool = True
    # How the router scores its experts (ops/moe.py `_route`). "softmax":
    # probabilities over all experts, the k largest chosen. "sigmoid": a
    # score an expert on its own; the k chosen are the largest of score +
    # a learned selection bias (layers/moe/router_bias, read for the
    # choice alone), the gates the chosen scores over their sum
    # (moe_renorm_gates) times moe_route_scale, and no load-balance loss.
    moe_router_score: str = "softmax"
    moe_route_scale: float = 1.0
    # "mlp" (ZAYA1's router, ops/moe.py `router_mlp`): the layer's
    # input projected down to moe_router_hidden_size, plus a learned
    # per-channel scale times the previous expert layer's such state (an
    # activation the layer stack carries from layer to layer and the
    # backward pass differentiates through), through a three-layer gelu
    # MLP to the logits. Dropless, unsharded and training alone.
    moe_router_form: str = "linear"
    moe_router_hidden_size: Optional[int] = None
    # Balancing by the selection bias in place of an auxiliary loss: after
    # each optimizer step every expert layer's bias_e += rate * sign(1/E
    # - load_e), load_e the share of the step's tokens that chose e
    # (training/optimizer.py update_selection_bias). The bias
    # (layers/moe/router_bias) is added to the logits (softmax form) or
    # the scores (sigmoid form) for the choice alone and is trained by no
    # gradient. None: no update (the softmax form then has no bias).
    moe_bias_update_rate: Optional[float] = None
    # The routed experts work in a narrower width: a linear projection of
    # the layer's input down to it in front of the dispatch, and one back
    # up behind the weighted sum (None: the experts read the hidden size).
    moe_latent_size: Optional[int] = None
    # A shared expert of this width beside the routed ones: an MLP every
    # token goes through, its result added to theirs (None: none).
    moe_shared_ffn_size: Optional[int] = None
    # "capacity": GShard grouped capacity dispatch (einsum, EP-shardable);
    # "dropless": sort-based dispatch over lax.ragged_dot — NO token ever
    # dropped and no dense [.., E, C] dispatch FLOPs; under ep > 1 rows
    # travel an explicit expert-axis all-to-all (moe_block_dropless_ep)
    moe_dispatch: str = "capacity"
    # Receive-buffer factor for dropless dispatch under expert
    # parallelism: each expert shard accepts up to n_local*top_k*factor
    # rows per step. None = ep (mathematically dropless for any routing,
    # the default); smaller trades FLOPs/memory (both scale with the
    # buffer) for greedy source-order drops when routing is imbalanced
    # beyond factor x fair share.
    moe_ep_buffer_factor: Optional[float] = None
    # GShard token-group size for dispatch: capacity is enforced within
    # fixed-size groups of tokens so the combine/dispatch tensors are
    # [G, Sg, E, Cg] — linear in total tokens — instead of the global
    # [N, E, C] quadratic form. 0 = auto (largest divisor of seq_length
    # <= 2048). Must divide seq_length when set.
    moe_group_size: int = 0

    # regularization
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    # LIMA per-layer linear dropout ramp (ref: transformer.py:994-1001)
    lima_dropout: bool = False

    # initialization (ref: arguments.py --init_method_std)
    init_method_std: float = 0.02
    # scale init of output-facing mats by 1/sqrt(2*num_layers)
    use_scaled_init: bool = True

    # numerics
    params_dtype: str = "bfloat16"
    # fp8 training GEMMs (ref: TransformerEngine autocast,
    # megatron/model/transformer.py:962-1043): None | "e4m3" | "hybrid"
    # (e4m3 forward, e5m2 grads). Current-scaling TPU substitution for
    # the DelayedScaling recipe — see ops/fp8.py for the design argument.
    fp8_format: Optional[str] = None
    fp8_margin: int = 0          # ref --fp8_margin: scale back-off 2^-m
    fp8_wgrad: bool = True       # ref --no_fp8_wgrad: fp32 wgrad GEMM
    # compute softmax / norms in fp32 (ref: attention_softmax_in_fp32)
    softmax_fp32: bool = True
    attn_mask_type: str = "causal"

    # chunked fused logits+cross-entropy (beyond the reference): compute
    # the LM head and CE over sequence chunks of this many tokens, each
    # chunk's gradient formed while its logits are there — the full
    # [B,S,V] logits buffer (plus its fp32 CE intermediates and gradient)
    # never lives in HBM. 0 = unchunked. Must divide seq_length.
    ce_chunk_size: int = 0

    # attention implementation: "xla" einsum path, "pallas" flash kernel
    # (falls back to xla for unsupported shapes), or "ring" context-parallel
    # ring attention (requires an ambient mesh with a "context" axis).
    attention_impl: str = "xla"

    # BERT-style extras (ref: megatron/model/bert_model.py,
    # language_model.py Embedding tokentype path)
    num_tokentypes: int = 0
    # adds pooler + binary (NSP/SOP) head + MLM transform head params
    bert_binary_head: bool = False

    # ----- derived helpers -------------------------------------------------

    @property
    def head_dim(self) -> int:
        return self.kv_channels or self.hidden_size // self.num_attention_heads

    @property
    def n_kv_heads(self) -> int:
        return self.num_kv_heads or self.num_attention_heads

    @property
    def is_glu(self) -> bool:
        return self.activation in GLU_ACTIVATIONS

    @property
    def ffn_size(self) -> int:
        if self.ffn_hidden_size is not None:
            return self.ffn_hidden_size
        if self.is_glu:
            # llama convention: 2/3 * 4h rounded up to multiple of 256
            raw = int(2 * 4 * self.hidden_size / 3)
            return 256 * ((raw + 255) // 256)
        return 4 * self.hidden_size

    @property
    def dtype(self):
        return _resolve_dtype(self.params_dtype)

    @property
    def attention_period(self) -> Tuple[AttentionKind, ...]:
        """The kinds of the layers of one period of the stack, in order;
        one entry for a model whose layers are all alike."""
        if self.attention_pattern is not None:
            return self.attention_pattern
        return (AttentionKind(
            name="sliding" if self.sliding_window_size else "full",
            sliding_window_size=self.sliding_window_size,
            rope_theta=self.rope_theta,
            rope_scaling_factor=self.rope_scaling_factor),)

    @property
    def attention_kind(self) -> AttentionKind:
        """The one kind of a model whose layers are all alike, for the
        paths that run no other (pipeline stages, the serving engines)."""
        kinds = set(self.attention_period)
        if len(kinds) > 1:
            raise NotImplementedError(
                "this path runs one kind of attention layer; the model "
                f"has {sorted(k.name for k in kinds)}")
        return self.attention_period[0]

    @property
    def layer_period(self) -> Tuple[str, ...]:
        """The types of the layers of one period of the stack, in order."""
        return self.layer_pattern or ("attention",)

    @property
    def ssm_type(self) -> Optional[str]:
        """The form of the stack's state-space layers (SSM_TYPES), None
        for a stack without."""
        return next((t for t in SSM_TYPES if t in self.layer_period), None)

    @property
    def has_ssm(self) -> bool:
        """Some layers carry a recurrent state and no keys."""
        return self.ssm_type is not None

    @property
    def single_block_layers(self) -> bool:
        """Every layer is one block behind one norm and one residual add,
        a mixer alone or a feed-forward block alone: the pattern names a
        feed-forward type (`layer_pattern`'s comment)."""
        return bool(set(self.layer_period) & set(FFN_TYPES))

    def layers_of(self, layer_type: str) -> int:
        """How many of the stack's layers are of `layer_type`."""
        period = self.layer_period
        return self.num_layers // len(period) * period.count(layer_type)

    @property
    def expert_layers(self) -> int:
        """The layers that hold experts (0 for a dense model)."""
        if self.num_experts is None:
            return 0
        return (self.layers_of("moe") if self.single_block_layers
                else self.num_layers)

    @property
    def ssm_head_dim(self) -> int:
        """P: the channels of a Mamba-2 head."""
        return self.ssm_d_inner // self.ssm_num_heads

    @property
    def ssm_conv_width(self) -> int:
        """The channels the causal convolution runs over: the inner width
        (Mamba-1), with B and C of every group behind it (Mamba-2)."""
        if self.ssm_type == "mamba2":
            return (self.ssm_d_inner
                    + 2 * self.ssm_n_groups * self.ssm_d_state)
        return self.ssm_d_inner

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.hidden_size

    @property
    def ssm_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.hidden_size // 16)

    @property
    def experts_held(self) -> Optional[int]:
        """The experts whose weights exist here; None for a dense model."""
        return self.moe_experts_held or self.num_experts

    @property
    def has_router_bias(self) -> bool:
        """The expert layers hold a selection bias (`router_bias`)."""
        return self.num_experts is not None and (
            self.moe_router_score == "sigmoid"
            or self.moe_bias_update_rate is not None)

    @property
    def balances_by_bias(self) -> bool:
        """The selection bias moves by the experts' load of each step."""
        return (self.num_experts is not None
                and self.moe_bias_update_rate is not None)

    @property
    def carries_router_state(self) -> bool:
        """The layer stack's carry holds the router's state."""
        return (self.num_experts is not None
                and self.moe_router_form == "mlp")

    @property
    def rotary_dim(self) -> int:
        """The channels of a head that rotary turns."""
        return int(self.head_dim * self.rotary_percent)

    @property
    def holds_expert_share(self) -> bool:
        """The router is wider than the experts held."""
        return (self.moe_experts_held is not None
                and self.moe_experts_held < (self.num_experts or 0))

    def validate(self) -> "ModelConfig":
        if self.position_embedding_type not in POSITION_EMBEDDING_TYPES:
            raise ValueError(f"bad position_embedding_type {self.position_embedding_type}")
        if self.normalization not in NORMALIZATION_TYPES:
            raise ValueError(f"bad normalization {self.normalization}")
        if self.activation not in ACTIVATION_TYPES:
            raise ValueError(f"bad activation {self.activation}")
        if self.attn_mask_type not in ATTN_MASK_TYPES:
            raise ValueError(f"bad attn_mask_type {self.attn_mask_type}")
        if self.attention_impl not in ATTENTION_IMPLS:
            raise ValueError(f"bad attention_impl {self.attention_impl}")
        if self.fp8_format not in (None, "e4m3", "hybrid"):
            raise ValueError(
                f"fp8_format={self.fp8_format!r} must be None, 'e4m3' or "
                "'hybrid' (ref --fp8_e4m3 / --fp8_hybrid)")
        if self.use_post_ln and self.parallel_attn:
            raise ValueError("use_post_ln is incompatible with parallel_attn")
        if self.hidden_size % self.num_attention_heads and self.kv_channels is None:
            raise ValueError("num_attention_heads must divide hidden_size")
        if self.num_attention_heads % self.n_kv_heads:
            raise ValueError("num_attention_heads must be divisible by num_kv_heads")
        if self.position_embedding_type == "absolute" and not self.max_position_embeddings:
            raise ValueError("absolute position embeddings need max_position_embeddings")
        if self.parallel_layernorm and not self.parallel_attn:
            raise ValueError("parallel_layernorm requires parallel_attn")
        if self.attention_pattern is not None:
            if not self.attention_pattern or (
                    self.num_layers % len(self.attention_pattern)):
                raise ValueError(
                    f"attention_pattern of {len(self.attention_pattern)} "
                    f"layers does not divide num_layers={self.num_layers}")
            if (self.sliding_window_size is not None
                    or self.rope_theta != 10000.0
                    or self.rope_scaling_factor != 1.0):
                raise ValueError(
                    "attention_pattern states each kind's window and "
                    "rotary table: leave sliding_window_size, rope_theta "
                    "and rope_scaling_factor at their defaults")
            for kind in self.attention_pattern:
                kind.validate()
            # one spelling a model: layers that are all alike are said by
            # the three scalars (names apart), as far as those can say them
            alike = {dataclasses.replace(k, name="")
                     for k in self.attention_pattern}
            if len(alike) == 1 and (
                    len(self.attention_pattern) > 1
                    or dataclasses.replace(
                        alike.pop(), sliding_window_size=None,
                        rope_theta=10000.0, rope_scaling_factor=1.0)
                    == AttentionKind(name="")):
                raise ValueError(
                    "attention_pattern's layers are all alike: say a "
                    "one-kind model with sliding_window_size, rope_theta "
                    "and rope_scaling_factor (or, where those cannot say "
                    "its rotary table, with a pattern of one layer)")
        if self.layer_pattern is not None:
            bad = set(self.layer_pattern) - set(LAYER_TYPES)
            if bad or not self.layer_pattern:
                raise ValueError(
                    f"layer_pattern holds {sorted(bad)}; a layer is one of "
                    f"{LAYER_TYPES}")
            if self.num_layers % len(self.layer_pattern):
                raise ValueError(
                    f"layer_pattern of {len(self.layer_pattern)} layers "
                    f"does not divide num_layers={self.num_layers}")
            if set(self.layer_pattern) == {"attention"}:
                raise ValueError(
                    "layer_pattern's layers are all attention layers: "
                    "leave it out")
            if self.attention_pattern is not None:
                raise NotImplementedError(
                    "layer_pattern with attention_pattern: a typed stack's "
                    "attention layers are of one kind")
            if self.parallel_attn or self.use_post_ln or self.fp8_format:
                raise NotImplementedError(
                    "a stack of several layer types is pre-norm and "
                    "sequential in bf16/f32: no parallel_attn, "
                    "use_post_ln or fp8_format")
            if ("moe" in self.layer_pattern) != (self.num_experts is not None):
                raise NotImplementedError(
                    "in a stack of several layer types the experts are "
                    "the \"moe\" layers' (a feed-forward block alone, "
                    "which makes every layer one block): num_experts "
                    "comes with that type in the pattern, and not beside "
                    "a pattern of mixers whose layers each hold a dense "
                    "FFN")
            if not set(self.layer_pattern) & set(MIXER_TYPES):
                raise ValueError(
                    "layer_pattern names no sequence mixer "
                    f"(one of {MIXER_TYPES})")
            if len(set(self.layer_pattern) & set(SSM_TYPES)) > 1:
                raise NotImplementedError(
                    "layer_pattern with both state-space forms: the state "
                    "store holds rows of one shape")
            if min(self.ssm_d_state, self.ssm_d_conv, self.ssm_expand,
                   self.ssm_rank) < 1:
                raise ValueError("the state-space sizes must be >= 1")
            if "mamba2" in self.layer_pattern:
                heads, groups = self.ssm_num_heads, self.ssm_n_groups
                if (not heads or groups < 1 or self.ssm_d_inner % heads
                        or heads % groups or self.ssm_chunk_size < 1):
                    raise ValueError(
                        "a \"mamba2\" layer needs ssm_num_heads that "
                        f"divide the inner width {self.ssm_d_inner}, "
                        "ssm_n_groups that divide the heads and "
                        f"ssm_chunk_size >= 1 (heads {heads}, groups "
                        f"{groups}, chunk {self.ssm_chunk_size})")
        if not 0.0 < self.rotary_percent <= 1.0 or self.rotary_dim % 2:
            raise ValueError(
                f"rotary_percent={self.rotary_percent} must leave an even "
                f"number of a head's {self.head_dim} channels to rotate")
        if self.attention_form not in ATTENTION_FORMS:
            raise ValueError(f"bad attention_form {self.attention_form!r}; "
                             f"one of {ATTENTION_FORMS}")
        if self.attention_form == "cca":
            self._validate_cca()
        if self.residual_scale and (
                self.layer_pattern is not None or self.parallel_attn
                or self.use_post_ln or self.apply_residual_post_ln):
            raise NotImplementedError(
                "residual_scale scales the two adds of a pre-norm layer of "
                "an attention block and an FFN: no layer_pattern, "
                "parallel_attn, use_post_ln or apply_residual_post_ln")
        if self.moe_experts_held is not None:
            if self.num_experts is None or self.moe_dispatch != "dropless":
                raise ValueError(
                    "moe_experts_held needs num_experts (the router's "
                    "width) and moe_dispatch='dropless'")
            shares, rest = divmod(self.num_experts, self.moe_experts_held)
            if rest or not 0 <= self.moe_expert_share < shares:
                raise ValueError(
                    f"moe_experts_held={self.moe_experts_held} must divide "
                    f"num_experts={self.num_experts}, and moe_expert_share="
                    f"{self.moe_expert_share} be one of its {shares} shares")
        if self.num_experts is not None:
            if self.num_experts < 1:
                raise ValueError("num_experts must be >= 1")
            if not 1 <= self.moe_top_k <= self.num_experts:
                raise ValueError(
                    f"moe_top_k={self.moe_top_k} must be in "
                    f"[1, num_experts={self.num_experts}]")
            if self.moe_router_score not in MOE_ROUTER_SCORES:
                raise ValueError(
                    f"moe_router_score={self.moe_router_score!r} must be "
                    f"one of {MOE_ROUTER_SCORES}")
            if ((self.moe_router_score == "sigmoid"
                 or self.moe_latent_size is not None
                 or self.moe_shared_ffn_size is not None)
                    and self.moe_dispatch != "dropless"):
                raise NotImplementedError(
                    "sigmoid router scores, moe_latent_size and "
                    "moe_shared_ffn_size are the dropless block's "
                    "(moe_dispatch='dropless')")
            if self.moe_router_form not in MOE_ROUTER_FORMS:
                raise ValueError(
                    f"moe_router_form={self.moe_router_form!r} must be "
                    f"one of {MOE_ROUTER_FORMS}")
            if (self.moe_router_form == "mlp") != (
                    self.moe_router_hidden_size is not None):
                raise ValueError(
                    "moe_router_hidden_size is the width of "
                    "moe_router_form='mlp': give both or neither")
            if (self.moe_bias_update_rate is not None
                    and self.moe_bias_update_rate <= 0):
                raise ValueError(
                    f"moe_bias_update_rate={self.moe_bias_update_rate} "
                    "must be > 0 (None: no selection bias moves)")
            for what, on in (
                    ("moe_router_form='mlp'", self.moe_router_form == "mlp"),
                    ("moe_bias_update_rate",
                     self.moe_bias_update_rate is not None)):
                if on and (self.moe_dispatch != "dropless"
                           or self.layer_pattern is not None):
                    raise NotImplementedError(
                        f"{what} hands what it carries from layer to "
                        "layer through the dropless block of a stack "
                        "whose layers are all alike: no capacity dispatch "
                        "(moe_dispatch='capacity'), no layer_pattern")
            if self.moe_dispatch not in ("capacity", "dropless"):
                raise ValueError(
                    f"moe_dispatch={self.moe_dispatch!r} must be "
                    "'capacity' or 'dropless'")
            if self.moe_group_size < 0:
                raise ValueError("moe_group_size must be >= 0")
            if (self.moe_ep_buffer_factor is not None
                    and self.moe_ep_buffer_factor <= 0):
                # <= 0 would zero every shard's receive buffer and the MoE
                # layer would silently drop every routed token (ADVICE r5
                # low #2)
                raise ValueError(
                    f"moe_ep_buffer_factor={self.moe_ep_buffer_factor} "
                    "must be > 0 (None = exact dropless)")
            if self.moe_group_size and self.seq_length % self.moe_group_size:
                raise ValueError(
                    f"moe_group_size={self.moe_group_size} must divide "
                    f"seq_length={self.seq_length}")
        if self.ce_chunk_size < 0:
            raise ValueError("ce_chunk_size must be >= 0")
        if self.ce_chunk_size and self.seq_length % self.ce_chunk_size:
            raise ValueError(
                f"ce_chunk_size={self.ce_chunk_size} must divide "
                f"seq_length={self.seq_length}")
        return self

    def refuse_serving(self) -> None:
        """Raises, by name, for what of the model trains and no serving
        path holds (the engines call it before they build anything)."""
        for what, on, why in (
                ("attention_form='cca'", self.attention_form == "cca",
                 "a slot would hold, beside its keys and values, the last "
                 "positions the convolutions and the value shift read"),
                ("moe_router_form='mlp'", self.carries_router_state,
                 "the router's state of a chunk's or a tick's positions "
                 "would have to pass from layer to layer through the "
                 "serving step")):
            if on:
                raise NotImplementedError(
                    f"serving a model with {what}: {why}; it trains "
                    "(pretrain_gpt.py) and is not served")

    def _validate_cca(self) -> None:
        k0, k1 = self.cca_conv_kernels
        if min(k0, k1) < 1 or (self.n_kv_heads * self.head_dim) % 2:
            raise ValueError(
                f"attention_form='cca' needs cca_conv_kernels >= 1 "
                f"({self.cca_conv_kernels}) and an even value width "
                f"({self.n_kv_heads} x {self.head_dim}) to shift half of")
        if (self.layer_pattern is not None or self.qk_norm
                or self.use_bias_qkv or self.fp8_format
                or self.attn_mask_type != "causal"
                or self.attention_dropout > 0):
            raise NotImplementedError(
                "attention_form='cca' is a causal attention layer of a "
                "stack whose layers are all alike, in bf16/f32 without "
                "biases: no layer_pattern, qk_norm, use_bias, fp8_format, "
                "attention_dropout or a mask other than causal")
        if self.attention_impl in ("ring", "ulysses"):
            raise NotImplementedError(
                "attention_form='cca' under context parallelism "
                f"(attention_impl={self.attention_impl!r}): the "
                "convolutions and the value shift read the previous "
                "position, which lies on another rank at a shard's start")

    # FLOPs per token for one fwd pass, used for MFU accounting
    # (ref formula: megatron/model/language_model.py:370-384).
    def flops_per_token_fwd(self, seq_length: Optional[int] = None) -> float:
        s = seq_length or self.seq_length
        h, hd = self.hidden_size, self.head_dim
        nq, nkv = self.num_attention_heads, self.n_kv_heads
        f = self.ffn_size
        per_layer = 0.0
        per_layer += 2 * h * (nq + 2 * nkv) * hd        # qkv proj
        per_layer += 2 * nq * hd * h                    # out proj
        if self.attention_form == "cca":
            # a tap a channel, then a [head_dim, head_dim] matrix a tap
            k0, k1 = self.cca_conv_kernels
            per_layer += 2 * (nq + nkv) * hd * (k0 + k1 * hd)
        mlp_in_width = f * (2 if self.is_glu else 1)
        mlp = 2 * h * mlp_in_width + 2 * f * h
        if self.num_experts is not None:
            # each token visits top_k experts, of which the share held
            # here is computed here (all of them where every expert is
            # held); the router matmul is extra, over its whole width
            mlp = (mlp * self.moe_top_k * self.experts_held
                   / self.num_experts + self._router_flops())
        per_layer += mlp
        # qk^T and av over the keys a layer's kind lets a query see (causal
        # ~ /2 but count full): s, or the window where it is shorter
        period = self.attention_period
        keys = sum(min(s, k.sliding_window_size or s) for k in period)
        per_layer += 2 * 2 * nq * hd * keys / len(period)
        total = self.num_layers * per_layer
        if self.single_block_layers:
            return self._flops_per_token_single_blocks(
                per_layer - mlp, 2 * h * mlp_in_width + 2 * f * h)
        if self.has_ssm:
            # a state-space layer has its mixer's products in place of the
            # projections and the scores
            total += self.layers_of(self.ssm_type) * (
                self._ssm_mixer_flops() - (per_layer - mlp))
        total += 2 * h * self.vocab_size                # logits
        return float(total)

    def _router_flops(self) -> float:
        """The router's operations a token, over its whole width."""
        if self.moe_router_form == "mlp":
            r = self.moe_router_hidden_size
            return 2 * self.hidden_size * r + 2 * 2 * r * r + (
                2 * r * self.num_experts)
        return 2 * self.hidden_size * self.num_experts

    def _ssm_mixer_flops(self) -> float:
        """A state-space mixer's operations a token: its projections, the
        convolution, and ~9 operations a state element."""
        h, di, n = self.hidden_size, self.ssm_d_inner, self.ssm_d_state
        conv = 2 * self.ssm_d_conv * self.ssm_conv_width
        if self.ssm_type == "mamba2":
            into = 2 * h * (di + self.ssm_conv_width + self.ssm_num_heads)
            return into + conv + 9 * di * n + 2 * di * h
        r = self.ssm_rank
        return (2 * h * 2 * di + 2 * di * (r + 2 * n) + 2 * r * di
                + 2 * di * h + conv + 9 * di * n)

    def _flops_per_token_single_blocks(self, attention: float,
                                       dense: float) -> float:
        """flops_per_token_fwd of a stack whose layers are one block each
        (`single_block_layers`): each type's layers counted as what they
        are. attention: an attention layer's mixer; dense: one MLP of
        ffn_size between hidden-size rows."""
        h = self.hidden_size
        total = (self.layers_of("attention") * attention
                 + self.layers_of("mlp") * dense)
        if self.has_ssm:
            total += self.layers_of(self.ssm_type) * self._ssm_mixer_flops()
        if self.num_experts is not None:
            width = self.moe_latent_size or h
            expert = dense * width / h
            layer = (self._router_flops() + expert * self.moe_top_k
                     * self.experts_held / self.num_experts)
            if self.moe_latent_size is not None:
                layer += 2 * 2 * h * width
            if self.moe_shared_ffn_size is not None:
                layer += dense * self.moe_shared_ffn_size / self.ffn_size
            total += self.layers_of("moe") * layer
        return float(total + 2 * h * self.vocab_size)


# ---------------------------------------------------------------------------
# parallel topology
# ---------------------------------------------------------------------------


# Fields a checkpoint's meta.json may still hold that ModelConfig no longer
# has. Exactly these are dropped on load; any other unknown key still
# raises TypeError.
RETIRED_MODEL_FIELDS = ("flash_bwd",)


def model_config_from_saved(saved: dict) -> ModelConfig:
    """ModelConfig from the "model" dict of a saved run config."""
    kept = {k: v for k, v in saved.items() if k not in RETIRED_MODEL_FIELDS}
    if kept.get("attention_pattern") is not None:
        # JSON knows no dataclass and no tuple
        kept["attention_pattern"] = tuple(
            k if isinstance(k, AttentionKind) else AttentionKind(**k)
            for k in kept["attention_pattern"])
    if kept.get("layer_pattern") is not None:
        kept["layer_pattern"] = tuple(kept["layer_pattern"])
    if "cca_conv_kernels" in kept:
        kept["cca_conv_kernels"] = tuple(kept["cca_conv_kernels"])
    return ModelConfig(**kept)


@dataclass(frozen=True)
class ParallelConfig:
    """Parallel topology over one device mesh.

    Replaces the reference's process-group builder
    (megatron/core/parallel_state.py:51-199). Mesh axis order is
    ("data", "pipe", "context", "tensor"); tensor is the fastest-varying
    axis so TP collectives ride the innermost ICI links, matching the
    reference's TP-innermost-contiguous rank layout
    (parallel_state.py:68-82 docstring).
    """

    tensor_parallel: int = 1
    pipeline_parallel: int = 1
    # context/sequence-dimension sharding with ring attention — the
    # long-context axis (beyond reference parity; ref has only
    # Korthikanti-style SP, see SURVEY.md §2.2).
    context_parallel: int = 1
    # expert parallelism: a sub-axis of data parallelism that MoE expert
    # weights shard over (E % expert_parallel == 0); dense params are
    # replicated over it and the batch shards over (data, expert), so it
    # behaves as extra DP outside MoE blocks. Decoupled from dp so the
    # expert count never constrains the data-parallel degree.
    expert_parallel: int = 1
    # data_parallel: None => derived from device count
    data_parallel: Optional[int] = None
    # Korthikanti sequence parallelism: shard residual-stream activations
    # along seq over the *tensor* axis outside matmul blocks
    # (ref: layers.py:225-236,285-296,691-692).
    sequence_parallel: bool = False
    # number of virtual-pipeline chunks per stage (interleaved 1F1B),
    # ref: schedules.py:253-502. None => non-interleaved.
    virtual_pipeline_parallel: Optional[int] = None

    def derive_data_parallel(self, n_devices: int) -> int:
        model_devices = (self.tensor_parallel * self.pipeline_parallel
                         * self.context_parallel * self.expert_parallel)
        if n_devices % model_devices:
            raise ValueError(
                f"{n_devices} devices not divisible by "
                f"tp*pp*cp*ep={model_devices}")
        dp = n_devices // model_devices
        if self.data_parallel is not None and self.data_parallel != dp:
            raise ValueError(
                f"data_parallel={self.data_parallel} inconsistent with "
                f"{n_devices} devices / (tp*pp*cp*ep={model_devices})")
        return dp

    def validate(self) -> "ParallelConfig":
        for name in ("tensor_parallel", "pipeline_parallel",
                     "context_parallel", "expert_parallel"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1")
        if self.virtual_pipeline_parallel is not None:
            if self.pipeline_parallel < 2:
                raise ValueError("interleaved schedule needs pipeline_parallel >= 2")
            if self.virtual_pipeline_parallel < 2:
                raise ValueError("virtual_pipeline_parallel must be >= 2")
        if self.sequence_parallel and self.tensor_parallel == 1:
            # ref disables SP when tp==1 (arguments.py:331-341)
            return dataclasses.replace(self, sequence_parallel=False)
        return self


# ---------------------------------------------------------------------------
# optimizer / schedule
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OptimizerConfig:
    """Adam/SGD + lr schedule + mixed-precision policy.

    Mirrors megatron/optimizer/* and megatron/optimizer_param_scheduler.py.
    fp32 master weights and fp32 grad accumulation are the default, like the
    reference's bf16 path (arguments.py: bf16 => accumulate_allreduce_grads_in_fp32).
    """

    optimizer: str = "adam"
    lr: float = 3e-4
    min_lr: float = 0.0
    lr_decay_style: str = "cosine"  # constant | linear | cosine | inverse-square-root
    lr_decay_iters: Optional[int] = None
    lr_warmup_iters: int = 0
    lr_warmup_fraction: Optional[float] = None

    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    sgd_momentum: float = 0.9
    weight_decay: float = 0.01
    # weight-decay ramp (ref: start_weight_decay/end_weight_decay/incr style)
    start_weight_decay: Optional[float] = None
    end_weight_decay: Optional[float] = None
    weight_decay_incr_style: str = "constant"  # constant | linear | cosine

    # per-group LR/WD multipliers: ((path_regex, lr_mult, wd_mult), ...) —
    # first matching pattern wins, unmatched params use (1.0, 1.0). The
    # param "group" is a path predicate over the param tree, replacing the
    # reference's torch param_groups carrying lr_mult/wd_mult
    # (ref: optimizer_param_scheduler.py:124-127, optimizer/__init__.py:16-59)
    param_group_mults: tuple = ()

    clip_grad: float = 1.0
    # ZeRO-1: shard optimizer state over the data axis
    # (ref: megatron/optimizer/distrib_optimizer.py, 700 LoC -> sharding specs)
    use_distributed_optimizer: bool = False
    # keep fp32 master params for bf16/fp16 training
    # (ref: Float16OptimizerWithFloat16Params, optimizer.py:508-563)
    fp32_master_weights: bool = True
    # dynamic loss scaling for fp16 (never needed for bf16)
    loss_scale: Optional[float] = None  # None => dynamic when fp16
    initial_loss_scale: float = 2.0**32
    min_loss_scale: float = 1.0
    loss_scale_window: int = 1000
    hysteresis: int = 2
    log_num_zeros_in_grad: bool = False


@dataclass(frozen=True)
class TrainingConfig:
    """Top-level run config: batching, duration, recompute, checkpoints.

    Mirrors the 'training' / 'checkpointing' / 'mixed precision' argument
    groups (megatron/arguments.py).
    """

    micro_batch_size: int = 1
    global_batch_size: int = 1
    # batch-size rampup: (start_batch, increment, ramp_samples)
    # (ref: megatron/microbatches.py RampupBatchsizeNumMicroBatches)
    rampup_batch_size: Optional[Tuple[int, int, int]] = None
    train_iters: Optional[int] = None
    train_samples: Optional[int] = None
    eval_interval: int = 1000
    eval_iters: int = 100
    seed: int = 1234
    # per-pipeline-stage seed offset policy (ref: initialize.py:179-193)
    seed_pipeline_offset: int = 100
    data_parallel_random_init: bool = False

    # activation recompute (ref: transformer.py:1110-1176)
    # none | selective | full | "block:N" (remat only the first N layers
    # per stack/pipeline-chunk) | "uniform:N" (chunked two-level remat,
    # sqrt-remat carry storage) — ref --recompute_method +
    # --recompute_num_layers, transformer.py:1110-1172
    recompute_granularity: str = "none"

    # checkpointing
    save: Optional[str] = None
    load: Optional[str] = None
    save_interval: Optional[int] = None
    exit_interval: Optional[int] = None
    exit_duration_in_mins: Optional[int] = None
    finetune: bool = False
    no_load_optim: bool = False
    no_load_rng: bool = False
    # overlap checkpoint serialization/writes with training compute
    # (training/checkpointing.py AsyncCheckpointSaver); --no_async_save
    # falls back to blocking saves
    async_save: bool = True
    # retention: keep only the newest K committed checkpoints (staging dirs
    # and whatever the tracker points at are never pruned); None = keep all
    keep_latest_k: Optional[int] = None

    # async goodput loop (training/prefetch.py + the lagged-metrics train
    # loop; docs/performance.md "Async goodput loop"). --no_async_loop
    # restores the fully synchronous loop — it stays the differential-test
    # oracle: loss curves are bitwise-identical between the two.
    async_loop: bool = True
    # bounded device-side double-buffer depth of the background batch
    # prefetcher (>=1 when async_loop; 0 keeps host->device placement on
    # the critical path even with the async loop on)
    prefetch_depth: int = 2
    # fetch step metrics (loss/lr/grad_norm) K steps late so dispatch of
    # the next step overlaps the current one; the divergence sentinel,
    # logger, goodput accounting and flight-recorder heartbeat all consume
    # the lagged stream (sentinel trip latency grows by K — bounded; the
    # rollback discards the in-flight steps, docs/fault_tolerance.md)
    metrics_lag: int = 1
    # persistent XLA compilation cache directory
    # (jax_compilation_cache_dir): crash-resume restarts and re-runs pay
    # the goodput `compile` bucket once; cache hits surface in step
    # records and the recompile tracker
    compilation_cache_dir: Optional[str] = None

    # divergence sentinel (training/resilience.py): abort — or roll back,
    # with rollback_on_divergence — after this many CONSECUTIVE
    # non-finite/skipped optimizer steps; 0 disables
    divergence_patience: int = 100
    # trip when the loss exceeds factor * EMA for loss_spike_patience
    # consecutive steps; 0.0 disables spike detection
    loss_spike_factor: float = 0.0
    loss_spike_patience: int = 5
    # on sentinel trip: reload the newest valid checkpoint and fast-forward
    # the data past the poison window instead of aborting
    rollback_on_divergence: bool = False
    # give up (DivergenceError) after this many rollbacks — a model that
    # re-diverges every time is genuinely diverging, not unlucky
    max_rollbacks: int = 3

    # preemption + elastic resume + sentinels
    # (docs/fault_tolerance.md "Preemption and elastic resume"):
    # deadline on the expedited SIGTERM-notice checkpoint — the first
    # SIGTERM drains the async pipeline and forces a SYNCHRONOUS
    # committed save (bypassing --save_interval); if the commit misses
    # this many seconds the process force-exits
    # resilience.PREEMPT_TIMEOUT_EXIT_CODE instead of overstaying the
    # notice window. 0 disables the deadline (wait however long).
    preempt_save_timeout: float = 600.0
    # step-deadline hang watchdog (training/resilience.py StepWatchdog):
    # if no step completes for this many seconds, dump a flight-recorder
    # bundle, journal `hang_detected`, and abort cleanly with
    # resilience.HANG_EXIT_CODE instead of hanging until the scheduler's
    # timeout kill destroys the evidence. Must exceed the longest
    # legitimate heartbeat gap (a step + the worst eval/save stall).
    # 0 disables.
    step_timeout_s: float = 0.0
    # opt-in silent-data-corruption sentinel: every N steps re-run the
    # jitted train step on the retained (state, batch) and compare the
    # committed outputs BITWISE; a mismatch journals `sdc_detected` with
    # the leaf paths and aborts (resilience.SDCError). Costs one state
    # copy + one extra step per check. 0 disables.
    replay_check_interval: int = 0
    # journal a crc32 fingerprint of every host batch (`data_crc` on step
    # records) — the sample-identity evidence elastic-resume tests diff
    # across topologies; negligible cost, off by default
    log_data_fingerprint: bool = False

    # multi-host coordination (training/coordination.py;
    # docs/fault_tolerance.md "Multi-host coordination"): shared directory
    # for the file-backed agreement seam — signal agreement, peer-death
    # poison records, two-phase checkpoint commit, restart barrier.
    # None + jax.process_count() > 1 selects the jax.distributed KV-store
    # backend automatically; None single-process disables coordination
    # entirely (byte-identical single-host behavior).
    coordination_dir: Optional[str] = None
    # declare a peer dead after this many seconds without a heartbeat (or
    # immediately on its poison record); survivors exit
    # resilience.PEER_ABORT_EXIT_CODE with `peer_abort` journaled instead
    # of wedging in the next collective. 0 disables peer-death detection
    # (poison records still observed).
    peer_death_timeout_s: float = 60.0

    # --save_interval auto: derive the checkpoint cadence from measured
    # commit latency (save_interval ~= (preempt grace - p95 commit) /
    # p50 step), re-derived as measurements accrue and journaled as
    # `cadence_retune` on every change (resilience.CheckpointCadenceTuner)
    save_interval_auto: bool = False
    # lower clamp on the autotuned cadence, in steps
    save_interval_floor: int = 25

    # logging
    log_interval: int = 100
    tensorboard_dir: Optional[str] = None
    wandb_logger: bool = False
    wandb_project: str = "megatron_tpu"
    wandb_name: Optional[str] = None
    timing_log_level: int = 0
    # per-span wall-clock to the writer each log_interval
    # (ref --log_timers_to_tensorboard, training.py:500-525)
    log_timers_to_tensorboard: bool = False
    # opt-in jax.profiler trace window — the TPU-native deep-profiling
    # story (where the reference reaches for nsys/nvtx): traces device +
    # host activity for iterations [profile_step_start, profile_step_end)
    # into profile_dir (default: tensorboard_dir)
    profile: bool = False
    profile_step_start: int = 10
    profile_step_end: int = 12
    profile_dir: Optional[str] = None
    # SIGUSR1 mid-run arms a bounded trace window of this many steps —
    # on-demand incident profiling with no restart and no --profile
    # (docs/observability.md "Runtime traces")
    profile_signal_steps: int = 2

    # telemetry (megatron_tpu/telemetry; docs/observability.md):
    # structured event journal (per-step records, goodput ledger,
    # checkpoint/rollback/fault events) written as append-only JSONL under
    # this dir; None disables
    telemetry_dir: Optional[str] = None
    # journal rotation threshold (segments beyond the live file + 2 are
    # dropped, so disk stays bounded on unbounded runs); 0 disables
    # rotation (one unbounded file, e.g. under an external log shipper)
    journal_max_mb: float = 64.0
    # sidecar Prometheus /metrics listener for the train loop (the serving
    # server mounts /metrics on its own port); None disables, 0 binds a
    # free port
    metrics_port: Optional[int] = None
    # flight recorder: watchdog armed by a per-step heartbeat that dumps
    # all-thread stacks + the journal tail to a bundle when a step stalls
    # past the deadline, then optionally SIGABRTs so the supervisor
    # restarts the process with the evidence on disk
    flight_recorder: bool = False
    flight_recorder_deadline_s: float = 600.0
    flight_recorder_abort: bool = False

    # run only the validation loop, then exit (ref --eval_only)
    eval_only: bool = False

    # iterations whose update is skipped — crude fault injection
    # (ref --skip_iters, training.py:397-425)
    skip_iters: tuple = ()

    # extra per-log-interval scalars (ref --log_params_norm,
    # --log_memory_to_tensorboard)
    log_params_norm: bool = False
    log_memory: bool = False
    log_batch_size: bool = False
    log_world_size: bool = False

    # loss averaging for instruction tuning (ref finetune.py scalar_loss_mask)
    scalar_loss_mask: float = 0.0
    variable_seq_lengths: bool = False
    # validation metrics registry names (ref: --metrics, megatron/metrics.py)
    metrics: Tuple[str, ...] = ()

    def num_microbatches(self, global_batch: Optional[int], data_parallel: int) -> int:
        gbs = global_batch or self.global_batch_size
        denom = self.micro_batch_size * data_parallel
        if gbs % denom:
            raise ValueError(
                f"global batch {gbs} not divisible by micro_batch*dp={denom}")
        return gbs // denom

    def validate(self) -> "TrainingConfig":
        g = self.recompute_granularity
        if g.startswith(("block:", "uniform:")):
            kind = g.split(":", 1)[0]
            try:
                n = int(g.split(":", 1)[1])
                ok = n >= (1 if kind == "uniform" else 0)
            except ValueError:
                ok = False
            if not ok:
                raise ValueError(
                    f"bad recompute_granularity {g!r} — form is "
                    f"'{kind}:<N>' with N a "
                    + ("positive chunk size" if kind == "uniform"
                       else "non-negative layer count"))
        elif g not in RECOMPUTE_POLICIES:
            raise ValueError(f"bad recompute_granularity {g}")
        if self.flight_recorder and self.flight_recorder_deadline_s <= 0:
            raise ValueError(
                f"flight_recorder_deadline_s="
                f"{self.flight_recorder_deadline_s} must be > 0 (seconds "
                "without a step heartbeat before the stall bundle dumps)")
        if self.journal_max_mb < 0:
            raise ValueError(
                "journal_max_mb must be >= 0 (0 disables rotation: one "
                "unbounded journal file)")
        if self.prefetch_depth < 0:
            raise ValueError(
                "prefetch_depth must be >= 0 (0 disables the background "
                "prefetcher; use --no_async_loop for the fully "
                "synchronous loop)")
        if self.metrics_lag < 0:
            raise ValueError(
                "metrics_lag must be >= 0 (0 fetches metrics inside each "
                "step, the synchronous behavior)")
        if self.preempt_save_timeout < 0:
            raise ValueError(
                "preempt_save_timeout must be >= 0 seconds (0 disables "
                "the preemption-save deadline)")
        if self.step_timeout_s < 0:
            raise ValueError(
                "step_timeout_s must be >= 0 seconds (0 disables the "
                "step-deadline hang watchdog)")
        if self.replay_check_interval < 0:
            raise ValueError(
                "replay_check_interval must be >= 0 steps (0 disables "
                "the SDC replay check)")
        if self.peer_death_timeout_s < 0:
            raise ValueError(
                "peer_death_timeout_s must be >= 0 seconds (0 disables "
                "heartbeat-based peer-death detection)")
        if self.save_interval_auto and self.save_interval is not None:
            raise ValueError(
                "--save_interval auto and a fixed --save_interval are "
                "mutually exclusive")
        if self.save_interval_auto and not self.preempt_save_timeout:
            raise ValueError(
                "--save_interval auto derives the cadence from the "
                "--preempt_save_timeout grace window; set a positive one")
        if self.save_interval_floor < 1:
            raise ValueError("save_interval_floor must be >= 1 step")
        if self.train_iters is None and self.train_samples is None:
            pass  # inference / tooling use
        return self


# ---------------------------------------------------------------------------
# convenience bundle
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    training: TrainingConfig = field(default_factory=TrainingConfig)

    def validate(self) -> "RunConfig":
        self.model.validate()
        self.parallel.validate()
        self.training.validate()
        self._refuse_unbuilt_sharding()
        return self

    def _refuse_unbuilt_sharding(self) -> None:
        """What of the model no sharded path holds yet, by name."""
        m, p = self.model, self.parallel
        sharded = [name for name in (
            "tensor_parallel", "pipeline_parallel", "context_parallel",
            "expert_parallel") if getattr(p, name) > 1]
        if not sharded:
            return
        for what, on in (
                ("attention_form='cca'", m.attention_form == "cca"),
                ("moe_router_form='mlp'", m.carries_router_state),
                ("moe_bias_update_rate", m.balances_by_bias)):
            if on:
                raise NotImplementedError(
                    f"{what} under {', '.join(sharded)} > 1: its leaves "
                    "and the state it carries are replicated and its "
                    "sums are one chip's; it trains under data "
                    "parallelism alone")

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "RunConfig":
        return RunConfig(
            model=model_config_from_saved(d["model"]),
            parallel=ParallelConfig(**d["parallel"]),
            optimizer=OptimizerConfig(**d["optimizer"]),
            training=TrainingConfig(**{k: (tuple(v) if k == "rampup_batch_size" and v else v)
                                       for k, v in d["training"].items()}),
        )
