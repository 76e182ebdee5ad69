"""The names the train step carries for a device trace (docs/
observability.md "Runtime traces"): `jax.named_scope` on the model's
regions and on the flash kernels. A trace reader finds an operation's
region and kernel as tokens of its `op_name` (on a TPU: the `tf_op` of the
event's metadata), so what is held here is that every name arrives in the
compiled step, wherever differentiation, recomputation and the mesh's
`shard_map` put the operation."""

import dataclasses
import re

import pytest

from megatron_tpu.analysis import targets
from megatron_tpu.telemetry.tracing.events import (
    REGION_SCOPES, kernel_of, op_class, scope_tokens,
)

TRAIN_KERNELS = ("flash_fwd", "flash_bwd", "flash_bwd_stats")
# the parts of the two layer regions (models/transformer.py), the layers'
# scan (models/language_model.py) and the micro-batch loop (train_step.py)
SUB_SCOPES = {"attention": ("attn_norm", "attn_qkv", "attn_rope",
                            "attn_core", "attn_out"),
              "mlp": ("mlp_norm", "mlp_in", "mlp_act", "mlp_out")}
LOOP_SCOPES = ("micro_batches", "layer_stack")


def _op_names(parallel, zero1, recompute):
    """Every op_name of the toy train step, compiled for the CPU mesh with
    the flash kernels dispatched (interpreted)."""
    t = targets.train_step_target(
        "named", parallel, zero1=zero1, recompute=recompute,
        model_overrides={"attention_impl": "pallas"})
    t = dataclasses.replace(t, env={"MEGATRON_TPU_FLASH_INTERPRET": "1"})
    found = set(re.findall(r'op_name="([^"]+)"', t.compiled_text()))
    # whole name stacks only: interpreted, a kernel's inner loops are
    # computations of their own whose stacks start at the kernel
    return sorted(n for n in found if n.startswith("jit("))


@pytest.mark.parametrize("recompute", ["full", "selective"])
@pytest.mark.parametrize("parallel, zero1", [
    ({}, False),
    ({"tensor_parallel": 2, "sequence_parallel": True}, True),
], ids=["one_replica_per_device", "tp2_sp_zero1"])
def test_the_compiled_step_holds_every_scope(parallel, zero1, recompute):
    names = _op_names(parallel, zero1, recompute)
    stacks = [(n, scope_tokens(n)) for n in names]
    for scope in REGION_SCOPES:
        assert any(scope in toks for _n, toks in stacks), scope
    # each kernel sits under `attention`, never beside it
    for kernel in TRAIN_KERNELS:
        under = [toks for _n, toks in stacks if kernel in toks]
        assert under, kernel
        assert all("attention" in toks[:toks.index(kernel)]
                   for toks in under), kernel
    # forward, backward and recomputation keep the names: the forward
    # kernel runs under jvp, the fused backward kernel and the kernel
    # of its row statistics under the transpose. Under `full` the forward runs again as rematted
    # computation; under `selective` the layer's checkpoint keeps its
    # output and log-sum-exp, and no flash forward is computed twice
    fwd = [n for n, toks in stacks if "flash_fwd" in toks]
    assert any("jvp(" in n and "rematted_computation" not in n for n in fwd)
    assert any("rematted_computation" in n for n in fwd) == (
        recompute == "full")
    for kernel in TRAIN_KERNELS[1:]:
        assert all("transpose(" in n for n, toks in stacks
                   if kernel in toks), kernel
    # no operation is under two regions at once, nor under a kernel of
    # the serving path
    for n, toks in stacks:
        assert len({t for t in toks if t in REGION_SCOPES}) <= 1, n
        assert not {"flash_decode", "paged_flash_decode"} & set(toks), n
    # a kernel is found by a rule, not by a list: the part in front of a
    # stack's closing `pallas_call` (tests/test_chip_compile.py holds it
    # on the chip compiler's own stacks; interpreted, here, the kernel's
    # body stands where the call would)
    for kernel in TRAIN_KERNELS:
        assert kernel_of(next(toks[:toks.index(kernel) + 1] for _n, toks
                              in stacks if kernel in toks)
                         + ["pallas_call"]) == kernel
    # the parts of a region sit inside it and nowhere else, on forward,
    # transposed and recomputed operations alike; the kernels are part of
    # `attn_core`; a matmul of a region is in one of its parts
    for region, parts in SUB_SCOPES.items():
        for part in parts:
            under = [(n, toks) for n, toks in stacks if part in toks]
            assert under, part
            assert all(region in toks[:toks.index(part)]
                       for _n, toks in under), part
            assert any("transpose(" in n for n, _toks in under), part
        for n, toks in stacks:
            if region in toks and "dot_general" in toks:
                assert set(parts) & set(toks), n
    for kernel in TRAIN_KERNELS:
        assert all("attn_core" in toks[:toks.index(kernel)]
                   for _n, toks in stacks if kernel in toks), kernel
    # the loops carry their names, and every layer region is inside both
    for scope in LOOP_SCOPES:
        assert any(scope in toks for _n, toks in stacks), scope
    for _n, toks in stacks:
        if {"attention", "mlp"} & set(toks):
            region = next(t for t in toks if t in ("attention", "mlp"))
            assert toks.index("micro_batches") < toks.index(
                "layer_stack") < toks.index(region), toks
    # the scan's own work (slicing the stacked weights, stacking what the
    # backward pass saved) is under `layer_stack` and under no region
    assert any("layer_stack" in toks and not set(REGION_SCOPES) & set(toks)
               for _n, toks in stacks)
    # the matmuls that hold the time fall under the layer that owns them
    owners = {next((t for t in toks if t in REGION_SCOPES), None)
              for _n, toks in stacks if "dot_general" in toks}
    assert {"attention", "mlp", "head_loss"} <= owners


@pytest.mark.parametrize("text, want", [
    ("jit(train_step)/while/body/closed_call/transpose(jvp(attention))/"
     "flash_bwd/pallas_call:",
     ["train_step", "while", "body", "closed_call", "attention",
      "flash_bwd", "pallas_call"]),
    ("jit(f)/jvp(head_loss)/bsh,hv->bsv/dot_general",
     ["f", "head_loss", "bsh,hv->bsv", "dot_general"]),
    ("", [""]),
    (None, [""]),
])
def test_scope_tokens_strip_the_wrappers(text, want):
    assert scope_tokens(text) == want
    # the rule that finds a kernel: only a stack that closes in the call
    assert kernel_of(want) == ("flash_bwd" if want[-1] == "pallas_call"
                               else None)


# --- a stack with state-space layers (ops/ssm.py) ---------------------------------

SSM_SCOPES = ("ssm_in", "ssm_conv", "ssm_proj", "ssm_scan", "ssm_out")


def _served_op_names(monkeypatch, positions):
    """Every op_name of a toy typed stack's served forward pass (one
    prefill chunk of `positions`, or one decode step over 2 slots where
    it is 1), compiled for the CPU with the kernels dispatched
    (interpreted)."""
    import jax
    import jax.numpy as jnp

    from megatron_tpu.config import ModelConfig
    from megatron_tpu.models.language_model import lm_forward
    from megatron_tpu.models.params import param_shapes
    from megatron_tpu.ops import kv_store, ssm

    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")
    cfg = ModelConfig(
        num_layers=4, hidden_size=32, num_attention_heads=4, num_kv_heads=1,
        vocab_size=64, seq_length=32, ffn_hidden_size=48,
        position_embedding_type="none", tie_embed_logits=True,
        params_dtype="float32", attention_impl="pallas",
        layer_pattern=("mamba", "attention"), ssm_d_state=4,
        ssm_inner_norms=True).validate()
    rows = 2 if positions == 1 else 1

    def step(params, kv, state, table, tokens, lengths):
        return lm_forward(
            cfg, params, tokens, kv_caches=kv, page_table=table,
            cache_index=lengths if positions == 1 else lengths[0],
            ssm_state=state, state_row=None if positions == 1 else 0,
            state_valid=jnp.ones((rows,), jnp.int32))

    args = (param_shapes(cfg),
            jax.eval_shape(lambda: kv_store.create(cfg, 9, 8)),
            jax.eval_shape(lambda: ssm.create_state(cfg, 2)),
            jax.ShapeDtypeStruct((rows, 4), jnp.int32),
            jax.ShapeDtypeStruct((rows, positions), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32))
    text = jax.jit(step).lower(*args).compile().as_text()
    found = set(re.findall(r'op_name="([^"]+)"', text))
    return [scope_tokens(n) for n in sorted(found) if n.startswith("jit(")]


@pytest.mark.parametrize("positions", [16, 1], ids=["chunk", "decode"])
def test_a_state_space_layer_runs_named_under_attention(monkeypatch,
                                                        positions):
    """The mixer is its layer's sequence mixer: every operation of it is
    under the region `attention` and the scope `ssm_mixer`, in one of its
    five parts; the prefill chunk's scan is the kernel `ssm_scan`, the
    decode step's XLA's own fusion; the attention layers of the same
    stack keep their `attn_*` parts; the loop is `layer_stack`."""
    stacks = _served_op_names(monkeypatch, positions)
    mixer = [t for t in stacks if "ssm_mixer" in t]
    assert mixer
    for toks in mixer:
        at = toks.index("ssm_mixer")
        assert "attention" in toks[:at] and "layer_stack" in toks[:at]
        assert set(toks[at + 1:]) & set(SSM_SCOPES), toks
    for scope in SSM_SCOPES:
        assert any(scope in toks for toks in mixer), scope
    # the kernel's own scope stands inside the part of that name
    # (interpreted, its operations carry the scope and no `pallas_call`)
    assert any(t.count("ssm_scan") > 1 for t in mixer) == (positions > 1)
    for scope in ("attn_qkv", "attn_core", "attn_out"):
        under = [t for t in stacks if scope in t]
        assert under and not any("ssm_mixer" in t for t in under), scope
    assert not any("attn_rope" in t for t in stacks)   # no positions
    # the attention layers' kernel over the pages (the chunk's
    # instantiation of the decode loop for a chunk, the decode kernel for
    # a step) stands under `attention/attn_core`; a reader finds it by
    # the rule and books its time as class `kernel`
    kernel = "paged_flash_chunk" if positions > 1 else "paged_flash_decode"
    under = [t for t in stacks if kernel in t]
    assert under
    for toks in under:
        at = toks.index(kernel)
        assert "attention" in toks[:at] and "attn_core" in toks[:at], toks
        assert toks.index("attention") < toks.index("attn_core")
        assert "ssm_mixer" not in toks
        assert kernel_of(toks[:at + 1] + ["pallas_call"]) == kernel
    assert op_class("paged_flash_chunk.3", "custom-call",
                    kernel=True) == "kernel"
