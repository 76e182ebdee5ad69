"""Audit targets: the repo's real jitted programs, traced on CPU.

Each builder returns an :class:`AuditTarget` whose ``jaxpr()`` /
``lowered()`` / ``compiled_text()`` feed the jaxpr auditor, the
donation audit, and the HLO collective counter. Everything runs on the
8-device fake CPU mesh (tests/conftest.py) — no chip needed; geometry
is pinned tiny so contract manifests stay byte-stable.

The train-step targets build a real TrainLoop (the same construction
tier-1's parallel-matrix tests exercise) so the audited program IS the
production step — pipeline schedule, ZeRO-1 placement, donation and
all — not a lookalike.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import (
    ModelConfig, OptimizerConfig, ParallelConfig, RunConfig, TrainingConfig,
)


@dataclasses.dataclass
class AuditTarget:
    """A traceable program plus the arguments to trace it with."""

    name: str
    fn: Callable                 # already-jitted or plain callable
    args: tuple                  # ShapeDtypeStructs (sharded where needed)
    mesh: Optional[Any] = None   # entered (set_mesh) around trace/lower
    can_compile: bool = True     # False: old-XLA paths that CHECK-crash
    env: Optional[Dict[str, str]] = None  # env vars set around trace/lower

    def _scope(self):
        import contextlib
        import os
        import unittest.mock

        stack = contextlib.ExitStack()
        if self.mesh is not None:
            stack.enter_context(jax.sharding.set_mesh(self.mesh))
        if self.env:
            # trace-time dispatch switches (e.g. MEGATRON_TPU_FLASH_INTERPRET
            # routes attention through the pallas template on a CPU host)
            stack.enter_context(
                unittest.mock.patch.dict(os.environ, self.env))
        return stack

    def jaxpr(self):
        with self._scope():
            return jax.make_jaxpr(lambda *a: self.fn(*a))(*self.args)

    def lowered(self):
        fn = self.fn
        if not hasattr(fn, "lower"):
            fn = jax.jit(fn)
        with self._scope():
            return fn.lower(*self.args)

    def compiled_text(self) -> str:
        if not self.can_compile:
            raise RuntimeError(
                f"{self.name}: marked can_compile=False (its compile "
                "crashed the XLA of jax 0.4.37; not re-tried on jax 0.9, "
                "ROADMAP D9); jaxpr-level audit only")
        with self._scope():
            return self.lowered().compile().as_text()


def tiny_model(**overrides) -> ModelConfig:
    """The pinned contract geometry (matches the parallel-matrix tests)."""
    kw: Dict[str, Any] = dict(
        num_layers=4, hidden_size=32, num_attention_heads=4, num_kv_heads=2,
        ffn_hidden_size=64, vocab_size=128, seq_length=32,
        params_dtype="float32")
    kw.update(overrides)
    return ModelConfig(**kw).validate()


def _sds(tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------


def train_step_target(name: str, parallel_kwargs: Dict[str, Any],
                      zero1: bool = False,
                      model_overrides: Optional[Dict[str, Any]] = None,
                      global_batch: int = 8,
                      recompute: str = "full") -> AuditTarget:
    """The production train step: a real TrainLoop's jitted step lowered
    on ShapeDtypeStructs (state donated, batch sharded like _put_batch)."""
    from megatron_tpu.training.pretrain import TrainLoop

    cfg = RunConfig(
        model=tiny_model(**(model_overrides or {})),
        parallel=ParallelConfig(**parallel_kwargs),
        optimizer=OptimizerConfig(lr=1e-3, lr_decay_style="constant",
                                  use_distributed_optimizer=zero1),
        training=TrainingConfig(micro_batch_size=1,
                                global_batch_size=global_batch,
                                train_iters=2, log_interval=1,
                                recompute_granularity=recompute))
    loop = TrainLoop(cfg, log=lambda s: None)
    n_micro = max(global_batch // (1 * loop.rt.dp), 1)
    step = loop._train_step_for(n_micro)
    seq = cfg.model.seq_length
    batch = {
        "tokens": jax.ShapeDtypeStruct((global_batch, seq), jnp.int64,
                                       sharding=loop.batch_sharding),
        "labels": jax.ShapeDtypeStruct((global_batch, seq), jnp.int64,
                                       sharding=loop.batch_sharding),
        "loss_mask": jax.ShapeDtypeStruct((global_batch, seq), jnp.float32,
                                          sharding=loop.batch_sharding),
    }
    state = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        loop.state, loop.state_shardings)
    return AuditTarget(name=name, fn=step, args=(state, batch),
                       mesh=loop.rt.mesh)


def flash_bwd_train_step_target(
        name: str = "train_flash_bwd") -> AuditTarget:
    """The production train step with attention routed through the flash
    template (ops/pallas/flash_template.py): interpret mode is forced via
    the env knob so the CPU host traces the REAL kernel dispatch, and the
    audited gradient path is the custom-vjp recompute backward — the
    pallas calls sit visibly in the jaxpr (asserted in
    tests/test_analysis.py) instead of
    an XLA-generated O(S^2) attention gradient. Not part of
    contracts.CONFIGS: pallas_call bodies hide their innards from the
    jaxpr collective walk, so the golden-manifest ledger keeps auditing
    the einsum form (identical collective structure — attention is
    collective-free at dp=1)."""
    t = train_step_target(
        name, {}, model_overrides={"attention_impl": "pallas"})
    return dataclasses.replace(
        t, env={"MEGATRON_TPU_FLASH_INTERPRET": "1"})


# ---------------------------------------------------------------------------
# engine decode step
# ---------------------------------------------------------------------------


def paged_decode_step_target(name: str = "decode_paged",
                             dtype: str = "bfloat16",
                             num_slots: int = 4) -> AuditTarget:
    """The serving engine's batched decode step (page-table KV gather +
    per-slot lengths). The contract: ZERO collectives, zero host
    callbacks, full cache donation — a hidden all_gather or callback in
    serving fails here. Donation is forced on (the TPU configuration) so
    the audit checks the shipped intent even though XLA:CPU would ignore
    it at execution time."""
    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.models.params import init_params

    cfg = tiny_model(params_dtype=dtype)
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(cfg, params, num_slots=num_slots,
                          max_seq_len=cfg.seq_length, page_size=8,
                          prefill_chunk=16, force_donate=True)
    N = num_slots
    args = (
        _sds(params),
        _sds(eng.caches),
        eng.state,                                  # no state-space layers
        jax.ShapeDtypeStruct((N, eng.max_pages), jnp.int32),  # page table
        jax.ShapeDtypeStruct((N,), jnp.int32),      # last_tok
        jax.ShapeDtypeStruct((N,), jnp.int32),      # lengths
        jax.ShapeDtypeStruct((N, 2), jnp.uint32),   # keys
        jax.ShapeDtypeStruct((N,), jnp.float32),    # temps
        jax.ShapeDtypeStruct((N,), jnp.int32),      # top_ks
        jax.ShapeDtypeStruct((N,), jnp.float32),    # top_ps
    )
    return AuditTarget(name=name, fn=eng._decode_step, args=args)


def tp_decode_step_target(name: str = "decode_tp2_dense",
                          mode: str = "dense", tp: int = 2,
                          num_slots: int = 4) -> AuditTarget:
    """The serving engine's decode step on a tensor-parallel mesh with
    EXPLICIT collectives (quant/collectives.py): per-layer attn_out /
    mlp_out row-parallel reductions + the vocab-parallel logits gather
    run as shard_map collectives the jaxpr auditor can SEE (GSPMD's
    inserted all-reduces only exist at HLO level).

    mode "dense" pins the full-precision baseline ledger; "int8"/"fp8"
    pin the compressed transport — the manifest pair is the contract-
    verified byte reduction (contracts.COMPRESSION_GATES: >= 3x wire
    bytes). Geometry stays at the pinned fp32 contract dtype, like the
    ring/ulysses op targets: the ratio measured is f32-dense vs
    quantized+scales at tp=2."""
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.models.params import init_params, param_specs
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import shard_tree

    cfg = tiny_model()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = build_mesh(ParallelConfig(tensor_parallel=tp),
                    devices=jax.devices()[:tp])
    sparams = shard_tree(rt, params, param_specs(cfg))
    eng = InferenceEngine(cfg, sparams, num_slots=num_slots,
                          max_seq_len=cfg.seq_length, mesh=rt.mesh,
                          force_donate=True, compress_collectives=mode)
    N = num_slots
    args = (
        _sds(sparams),
        _sds(eng.caches),
        eng.state,                                  # no state-space layers
        jax.ShapeDtypeStruct((N, eng.max_pages), jnp.int32),  # page table
        jax.ShapeDtypeStruct((N,), jnp.int32),      # last_tok
        jax.ShapeDtypeStruct((N,), jnp.int32),      # lengths
        jax.ShapeDtypeStruct((N, 2), jnp.uint32),   # keys
        jax.ShapeDtypeStruct((N,), jnp.float32),    # temps
        jax.ShapeDtypeStruct((N,), jnp.int32),      # top_ks
        jax.ShapeDtypeStruct((N,), jnp.float32),    # top_ps
    )
    return AuditTarget(name=name, fn=eng._decode_step, args=args,
                       mesh=rt.mesh)


def cp_paged_decode_step_target(name: str = "decode_tp2_cp2",
                                tp: int = 2, cp: int = 2,
                                num_slots: int = 4,
                                geometry: str = "ring",
                                subgroup: int = 0,
                                overlap: bool = True) -> AuditTarget:
    """The context-parallel serving engine's batched decode step on a
    TP x CP mesh: per-layer ring attention over the sequence-striped
    page pools — (cp-1) ppermute hops per layer moving the normalized
    (out, lse) partials — composed with the explicit TP collectives
    (attn_out/mlp_out psum + the vocab-parallel logits all_gather).
    The manifest is the dense CP ring ledger the compressed cp_ring
    policy diffs against.

    geometry/subgroup/overlap pin the topology-aware variants:
    `decode_cp2_overlap` (flat ring, double-buffered hop schedule —
    its ledger must EQUAL the serial ring's, proving the overlap moves
    no extra bytes) and `decode_cp4_2d` (cp = cp_seq x cp_head: head
    all-to-all + all_gather inside each subgroup, ppermute hops only
    across subgroups at 1/subgroup payload). jaxpr-only: like moe_ep2,
    compiling the full-manual shard_map output back into GSPMD context
    RET_CHECK-crashed the XLA of jax 0.4.37, so can_compile=False (not
    re-tried on jax 0.9 — ROADMAP D9)."""
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.inference.context_parallel import ContextParallelEngine
    from megatron_tpu.models.params import init_params, param_specs
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import shard_tree

    cfg = tiny_model()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = build_mesh(ParallelConfig(tensor_parallel=tp, context_parallel=cp),
                    devices=jax.devices()[:tp * cp])
    sparams = shard_tree(rt, params, param_specs(cfg))
    eng = ContextParallelEngine(
        cfg, sparams, num_slots=num_slots, max_seq_len=cfg.seq_length,
        page_size=8, prefill_chunk=16, mesh=rt.mesh, force_donate=True,
        compress_collectives="dense", cp_collectives="dense",
        cp_geometry=geometry, cp_subgroup=subgroup, cp_overlap=overlap)
    N = num_slots
    args = (
        _sds(sparams),
        _sds(eng.caches),
        eng.state,                                  # no state-space layers
        jax.ShapeDtypeStruct((cp, N, eng._mpl), jnp.int32),  # local tables
        jax.ShapeDtypeStruct((N,), jnp.int32),      # last_tok
        jax.ShapeDtypeStruct((N,), jnp.int32),      # lengths
        jax.ShapeDtypeStruct((N, 2), jnp.uint32),   # keys
        jax.ShapeDtypeStruct((N,), jnp.float32),    # temps
        jax.ShapeDtypeStruct((N,), jnp.int32),      # top_ks
        jax.ShapeDtypeStruct((N,), jnp.float32),    # top_ps
    )
    return AuditTarget(name=name, fn=eng._decode_step, args=args,
                       mesh=rt.mesh, can_compile=False)


def cp_chunk_step_target(name: str = "prefill_cp2",
                         cp: int = 2) -> AuditTarget:
    """The context-parallel chunked-prefill step at cp=2 (tp=1): one
    [1, C] chunk of one prompt scatter-written into the striped pools
    and ring-attended — the distributed-prefill half of the CP serving
    ledger. Same jaxpr-only caveat as decode_tp2_cp2."""
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.inference.context_parallel import ContextParallelEngine
    from megatron_tpu.models.params import init_params, param_specs
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import shard_tree

    cfg = tiny_model()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rt = build_mesh(ParallelConfig(context_parallel=cp),
                    devices=jax.devices()[:cp])
    sparams = shard_tree(rt, params, param_specs(cfg))
    eng = ContextParallelEngine(
        cfg, sparams, num_slots=4, max_seq_len=cfg.seq_length,
        page_size=8, prefill_chunk=16, mesh=rt.mesh, force_donate=True,
        cp_collectives="dense")
    C = eng.prefill_chunk
    args = (
        _sds(sparams),
        _sds(eng.caches),
        eng.state,                                  # no state-space layers
        jax.ShapeDtypeStruct((cp, 1, eng._mpl), jnp.int32),  # local table
        jax.ShapeDtypeStruct((1, C + 1), jnp.int32),  # tokens_ext
        jax.ShapeDtypeStruct((), jnp.int32),          # off
        jax.ShapeDtypeStruct((), jnp.int32),          # write_start
        jax.ShapeDtypeStruct((), jnp.int32),          # write_end
        jax.ShapeDtypeStruct((), jnp.int32),          # sample_pos
        jax.ShapeDtypeStruct((2,), jnp.uint32),       # key
        jax.ShapeDtypeStruct((), jnp.float32),        # temp
        jax.ShapeDtypeStruct((), jnp.int32),          # top_k
        jax.ShapeDtypeStruct((), jnp.float32),        # top_p
    )
    return AuditTarget(name=name, fn=eng._chunk_step, args=args,
                       mesh=rt.mesh, can_compile=False)


def spec_paged_decode_step_target(name: str = "decode_spec_paged",
                                  dtype: str = "bfloat16",
                                  num_slots: int = 4,
                                  k: int = 3) -> AuditTarget:
    """The speculative decode step (inference/speculative.py), model
    drafter: k-step draft-proposal scan + one [N, k+1] target verify +
    in-step accept/reject, with the page-table indirection on BOTH cache
    trees (target pools and draft pools share one table). Contract: ZERO
    collectives, ZERO host callbacks (the accept math must stay on
    device), and FULL donation of BOTH cache trees."""
    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.inference.speculative import SpecConfig
    from megatron_tpu.models.params import init_params

    cfg = tiny_model(params_dtype=dtype)
    dcfg = tiny_model(params_dtype=dtype, num_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    dparams = init_params(dcfg, jax.random.PRNGKey(1))
    eng = InferenceEngine(
        cfg, params, num_slots=num_slots, max_seq_len=cfg.seq_length,
        page_size=8, prefill_chunk=16, force_donate=True,
        speculative=SpecConfig(k=k, drafter="model", draft_cfg=dcfg,
                               draft_params=dparams))
    N = num_slots
    args = (
        _sds(params),
        _sds(eng.caches),
        _sds(dparams),
        _sds(eng.draft_caches),
        jax.ShapeDtypeStruct((N, eng.max_pages), jnp.int32),  # page table
        jax.ShapeDtypeStruct((N,), jnp.int32),      # last_tok
        jax.ShapeDtypeStruct((N,), jnp.int32),      # lengths
        jax.ShapeDtypeStruct((N, 2), jnp.uint32),   # keys
        jax.ShapeDtypeStruct((N,), jnp.float32),    # temps
        jax.ShapeDtypeStruct((N,), jnp.int32),      # top_ks
        jax.ShapeDtypeStruct((N,), jnp.float32),    # top_ps
        jax.ShapeDtypeStruct((N,), jnp.bool_),      # spec_rows
    )
    return AuditTarget(name=name, fn=eng._spec_step, args=args)


# ---------------------------------------------------------------------------
# op-level bodies: ring / ulysses / moe
# ---------------------------------------------------------------------------


def _context_mesh(cp: int = 2):
    from megatron_tpu.parallel.mesh import build_mesh

    return build_mesh(ParallelConfig(context_parallel=cp)).mesh


def ring_attention_target(name: str = "ring_cp2", cp: int = 2,
                          with_grad: bool = True) -> AuditTarget:
    """Zig-zag causal ring attention (einsum inner: the CPU-provable
    path) + its backward — K/V rotate cp times fwd, grads add two more
    ppermute streams bwd."""
    from megatron_tpu.ops.ring_attention import ring_attention_sharded

    mesh = _context_mesh(cp)
    B, S, Hq, Hkv, D = 2, 32, 4, 2, 8
    q = jax.ShapeDtypeStruct((B, S, Hq, D), jnp.float32)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.float32)

    def fwd(q, k, v):
        return ring_attention_sharded(q, k, v, mesh, mask_type="causal",
                                      inner_impl="einsum")

    fn = (lambda q, k, v: jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)) if with_grad else fwd
    return AuditTarget(name=name, fn=fn, args=(q, kv, kv), mesh=mesh)


def ulysses_attention_target(name: str = "ulysses_cp2",
                             cp: int = 2,
                             with_grad: bool = True) -> AuditTarget:
    """Ulysses all-to-all attention: 3 scatter-heads + 1 inverse
    all-to-all forward; the backward mirrors them."""
    from megatron_tpu.ops.ulysses import ulysses_attention_sharded

    mesh = _context_mesh(cp)
    B, S, Hq, Hkv, D = 2, 32, 4, 2, 8
    q = jax.ShapeDtypeStruct((B, S, Hq, D), jnp.float32)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.float32)

    def fwd(q, k, v):
        return ulysses_attention_sharded(q, k, v, mesh, inner_impl="xla")

    fn = (lambda q, k, v: jax.grad(
        lambda q, k, v: fwd(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)) if with_grad else fwd
    return AuditTarget(name=name, fn=fn, args=(q, kv, kv), mesh=mesh)


def moe_block_target(name: str = "moe_ep2", ep: int = 2) -> AuditTarget:
    """Dropless expert-parallel MoE dispatch (CPU transport: all_gather
    reconstruction). jaxpr-only: compiling the shard_map output back
    into GSPMD context RET_CHECK-crashed the sharding remover of jax
    0.4.37's XLA, so can_compile=False (not re-tried on jax 0.9 —
    ROADMAP D9)."""
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.ops.moe import moe_block

    mesh = build_mesh(ParallelConfig(expert_parallel=ep)).mesh
    from megatron_tpu.ops.activations import mlp_input_width_factor

    cfg = tiny_model(num_experts=4, moe_top_k=2, moe_dispatch="dropless")
    H, F, E = cfg.hidden_size, cfg.ffn_size, cfg.num_experts
    Fin = F * mlp_input_width_factor(cfg.activation)
    p = {
        "router": jax.ShapeDtypeStruct((H, E), jnp.float32),
        "w_in": jax.ShapeDtypeStruct((E, H, Fin), jnp.float32),
        "w_out": jax.ShapeDtypeStruct((E, F, H), jnp.float32),
    }
    x = jax.ShapeDtypeStruct((4, cfg.seq_length, H), jnp.float32)
    return AuditTarget(name=name, fn=lambda p, x: moe_block(cfg, p, x),
                       args=(p, x), mesh=mesh, can_compile=False)
