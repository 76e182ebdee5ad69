"""Pipelined inference forward for pp > 1.

Equivalent of the reference's pipelined ForwardStep
(megatron/text_generation/forward_step.py:45-204): there, each decode step
streams (micro)batches through pipeline stages with NCCL p2p and the last
stage broadcasts logits back. Here the layer stack runs under shard_map
manual over the "pipe" axis — the stacked layer params and the KV store
(ops/kv_store.py) are sharded over their layers, the hidden state rotates
stage-to-stage with lax.ppermute, and a final psum broadcasts the
last stage's logits to every stage (the reference's
broadcast_from_last_pipeline_stage, text_generation/communication.py).

Each stage computes only at its own tick (lax.cond), so one forward costs
Pn sequential stage-times — the unavoidable pipeline latency for a single
batch — and each stage's KV caches stay resident on its devices.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.language_model import (
    final_hidden_norm, lm_logits, rope_tables, run_layers,
)
from megatron_tpu.ops import kv_store
from megatron_tpu.ops.moe import moe_stats_zero
from megatron_tpu.training.pipeline import _embed_onehot


def make_pipelined_lm_forward(cfg: ModelConfig, mesh: Mesh, num_stages: int):
    """Returns fwd(params, tokens, positions, caches, cache_index) ->
    (logits, caches) with the same contract as the lm_forward cached path
    (language_model.py), usable as generation's forward_fn."""
    Pn = num_stages
    L = cfg.num_layers
    if L % Pn:
        raise ValueError(f"num_layers={L} not divisible by stages {Pn}")
    Lp = L // Pn
    perm = [(i, (i + 1) % Pn) for i in range(Pn)]

    def pipelined(layers, other, tokens, positions, caches, cache_index):
        params_local = dict(other, layers=layers)
        stage = jax.lax.axis_index("pipe")
        B, S = tokens.shape
        total = kv_store.logical_length(caches)

        ropes = rope_tables(cfg, [cfg.attention_kind],
                            max(cfg.seq_length, total))

        x0 = _embed_onehot(cfg, params_local, tokens, None,
                           positions=positions).astype(cfg.dtype)

        def tick(carry, t):
            state, caches, logits = carry
            active = t == stage

            def compute(args):
                state, caches = args
                x = jnp.where(stage == 0, x0, state)

                # this stage's layers write their rows into its shard of
                # the store in place
                y, _, caches, _, _ = run_layers(
                    cfg, layers, (x, moe_stats_zero(cfg), caches, None, None),
                    ropes, positions, first_layer=stage * Lp,
                    cache_index=cache_index)
                return y, caches

            state2, caches2 = jax.lax.cond(
                active, compute, lambda a: a, (state, caches))

            def mk_logits(_):
                h = final_hidden_norm(cfg, params_local, state2)
                return lm_logits(cfg, params_local, h).astype(jnp.float32)

            logits = jax.lax.cond(active & (stage == Pn - 1), mk_logits,
                                  lambda _: logits, None)
            state3 = jax.lax.ppermute(state2, "pipe", perm)
            return (state3, caches2, logits), None

        V = (cfg.vocab_size if not cfg.tie_embed_logits
             else params_local["embed"]["tokens"].shape[0])
        init = (jnp.zeros((B, S, cfg.hidden_size), cfg.dtype), caches,
                jnp.zeros((B, S, V), jnp.float32))
        (state, caches, logits), _ = jax.lax.scan(tick, init,
                                                  jnp.arange(Pn))
        # zeros everywhere but the last stage: psum = broadcast
        logits = jax.lax.psum(logits, "pipe")
        return logits, caches

    def fwd(params, tokens, positions, caches, cache_index):
        layers = params["layers"]
        other = {k: v for k, v in params.items() if k != "layers"}
        by_stage = tuple(kv_store.partition_spec(layers="pipe")
                         for _ in caches)
        fn = jax.shard_map(
            pipelined,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P("pipe"), layers),
                      jax.tree.map(lambda _: P(), other),
                      P(), P(), by_stage, P()),
            out_specs=(P(), by_stage),
            axis_names={"pipe"},
            check_vma=False,
        )
        return fn(layers, other, tokens, positions, tuple(caches),
                  cache_index)

    return fwd
