"""The jitted training step: microbatch grad accumulation + optimizer.

Equivalent of megatron/training.py train_step (zero grads -> forward/backward
over microbatches -> reduce grads -> optimizer step) with
forward_backward_no_pipelining's microbatch loop (schedules.py:213-250)
expressed as a lax.scan. Data-parallel gradient reduction
(megatron/model/distributed.py allreduce_gradients) is implicit: grads of
data-sharded batches are partial sums that XLA reduces when they meet the
(replicated or ZeRO-sharded) optimizer state.

Gradients accumulate in fp32 regardless of compute dtype
(ref: accumulate_allreduce_grads_in_fp32 / MemoryBuffer main_grad).

Pipeline-parallel schedules live in megatron_tpu/training/pipeline.py.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig, OptimizerConfig, TrainingConfig
from megatron_tpu.models.language_model import lm_loss
from megatron_tpu.models.transformer import Sharder, _identity_sharder
from megatron_tpu.ops.moe import LOAD_METRIC
from megatron_tpu.parallel.random import RngStreams
from megatron_tpu.training.optimizer import TrainState, make_optimizer_step


def make_train_step(
    model_cfg: ModelConfig,
    opt_cfg: OptimizerConfig,
    train_cfg: TrainingConfig,
    num_microbatches: int,
    train_iters: Optional[int] = None,
    sharder: Sharder = _identity_sharder,
    loss_fn: Optional[Callable] = None,
    pipeline_loss_fn: Optional[Callable] = None,
) -> Callable[[TrainState, Dict[str, jnp.ndarray]], Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """Build train_step(state, batch) -> (state, metrics).

    batch leaves are [global_batch_per_step, ...] where
    global_batch_per_step = num_microbatches * micro_batch * dp; the leading
    axis is split into scan microbatches. loss_fn defaults to lm_loss —
    entry points may substitute task losses (the reference's
    forward_step_func indirection, training.py pretrain(forward_step_func)).

    With pipeline_loss_fn (from make_pipeline_loss_fn), the pipeline owns
    the microbatch loop (the reference's 1F1B schedule vs the no-pipelining
    path, schedules.py:18-33) and this step differentiates the whole batch
    at once.
    """
    if loss_fn is not None:
        # thread the activation sharder into task losses that accept it
        # (the residual-stream constraint IS sequence parallelism here)
        import inspect

        if "sharder" in inspect.signature(loss_fn).parameters:
            user_fn = loss_fn
            loss_fn = (lambda cfg, p, b, key:
                       user_fn(cfg, p, b, key, sharder=sharder))
    loss_fn = loss_fn or (lambda cfg, p, b, key: lm_loss(
        cfg, p, b, dropout_key=key, recompute=train_cfg.recompute_granularity,
        sharder=sharder))
    opt_apply = make_optimizer_step(opt_cfg, train_iters or train_cfg.train_iters or 1)
    dropout_on = model_cfg.hidden_dropout > 0 or model_cfg.attention_dropout > 0
    streams = RngStreams(train_cfg.seed)

    if pipeline_loss_fn is not None:
        def pp_train_step(state: TrainState, batch: Dict[str, jnp.ndarray]):
            scale = (state.scaler.scale if state.scaler is not None
                     else jnp.float32(1.0))
            key = streams.dropout(state.step) if dropout_on else None

            def scaled_loss(p):
                loss, _ = pipeline_loss_fn(p, batch, key)
                return loss * scale, loss

            (_, loss), grads = jax.value_and_grad(scaled_loss, has_aux=True)(
                state.params)
            with jax.named_scope("optimizer"):
                new_state, metrics = opt_apply(state, grads)
            metrics["loss"] = loss
            return new_state, metrics

        return pp_train_step

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]):
        n = num_microbatches
        micro = jax.tree.map(
            lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)

        scale = state.scaler.scale if state.scaler is not None else jnp.float32(1.0)

        def one_micro(acc, scanned):
            mb, idx = scanned
            if dropout_on:
                # dedicated dropout stream, step- and microbatch-indexed
                key = jax.random.fold_in(streams.dropout(state.step), idx)
            else:
                key = None

            def scaled_loss(p):
                loss, aux = loss_fn(model_cfg, p, mb, key)
                # None for a model without experts: no leaf, no output
                return loss * scale, (loss, aux.get(LOAD_METRIC))

            (_, out), grads = jax.value_and_grad(scaled_loss, has_aux=True)(state.params)
            with jax.named_scope("grad_accumulate"):
                acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return acc, out

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        acc, (losses, loads) = jax.lax.scan(one_micro, zeros,
                                            (micro, jnp.arange(n)))
        # mean over microbatches; scaled grads stay scaled for the optimizer
        grads = jax.tree.map(lambda g: g / n, acc)

        with jax.named_scope("optimizer"):
            new_state, metrics = opt_apply(state, grads)
        metrics["loss"] = jnp.mean(losses)
        if loads is not None:
            # worst layer's largest expert over the mean, mean of micro-batches
            metrics[LOAD_METRIC] = jnp.mean(loads)
        return new_state, metrics

    return train_step


def make_eval_step(
    model_cfg: ModelConfig,
    train_cfg: TrainingConfig,
    sharder: Sharder = _identity_sharder,
    loss_fn: Optional[Callable] = None,
):
    """Forward-only loss (ref: training.py evaluate loop, :773-826).

    loss_fn(model_cfg, params, batch) -> (loss, aux) overrides the GPT LM
    loss for task models (BERT/T5), mirroring make_train_step's loss_fn."""

    if loss_fn is not None:
        def task_eval_step(params: Any, batch: Dict[str, jnp.ndarray]):
            loss, aux = loss_fn(model_cfg, params, batch)
            out = {"lm_loss": loss}
            out.update({k: v for k, v in aux.items() if k != "loss"})
            return out

        return task_eval_step

    def eval_step(params: Any, batch: Dict[str, jnp.ndarray]):
        from megatron_tpu.models.language_model import lm_forward
        from megatron_tpu.ops.cross_entropy import cross_entropy_loss
        from megatron_tpu.training.metrics import compute_metrics

        logits = lm_forward(model_cfg, params, batch["tokens"],
                            positions=batch.get("position_ids"),
                            sharder=sharder)
        loss_mask = batch.get("loss_mask")
        if loss_mask is None:
            loss_mask = jnp.ones(batch["labels"].shape, jnp.float32)
        loss, per_token = cross_entropy_loss(logits, batch["labels"],
                                             loss_mask=loss_mask)
        out = {"lm_loss": loss, "ntokens": jnp.sum(loss_mask)}
        out.update(compute_metrics(train_cfg.metrics, logits, batch["labels"],
                                   loss_mask, per_token))
        return out

    return eval_step
