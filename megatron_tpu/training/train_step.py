"""The jitted training step: microbatch grad accumulation + optimizer.

Equivalent of megatron/training.py train_step (zero grads -> forward/backward
over microbatches -> reduce grads -> optimizer step) with
forward_backward_no_pipelining's microbatch loop (schedules.py:213-250)
expressed as a lax.scan. Data-parallel gradient reduction
(megatron/model/distributed.py allreduce_gradients) is implicit: grads of
data-sharded batches are partial sums that XLA reduces when they meet the
(replicated or ZeRO-sharded) optimizer state.

Gradients accumulate in fp32 regardless of compute dtype
(ref: accumulate_allreduce_grads_in_fp32 / MemoryBuffer main_grad). One
accumulation, two places where the add is made: for a leaf whose gradient
a Pallas kernel of the program makes (the stacked expert matrices of a
dropless MoE on one TPU: `kernel_summed`), the kernel adds its float32
product into the accumulator, which reaches it as a cotangent and rides
the backward pass in the layer scan's carry (models/language_model.py
`grad_sink`; the reference's `wgrad_gemm_accum_fp32`, main_grad += dYᵀ·X
inside the GEMM); for every other leaf XLA adds the micro-batch's
gradient under the scope `grad_accumulate`. Which leaf goes where follows
from what the trace can see (backend, mesh, shapes), never from a flag.

One leaf is trained by no gradient: the router's selection bias of a
model that balances by it (ModelConfig.moe_bias_update_rate). The loss
hands up each expert layer's load of the call (ops/moe.py EXPERT_LOAD),
the step sums it over its micro-batches (over every data-parallel replica
too: the count is of the global batch), and behind the optimizer the bias
moves by its sign rule (training/optimizer.py update_selection_bias);
Adam, weight decay and the clipped norm leave the leaf out.

Pipeline-parallel schedules live in megatron_tpu/training/pipeline.py.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig, OptimizerConfig, TrainingConfig
from megatron_tpu.models.language_model import (
    GRAD_SINK, grad_sink_leaves, lm_loss,
)
from megatron_tpu.models.transformer import Sharder, _identity_sharder
from megatron_tpu.ops.moe import BIAS_METRIC, EXPERT_LOAD, STEP_METRICS
from megatron_tpu.parallel.random import RngStreams
from megatron_tpu.training.optimizer import (
    TrainState, make_optimizer_step, update_selection_bias,
)


def kernel_summed(model_cfg: ModelConfig, params: Any,
                  batch: Dict[str, Any], num_microbatches: int,
                  own_loss: bool = True) -> Any:
    """A tree shaped like `params`: True at the leaves whose gradient
    `make_train_step`'s step, traced here over `batch` (arrays or shapes,
    [global_batch_per_step, ...]), sums over its micro-batches inside the
    kernel that makes it; False where XLA adds (`grad_accumulate`).
    own_loss: the step runs the language model's own loss (no `loss_fn`,
    no pipeline), the one that knows what to do with a sink. A single
    micro-batch has no sum to make. For the rest the model says which
    leaves a kernel of its own can take (language_model.grad_sink_leaves:
    by the predicate its products themselves go by, never by a flag)."""
    if num_microbatches == 1 or not own_loss:
        return jax.tree.map(lambda _: False, params)
    rows, *rest = batch["tokens"].shape
    return grad_sink_leaves(model_cfg, params,
                            (rows // num_microbatches, *rest))


def _where(mask: Any, keep: bool, tree: Any) -> Any:
    """`tree` with None at the leaves where `mask` is not `keep`."""
    return jax.tree.map(lambda m, x: x if m is keep else None, mask, tree)


def _either(a: Any, b: Any) -> Any:
    """Two trees of one shape with None where the other has a leaf."""
    return jax.tree.map(lambda x, y: y if x is None else x, a, b,
                        is_leaf=lambda x: x is None)


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
    own_loss = loss_fn is None
    loss_fn = loss_fn or (lambda cfg, p, b, key, **sink: lm_loss(
        cfg, p, b, dropout_key=key, recompute=train_cfg.recompute_granularity,
        sharder=sharder, **sink))
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
        # the leaves whose gradient the kernel that makes it also sums
        summed = kernel_summed(model_cfg, state.params, batch, n, own_loss)
        any_summed = any(jax.tree.leaves(summed))
        params = _where(summed, False, state.params)
        held = _where(summed, True, state.params)

        def one_micro(acc, scanned):
            mb, idx = scanned
            if dropout_on:
                # dedicated dropout stream, step- and microbatch-indexed
                key = jax.random.fold_in(streams.dropout(state.step), idx)
            else:
                key = None

            def scaled_loss(p, sink):
                loss, aux = loss_fn(model_cfg, _either(p, held), mb, key,
                                    **({"grad_sink": sink} if any_summed
                                       else {}))
                # empty for a model without experts: no leaf, no output
                return ((loss * scale, aux.get(GRAD_SINK, sink)),
                        (loss, {k: aux[k] for k in (*STEP_METRICS, EXPERT_LOAD)
                                if k in aux}))

            # A summed leaf is no argument of the differentiated function,
            # so its own gradient is never formed; its accumulator is one,
            # and goes in again as the cotangent of the sink the loss hands
            # through: what comes back for it is the accumulator plus this
            # micro-batch's gradient, added by the kernel (lm_forward).
            sink = _where(summed, True, acc)
            _, vjp, out = jax.vjp(scaled_loss, params, sink, has_aux=True)
            grads, sink = vjp((jnp.ones((), jnp.float32), sink))
            with jax.named_scope("grad_accumulate"):
                acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                   _where(summed, False, acc), grads)
            return _either(acc, sink), out

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        # the loop's own slicing of the batch and carrying of the
        # accumulators sit under no region: this names them for a trace
        with jax.named_scope("micro_batches"):
            acc, (losses, moe) = jax.lax.scan(one_micro, zeros,
                                              (micro, jnp.arange(n)))
        # mean over microbatches; scaled grads stay scaled for the optimizer
        grads = jax.tree.map(lambda g: g / n, acc)

        # what the selection bias moves by: each layer's choices an expert
        # over the whole step
        load = moe.pop(EXPERT_LOAD, None)
        with jax.named_scope("optimizer"):
            new_state, metrics = opt_apply(state, grads)
            if load is not None:
                new_state, metrics[BIAS_METRIC] = update_selection_bias(
                    new_state, jnp.sum(load, axis=0),
                    model_cfg.moe_bias_update_rate, metrics["skipped"])
        metrics["loss"] = jnp.mean(losses)
        # a model with experts: the worst layer's largest expert over the
        # mean and, of a share, the rows routed to held experts; each the
        # mean of the micro-batches
        metrics.update({k: jnp.mean(v) for k, v in moe.items()})
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
