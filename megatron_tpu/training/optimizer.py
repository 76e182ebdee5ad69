"""Mixed-precision optimizer with fp32 master weights and ZeRO-1 placement.

Replaces megatron/optimizer/optimizer.py (783 LoC), grad_scaler.py (120),
clip_grads.py (136) and distrib_optimizer.py (700):

  * fp32 master params + fp32 Adam moments next to bf16/fp16 model params
    (ref: Float16OptimizerWithFloat16Params' three param groups,
    optimizer.py:508-563) — here one TrainState pytree.
  * global-norm clipping (ref: clip_grad_norm_fp32; the model-parallel
    allreduce + TP-duplicate dedup disappears: the norm of logical arrays
    is computed once, sharding makes it correct).
  * dynamic loss scaling with growth/backoff/hysteresis for fp16
    (ref: DynamicGradScaler) and skip-step-on-overflow
    (ref: optimizer.py:431-444) expressed as a masked update.
  * ZeRO-1 = PartitionSpecs that shard master/moments over the data axis
    (zero1_spec_tree) — reduce-scatter/all-gather emitted by XLA
    (ref: distrib_optimizer.py:522-612 does this by hand).

AdamW semantics match apex FusedAdam(adam_w_mode=True) as the reference
uses it: decoupled weight decay, bias correction. Weight decay applies only
to >=2-D params (the reference excludes biases and 1-D layernorm params).
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import struct
from jax.sharding import PartitionSpec as P

from megatron_tpu.config import OptimizerConfig
from megatron_tpu.parallel.sharding import zero1_spec_tree
from megatron_tpu.training.scheduler import lr_at_step, wd_at_step


@struct.dataclass
class ScalerState:
    scale: jnp.ndarray          # f32 scalar
    growth_tracker: jnp.ndarray  # i32 consecutive good steps
    hysteresis: jnp.ndarray      # i32 remaining tolerated overflows


@struct.dataclass
class TrainState:
    params: Any                  # model-dtype params (what forward consumes)
    master: Optional[Any]        # fp32 masters (None when params are fp32)
    mu: Any                      # Adam first moment, fp32
    nu: Any                      # Adam second moment, fp32
    step: jnp.ndarray            # i32 scalar, completed optimizer steps
    scaler: Optional[ScalerState]
    # i32 scalar, CONSECUTIVE skipped (non-finite) updates ending at the
    # current step; reset to 0 by any finite step. The divergence sentinel
    # (training/resilience.py) reads it via metrics["skip_streak"] — a run
    # that has gone permanently NaN shows a monotonically growing streak,
    # while fp16 loss-scale backoff shows isolated blips.
    nonfinite_streak: jnp.ndarray


# Leaf-name test for "is a bias or a norm scale" in models/params.py's
# naming scheme: scale / norm_scale / bias / norm_bias / b / bq bk bv bo /
# b_in b_out / dense_b. Matmul weights (w*, router, dense_w) and
# embeddings never match.
_NO_DECAY_RE = None


def _wd_mask(name: str, leaf) -> bool:
    """Whether weight decay applies to a param leaf.

    Matches the reference's param-group split
    (megatron/optimizer/__init__.py:16-59): biases and ALL norm params are
    excluded from decay, everything else (matmul weights, embeddings)
    decays. The reference tests torch's ndim==1; here per-layer norm
    scales and biases are STACKED (e.g. [num_layers, hidden]), so the
    test must be by path name, against the naming convention of
    models/params.py (see _NO_DECAY_RE)."""
    global _NO_DECAY_RE
    if _NO_DECAY_RE is None:
        import re

        _NO_DECAY_RE = re.compile(r"scale|bias|^b([qkvo]|_\w+)?$|_b$")
    if _NO_DECAY_RE.search(name.rsplit("/", 1)[-1]):
        return False
    return leaf.ndim >= 2


# The leaves no gradient trains: the router's selection bias, which the
# step moves by the experts' load (update_selection_bias) or nothing moves
# at all. Adam, weight decay and the clipped norm leave them out.
_NO_GRADIENT_RE = re.compile(r"(^|/)router_bias$")


def update_selection_bias(state: TrainState, load: jnp.ndarray, rate: float,
                          skipped) -> Tuple[TrainState, jnp.ndarray]:
    """The step's move of the router's selection bias (layers/moe/
    router_bias [layers, E]): bias_e += rate * sign(1/E - load_e), load_e
    the share of `load` [layers, E] (each expert layer's count of the
    step's choices) that chose expert e: an expert under the even share
    becomes likelier to be chosen, one over it less. The move is made on
    the float32 master where there is one (a bf16 leaf would round steps
    of 1e-3 away) and the model's leaf follows it; a skipped
    (non-finite) step moves nothing. Returns (the state, the largest
    |bias| after the move)."""
    share = load / jnp.maximum(jnp.sum(load, axis=-1, keepdims=True), 1.0)
    move = rate * jnp.sign(1.0 / load.shape[-1] - share) * (1.0 - skipped)

    def bias_of(tree):
        return tree["layers"]["moe"]["router_bias"]

    def with_bias(tree, bias):
        layers = tree["layers"]
        return {**tree, "layers": {**layers, "moe": {
            **layers["moe"], "router_bias": bias}}}

    if state.master is not None:
        bias = bias_of(state.master) + move
        state = state.replace(master=with_bias(state.master, bias))
    else:
        bias = bias_of(state.params).astype(jnp.float32) + move
    leaf = bias_of(state.params)
    state = state.replace(
        params=with_bias(state.params, bias.astype(leaf.dtype)))
    return state, jnp.max(jnp.abs(bias))


def _leaf_names(tree: Any):
    """Slash-joined path names, in jax.tree.leaves order — THE name
    derivation for both the wd mask and param-group mults (one definition
    so path-pattern semantics cannot drift apart)."""
    from jax.tree_util import tree_flatten_with_path

    leaves_with_paths, _ = tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in leaves_with_paths]


def init_train_state(
    cfg: OptimizerConfig, params: Any, use_fp16_scaler: bool = False
) -> TrainState:
    f32 = lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), t)
    needs_master = cfg.fp32_master_weights and any(
        x.dtype != jnp.float32 for x in jax.tree.leaves(params))
    master = jax.tree.map(lambda x: x.astype(jnp.float32), params) if needs_master else None
    scaler = None
    if use_fp16_scaler:
        init_scale = cfg.loss_scale if cfg.loss_scale is not None else cfg.initial_loss_scale
        scaler = ScalerState(
            scale=jnp.asarray(init_scale, jnp.float32),
            growth_tracker=jnp.zeros((), jnp.int32),
            hysteresis=jnp.asarray(cfg.hysteresis, jnp.int32),
        )
    return TrainState(
        params=params, master=master, mu=f32(params), nu=f32(params),
        step=jnp.zeros((), jnp.int32), scaler=scaler,
        nonfinite_streak=jnp.zeros((), jnp.int32),
    )


def train_state_specs(
    param_specs: Any, params: Any, dp: int, zero1: bool, ep: int = 1,
) -> TrainState:
    """PartitionSpec tree shaped like TrainState. With zero1, master and
    moments additionally shard over the batch axes ("data", "expert");
    dp is the TOTAL batch degree, ep the expert-axis size within it."""
    opt_specs = (zero1_spec_tree(param_specs, params, dp, ep)
                 if zero1 else param_specs)
    has_master = any(x.dtype != jnp.float32 for x in jax.tree.leaves(params))
    return TrainState(
        params=param_specs,
        master=opt_specs if has_master else None,
        mu=opt_specs, nu=opt_specs,
        step=P(),
        scaler=None,  # replaced by caller if scaler in use
        nonfinite_streak=P(),
    )


def global_grad_norm(grads: Any) -> jnp.ndarray:
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads)))


def count_zeros(grads: Any) -> jnp.ndarray:
    """ref: count_zeros_fp32 (clip_grads.py) — debugging metric."""
    return sum(jnp.sum(g == 0.0) for g in jax.tree.leaves(grads)).astype(jnp.float32)


def _update_scaler(cfg: OptimizerConfig, s: ScalerState, found_inf) -> ScalerState:
    """DynamicGradScaler semantics (ref grad_scaler.py): on overflow consume
    hysteresis then backoff 2x; after loss_scale_window good steps grow 2x."""
    if cfg.loss_scale is not None:  # constant scaler
        return s
    hy = jnp.where(found_inf, jnp.maximum(s.hysteresis - 1, 0), s.hysteresis)
    do_backoff = found_inf & (hy <= 0)
    new_scale = jnp.where(
        do_backoff, jnp.maximum(s.scale * 0.5, cfg.min_loss_scale), s.scale)
    tracker = jnp.where(found_inf, 0, s.growth_tracker + 1)
    do_growth = ~found_inf & (tracker >= cfg.loss_scale_window)
    new_scale = jnp.where(do_growth, new_scale * 2.0, new_scale)
    tracker = jnp.where(do_growth, 0, tracker)
    # hysteresis budget is restored only on a growth event, matching the
    # reference: spaced-out isolated overflows then never force a backoff
    hy = jnp.where(do_growth, cfg.hysteresis, hy)
    return ScalerState(scale=new_scale, growth_tracker=tracker, hysteresis=hy)


def leaf_group_mults(cfg: OptimizerConfig, tree: Any):
    """[(lr_mult, wd_mult)] per leaf of `tree`, in leaf order — the
    path-predicate form of the reference's param groups
    (ref: optimizer_param_scheduler.py:124-127). Static floats, resolved
    at trace time; first matching pattern wins."""
    import re

    out = []
    for name in _leaf_names(tree):
        lrm = wdm = 1.0
        for pat, l, w in cfg.param_group_mults:
            if re.search(pat, name):
                lrm, wdm = float(l), float(w)
                break
        out.append((lrm, wdm))
    return out


def make_optimizer_step(cfg: OptimizerConfig, train_iters: int):
    """Returns apply(state, grads) -> (new_state, metrics).

    grads are fp32 *scaled* grads (loss was multiplied by scaler.scale when
    a scaler is present). The whole step — unscale, inf check, clip, Adam,
    master->model cast — is one fused jitted region
    (ref hot path: MixedPrecisionOptimizer.step, optimizer.py:384-466).
    """

    def apply(state: TrainState, grads: Any) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        inv_scale = (1.0 / state.scaler.scale) if state.scaler is not None else 1.0
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv_scale, grads)

        masters = state.master if state.master is not None else state.params
        names = _leaf_names(masters)
        trained = [not _NO_GRADIENT_RE.search(name) for name in names]
        norm = global_grad_norm(
            [g for g, t in zip(jax.tree.leaves(grads), trained) if t])
        finite = jnp.isfinite(norm)

        if cfg.clip_grad > 0:
            clip_coef = jnp.minimum(1.0, cfg.clip_grad / (norm + 1e-6))
            grads = jax.tree.map(lambda g: g * clip_coef, grads)

        step1 = state.step + 1
        lr = lr_at_step(cfg, state.step, train_iters)
        wd = wd_at_step(cfg, state.step, train_iters)
        b1, b2 = cfg.adam_beta1, cfg.adam_beta2
        t = step1.astype(jnp.float32)
        bc1 = 1.0 - b1 ** t
        bc2 = 1.0 - b2 ** t

        def adam_leaf(m, v, g, p, decays, lr_mult=1.0, wd_mult=1.0):
            m1 = b1 * m + (1 - b1) * g
            v1 = b2 * v + (1 - b2) * jnp.square(g)
            update = (m1 / bc1) / (jnp.sqrt(v1 / bc2) + cfg.adam_eps)
            if decays:
                update = update + (wd * wd_mult) * p.astype(jnp.float32)
            p1 = p.astype(jnp.float32) - (lr * lr_mult) * update
            return m1, v1, p1

        new_mu, new_nu, new_master = {}, {}, {}
        flat = jax.tree.structure(masters)
        mus = jax.tree.leaves(state.mu)
        nus = jax.tree.leaves(state.nu)
        gs = jax.tree.leaves(grads)
        ps = jax.tree.leaves(masters)
        mults = (leaf_group_mults(cfg, masters) if cfg.param_group_mults
                 else [(1.0, 1.0)] * len(ps))
        out = [adam_leaf(m, v, g, p, _wd_mask(name, p), lm, wm) if t
               else (m, v, p.astype(jnp.float32))
               for (m, v, g, p), name, (lm, wm), t in zip(
                   zip(mus, nus, gs, ps), names, mults, trained)]
        new_mu = jax.tree.unflatten(flat, [o[0] for o in out])
        new_nu = jax.tree.unflatten(flat, [o[1] for o in out])
        new_master = jax.tree.unflatten(flat, [o[2] for o in out])

        # skip the whole update when non-finite (ref optimizer.py:431-444)
        keep = lambda new, old: jax.tree.map(
            lambda n, o: jnp.where(finite, n, o.astype(n.dtype)), new, old)
        new_mu = keep(new_mu, state.mu)
        new_nu = keep(new_nu, state.nu)
        new_master = keep(new_master, masters)

        new_params = jax.tree.map(
            lambda mref, pold: mref.astype(pold.dtype), new_master, state.params)
        master_out = new_master if state.master is not None else None

        scaler = (_update_scaler(cfg, state.scaler, ~finite)
                  if state.scaler is not None else None)

        streak = jnp.where(finite, 0, state.nonfinite_streak + 1
                           ).astype(jnp.int32)
        new_state = TrainState(
            params=new_params, master=master_out, mu=new_mu, nu=new_nu,
            step=jnp.where(finite, step1, state.step), scaler=scaler,
            nonfinite_streak=streak,
        )
        metrics = {
            "grad_norm": norm,
            "lr": lr,
            "skipped": (~finite).astype(jnp.float32),
            "skip_streak": streak.astype(jnp.float32),
        }
        if cfg.log_num_zeros_in_grad:
            metrics["num_zeros"] = count_zeros(grads)
        if state.scaler is not None:
            metrics["loss_scale"] = scaler.scale
        return new_state, metrics

    if cfg.optimizer == "sgd":
        def apply_sgd(state: TrainState, grads: Any):
            inv_scale = (1.0 / state.scaler.scale) if state.scaler is not None else 1.0
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv_scale, grads)
            norm = global_grad_norm(grads)
            finite = jnp.isfinite(norm)
            if cfg.clip_grad > 0:
                coef = jnp.minimum(1.0, cfg.clip_grad / (norm + 1e-6))
                grads = jax.tree.map(lambda g: g * coef, grads)
            lr = lr_at_step(cfg, state.step, train_iters)
            masters = state.master if state.master is not None else state.params
            # mu doubles as momentum buffer
            new_mu = jax.tree.map(
                lambda m, g: cfg.sgd_momentum * m + g, state.mu, grads)
            # one update path; mults default to 1.0 everywhere (this SGD
            # has no weight-decay term, so wd_mult has nothing to scale)
            flat = jax.tree.structure(masters)
            mults = (leaf_group_mults(cfg, masters) if cfg.param_group_mults
                     else [(1.0, 1.0)] * flat.num_leaves)
            new_master = jax.tree.unflatten(flat, [
                p.astype(jnp.float32) - (lr * lm) * m
                for (p, m), (lm, _) in zip(
                    zip(jax.tree.leaves(masters), jax.tree.leaves(new_mu)),
                    mults)])
            keep = lambda new, old: jax.tree.map(
                lambda n, o: jnp.where(finite, n, o.astype(n.dtype)), new, old)
            new_mu = keep(new_mu, state.mu)
            new_master = keep(new_master, masters)
            new_params = jax.tree.map(
                lambda mref, pold: mref.astype(pold.dtype), new_master, state.params)
            scaler = (_update_scaler(cfg, state.scaler, ~finite)
                      if state.scaler is not None else None)
            streak = jnp.where(finite, 0, state.nonfinite_streak + 1
                               ).astype(jnp.int32)
            new_state = TrainState(
                params=new_params,
                master=new_master if state.master is not None else None,
                mu=new_mu, nu=state.nu,
                step=jnp.where(finite, state.step + 1, state.step),
                scaler=scaler, nonfinite_streak=streak)
            return new_state, {"grad_norm": norm, "lr": lr,
                               "skipped": (~finite).astype(jnp.float32),
                               "skip_streak": streak.astype(jnp.float32)}
        return apply_sgd

    if cfg.optimizer != "adam":
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    return apply
