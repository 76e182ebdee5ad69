"""Training orchestration: the pretrain()/train loop.

Equivalent of megatron/training.py (966 LoC): setup -> train loop with
batch-size rampup, periodic eval, logging, checkpointing, graceful exit
(SIGTERM / --exit_duration_in_mins / --exit_interval). Differences:

  * single-controller: no rank-conditional printing/broadcasts; the loop
    body is one jitted train step with explicit shardings
  * the data iterator yields numpy global batches; device placement happens
    here with the batch PartitionSpec
  * tokens/sec and MFU are derived from the model FLOP estimate
    (ModelConfig.flops_per_token_fwd, ref language_model.py:370-384)
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import signal as signal_module
import sys
import threading
import time
import warnings
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from megatron_tpu.analysis import step_program
from megatron_tpu.config import RunConfig
from megatron_tpu.models.language_model import (
    is_full_remat_family, lm_loss,
)
from megatron_tpu.models.params import init_params, param_specs
from megatron_tpu.ops.moe import STEP_METRICS
from megatron_tpu.parallel.mesh import MeshRuntime, build_mesh
from megatron_tpu.platform import device_summary, enable_compile_cache
from megatron_tpu.telemetry.tracing import capture
from megatron_tpu.parallel.sharding import (
    ActivationSharder, batch_spec, shard_tree, tree_shardings,
)
from megatron_tpu.training import (
    checkpointing, coordination, prefetch, resilience,
)
from megatron_tpu.training.microbatches import MicroBatchCalculator
from megatron_tpu.training.optimizer import (
    TrainState, init_train_state, train_state_specs,
)
from megatron_tpu.training.pipeline import (
    make_pipeline_loss_fn, vpp_place_indices,
)
from megatron_tpu.training.signal_handler import DistributedSignalHandler
from megatron_tpu.training.timers import Timers
from megatron_tpu.training.train_step import (
    kernel_summed, make_eval_step, make_train_step,
)


def get_ltor_masks_and_position_ids(
    tokens: np.ndarray,
    eod_token: Optional[int] = None,
    reset_position_ids: bool = False,
    eod_mask_loss: bool = False,
) -> Tuple[np.ndarray, np.ndarray]:
    """(loss_mask, position_ids) for left-to-right LM batches
    (ref: megatron/utils.py get_ltor_masks_and_position_ids; the
    block-diagonal attention-mask reset is handled by packed position ids +
    causal masking rather than a materialized [S,S] mask)."""
    b, s = tokens.shape
    loss_mask = np.ones((b, s), np.float32)
    if eod_mask_loss and eod_token is not None:
        loss_mask[tokens == eod_token] = 0.0
    position_ids = np.tile(np.arange(s, dtype=np.int64), (b, 1))
    if reset_position_ids and eod_token is not None:
        for i in range(b):
            for j in np.nonzero(tokens[i] == eod_token)[0]:
                if j + 1 < s:
                    position_ids[i, j + 1:] = np.arange(s - (j + 1))
    return loss_mask, position_ids


def gpt_collate(items, eod_token=None, eod_mask_loss=False,
                reset_position_ids=False):
    """'text' [seq+1] items -> tokens/labels/loss_mask batch (+ packed
    position_ids with --reset_position_ids)."""
    text = np.stack([it["text"] for it in items]).astype(np.int64)
    tokens, labels = text[:, :-1], text[:, 1:]
    _, position_ids = get_ltor_masks_and_position_ids(
        tokens, eod_token, reset_position_ids=reset_position_ids)
    loss_mask = np.ones(labels.shape, np.float32)
    if eod_mask_loss and eod_token is not None:
        loss_mask[labels == eod_token] = 0.0
    batch = {"tokens": tokens, "labels": labels, "loss_mask": loss_mask}
    if reset_position_ids:
        batch["position_ids"] = position_ids
    return batch


def _attention_tiles(model_cfg, batch) -> Dict[str, Dict[str, Any]]:
    """`flash_template.tile_counts` by the name of each kind of attention
    layer, for a step whose attention is the flash kernels over the whole
    sequences of the batch's `tokens` (`--attention_impl pallas` on a
    TPU, or interpreted on request) at the tiles `pick_blocks` gives
    them: how far the kernels' tile classes engage at this shape. Empty
    where the step runs other attention."""
    from megatron_tpu.ops.attention import _kernels_dispatchable
    from megatron_tpu.ops.pallas import flash_template

    if (model_cfg.attention_impl != "pallas" or "tokens" not in batch
            or not _kernels_dispatchable()):
        return {}
    seq_len = batch["tokens"].shape[-1]
    block, _ = flash_template.pick_blocks(seq_len, model_cfg.head_dim,
                                          model_cfg.dtype)
    return {kind.name: flash_template.tile_counts(
        seq_len, block, True, kind.sliding_window_size)
        for kind in model_cfg.attention_period}


class TrainLoop:
    """Owns mesh, state, jitted steps, and the iteration loop."""

    def __init__(
        self,
        run_cfg: RunConfig,
        log: Callable[[str], None] = print,
        init_params_fn: Optional[Callable] = None,
        param_specs_fn: Optional[Callable] = None,
        loss_fn: Optional[Callable] = None,
        fixed_num_microbatches: Optional[int] = None,
        pipeline_loss_factory: Optional[Callable] = None,
    ):
        """init_params_fn(model_cfg, key) / param_specs_fn(model_cfg) let
        task entry points with their own parameter trees (T5's separate
        encoder/decoder stacks) reuse the loop; default is the GPT-family
        language model. loss_fn(model_cfg, params, batch, key) swaps the
        training objective (BERT/T5/ICT entries); fixed_num_microbatches
        pins the microbatch count regardless of batch size (ICT's in-batch
        softmax needs the whole global batch as negatives).

        pipeline_loss_factory(model_cfg, mesh, num_stages,
        num_microbatches, recompute) -> loss_fn(params, batch, key) lets a
        task model supply its own pipelined schedule at pp>1 (T5's
        enc+dec interleaved ring, training/t5_pipeline.py); the built-in
        GPT schedule is used when it is None."""
        run_cfg.validate()
        self.cfg = run_cfg
        self.log = log
        # persistent XLA compilation cache, placed BEFORE the first jit
        # (init_params below compiles): a crash-resume restart or re-run
        # pays the goodput `compile` bucket once. The config is
        # PROCESS-GLOBAL and deliberately not restored on loop exit —
        # eval/serving work after training in the same process keeps the
        # cache.
        cache_dir = enable_compile_cache(
            run_cfg.training.compilation_cache_dir or "")
        if run_cfg.training.compilation_cache_dir:
            # the user asked for a cache of THIS run: keep every program,
            # not only those over jax's default one-second threshold
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
        # multi-host coordination (training/coordination.py): the
        # agreement seam for signals/aborts/commits/restarts — None on
        # single-process runs, where every downstream path is untouched.
        # The restart barrier runs BEFORE any mesh work so a topology
        # disagreement (host count changed under the run) is a loud,
        # journaled error here instead of a coordinator timeout inside
        # jax.distributed or the first collective.
        self.coord = coordination.for_training(run_cfg.training, log=self.log)
        if self.coord is not None:
            if run_cfg.training.save_interval_auto:
                # per-host MEASURED latencies differ, and hosts that are
                # not in iteration-lockstep cannot agree on exact future
                # save iterations without a blocking rendezvous — an
                # un-agreed cadence would desynchronize the two-phase
                # commit votes. Refuse loudly; a fixed interval is
                # deterministic by arithmetic on every host.
                self.coord.close()  # stop the heartbeat sideband first
                raise ValueError(
                    "--save_interval auto is not supported on coordinated "
                    "multi-host runs yet (the autotuned cadence is per-"
                    "host-measured and would desynchronize the two-phase "
                    "checkpoint commit); use a fixed --save_interval")
            self.coord.topology_barrier()
        if jax.process_count() > 1:
            # multi-host: DCN-aware mesh (data axis outermost across slices)
            from megatron_tpu.parallel.distributed import build_multihost_mesh

            self.rt: MeshRuntime = build_multihost_mesh(run_cfg.parallel)
        else:
            self.rt = build_mesh(run_cfg.parallel)
        # one line at startup naming what the run is on — a log that does
        # not say so cannot be told from a run on another backend
        self.log(f"devices: {json.dumps(device_summary())} | "
                 f"compile cache: {cache_dir}")
        level = run_cfg.training.timing_log_level
        if run_cfg.training.log_timers_to_tensorboard:
            level = max(level, 1)  # sub-spans become real timers
        self.timers = Timers(level)
        self._profiling = False
        # SIGUSR1 arms a bounded trace window at the next loop pass —
        # production incidents get profiled without a restart or
        # --profile having been set (docs/observability.md)
        self._profile_signal_pending = False
        self._profile_until: Optional[int] = None
        # (jitted step, its microbatch count, batch avals) of the first
        # step a trace window held
        self._profiled_step: Optional[Tuple[Callable, int, Any]] = None

        model_cfg = run_cfg.model
        if model_cfg.attention_impl == "pallas":
            # one line at startup so the gradient path is never a mystery
            # in the log (chip_smoke.py checks for it)
            self.log("attention: pallas flash template, "
                     "fused fwd+bwd (custom vjp)")
        E = model_cfg.num_experts
        if E is not None and E % self.rt.ep:
            raise ValueError(
                f"num_experts={E} must be divisible by "
                f"expert_parallel={self.rt.ep} (experts shard over the "
                f"dedicated expert axis; dp is unconstrained)")
        if E is None and self.rt.ep > 1:
            raise ValueError(
                f"expert_parallel={self.rt.ep} set but the model has no "
                "experts — use data_parallel instead")
        self.specs = (param_specs_fn or param_specs)(model_cfg)
        params = (init_params_fn or init_params)(model_cfg, jax.random.fold_in(
            jax.random.PRNGKey(run_cfg.training.seed), 0))
        params = shard_tree(self.rt, params, self.specs)
        self.state = init_train_state(
            run_cfg.optimizer, params,
            use_fp16_scaler=(model_cfg.params_dtype == "float16"))

        # Interleaved pipeline: keep the layer subtrees of the whole
        # training state in placed (round-robin chunk) order for the run,
        # so the per-step permutation — ~(V-1)/V of layer weights crossing
        # the pipe axis each step — disappears. Canonical order is restored
        # at checkpoint and eval boundaries (_place_state/_unplace below).
        self._vpp_perms = None
        vpp = run_cfg.parallel.virtual_pipeline_parallel or 1
        if self.rt.pp > 1 and vpp > 1:
            self._vpp_perms = vpp_place_indices(
                model_cfg.num_layers, self.rt.pp, vpp)

        zero1 = run_cfg.optimizer.use_distributed_optimizer
        self.state_specs = train_state_specs(self.specs, params, self.rt.dp,
                                             zero1=zero1, ep=self.rt.ep)
        self.state_shardings = jax.tree.map(
            lambda s: NamedSharding(self.rt.mesh, s), self.state_specs,
            is_leaf=lambda s: isinstance(s, P))
        # place the fresh state on its training shardings — with ZeRO-1 the
        # optimizer moments are data-sharded, which param-derived init does
        # not produce
        self.state = jax.device_put(self.state, self.state_shardings)
        self.batch_sharding = NamedSharding(self.rt.mesh, batch_spec())

        self.calc = MicroBatchCalculator.from_config(run_cfg.training, self.rt.dp)
        self.iteration = 0
        self.consumed_samples = 0

        # the config recorded in every checkpoint: the RunConfig dict with
        # the RESOLVED data-parallel degree (ParallelConfig.data_parallel
        # is usually None/derived) — the next resume compares it against
        # its own topology to detect an elastic dp change (_load)
        self._save_config = run_cfg.to_dict()
        self._save_config["parallel"]["data_parallel"] = self.rt.dp
        # the HOST topology rides in the checkpoint too, so a resume at a
        # different host count is detected the same way a dp change is
        self._save_config["coordination"] = {
            "num_hosts": self.coord.num_hosts if self.coord else 1}
        self._elastic_resume: Optional[Dict[str, Any]] = None

        if run_cfg.training.load:
            self._load()
        self.state = self._permute_state(self.state, to_placed=True)

        # fault tolerance: async checkpoint writer (created on first save)
        # and divergence sentinel (training/resilience.py)
        t = run_cfg.training
        self._saver: Optional[checkpointing.AsyncCheckpointSaver] = None
        self._sentinel = None
        if t.divergence_patience or t.loss_spike_factor:
            self._sentinel = resilience.DivergenceSentinel(
                patience=t.divergence_patience,
                spike_factor=t.loss_spike_factor,
                spike_patience=t.loss_spike_patience)
        self._rollbacks = 0
        self._skip_data_until = 0  # fast-forward bound after a rollback
        # consecutive healthy (finite, real) steps since the last rollback;
        # once training has advanced well past the poison window the
        # rollback budget is restored, so widely separated TRANSIENT
        # divergences over a long run don't exhaust max_rollbacks — only a
        # model that re-diverges shortly after every restore does (the
        # documented intent of the knob). The margin guarantees net forward
        # progress between restores.
        self._healthy_steps = 0
        self._rollback_reset_after = 20 * max(
            t.divergence_patience, t.loss_spike_patience, 25)

        # async goodput loop state (training/prefetch.py): the background
        # batch prefetcher (rebuilt at every consumed_samples watermark
        # change) and the count of blocking device->host syncs the loop
        # has issued — the steady-state invariant is exactly one per step
        # (the batched metrics fetch), regression-gated in
        # tests/test_prefetch.py
        self._prefetcher: Optional[prefetch.DevicePrefetcher] = None
        self._pf_credited = (0.0, 0.0)
        self.host_sync_points = 0

        # preemption / hang / SDC sentinels (training/resilience.py;
        # docs/fault_tolerance.md "Preemption and elastic resume"):
        # which signal(s) ended the run (run_end's received_signal), the
        # step-deadline watchdog (armed in _train_inner when
        # --step_timeout_s > 0), and the per-iteration host-batch
        # fingerprints (--log_data_fingerprint) consumed by
        # _process_record
        self._exit_signal: Optional[str] = None
        self._watchdog: Optional[resilience.StepWatchdog] = None
        self._batch_fps: Dict[int, str] = {}
        # multi-host exit agreement cache: (target_iteration, notice_host)
        # once the cluster has agreed where to drain+save, else None
        self._exit_agreement: Optional[Tuple[int, Optional[int]]] = None
        self._notice_host: Optional[int] = None
        # set when the exit agreement proved unreachable: the final save
        # must commit SOLO (coordinator dropped) or its two-phase barrier
        # would wait on the same unreachable peers forever
        self._commit_solo = False

        # --save_interval auto (resilience.CheckpointCadenceTuner): the
        # cadence is re-derived from measured commit latency; seeded from
        # the journal of previous incarnations so a restart's FIRST
        # interval is already informed
        self._cadence: Optional[resilience.CheckpointCadenceTuner] = None
        self._cadence_commit_seen: Optional[float] = None
        self._last_save_iter = self.iteration
        if t.save_interval_auto:
            self._cadence = resilience.CheckpointCadenceTuner(
                grace_s=t.preempt_save_timeout,
                floor_steps=t.save_interval_floor)
            if t.telemetry_dir:
                from megatron_tpu.telemetry.journal import read_events

                path = os.path.join(t.telemetry_dir, "events.jsonl")
                if os.path.exists(path):
                    n = self._cadence.seed_from_journal(read_events(path)[0])
                    if n:
                        self.log(f"save cadence: seeded from {n} journaled "
                                 "commit-latency samples")

        self._sharder = ActivationSharder(run_cfg.parallel.sequence_parallel)
        self._step_cache: Dict[int, Callable] = {}
        self.loss_fn = loss_fn
        self.fixed_num_microbatches = fixed_num_microbatches
        self.pipeline_loss_factory = pipeline_loss_factory
        if (loss_fn is not None and self.rt.pp > 1
                and pipeline_loss_factory is None):
            raise ValueError(
                "pipeline parallelism drives the built-in LM loss through "
                "the pipe schedule; task losses (BERT/ICT/classification) "
                "would silently train unpipelined — use tensor/data/context"
                " parallelism for them, or supply a pipeline_loss_factory "
                "(T5 has one: training/t5_pipeline.py)")
        self.eval_step = None
        # task entry points (BERT/T5/ICT) set this to their loss for
        # evaluate(); defaults to loss_fn without the dropout key
        self.eval_loss_fn = None
        if loss_fn is not None:
            self.eval_loss_fn = lambda mc, p, b: loss_fn(mc, p, b, None)

        from megatron_tpu.training.logging_writer import Writer

        self.writer = Writer(
            tensorboard_dir=run_cfg.training.tensorboard_dir,
            wandb=run_cfg.training.wandb_logger,
            wandb_project=run_cfg.training.wandb_project,
            wandb_name=run_cfg.training.wandb_name,
            config=run_cfg.to_dict())

        # unified telemetry (megatron_tpu/telemetry): event journal,
        # goodput ledger, /metrics sidecar, flight recorder — None unless
        # the config enables a component (docs/observability.md)
        from megatron_tpu import telemetry as _telemetry

        self.telemetry = _telemetry.for_training(t, log=self.log)
        if self.telemetry is not None:
            self.telemetry.emit(
                "run_start", iteration=self.iteration,
                consumed_samples=self.consumed_samples,
                mesh={k: int(v) for k, v in dict(self.rt.mesh.shape).items()},
                model_flops_per_token_fwd=model_cfg.flops_per_token_fwd(),
                async_loop=t.async_loop, prefetch_depth=t.prefetch_depth,
                metrics_lag=t.metrics_lag,
                compilation_cache_dir=t.compilation_cache_dir,
                # host identity on the run record: every later event in
                # this journal is attributable to one host of the
                # cluster (tools/telemetry_report.py merges per-host
                # journals off exactly this field)
                **({"host": self.coord.host,
                    "num_hosts": self.coord.num_hosts}
                   if self.coord is not None else {}))
            if self._elastic_resume is not None:
                # the topology changed under the run (detected in _load,
                # journaled here because telemetry outlives _load)
                self.telemetry.emit("elastic_resume", **self._elastic_resume)

        if self.coord is not None:
            # sideband liveness: heartbeats + peer abort/death polling on
            # a bounded daemon thread, so even a host wedged inside a
            # collective observes a peer's poison record and exits
            # PEER_ABORT_EXIT_CODE instead of waiting for the scheduler.
            # Started after telemetry so the verdict can be journaled;
            # stopped in train()'s finally after the last commit flushed.
            self.coord.start_watchdog(self._on_peer_abort)

    # -- placed (interleaved) layer order -----------------------------------

    def _permute_state(self, state, to_placed: bool):
        """Permute the layer subtrees of every params-like tree in the
        state between canonical and placed order (identity unless VPP)."""
        if self._vpp_perms is None:
            return state
        idx = self._vpp_perms[0] if to_placed else self._vpp_perms[1]

        def fix(tree):
            if tree is None or "layers" not in tree:
                return tree
            layers = jax.tree.map(lambda a: jnp.take(a, idx, axis=0),
                                  tree["layers"])
            return {**tree, "layers": layers}

        out = dataclasses.replace(state, params=fix(state.params),
                                  master=fix(state.master), mu=fix(state.mu),
                                  nu=fix(state.nu))
        # the eager take drops sharding; restore the state placement
        return jax.device_put(out, self.state_shardings)

    # -- checkpoint ---------------------------------------------------------

    def _load(self):
        t = self.cfg.training
        pinned = None
        if self.coord is not None:
            # cluster-consistent resume: every host publishes the
            # checkpoint iterations IT holds valid (per-host manifests
            # verified by list_valid_checkpoints) and the cluster loads
            # the newest one valid EVERYWHERE — a host whose tracker ran
            # ahead of a two-phase commit its peers never finished is
            # pulled back here instead of resuming a torn cluster state
            valid = checkpointing.list_valid_checkpoints(t.load)
            pinned = self.coord.agree_resume_iteration(valid)
            if pinned is None:
                self.log(
                    "coordination: no checkpoint is valid on every host "
                    f"(local valid: {valid}); all hosts start fresh")
                return
            local = checkpointing.read_tracker(t.load)
            if local != pinned:
                self.log(
                    f"coordination: local tracker points at {local} but "
                    f"the cluster-consistent checkpoint is {pinned} — "
                    "loading the agreed iteration")
        try:
            state, it, consumed = checkpointing.load_checkpoint(
                t.load, self.state, shardings=self.state_shardings,
                iteration=pinned,
                finetune=t.finetune, no_load_optim=t.no_load_optim,
                config=self._save_config)
        except FileNotFoundError:
            self.log(f"no checkpoint found in {t.load}, starting fresh")
            return
        self.state = state
        self.iteration = it
        self.consumed_samples = consumed
        self.log(f"loaded checkpoint at iteration {it} "
                 f"(consumed {consumed} samples)")
        self._detect_topology_change(t)

    def _detect_topology_change(self, t):
        """Elastic resume: the checkpoint layer is topology-free (orbax
        sharding metadata reshard on load), so a dp change only moves the
        gradient-accumulation split — the global batch, sample order, and
        LR schedule stay invariant (MicroBatchCalculator validated that
        at __init__, with a loud error naming the valid choices when it
        can't hold). Here we merely detect and record the change so the
        journal shows it and operators see the re-derivation."""
        try:
            saved = checkpointing.saved_run_config(t.load)
        except (OSError, ValueError, FileNotFoundError):
            return  # pre-config checkpoint: nothing to compare
        saved_t = saved.get("training") or {}
        saved_par = saved.get("parallel") or {}
        saved_dp = saved_par.get("data_parallel")
        saved_mb = saved_t.get("micro_batch_size", t.micro_batch_size)
        saved_gbs = saved_t.get("global_batch_size", t.global_batch_size)
        # model-parallel and host-topology changes ride the same
        # detection: the checkpoint layer is topology-free (orbax
        # reshards on load), so tp/pp/host-count changes are legal — but
        # they must be VISIBLE (journaled elastic_resume), never silent
        saved_tp = int(saved_par.get("tensor_parallel") or self.rt.tp)
        saved_pp = int(saved_par.get("pipeline_parallel") or self.rt.pp)
        saved_cp = int(saved_par.get("context_parallel") or self.rt.cp)
        saved_hosts = int((saved.get("coordination") or {}).get(
            "num_hosts") or 0)
        cur_hosts = self.coord.num_hosts if self.coord else 1
        if not saved_dp:
            return
        saved_dp, saved_mb = int(saved_dp), int(saved_mb)
        saved_gbs = int(saved_gbs)
        gbs = t.global_batch_size
        if saved_gbs != gbs:
            # a DIVISIBLE gbs change sails through MicroBatchCalculator,
            # but it re-times the LR schedule and re-phases sample order
            # against consumed_samples — legal for a deliberate schedule
            # change, catastrophic as an accident. Loud, and on the
            # journal, either way.
            warnings.warn(
                f"resuming with --global_batch_size {gbs} but the "
                f"checkpoint was written at {saved_gbs}: sample order and "
                f"the LR schedule will DIFFER from the saved run (elastic "
                f"resume keeps the global batch invariant — only "
                f"micro_batch_size / data_parallel may change); continuing "
                "only makes sense as a deliberate schedule change")
        changed_dp = saved_dp != self.rt.dp
        changed_mb = saved_mb != t.micro_batch_size
        changed_mp = (saved_tp != self.rt.tp or saved_pp != self.rt.pp
                      or saved_cp != self.rt.cp)
        changed_hosts = bool(saved_hosts) and saved_hosts != cur_hosts
        if not (changed_dp or changed_mb or changed_mp or changed_hosts
                or saved_gbs != gbs):
            return
        accum_from = saved_gbs // max(saved_mb * saved_dp, 1)
        accum_to = gbs // (t.micro_batch_size * self.rt.dp)
        self._elastic_resume = {
            "iteration": self.iteration,
            "from_dp": saved_dp, "to_dp": self.rt.dp,
            "from_micro_batch": saved_mb,
            "to_micro_batch": t.micro_batch_size,
            "from_global_batch": saved_gbs,
            "global_batch_size": gbs,
            "accum_from": accum_from, "accum_to": accum_to,
            "from_tp": saved_tp, "to_tp": self.rt.tp,
            "from_pp": saved_pp, "to_pp": self.rt.pp,
            "from_hosts": saved_hosts or cur_hosts, "to_hosts": cur_hosts,
        }
        mp_note = ""
        if changed_mp:
            mp_note = (f"; model parallelism tp {saved_tp}->{self.rt.tp} "
                       f"pp {saved_pp}->{self.rt.pp} (orbax reshard on "
                       "load; sample order unaffected)")
        if changed_hosts:
            mp_note += f"; hosts {saved_hosts}->{cur_hosts}"
        self.log(
            f"elastic resume: checkpoint written at data_parallel="
            f"{saved_dp} x micro_batch={saved_mb} (accumulation "
            f"{accum_from}), resuming at data_parallel={self.rt.dp} x "
            f"micro_batch={t.micro_batch_size} (accumulation {accum_to}) "
            + (f"— WARNING: global batch changed {saved_gbs} -> {gbs}"
               if saved_gbs != gbs else
               f"— global batch {gbs}, sample order, and "
               f"consumed_samples={self.consumed_samples} are unchanged")
            + mp_note)

    def save(self, tags: Tuple[str, ...] = ()):
        t = self.cfg.training
        if not t.save:
            return
        # the save-checkpoint span measures the train-loop STALL: with
        # async_save that is the barrier on the previous save + the
        # device->host copy; the serialization/write/commit runs on the
        # saver's finalizer thread while the next steps compute
        self.timers("save-checkpoint", 0).start()
        # checkpoints are always canonical layer order (topology-portable)
        state = self._permute_state(self.state, to_placed=False)
        if self._saver is None:
            self._saver = checkpointing.AsyncCheckpointSaver(
                t.save, keep_latest_k=t.keep_latest_k, log=self.log,
                async_save=t.async_save,
                # journal_sink: commit events also feed the /metrics
                # event counters (train_commit_aborts_total)
                journal=(self.telemetry.journal_sink()
                         if self.telemetry else None))
        # per-save coordinator (the ONE wiring point): coordinated
        # two-phase commit normally; dropped on a solo drain (exit
        # agreement unreachable) so the commit doesn't wait on the peers
        # the agreement already proved unreachable — resume's valid-set
        # intersection keeps the cluster consistent around a solo commit
        self._saver.coordinator = None if self._commit_solo else self.coord
        self._saver.save(state, self.iteration, self.consumed_samples,
                         config=self._save_config, tags=tags)
        self._last_save_iter = self.iteration
        self.timers("save-checkpoint", 0).stop()
        if self.telemetry is not None:
            # the span above is the train-loop STALL (async: barrier +
            # host copy), i.e. wall-clock the step loop did NOT train
            self.telemetry.stall(
                "checkpoint_stall", self.timers.last_s("save-checkpoint"),
                iteration=self.iteration)

    def _flush_saves(self):
        """Barrier on any in-flight checkpoint write — the forced flush on
        every exit path (normal return, SIGTERM, exception)."""
        if self._saver is not None:
            self._saver.wait()

    def _cadence_due(self) -> bool:
        """--save_interval auto: is a checkpoint due this iteration?
        Feeds the tuner any newly observed commit latency and journals
        `cadence_retune` when the derived interval moves."""
        t = self.cfg.training
        if not t.save:
            return False
        if (self._saver is not None
                and self._saver.last_commit_seconds is not None
                and self._saver.last_commit_seconds
                != self._cadence_commit_seen):
            self._cadence_commit_seen = self._saver.last_commit_seconds
            self._cadence.note_commit(self._cadence_commit_seen)
        retune = self._cadence.retune()
        if retune is not None:
            self.log(
                f"save cadence: interval {retune['from_interval']} -> "
                f"{retune['to_interval']} steps (grace "
                f"{retune['grace_s']:g}s - p95 commit "
                f"{retune['p95_commit_ms']:g}ms over p50 step "
                f"{retune['p50_step_ms']:g}ms, floor {retune['floor']})")
            if self.telemetry is not None:
                self.telemetry.emit("cadence_retune", iteration=self.iteration,
                                    **retune)
        interval = self._cadence.interval()
        if not interval:
            return False
        return (self.iteration - self._last_save_iter) >= interval

    # -- preemption / hang / SDC sentinels -----------------------------------

    def _preempt_save(self, sig, already_saved: bool = False) -> None:
        """Expedited preemption path: the first SIGTERM already drained
        the metrics pipeline (caller); here the loop forces a SYNCHRONOUS
        committed checkpoint — bypassing --save_interval, tagged
        "preemption" in the manifest so retention never prunes it —
        bounded by --preempt_save_timeout, then journals a `preemption`
        event with the notice->commit latency. A save that misses the
        deadline force-exits PREEMPT_TIMEOUT_EXIT_CODE: overstaying a
        preemption notice means the scheduler's SIGKILL lands mid-write
        anyway, so dying deliberately with the journal flushed is
        strictly better evidence.

        already_saved: the loop's periodic save this same pass already
        checkpointed exactly this iteration (save-interval arithmetic is
        identical on every host, so the skip is cluster-symmetric): only
        flush that commit durable instead of writing the state a second
        time — a duplicate full write could spend the remaining grace
        window for nothing (and, coordinated, would open a second commit
        attempt a completer that already exited can never vote in). The
        tracker points at the periodic checkpoint, so retention keeps it
        even without the `preemption` tag."""
        t = self.cfg.training
        self._stop_watchdog()  # the preempt deadline takes over
        first = sig.first_signal()
        notice_t = first[1] if first else time.monotonic()
        # a profile window still open would burn grace time and die torn
        # with the process — flush it NOW while the disk is still ours,
        # but never let the flush spend more than a sliver of the grace
        # window: the checkpoint is what the window exists to protect
        if self._profiling:
            flush_budget = 10.0
            if t.preempt_save_timeout:
                remaining = (t.preempt_save_timeout
                             - (time.monotonic() - notice_t))
                flush_budget = min(10.0, max(remaining * 0.2, 1.0))
            self._profile_abort("preemption",
                                flush_timeout_s=flush_budget)
        # the deadline is anchored at the NOTICE's arrival, not at this
        # call: the in-flight iteration + eval + drain between the two
        # already spent part of the grace window, and granting the save a
        # fresh full budget would overstay it — exactly what the knob
        # exists to prevent. If the budget is effectively gone, a short
        # floor still lets a small/fast checkpoint make it out the door.
        budget = (max(t.preempt_save_timeout
                      - (time.monotonic() - notice_t), 1.0)
                  if t.preempt_save_timeout else 0.0)
        timer = None
        committed = threading.Event()
        if t.preempt_save_timeout:
            def _overdue():
                # timer.cancel() cannot stop a callback already running:
                # a save that commits right AT the deadline must not be
                # reported as a timeout after the fact — re-check the
                # commit flag here and again just before dying
                if committed.is_set():
                    return
                sys.stderr.write(
                    f"preemption checkpoint exceeded --preempt_save_timeout"
                    f"={t.preempt_save_timeout}s; forcing exit "
                    f"{resilience.PREEMPT_TIMEOUT_EXIT_CODE}\n")
                sys.stderr.flush()

                def _journal_timeout():
                    if self.telemetry is None:
                        return
                    self.telemetry.emit(
                        "preemption_timeout", iteration=self.iteration,
                        timeout_s=t.preempt_save_timeout)
                    if self.telemetry.journal is not None:
                        try:
                            self.telemetry.journal.flush()
                        except OSError:
                            pass

                # the journal may share the wedged filesystem that
                # stalled the save — attempt it on a bounded helper so a
                # dead mount can never stall the forced exit itself (the
                # same reason the second-signal escape writes only
                # stderr)
                jt = threading.Thread(target=_journal_timeout, daemon=True)
                jt.start()
                jt.join(timeout=5.0)
                if committed.is_set():
                    return
                if self.coord is not None:
                    # poison record: peers must not wait for a commit
                    # vote this host will never cast
                    self.coord.publish_abort(
                        "preempt_timeout", iteration=self.iteration)
                os._exit(resilience.PREEMPT_TIMEOUT_EXIT_CODE)

            timer = threading.Timer(budget, _overdue)
            timer.daemon = True
            timer.start()
        try:
            t0 = time.monotonic()
            if not already_saved:
                self.save(tags=("preemption",))
            self._flush_saves()  # commit NOW — the exit must find it durable
            t1 = time.monotonic()
        finally:
            committed.set()
            if timer is not None:
                timer.cancel()
        save_ms = (t1 - t0) * 1e3
        notice_ms = (t1 - notice_t) * 1e3
        self.log(f"preemption checkpoint committed at iteration "
                 f"{self.iteration} (save {save_ms:.0f} ms, "
                 f"notice->commit {notice_ms:.0f} ms"
                 + ("" if t.save else "; no --save dir: nothing written")
                 + ")")
        if self.telemetry is not None:
            extra = {}
            if self.coord is not None:
                # which host the cluster's notice landed on (the signal
                # agreement protocol carried it here) + who is reporting
                extra = {"notice_host": self._notice_host,
                         "host": self.coord.host}
            if already_saved:
                extra["pre_saved"] = True  # periodic save covered it
            self.telemetry.emit(
                "preemption", iteration=self.iteration,
                signal="SIGTERM", consumed_samples=self.consumed_samples,
                save_latency_ms=round(save_ms, 1),
                notice_to_commit_ms=round(notice_ms, 1),
                save_timeout_s=t.preempt_save_timeout,
                saved=bool(t.save), **extra)

    def _heartbeat(self, note: str) -> None:
        """Progress beat shared by the flight recorder and the step
        watchdog — called once per processed record and after save/eval
        stalls, so both deadline monitors measure the same liveness."""
        if self.telemetry is not None:
            self.telemetry.heartbeat(note)
        if self._watchdog is not None:
            self._watchdog.beat()

    def _stop_watchdog(self) -> None:
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None

    def _on_hang(self, age: float) -> None:
        """StepWatchdog verdict (runs on the watchdog thread): the loop
        made no progress past --step_timeout_s. Dump a flight-recorder
        bundle (reusing the armed recorder when there is one), journal
        `hang_detected`, and exit HANG_EXIT_CODE cleanly — a diagnosable
        deliberate abort instead of an infinite hang that ends in an
        evidence-destroying timeout kill."""
        t = self.cfg.training
        stuck_at = self.iteration + 1  # the step in flight
        self.log(f"step watchdog: no progress for {age:.1f}s "
                 f"(step_timeout_s={t.step_timeout_s}) at iteration "
                 f"~{stuck_at} — dumping flight bundle and aborting")
        # os._exit below would tear a live trace window; flush it first —
        # a trace ENDING at the hang is exactly the evidence wanted
        self._profile_abort("hang")
        bundle = None
        try:
            flight = self.telemetry.flight if self.telemetry else None
            if flight is not None:
                # both watchdogs armed: park the recorder's own watch
                # thread first so one hang yields one bundle and one
                # abort (ours), not a dump/SIGABRT race
                flight.stop()
            if flight is None:
                from megatron_tpu.telemetry.flight_recorder import (
                    FlightRecorder,
                )

                base = t.telemetry_dir or t.save
                out = (os.path.join(base, "flight_bundles") if base
                       else "flight_bundles")
                flight = FlightRecorder(
                    out_dir=out, deadline_s=t.step_timeout_s,
                    journal=(self.telemetry.journal if self.telemetry
                             else None), log=self.log)
            bundle = flight.dump(
                reason=f"step watchdog: no heartbeat for {age:.1f}s "
                       f"(step_timeout_s={t.step_timeout_s})")
            self.log(f"step watchdog: bundle written to {bundle}")
        except Exception as e:  # noqa: BLE001 - the abort must proceed
            # even when the bundle can't be written (full disk): a hang
            # turning into an un-diagnosed but CLEAN abort still beats a
            # timeout kill
            self.log(f"step watchdog: bundle dump failed: {e}")
        if self.telemetry is not None:
            self.telemetry.emit(
                "hang_detected", iteration=stuck_at,
                heartbeat_age_s=round(age, 1),
                step_timeout_s=t.step_timeout_s, bundle=bundle)
            if self.telemetry.journal is not None:
                try:
                    self.telemetry.journal.flush()
                except OSError:
                    pass
        if self.coord is not None:
            # poison record BEFORE dying: peers abort with a journaled
            # peer_abort{host, cause:"hang"} instead of wedging in the
            # collective this host just abandoned
            self.coord.publish_abort("hang", iteration=stuck_at,
                                     heartbeat_age_s=round(age, 1))
        os._exit(resilience.HANG_EXIT_CODE)

    def _on_peer_abort(self, verdict: Dict[str, Any]) -> None:
        """A peer died (poison record, or heartbeat silence past
        --peer_death_timeout_s): journal `peer_abort{host, cause}`, flush,
        and exit PEER_ABORT_EXIT_CODE — a deliberate, attributable abort
        instead of hanging in the next collective until the scheduler's
        timeout kill. Runs on the sideband thread or inline from the
        between-steps poll."""
        host, cause = verdict.get("host"), verdict.get("cause")
        self.log(f"peer abort: host {host} ({cause}) — exiting "
                 f"{resilience.PEER_ABORT_EXIT_CODE} "
                 f"({verdict.get('detail', '')})")
        self._profile_abort("peer_abort")  # os._exit would tear the trace
        if self.telemetry is not None:
            self.telemetry.emit(
                "peer_abort", host=host, cause=cause,
                detail=verdict.get("detail"),
                iteration=self.iteration,
                observed_by=(self.coord.host if self.coord else None))
            if self.telemetry.journal is not None:
                try:
                    self.telemetry.journal.flush()
                except OSError:
                    pass
        os._exit(resilience.PEER_ABORT_EXIT_CODE)

    def _note_fingerprint(self, batch: Dict[str, np.ndarray],
                          iteration: int) -> Dict[str, np.ndarray]:
        """Record the host batch's crc32 for `iteration` (keyed so the
        lagged _process_record can attach it to the right step record).
        Runs on the prefetcher's worker thread in async mode — dict
        writes are GIL-atomic and each iteration has its own key."""
        if self.cfg.training.log_data_fingerprint:
            self._batch_fps[iteration] = resilience.batch_fingerprint(batch)
        return batch

    def _snapshot_state(self):
        """Bitwise copy of the training state on its own shardings — the
        replay check's pre-step retention. Jitted so sharded leaves stay
        in place (an eager jnp.copy would gather); the input is NOT
        donated, so the live state is untouched."""
        if not hasattr(self, "_snapshot_fn"):
            self._snapshot_fn = jax.jit(
                lambda s: jax.tree.map(jnp.copy, s),
                in_shardings=(self.state_shardings,),
                out_shardings=self.state_shardings)
        with jax.sharding.set_mesh(self.rt.mesh):
            return self._snapshot_fn(self.state)

    def _replay_check(self, pre_state, device_batch, metrics) -> None:
        """SDC sentinel (--replay_check_interval): re-run the jitted step
        on the retained (pre-step state, batch) and compare the committed
        outputs BITWISE. XLA programs are deterministic for fixed inputs
        — reduction order is compiled in — so ANY drift means the first
        execution was corrupted (flipped bit in HBM, bad ALU, torn DMA):
        journal `sdc_detected` with the mismatching leaf paths and abort.
        The injectable `corrupt_step:ITER` fault flips one params bit
        after the committed step so this path is deterministically
        testable."""
        it = self.iteration  # train_step_placed already advanced it
        t0 = time.perf_counter()
        if resilience.fault_active("corrupt_step", it):
            self.state = dataclasses.replace(
                self.state,
                params=resilience.corrupt_params(self.state.params, it))
        gbs = next(iter(device_batch.values())).shape[0]
        n_micro = gbs // (self.cfg.training.micro_batch_size * self.rt.dp)
        step = self._train_step_for(max(n_micro, 1))
        if not hasattr(self, "_replay_eq_fn"):
            # device-side comparison: each leaf reduces to one scalar
            # bool where it lives, so nothing but verdicts crosses to
            # the host — sharded/multi-host state never gathers
            self._replay_eq_fn = jax.jit(resilience.bitwise_equal_tree)
        with jax.sharding.set_mesh(self.rt.mesh):
            replay_state, replay_metrics = step(pre_state, device_batch)
            eq = self._replay_eq_fn(
                {"state": self.state, "metrics": metrics},
                {"state": replay_state, "metrics": replay_metrics})
        bad = resilience.mismatch_paths(eq)
        seconds = time.perf_counter() - t0
        if self.telemetry is not None:
            self.telemetry.goodput.attribute("other", seconds)
            self.telemetry.emit(
                "replay_check", iteration=it, ok=not bad,
                seconds=round(seconds, 4))
        if bad:
            if self.telemetry is not None:
                self.telemetry.emit("sdc_detected", iteration=it,
                                    leaves=bad)
                if self.telemetry.journal is not None:
                    self.telemetry.journal.flush()
            raise resilience.SDCError(
                f"silent data corruption at iteration {it}: replaying the "
                f"step on the retained batch diverged bitwise at "
                f"{len(bad)} leaf path(s), first: {bad}")
        self.log(f"replay check: iteration {it} bitwise-identical "
                 f"({seconds * 1e3:.0f} ms)")

    def _handle_divergence(self, reason: str,
                           trip_iter: Optional[int] = None) -> bool:
        """Sentinel tripped: roll back to the newest valid checkpoint (with
        --rollback_on_divergence, while rollbacks remain) or raise
        DivergenceError with the full diagnostic. Returns True after a
        rollback so the loop rebuilds its data iterator.

        trip_iter is the iteration whose metrics tripped the sentinel —
        with the async loop's lagged metrics it can be up to K behind
        self.iteration; the in-flight steps past it are discarded by the
        restore, and the fast-forward bound stays at trip_iter so the
        post-rollback trajectory matches the synchronous loop's exactly."""
        t = self.cfg.training
        trip_iter = self.iteration if trip_iter is None else trip_iter
        diag = (f"divergence sentinel tripped at iteration "
                f"{trip_iter}: {reason}")
        if self.telemetry is not None:
            self.telemetry.emit(
                "divergence", iteration=trip_iter, reason=reason,
                action=("rollback" if t.rollback_on_divergence
                        and self._rollbacks < t.max_rollbacks else "abort"))
        if not t.rollback_on_divergence:
            self.log(diag + " — aborting (use --rollback_on_divergence "
                     "to auto-recover from the last good checkpoint)")
            raise resilience.DivergenceError(diag)
        if self._rollbacks >= t.max_rollbacks:
            raise resilience.DivergenceError(
                f"{diag} — giving up after {self._rollbacks} rollbacks "
                f"(max_rollbacks={t.max_rollbacks}); the model re-diverges "
                "after every restore")
        # roll back to our own saves first; a resumed/finetune run that
        # diverges before its first save still has the checkpoint it was
        # launched from in t.load
        sources = [s for s in dict.fromkeys((t.save, t.load)) if s]
        if not sources:
            raise resilience.DivergenceError(
                diag + " — no --save/--load directory to roll back to")
        self._flush_saves()  # never roll back onto a half-written save
        t_rollback = time.perf_counter()
        state = None
        errors = []
        for src in sources:
            try:
                state, it, consumed = checkpointing.load_checkpoint(
                    src, self._permute_state(self.state, to_placed=False),
                    shardings=self.state_shardings, config=self._save_config)
                break
            except FileNotFoundError as e:
                errors.append(str(e))
        if state is None:
            raise resilience.DivergenceError(
                f"{diag} — no valid checkpoint to roll back to "
                f"({'; '.join(errors)})")
        self.state = self._permute_state(state, to_placed=True)
        self.iteration = it
        self.consumed_samples = consumed
        self._rollbacks += 1
        self._skip_data_until = trip_iter
        self._sentinel.reset()
        if self.telemetry is not None:
            # the fast-forward through [it, trip_iter) is attributed
            # per-iteration in the loop; this covers the restore itself
            self.telemetry.stall(
                "rollback_replay", time.perf_counter() - t_rollback,
                event="restore", from_iteration=trip_iter, to_iteration=it,
                rollback=self._rollbacks)
        self.log(f"{diag} — rolled back to checkpoint at iteration {it} "
                 f"(rollback {self._rollbacks}/{t.max_rollbacks}); "
                 f"fast-forwarding data through iteration {trip_iter} to "
                 "skip the poison window")
        return True

    # -- steps --------------------------------------------------------------

    def _train_step_for(self, num_microbatches: int) -> Callable:
        """Jitted step per microbatch count (rampup re-jits per level,
        like the reference re-deriving num_microbatches per iteration)."""
        if self.fixed_num_microbatches is not None:
            num_microbatches = self.fixed_num_microbatches
        if num_microbatches not in self._step_cache:
            pp = self.rt.pp
            pp_loss_fn = None
            if pp > 1 and self.pipeline_loss_factory is not None:
                pp_loss_fn = self.pipeline_loss_factory(
                    self.cfg.model, self.rt.mesh, pp, num_microbatches,
                    self.cfg.training.recompute_granularity)
            elif pp > 1 and self.loss_fn is None:
                recompute = self.cfg.training.recompute_granularity
                pp_loss_fn = make_pipeline_loss_fn(
                    self.cfg.model, self.rt.mesh, pp, num_microbatches,
                    recompute=recompute,
                    sharder=self._sharder,
                    num_virtual_chunks=(
                        self.cfg.parallel.virtual_pipeline_parallel or 1),
                    # full recompute = the memory-pressure regime: also
                    # segment the tick scan so live carries stay at the
                    # 1F1B-like ~2*pp bound instead of one per tick
                    remat_segment=pp if is_full_remat_family(recompute) else None,
                    # the state stores layers in placed order (see __init__)
                    layers_placed=self._vpp_perms is not None)
            step = make_train_step(
                self.cfg.model, self.cfg.optimizer, self.cfg.training,
                num_microbatches=num_microbatches,
                train_iters=self.cfg.training.train_iters or 1,
                sharder=self._sharder,
                loss_fn=self.loss_fn,
                pipeline_loss_fn=pp_loss_fn)
            # batch leaves were placed by _put_batch (rank-aware specs);
            # let jit infer their shardings from the arguments. The OUTPUT
            # state is pinned to the same shardings as the input — without
            # this, XLA may emit e.g. data-sharded masters from a ZeRO-1
            # step and the next call rejects its own output as input
            self._step_cache[num_microbatches] = jax.jit(
                step,
                in_shardings=(self.state_shardings, None),
                out_shardings=(self.state_shardings, None),
                donate_argnums=(0,))
        return self._step_cache[num_microbatches]

    def _params_norm(self) -> float:
        """Global params L2 (ref calc_params_l2_norm, utils.py:33-80)."""
        if not hasattr(self, "_params_norm_fn"):
            self._params_norm_fn = jax.jit(lambda p: jnp.sqrt(sum(
                jnp.sum(jnp.square(x.astype(jnp.float32)))
                for x in jax.tree.leaves(p))))
        return float(self._params_norm_fn(self.state.params))

    def _memory_stats(self) -> Dict[str, float]:
        """Device memory scalars (ref report_memory, utils.py:82-97);
        empty on backends without memory_stats (CPU)."""
        stats = jax.local_devices()[0].memory_stats() or {}
        out = {}
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if k in stats:
                out[k.replace("bytes", "mb")] = stats[k] / 1e6
        return out

    def _put_batch(self, batch: Dict[str, np.ndarray]) -> Dict[str, jnp.ndarray]:
        multihost = jax.process_count() > 1
        if multihost:
            from megatron_tpu.parallel.distributed import host_batch_slice

            rows = next(iter(batch.values())).shape[0]
            lo, hi = host_batch_slice(self.rt, rows)

        def put(v):
            if v.ndim == 1:  # per-sample scalars (e.g. BERT is_random)
                from megatron_tpu.parallel.sharding import BATCH_AXES

                sh = NamedSharding(self.rt.mesh, P(BATCH_AXES))
            else:
                sh = self.batch_sharding
            if multihost:
                # each process contributes only its addressable rows
                return jax.make_array_from_process_local_data(
                    sh, np.asarray(v[lo:hi]), v.shape)
            return jax.device_put(v, sh)

        return {k: put(np.asarray(v)) for k, v in batch.items()}

    def _transfer(self, batch: Dict[str, np.ndarray]) -> Dict[str, Any]:
        """Host->device placement with honest spans: `batch-transfer-
        dispatch` is the host cost of ISSUING the copies, `batch-transfer`
        additionally waits for them to land (block_until_ready), so
        neither span lies about what it covers at any log level. Under the async loop the
        prefetcher places batches on its worker thread and the loop
        credits the same two spans from the worker's measurements
        (_credit_prefetch_spans)."""
        tm_all = self.timers("batch-transfer", 1)
        tm_disp = self.timers("batch-transfer-dispatch", 1)
        tm_all.start()
        tm_disp.start()
        device_batch = self._put_batch(batch)
        tm_disp.stop()
        if self.timers.log_level >= 1:
            jax.block_until_ready(device_batch)
        tm_all.stop()
        return device_batch

    def train_step(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        return self.train_step_placed(self._transfer(batch))

    def train_step_placed(self, device_batch: Dict[str, Any]
                          ) -> Dict[str, float]:
        """Dispatch one optimizer step on an already device-resident batch
        (the prefetcher's product). Returns DEVICE metrics — no host sync;
        the caller decides when to pay it (_fetch_metrics)."""
        gbs = next(iter(device_batch.values())).shape[0]
        n_micro = gbs // (self.cfg.training.micro_batch_size * self.rt.dp)
        step = self._train_step_for(max(n_micro, 1))
        with jax.sharding.set_mesh(self.rt.mesh):
            self.state, metrics = step(self.state, device_batch)
        if self._profiling and self._profiled_step is None:
            # the program this window traces, for _journal_step_program
            self._profiled_step = (
                step, self.fixed_num_microbatches or max(n_micro, 1),
                jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=x.sharding),
                    device_batch))
        self.iteration += 1
        self.consumed_samples += gbs
        return metrics

    def _fetch_metrics(self, metrics: Dict[str, Any]) -> Dict[str, Any]:
        """ONE blocking device->host sync fetching every step metric at
        once — the single permitted host sync per steady-state step (the
        sync-freedom invariant: host_sync_points / train_host_syncs_total,
        tests/test_prefetch.py)."""
        self.host_sync_points += 1
        if self.telemetry is not None:
            self.telemetry.host_syncs.inc()
        return jax.device_get(metrics)

    # -- async-loop plumbing -------------------------------------------------

    def _make_data_iter(self, factory, gbs: int, depth: int):
        """Iterator of batches at the current consumed_samples watermark:
        the raw host iterator (sync path), or a DevicePrefetcher that
        pulls/places/lands batches on a background thread (async path).
        The prefetcher's transform applies host-side fault injection with
        the iteration each batch will be consumed at, so faults hit the
        same batches in both modes."""
        it = factory(self.consumed_samples, gbs)
        if depth <= 0:
            return it
        self._prefetcher = prefetch.DevicePrefetcher(
            it, self._put_batch, depth=depth,
            first_iteration=self.iteration + 1,
            # fingerprint BEFORE fault poisoning: an injected nan_loss
            # must not read as a data-order change
            transform=(lambda b, i:
                       resilience.host_batch_faults(
                           self._note_fingerprint(b, i), i, self.log)))
        self._pf_credited = (0.0, 0.0)
        return self._prefetcher

    def _close_prefetcher(self) -> None:
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None

    def _credit_prefetch_spans(self) -> None:
        """Surface the prefetch worker's transfer time in the loop's
        timers (the spans the sync path records inline), as credited
        deltas once per pop."""
        pf = self._prefetcher
        if pf is None:
            return
        # single read of the worker-updated counters: re-reading at store
        # time would swallow any increment landing between delta and store
        put_now, land_now = pf.put_s, pf.land_s
        put, land = self._pf_credited
        d_put, d_land = put_now - put, land_now - land
        if d_put or d_land:
            self._pf_credited = (put_now, land_now)
            self.timers.record("batch-transfer-dispatch", d_put, level=1)
            self.timers.record("batch-transfer", d_put + d_land, level=1)

    def evaluate(self, data_iter: Iterator, eval_iters: int) -> Dict[str, float]:
        """Forward-only eval (ref: training.py:773-826)."""
        if self.eval_step is None:
            es = make_eval_step(self.cfg.model, self.cfg.training,
                                sharder=self._sharder,
                                loss_fn=self.eval_loss_fn)
            self.eval_step = jax.jit(es)
        total, count = 0.0, 0
        extras: Dict[str, float] = {}
        # eval runs the unpipelined loss: restore canonical layer order —
        # params only (permuting master/mu/nu too would move 4x the bytes)
        eval_params = self.state.params
        if self._vpp_perms is not None:
            inv = self._vpp_perms[1]
            eval_params = {
                **eval_params,
                "layers": jax.tree.map(lambda a: jnp.take(a, inv, axis=0),
                                       eval_params["layers"]),
            }
            eval_params = jax.device_put(
                eval_params, self.state_shardings.params)
        with jax.sharding.set_mesh(self.rt.mesh):
            for _ in range(eval_iters):
                batch = next(data_iter, None)
                if batch is None:
                    break
                out = self.eval_step(eval_params, self._put_batch(batch))
                total += float(out["lm_loss"])
                for k, v in out.items():
                    if k not in ("lm_loss", "ntokens"):
                        extras[k] = extras.get(k, 0.0) + float(v)
                count += 1
        loss = total / max(count, 1)
        out = {"lm_loss": loss, "ppl": float(np.exp(min(loss, 20.0)))}
        for m in extras:
            out[m] = extras[m] / max(count, 1)
        return out

    # -- profiling ----------------------------------------------------------

    def _profile_window(self):
        """jax.profiler trace windows — device + host timeline into the
        profile dir, the TPU-native equivalent of the reference's nsys
        runs; read the result with tools/trace_report.py.

        Two arming paths share one window: the static --profile window
        [profile_step_start, profile_step_end), and a SIGUSR1 received
        mid-run, which opens a --profile_signal_steps window at the next
        pass (on-demand incident profiling, no restart, no --profile
        required). Called before each iteration; self.iteration is the
        number of COMPLETED iterations, so start/stop fire before the
        steps whose 1-based index enters/leaves the window. Range (not
        equality) checks so a resume landing mid-window, or a start step
        the caller skipped, still gets a trace of the remaining
        window."""
        t = self.cfg.training
        nxt = self.iteration + 1
        if self._profiling:
            if self._profile_until is not None and nxt >= self._profile_until:
                self._profile_stop()
            return
        if self._profile_signal_pending:
            self._profile_signal_pending = False
            self._profile_start(nxt, nxt + max(t.profile_signal_steps, 1),
                                source="SIGUSR1")
        elif (t.profile
                and t.profile_step_start <= nxt < t.profile_step_end):
            self._profile_start(nxt, t.profile_step_end, source="--profile")

    def _profile_out_dir(self) -> str:
        t = self.cfg.training
        return (t.profile_dir or t.tensorboard_dir
                or (os.path.join(t.telemetry_dir, "traces")
                    if t.telemetry_dir else "runs/profile"))

    def _profile_start(self, start: int, until: int, source: str) -> None:
        out = self._profile_out_dir()
        try:
            capture.start(out)
        except Exception as e:  # noqa: BLE001 - a capture already owned
            # by /admin-style tooling (the profiler session is process-
            # global) must not kill the run; the window is just skipped
            self.log(f"profiler: could not start trace ({e})")
            return
        self._profiling = True
        self._profile_until = until
        self.log(f"profiler: tracing steps [{start}, {until}) to {out}")
        if self.telemetry is not None:
            self.telemetry.emit("profile_begin", iteration=start,
                                until=until, dir=out, source=source)

    def _profile_stop(self):
        if not self._profiling:
            return
        self._profiling = False
        self._profile_until = None
        try:
            capture.stop()
        except Exception as e:  # noqa: BLE001 - an abort path on another
            # thread (peer-abort sideband) may have closed the session
            # between our flag check and here; the journal has its story
            self.log(f"profiler: stop_trace failed ({e})")
            return
        self.log("profiler: trace written")
        if self.telemetry is not None:
            self.telemetry.emit("profile_end",
                                iteration=self.iteration,
                                dir=self._profile_out_dir())

    def _profile_abort(self, reason: str, flush: bool = True,
                       flush_timeout_s: float = 10.0) -> None:
        """Close a live trace window on an abort path. A window left
        open across os._exit (or burned grace time mid-preemption) is a
        torn, unreadable trace; flushing when the path allows it keeps
        the evidence, and either way `profile_aborted` lands in the
        journal so the post-mortem knows whether the file is usable.

        The flush runs on a bounded helper thread: stop_trace writes
        files and (on a real chip) collects device-side data, and the
        very conditions that bring us here — a hung step, a wedged
        filesystem — are the ones where it could block forever; a
        deliberate abort must never be stalled by its own evidence
        collection."""
        if not self._profiling:
            return
        self._profiling = False
        self._profile_until = None
        flushed = False
        if flush:
            done = threading.Event()

            def _flush():
                try:
                    capture.stop()
                    done.set()
                except Exception as e:  # noqa: BLE001 - the abort
                    # proceeds regardless; an unreadable trace is
                    # journaled below
                    self.log(f"profiler: abort flush failed: {e}")

            ft = threading.Thread(target=_flush, daemon=True)
            ft.start()
            ft.join(timeout=flush_timeout_s)
            flushed = done.is_set()
            if flushed:
                self.log(f"profiler: trace flushed on abort ({reason})")
            elif ft.is_alive():
                self.log("profiler: abort flush did not finish in "
                         f"{flush_timeout_s:.0f}s; trace may be torn")
        if self.telemetry is not None:
            self.telemetry.emit("profile_aborted", reason=reason,
                                flushed=flushed, iteration=self.iteration)

    def _journal_step_program(self) -> None:
        """One `step_program` record for a run that traced: what the
        compiler says the traced step program needs on a chip, which
        `peak_bytes_in_use` cannot (it leaves a program's temporaries
        out). The program is lowered again from the live state and the
        traced batch's avals and shardings, so its compile is a hit in the
        persistent cache; still seconds for a large program, which is why
        this runs once, after the loop has returned and its last
        checkpoint is committed, never inside a step or a trace window,
        and not at all in a run that opened no window. The record also
        counts the leaves whose gradient the step sums inside the kernel
        that makes it (`train_step.kernel_summed`), and their share of
        the parameters' elements; and, read from the same compiled
        program's text (analysis/step_program.py), where each of its
        collectives stands (`collectives`: region and scope of its name
        stack, kind, whether the chip compiler fused it into an operation
        a trace shows as a `fusion`, result bytes, group size, times a
        step) and which instructions carry no name stack at all
        (`unnamed_instructions`): what a trace's classes of work are
        checked against (docs/observability.md "Runtime traces"); and
        each Pallas kernel's calls with how many of them are a
        recomputation (`kernel_calls`: under `selective` the flash
        forward has none) and the shape the flash training kernels take K
        in (`flash_k_operands`: the KV heads', where they read K and V
        by KV head); and, for each kind of attention layer, the
        tiles a head of the flash kernels visits by class and the score
        elements they compute over the visible pairs (`attention_tiles`:
        `_attention_tiles`)."""
        if self._profiled_step is None or self.telemetry is None:
            return
        step, n_micro, batch_avals = self._profiled_step
        self._profiled_step = None
        params = self.state.params
        try:
            with jax.sharding.set_mesh(self.rt.mesh):
                compiled = step.lower(self.state, batch_avals).compile()
                ma = compiled.memory_analysis()
                text = compiled.as_text()
                where = step_program.collectives(text)
                unnamed = step_program.unnamed_instructions(text)
                kernels = step_program.kernel_calls(text)
                k_operands = step_program.flash_k_operands(text)
                # traced under the mesh, as the step was: the same answer
                summed = kernel_summed(
                    self.cfg.model, params, batch_avals, n_micro,
                    own_loss=self.loss_fn is None and self.rt.pp == 1)
        except Exception as e:  # noqa: BLE001 - a note for the journal
            # must not turn a finished run into a failed one
            self.log(f"profiler: step program not analysed ({e})")
            return
        sizes = [(p.size, s) for p, s in zip(jax.tree.leaves(params),
                                             jax.tree.leaves(summed))]
        self.telemetry.emit(
            "step_program", iteration=self.iteration,
            num_microbatches=n_micro,
            argument_bytes=int(ma.argument_size_in_bytes),
            temp_bytes=int(ma.temp_size_in_bytes),
            output_bytes=int(ma.output_size_in_bytes),
            alias_bytes=int(ma.alias_size_in_bytes),
            kernel_summed_leaves=sum(s for _, s in sizes),
            kernel_summed_share=(sum(n for n, s in sizes if s)
                                 / sum(n for n, _ in sizes)),
            collectives=where, unnamed_instructions=unnamed,
            kernel_calls=kernels, flash_k_operands=k_operands,
            attention_tiles=_attention_tiles(self.cfg.model, batch_avals))

    # -- loop ---------------------------------------------------------------

    def train(
        self,
        train_iter_factory: Callable[[int, int], Iterator[Dict[str, np.ndarray]]],
        valid_iter_factory: Optional[Callable[[], Iterator]] = None,
    ) -> TrainState:
        """train_iter_factory(consumed_samples, global_batch) returns an
        iterator of global batches at that batch size (rampup-aware)."""
        try:
            state = self._train_inner(train_iter_factory, valid_iter_factory)
            self._journal_step_program()
            return state
        except BaseException as e:  # noqa: BLE001 - re-raised below; the
            # catch exists ONLY to publish the cluster poison record so
            # peers stop cleanly instead of wedging in a collective
            if self.coord is not None:
                # any abnormal exit is a poison record: peers must stop
                # cleanly (PEER_ABORT_EXIT_CODE) rather than block in the
                # next collective on a host that is unwinding its stack —
                # this covers DivergenceError/SDCError aborts and plain
                # crashes alike (the hang/preempt-timeout paths publish
                # their own cause before os._exit)
                self.coord.publish_abort(
                    type(e).__name__, iteration=self.iteration,
                    detail=str(e)[:300])
            raise
        finally:
            # forced flush: every exit path (normal return, SIGTERM,
            # exception) barriers on the in-flight async checkpoint write
            # so a committed tracker is what the next resume finds
            try:
                self._flush_saves()
            finally:
                if self.coord is not None:
                    # after the flush: the commit barrier needs the
                    # sideband alive to turn a peer death during the
                    # final commit into a clean exit
                    self.coord.stop_watchdog()
            if self.telemetry is not None:
                # after the flush so the last checkpoint_commit event is
                # in the journal before the final goodput line; run_end
                # records which signal (if any) ended the run so a
                # post-mortem can tell preemption from operator interrupt
                self.telemetry.close(
                    **({"received_signal": self._exit_signal}
                       if self._exit_signal else {}))

    def _reset_log_window(self) -> None:
        self._win_tokens = 0
        self._win_t0 = time.time()
        self._win_loss = 0.0
        self._win_n = 0

    def _process_record(self, rec: Dict[str, Any]) -> bool:
        """Consume one pipeline record — a dispatched step's device
        metrics, or a skipped iteration — in dispatch order: host-fetch,
        journal/metrics, sentinel, log-window bookkeeping. With
        --metrics_lag K the loop calls this K records behind dispatch, so
        the single blocking fetch here overlaps the K newer steps already
        in flight. Returns True when the sentinel tripped AND
        _handle_divergence rolled back (the caller resets its pipeline);
        a no-rollback trip raises DivergenceError out of here."""
        it = rec["iteration"]
        if "skip_reason" in rec:
            self._batch_fps.pop(it, None)
            fast_forward = rec["skip_reason"] == "rollback_fast_forward"
            self.log(f"iteration {it}: update skipped "
                     + ("(post-rollback fast-forward)" if fast_forward
                        else "(--skip_iters)"))
            if self.telemetry is not None:
                self.telemetry.emit("step_skipped", iteration=it,
                                    reason=rec["skip_reason"])
            self._heartbeat(f"iteration {it} (skipped)")
            self._maybe_log_window(rec)
            return False

        host = rec["host"]
        if host is None:
            # lagged fetch: this wait is the device catching up — in
            # steady state it IS the device step time, which the
            # dispatch-only forward-backward-optimizer span cannot see
            fm = self.timers("metrics-fetch", 0)
            fm.start()
            host = self._fetch_metrics(rec["metrics"])
            fm.stop()
            step_s = rec["dispatch_s"] + self.timers.last_s("metrics-fetch")
        else:
            # lag 0: the fetch already happened inside the span
            step_s = rec["dispatch_s"]
        if self._cadence is not None:
            self._cadence.note_step(step_s)
        loss_host = float(host["loss"])
        self._last_host_metrics = host
        ntok = rec["ntok"]
        data_crc = self._batch_fps.pop(it, None)
        if self.telemetry is not None:
            extra = {"data_crc": data_crc} if data_crc else {}
            extra.update({k: round(float(host[k]), 4)
                          for k in STEP_METRICS if k in host})
            self.telemetry.step(
                it, step_s, ntok, rec["compile_delta"],
                loss=loss_host,
                lr=float(host["lr"]),
                grad_norm=float(host["grad_norm"]),
                skipped=bool(float(host.get("skipped", 0.0))),
                data_wait_ms=round(rec["data_wait_s"] * 1e3, 3),
                dispatch_ms=round(rec["dispatch_s"] * 1e3, 3),
                tokens_per_s=round(ntok / max(step_s, 1e-9), 1),
                model_tflops_per_s=round(
                    ntok / max(step_s, 1e-9)
                    * self._model_flops_per_token / 1e12, 3),
                consumed_samples=rec["consumed"],
                **extra)
        self._heartbeat(f"iteration {it}")

        if self._sentinel is not None:
            streak = host.get("skip_streak")
            step_skipped = bool(float(host.get("skipped", 0.0)))
            trip = self._sentinel.observe(
                loss_host, step_skipped,
                streak=(int(float(streak)) if streak is not None
                        else None))
            if trip is None and not step_skipped:
                self._healthy_steps += 1
                if (self._rollbacks
                        and it > self._skip_data_until
                        and self._healthy_steps
                        >= self._rollback_reset_after):
                    self.log(
                        f"sentinel: {self._healthy_steps} healthy"
                        " steps since the last rollback —"
                        " restoring the rollback budget")
                    self._rollbacks = 0
            else:
                self._healthy_steps = 0
            if trip and self._handle_divergence(trip, trip_iter=it):
                return True

        self._win_tokens += ntok
        self._win_loss += loss_host
        self._win_n += 1
        self._maybe_log_window(rec)
        return False

    def _maybe_log_window(self, rec: Dict[str, Any]) -> None:
        """Close the log window when the processed record's iteration hits
        log_interval (record iterations arrive in order, so the cadence is
        identical to the synchronous loop's)."""
        t = self.cfg.training
        it = rec["iteration"]
        if it % t.log_interval != 0:
            return
        if self._win_n == 0:
            # window had only skipped iterations: still close it (discard
            # timer accumulation too, or the next window's per-iteration
            # averages count two windows of elapsed)
            self.log(f"iteration {it}/{t.train_iters} | "
                     f"consumed samples: {rec['consumed']} | "
                     "all iterations in window skipped")
            self.timers.elapsed_ms(reset=True)
            self._win_tokens, self._win_t0 = 0, time.time()
            return
        metrics = self._last_host_metrics
        dt = time.time() - self._win_t0
        tps = self._win_tokens / max(dt, 1e-9)
        mfu_flops = tps * self._model_flops_per_token
        self.log(
            f"iteration {it}/{t.train_iters} | "
            f"consumed samples: {rec['consumed']} | "
            f"lm loss: {self._win_loss / max(self._win_n, 1):.6f} | "
            f"lr: {float(metrics['lr']):.3e} | "
            f"grad norm: {float(metrics['grad_norm']):.3f} | "
            f"skipped: {int(metrics['skipped'])} | "
            f"tokens/sec: {tps:,.0f} | "
            f"model TFLOP/s: {mfu_flops / 1e12:.1f}")
        self.writer.add_scalar("train/lm_loss",
                               self._win_loss / max(self._win_n, 1), it)
        self.writer.add_scalar("train/lr", float(metrics["lr"]), it)
        self.writer.add_scalar("train/grad_norm",
                               float(metrics["grad_norm"]), it)
        self.writer.add_scalar("train/tokens_per_sec", tps, it)
        if "num_zeros" in metrics:
            self.writer.add_scalar(
                "train/num_zeros", float(metrics["num_zeros"]), it)
        if t.log_batch_size:
            self.writer.add_scalar("train/global_batch_size",
                                   rec["gbs"], it)
        if t.log_world_size:
            self.writer.add_scalar("train/world_size",
                                   jax.device_count(), it)
        if t.log_params_norm:
            self.writer.add_scalar("train/params_norm",
                                   self._params_norm(), it)
        if t.log_memory:
            for k, v in self._memory_stats().items():
                self.writer.add_scalar(f"memory/{k}", v, it)
        # per-span wall clock, averaged per iteration over the window
        # (ref: timers.log / --log_timers_to_tensorboard,
        # megatron/timers.py:79-96)
        if t.log_timers_to_tensorboard:
            for name, ms in self.timers.elapsed_ms(reset=False).items():
                self.writer.add_scalar(
                    f"timers/{name}", ms / max(self._win_n, 1), it)
        ts = self.timers.log_string(normalizer=max(self._win_n, 1))
        if ts:
            self.log(ts)
        if self.telemetry is not None:
            self.telemetry.emit("goodput", iteration=it,
                                **self.telemetry.goodput_report())
        self.writer.flush()
        self._win_tokens, self._win_t0 = 0, time.time()
        self._win_loss, self._win_n = 0.0, 0

    def _train_inner(self, train_iter_factory, valid_iter_factory):
        t = self.cfg.training
        if t.eval_only:
            if valid_iter_factory is None:
                self.log("--eval_only with no validation data; nothing to do")
                return self.state
            ev = self.evaluate(valid_iter_factory(), t.eval_iters)
            self.log(f"validation | lm loss: {ev['lm_loss']:.6f} | "
                     f"ppl: {ev['ppl']:.3f}")
            return self.state
        self._model_flops_per_token = \
            3.0 * self.cfg.model.flops_per_token_fwd()
        start_time = time.time()
        self._reset_log_window()
        self._last_host_metrics = None

        # Async goodput loop: dispatch-ahead with device-resident metrics.
        # The prefetcher lands step N+1's batch while step N computes; lag
        # K leaves up to K dispatched steps' metrics un-fetched so the
        # host never blocks between pop and the next dispatch. Records
        # flow through `pending` strictly in dispatch order; lag 0 + depth
        # 0 IS the synchronous loop (--no_async_loop) — one code path, so
        # the two modes are bitwise-identical by construction
        # (tests/test_prefetch.py differential tests).
        lag = max(t.metrics_lag, 0) if t.async_loop else 0
        depth = max(t.prefetch_depth, 0) if t.async_loop else 0
        pending: collections.deque = collections.deque()

        last_saved = None
        # a trace window still open at ANY exit from the loop (SIGTERM,
        # exit_interval, exhaustion, exception) must be closed or the
        # profile file is corrupt; same for the prefetch worker
        with DistributedSignalHandler() as sig, contextlib.ExitStack() as _s:
            _s.callback(self._profile_stop)
            _s.callback(self._close_prefetcher)
            if threading.current_thread() is threading.main_thread():
                # SIGUSR1 = on-demand profile window (the handler only
                # sets a flag; _profile_window opens the trace at the
                # next pass, off signal context)
                prev_usr1 = signal_module.signal(
                    signal_module.SIGUSR1,
                    lambda s, f: setattr(self, "_profile_signal_pending",
                                         True))
                _s.callback(signal_module.signal,
                            signal_module.SIGUSR1, prev_usr1)
            if t.step_timeout_s:
                # hang sentinel: deadline clock starts at the FIRST
                # processed step, so the initial compile is exempt
                self._watchdog = resilience.StepWatchdog(
                    t.step_timeout_s, self._on_hang).start()
                _s.callback(self._stop_watchdog)
            data_iter = None
            current_gbs = None
            # one pass of the loop below is one `train-pass` span on the
            # profiler's clock, numbered by the iteration it dispatches;
            # the timers' spans nest inside it (training/timers.py)
            pass_span = _s.enter_context(contextlib.ExitStack())

            def drain(n_keep: int) -> bool:
                """Process pending records down to n_keep, oldest first;
                True if one tripped the sentinel into a rollback."""
                while len(pending) > n_keep:
                    if self._process_record(pending.popleft()):
                        return True
                return False

            def on_rollback():
                """Reset the loop's pipeline after _handle_divergence
                reloaded the state: everything in flight (pending metric
                records, prefetched batches) belongs to the discarded
                trajectory, and the contaminated logging window goes too."""
                nonlocal data_iter, current_gbs
                pending.clear()
                self._batch_fps.clear()
                self._close_prefetcher()
                data_iter = None
                current_gbs = None
                self._reset_log_window()
                self.timers.elapsed_ms(reset=True)

            while True:
                pass_span.close()
                pass_span.enter_context(jax.profiler.StepTraceAnnotation(
                    "train-pass", step_num=self.iteration + 1))
                if self.iteration >= (t.train_iters or 0):
                    # drain the metrics pipeline before declaring victory:
                    # a sentinel trip hiding in the tail rolls back and
                    # resumes training instead of silently finishing
                    if drain(0):
                        on_rollback()
                        continue
                    if (self.coord is not None
                            and self._exit_agreement is None):
                        # completion publishes a NON-BLOCKING exit ack at
                        # train_iters: a preemption notice racing normal
                        # completion — even one published a pass after
                        # this check — resolves every peer's exit
                        # agreement to train_iters, so drainers catch up
                        # and every host's two-phase commit votes at ONE
                        # iteration (without this, a completer's final
                        # save and a drainer's preempt save would
                        # deadlock at different commit barriers, or the
                        # drainer's agreement would wait on a host that
                        # already left the loop)
                        self.coord.ack_exit(self.iteration)
                    break
                gbs = self.calc.global_batch(self.consumed_samples)
                if gbs != current_gbs or data_iter is None:
                    self._close_prefetcher()
                    current_gbs = gbs
                    data_iter = self._make_data_iter(
                        train_iter_factory, gbs, depth)

                self.timers("batch-generator", 0).start()
                batch = next(data_iter, None)
                if batch is None:
                    # epoch boundary: fresh iterator at the exact
                    # consumed_samples watermark (sampler order is a pure
                    # function of consumed_samples; batches the prefetcher
                    # pulled ahead were never counted, so none are lost)
                    self._close_prefetcher()
                    data_iter = self._make_data_iter(
                        train_iter_factory, gbs, depth)
                    batch = next(data_iter, None)
                    if batch is None:
                        self.timers("batch-generator", 0).stop()
                        self.log("data exhausted, stopping")
                        if drain(0):
                            on_rollback()
                            continue
                        break
                self.timers("batch-generator", 0).stop()
                # with the prefetcher this is pure queue-pop wait — ~0 in
                # steady state, the whole point of the async loop
                data_wait_s = self.timers.last_s("batch-generator")
                self._credit_prefetch_spans()

                fast_forward = self.iteration < self._skip_data_until
                skipped_iter = (fast_forward
                                or (self.iteration + 1) in t.skip_iters)
                if self.telemetry is not None:
                    # a fast-forward's data fetch is replay cost, not
                    # input-pipeline wait
                    self.telemetry.goodput.attribute(
                        "rollback_replay" if fast_forward else "data_wait",
                        data_wait_s)
                # trace-window management must see skipped iterations too,
                # or a skip at the boundary strands the trace open/closed
                self._profile_window()
                if skipped_iter:
                    # consume the data, skip the update — either --skip_iters
                    # fault injection (ref training.py:397-425) or the
                    # post-rollback fast-forward past a poison window; eval /
                    # SIGTERM / exit / save checks below still run
                    self.iteration += 1
                    self.consumed_samples += gbs
                    pending.append({
                        "iteration": self.iteration, "gbs": gbs,
                        "consumed": self.consumed_samples,
                        "skip_reason": ("rollback_fast_forward"
                                        if fast_forward else "skip_iters")})
                else:
                    resilience.maybe_kill("kill_at", self.iteration + 1)
                    # a preemption NOTICE at an exact step (the handler
                    # records it; the expedited save path below runs
                    # after this iteration completes)
                    resilience.maybe_signal("preempt_at", self.iteration + 1)
                    # multi-host forms: the fault hits exactly ONE host
                    # of the cluster (kill_host:HOST:ITER /
                    # preempt_host:HOST:ITER); host 0 when uncoordinated
                    fault_host = self.coord.host if self.coord else 0
                    resilience.maybe_kill_host(fault_host,
                                               self.iteration + 1)
                    resilience.maybe_signal_host(fault_host,
                                                 self.iteration + 1)
                    # a wedged collective/device step: only the
                    # --step_timeout_s watchdog turns this into a flight
                    # bundle + clean abort
                    resilience.maybe_hang("hang_step", self.iteration + 1)
                    replay_due = bool(
                        t.replay_check_interval
                        and (self.iteration + 1) % t.replay_check_interval
                        == 0)
                    if self._prefetcher is None:
                        # prefetched batches were fingerprinted/poisoned
                        # by the worker's transform (same iteration
                        # numbering); the sync path does both here
                        batch = self._note_fingerprint(
                            batch, self.iteration + 1)
                        batch = resilience.host_batch_faults(
                            batch, self.iteration + 1, self.log)
                        if replay_due:
                            # the replay needs the PLACED batch retained;
                            # transfer it here and take the placed path
                            batch = self._transfer(batch)
                    if self._watchdog is not None:
                        key = (self.fixed_num_microbatches
                               or max(gbs // (t.micro_batch_size
                                              * self.rt.dp), 1))
                        if (key not in self._step_cache
                                or (replay_due
                                    and not hasattr(self, "_replay_eq_fn"))):
                            # fresh jit level (rampup boundary, first
                            # replay check): the multi-minute compile
                            # ahead is not a hang — go dormant until the
                            # next completed-step beat, same policy as
                            # the startup compile exemption
                            self._watchdog.pause()
                    # the replay check re-runs this step from a bitwise
                    # state copy and compares outputs (SDC sentinel)
                    pre_state = self._snapshot_state() if replay_due else None
                    # forward + backward + optimizer are ONE fused jit
                    # region here (the reference's separate spans,
                    # training.py:500-525, would break that fusion);
                    # --profile gives the op-level breakdown instead
                    compile_snap = (self.telemetry.compile_snapshot()
                                    if self.telemetry is not None else None)
                    tm = self.timers("forward-backward-optimizer", 0)
                    tm.start()
                    if self._prefetcher is not None or replay_due:
                        metrics = self.train_step_placed(batch)
                    else:
                        metrics = self.train_step(batch)
                    # lag 0 pays the host sync inside the span (the
                    # synchronous loop's behavior: the span measures the
                    # full device step); lag K defers it to _process_record
                    host = self._fetch_metrics(metrics) if lag == 0 else None
                    tm.stop()
                    if replay_due:
                        self._replay_check(pre_state, batch, metrics)
                    ntok = int(batch.get(
                        "tokens", next(iter(batch.values()))).size)
                    pending.append({
                        "iteration": self.iteration, "gbs": gbs,
                        "consumed": self.consumed_samples, "ntok": ntok,
                        "metrics": metrics, "host": host,
                        "dispatch_s": self.timers.last_s(
                            "forward-backward-optimizer"),
                        "data_wait_s": data_wait_s,
                        "compile_delta": (
                            self.telemetry.recompiles.delta(compile_snap)
                            if self.telemetry is not None else None)})

                if drain(lag):
                    on_rollback()
                    continue

                if (valid_iter_factory and t.eval_interval
                        and self.iteration % t.eval_interval == 0):
                    # eval is a pipeline sync point anyway: drain so the
                    # sentinel's verdicts precede it (a trip cancels it)
                    if drain(0):
                        on_rollback()
                        continue
                    if self._watchdog is not None and self.eval_step is None:
                        # first eval compiles the eval step — not a hang
                        self._watchdog.pause()
                    self.timers("eval-time", 0).start()
                    ev = self.evaluate(valid_iter_factory(), t.eval_iters)
                    self.timers("eval-time", 0).stop()
                    if self.telemetry is not None:
                        self.telemetry.stall(
                            "eval", self.timers.last_s("eval-time"),
                            iteration=self.iteration,
                            lm_loss=float(ev["lm_loss"]))
                    self._heartbeat(f"iteration {self.iteration} (post-eval)")
                    extra = " | ".join(f"{k}: {v:.4f}" for k, v in ev.items()
                                       if k not in ("lm_loss", "ppl"))
                    self.log(f"validation | lm loss: {ev['lm_loss']:.6f} | "
                             f"ppl: {ev['ppl']:.3f}"
                             + (f" | {extra}" if extra else ""))
                    for k, v in ev.items():
                        self.writer.add_scalar(f"valid/{k}", v, self.iteration)
                    self.writer.flush()

                # periodic save FIRST — before anything that can block on
                # the cluster exit agreement. Periodic save iterations
                # are identical on every host by interval arithmetic, and
                # their two-phase votes are cast from here (the finalizer
                # thread), so a peer blocked in the exit agreement never
                # holds up a commit barrier: without this ordering, host
                # A can wedge in save().wait() on a commit that needs
                # B's vote while B wedges in the agreement that needs
                # A's ack — a distributed deadlock cycle (observed live).
                if self._cadence is not None:
                    saved_now = self._cadence_due()
                else:
                    saved_now = bool(
                        t.save_interval
                        and self.iteration % t.save_interval == 0)
                if saved_now:
                    if (self.coord is not None
                            and self._exit_agreement is None):
                        # about to block on the PREVIOUS save's commit
                        # barrier (saver.save waits on it): if a cluster
                        # drain is pending, publish our non-blocking exit
                        # ack FIRST — the peers' agreement resolves on
                        # it, they catch up through every periodic save
                        # iteration, and the barrier's missing votes get
                        # cast. Without this, a host that raced past the
                        # notice (snapshot staleness is ~poll_s ≈ many
                        # steps) wedges in the save wait before ever
                        # acking, while peers wedge in the agreement
                        # waiting for that ack (observed live). Uncached
                        # reads: once per save interval, not per step.
                        self.coord.cluster_signals()
                        if (self.coord.exit_pending()
                                or self.coord.cluster_signals(cached=True)):
                            self.coord.ack_exit(self.iteration)
                    # never checkpoint past un-judged metrics: a sentinel
                    # trip still in the pipeline CANCELS the save
                    if drain(0):
                        on_rollback()
                        continue
                    self.save()
                    self._heartbeat(f"iteration {self.iteration} (post-save)")

                should_exit = False
                preempting = False
                received = sig.signals_received()
                local_names = [signal_module.Signals(s).name
                               for s in received]
                cluster_names: set = set()
                if self.coord is not None:
                    # between-steps liveness poll, only when the armed
                    # sideband is NOT covering it (it normally is, at
                    # poll_s cadence, including inside collectives): a
                    # duplicate inline poll would re-pay the backend
                    # round-trips on every step for no added coverage
                    if not self.coord.sideband_armed():
                        verdict = self.coord.check_peers()
                        if verdict is not None:
                            self._on_peer_abort(verdict)
                    # signal agreement: publish what OUR handler saw,
                    # read the cluster-wide union — one host's SIGTERM
                    # drains ALL hosts
                    if received:
                        self.coord.publish_signals(local_names)
                    # sideband-maintained snapshot: no backend round-trip
                    # on the hot loop; propagation bounded by poll_s
                    cluster_names = {
                        n for r in self.coord.cluster_signals(
                            cached=True).values()
                        for n in r.get("signals", ())}
                names = sorted(set(local_names) | cluster_names)
                if names:
                    names_str = ",".join(names)
                    self._exit_signal = names_str
                    # SIGTERM is a cluster preemption NOTICE: take the
                    # expedited path (drain, forced SYNCHRONOUS committed
                    # save bypassing --save_interval, bounded by
                    # --preempt_save_timeout, journaled `preemption`).
                    # SIGINT (operator Ctrl-C) keeps the ordinary
                    # checkpoint-and-exit; run_end records which arrived.
                    preempting = "SIGTERM" in names
                    should_exit = True
                if t.exit_interval and self.iteration % t.exit_interval == 0:
                    should_exit = True
                if t.exit_duration_in_mins and (
                        (time.time() - start_time) / 60 > t.exit_duration_in_mins):
                    should_exit = True
                if (not should_exit and self.coord is not None
                        and self._exit_agreement is None
                        and self.coord.exit_pending(cached=True)):
                    # a PEER began draining (its --exit_duration clock
                    # crossed, or it completed train_iters): coordinated
                    # training cannot continue without it — join the exit
                    # instead of stepping until our own cause fires,
                    # which on a lockstep cluster could need collective
                    # participation the peer has already withdrawn
                    should_exit = True
                if should_exit and self.coord is not None:
                    # agree WHERE the cluster drains — for EVERY exit
                    # cause: signals propagate with a pass of skew, and
                    # --exit_duration_in_mins crosses at per-host wall
                    # clocks, so hosts may decide to exit at different
                    # iterations; everyone steps to the max acked
                    # iteration so the final two-phase commit votes at
                    # ONE cluster-consistent state (--exit_interval is
                    # iteration-deterministic but riding the same path
                    # costs nothing)
                    if self._exit_agreement is None:
                        try:
                            # generous window (startup-grade): a peer
                            # mid-compile acks at its first completed
                            # pass, a duration-exit peer acks when its
                            # own clock crosses, and a DEAD peer doesn't
                            # stall this wait — the peer-death watchdog
                            # exits out of it
                            self._exit_agreement = \
                                self.coord.agree_exit_iteration(
                                    self.iteration,
                                    timeout_s=coordination
                                    .startup_timeout_s())
                        except coordination.CoordinationError as e:
                            # agreement is unreachable (peer wedged but
                            # heartbeat-fresh, medium trouble): commit a
                            # SOLO checkpoint — this host's save must
                            # drop the coordinator or its commit barrier
                            # would wait on the same unreachable peers;
                            # resume's valid-set intersection keeps the
                            # cluster consistent around a solo commit
                            self.log(f"coordination: exit agreement "
                                     f"failed ({e}); draining solo "
                                     "(uncoordinated final commit)")
                            self._exit_agreement = (self.iteration, None)
                            self._commit_solo = True
                        target, nh = self._exit_agreement
                        self._notice_host = nh
                        self.log(
                            f"coordination: cluster exit agreed at "
                            f"iteration {target} (notice on host "
                            f"{nh}, this is host {self.coord.host})")
                    if self.iteration < self._exit_agreement[0]:
                        # behind the agreed boundary: keep stepping —
                        # deterministic data order converges every
                        # host on the same state at `target`
                        should_exit = False
                        preempting = False
                if should_exit and names:
                    self.log(
                        f"received {names_str}, checkpointing and "
                        "exiting"
                        + (" (preemption notice: expedited "
                           "synchronous save)" if preempting else ""))

                if should_exit:
                    # drain so a sentinel trip still in the pipeline
                    # CANCELS the exit save (this closes the lag-widened
                    # window where a diverged state could be committed
                    # and then rolled back onto)
                    if drain(0):
                        on_rollback()
                        continue
                    if preempting:
                        self._preempt_save(sig, already_saved=saved_now)
                    elif not saved_now:
                        # ordinary exit (SIGINT / exit_interval /
                        # exit_duration): checkpoint unless the periodic
                        # save above already covered this iteration
                        self.save()
                    self._heartbeat(f"iteration {self.iteration} (post-save)")
                    return self.state
                last_saved = self.iteration if saved_now else None

        if self.cfg.training.save and last_saved != self.iteration:
            self.save()
        return self.state


def pretrain(
    run_cfg: RunConfig,
    train_iter_factory,
    valid_iter_factory=None,
    log: Callable[[str], None] = print,
) -> TrainState:
    """One-call entry (ref: megatron/training.py pretrain())."""
    loop = TrainLoop(run_cfg, log=log)
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(loop.state.params))
    log(f"mesh: {dict(loop.rt.mesh.shape)} | params: {n_params:,}")
    try:
        return loop.train(train_iter_factory, valid_iter_factory)
    finally:
        loop.writer.close()
