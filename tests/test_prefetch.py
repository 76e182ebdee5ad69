"""Async goodput loop tests (ISSUE 5): the device prefetcher, the lagged-
metrics train loop, and the persistent compilation cache.

The load-bearing property is BITWISE EQUIVALENCE: the async loop
(prefetch + lagged metrics, the default) and the synchronous loop
(--no_async_loop, the oracle) must produce identical loss curves — same
seed, same data order — including across a divergence rollback, where the
prefetch queue is discarded and rebuilt at the rewound consumed_samples
watermark. Subprocess kill/resume coverage rides in test_resilience.py
(those runs exercise the async loop by default since this PR).

Also covered: the steady-state sync-freedom invariant (exactly one
blocking host transfer per step, zero recompiles after warmup) and
the warm-compilation-cache assertion (second process start pays the
goodput `compile` bucket from the cache, asserted via the recompile
tracker's cache-hit counters).
"""

import os
import re
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from megatron_tpu.config import (
    ModelConfig, OptimizerConfig, ParallelConfig, RunConfig, TrainingConfig,
)
from megatron_tpu.training import resilience
from megatron_tpu.training.prefetch import DevicePrefetcher


# ---------------------------------------------------------------------------
# prefetcher unit tests


def _host_batches(n, rows=2, seq=8, seed=0):
    rng = np.random.default_rng(seed)
    return [{"tokens": rng.integers(0, 16, (rows, seq)).astype(np.int64),
             "idx": np.full((rows,), i, np.int64)} for i in range(n)]


def test_prefetcher_strict_order_and_exhaustion():
    import jax

    batches = _host_batches(7)
    pf = DevicePrefetcher(iter(batches), jax.device_put, depth=2)
    seen = []
    while True:
        b = next(pf, None)
        if b is None:
            break
        seen.append(int(np.asarray(b["idx"])[0]))
        assert isinstance(b["tokens"], jax.Array)  # placed, not host
    assert seen == list(range(7))  # strict source order, nothing dropped
    assert next(pf, None) is None  # stays exhausted
    assert pf.batches_put == 7 and pf.put_s >= 0.0
    pf.close()
    pf.close()  # idempotent


def test_prefetcher_close_discards_in_flight():
    """close() mid-stream stops the worker without consuming the source
    dry — the rollback/epoch-rebuild path (in-flight batches are work, not
    state; the loop's consumed_samples watermark defines position)."""
    import itertools

    import jax

    pulled = []

    def source():
        for i in itertools.count():
            pulled.append(i)
            yield {"x": np.full((1,), i, np.int64)}

    pf = DevicePrefetcher(source(), jax.device_put, depth=2)
    first = next(pf)
    assert int(np.asarray(first["x"])[0]) == 0
    pf.close()
    n_after_close = len(pulled)
    time.sleep(0.2)
    # worker stopped: the infinite source is not consumed further
    assert len(pulled) == n_after_close
    # a bounded queue + one pop can only have pulled a handful ahead
    assert n_after_close <= 5


def test_prefetcher_transform_sees_consumption_iterations():
    """The per-batch transform receives the iteration each batch will be
    consumed at (first_iteration + i) — the contract nan_loss fault
    injection depends on for sync/async bitwise equivalence."""
    import jax

    calls = []

    def transform(batch, iteration):
        calls.append(iteration)
        return batch

    pf = DevicePrefetcher(iter(_host_batches(4)), jax.device_put, depth=2,
                          first_iteration=11, transform=transform)
    out = [next(pf, None) for _ in range(5)]
    assert out[-1] is None
    assert calls == [11, 12, 13, 14]
    pf.close()


def test_prefetcher_surfaces_source_exception():
    import jax

    def source():
        yield {"x": np.zeros((1,), np.int64)}
        raise RuntimeError("disk on fire")

    pf = DevicePrefetcher(source(), jax.device_put, depth=2)
    assert next(pf, None) is not None
    with pytest.raises(RuntimeError, match="disk on fire"):
        next(pf)
    pf.close()


# ---------------------------------------------------------------------------
# sync/async differential: bitwise-identical loss curves


def _tiny_run_cfg(tmp_path, tag, async_loop, train_iters=9, **training_kw):
    model = ModelConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4, num_kv_heads=2,
        ffn_hidden_size=64, vocab_size=64, seq_length=16,
        params_dtype="float32").validate()
    return RunConfig(
        model=model, parallel=ParallelConfig(),
        optimizer=OptimizerConfig(lr=1e-3, lr_decay_style="constant"),
        training=TrainingConfig(
            # conftest's 8-device fake CPU mesh: gbs 16 = mbs 2 x dp 8
            micro_batch_size=2, global_batch_size=16,
            train_iters=train_iters,
            log_interval=1, seed=7, async_loop=async_loop,
            **training_kw))


def _cycling_factory(n_samples=48, seq=16, vocab=64, seed=3):
    """Deterministic sample pool with epoch cycling: the iterator exhausts
    every n_samples/gbs batches, forcing the loop's epoch-boundary rebuild
    (and, in async mode, a prefetch-queue teardown/rebuild) mid-run."""
    rng = np.random.default_rng(seed)
    pool = rng.integers(0, vocab, (n_samples, seq + 1))

    def factory(consumed, gbs):
        def gen():
            i = consumed % n_samples
            while i + gbs <= n_samples:
                rows = pool[i:i + gbs]
                yield {"tokens": rows[:, :-1].astype(np.int64),
                       "labels": rows[:, 1:].astype(np.int64),
                       "loss_mask": np.ones((gbs, seq), np.float32)}
                i += gbs
        return gen()

    return factory


def _losses(logs):
    out = {}
    for line in logs:
        m = re.match(r"iteration (\d+)/\d+ \|.*?lm loss: ([0-9.einfa-]+)",
                     line)
        if m:
            out[int(m.group(1))] = m.group(2)
    return out


def test_async_loop_matches_sync_bitwise(tmp_path):
    """Acceptance: identical loss-curve STRINGS between --no_async_loop
    and the async loop over a run that crosses two epoch boundaries (two
    prefetch-queue rebuilds) — no sample lost, duplicated or reordered."""
    from megatron_tpu.training.pretrain import TrainLoop

    factory = _cycling_factory()
    curves = {}
    for tag, async_on in (("sync", False), ("async", True)):
        logs = []
        loop = TrainLoop(_tiny_run_cfg(tmp_path, tag, async_on),
                         log=logs.append)
        loop.train(factory)
        assert loop.iteration == 9
        assert loop.consumed_samples == 9 * 16
        if async_on:
            # steady state: exactly one blocking host sync per step
            assert loop.host_sync_points == 9
        curves[tag] = _losses(logs)
    assert set(curves["sync"]) == set(range(1, 10))
    assert curves["sync"] == curves["async"]  # bitwise (string) identical


def test_async_rollback_matches_sync_bitwise(tmp_path, monkeypatch):
    """Acceptance: a nan_loss window trips the sentinel into a rollback in
    BOTH modes and the full loss curves stay bitwise-identical — the async
    loop discards its in-flight steps and prefetched batches, rolls back
    with the OBSERVED trip iteration as the poison-window bound, and
    rebuilds the queue at the rewound consumed_samples watermark."""
    from megatron_tpu.training.pretrain import TrainLoop

    # iterations 4,5 poisoned -> optimizer skips -> streak 2 trips at 5;
    # rollback to the iteration-4 checkpoint, fast-forward 5, retrain 6..
    monkeypatch.setenv(resilience.FAULT_ENV, "nan_loss:4:2")
    factory = _cycling_factory(n_samples=64)
    curves = {}
    for tag, async_on in (("sync", False), ("async", True)):
        logs = []
        cfg = _tiny_run_cfg(
            tmp_path, tag, async_on, train_iters=8,
            save=str(tmp_path / f"ckpt_{tag}"), save_interval=2,
            # sync saves: the async-save interplay is covered by the
            # subprocess runs in test_resilience.py, and save mode cannot
            # affect the loss curve this test compares
            async_save=False,
            divergence_patience=2, rollback_on_divergence=True)
        loop = TrainLoop(cfg, log=logs.append)
        loop.train(factory)
        assert loop.iteration == 8
        assert any("rolled back to checkpoint at iteration 4" in l
                   for l in logs), logs
        assert any("tripped at iteration 5" in l for l in logs)
        assert any("(post-rollback fast-forward)" in l for l in logs)
        curves[tag] = _losses(logs)
    # both curves cover every iteration (5 is the skipped replay) and the
    # post-rollback retraining is bitwise-identical too
    assert set(curves["sync"]) == set(curves["async"])
    assert curves["sync"] == curves["async"]
    for it in (6, 7, 8):
        assert np.isfinite(float(curves["async"][it]))


@pytest.mark.slow  # two extra TrainLoop compiles, ~8s; the bitwise
# differentials above keep the pipeline-ordering coverage in tier-1
def test_async_loop_with_skip_iters_and_logging(tmp_path):
    """skip_iters records flow through the lagged pipeline in order: the
    skip log line, journal events, and the log-interval cadence match the
    synchronous loop."""
    from megatron_tpu.training.pretrain import TrainLoop

    factory = _cycling_factory()
    curves = {}
    for tag, async_on in (("sync", False), ("async", True)):
        logs = []
        cfg = _tiny_run_cfg(tmp_path, tag, async_on, train_iters=6,
                            skip_iters=(3,))
        loop = TrainLoop(cfg, log=logs.append)
        loop.train(factory)
        skip_lines = [l for l in logs if "update skipped" in l]
        assert len(skip_lines) == 1 and "iteration 3" in skip_lines[0]
        curves[tag] = _losses(logs)
    assert curves["sync"] == curves["async"]


# ---------------------------------------------------------------------------
# steady-state sync freedom: <=1 blocking transfer per step, 0 recompiles


def test_steady_state_sync_freedom_and_zero_recompiles(tmp_path):
    """Regression guard for the hot path: after warmup the async loop
    issues exactly ONE blocking device->host transfer per step (the
    batched metrics fetch) and zero XLA recompiles; journal step records
    show compile time only on the first step, and every batch was pulled
    from the source on the prefetch worker's thread: the loop's own data
    cost is a queue pop, by construction and not by a CPU's clock."""
    import collections
    import threading

    from megatron_tpu.telemetry.journal import read_events
    from megatron_tpu.training.pretrain import TrainLoop

    from megatron_tpu.telemetry.metrics import default_registry

    tele = str(tmp_path / "tele")
    cfg = _tiny_run_cfg(tmp_path, "guard", True, train_iters=8,
                        telemetry_dir=tele)
    # the train-side collectors live in the shared process registry:
    # measure the delta, not the absolute (other loops may have run here)
    before = default_registry().counter(
        "train_host_syncs_total",
        "blocking device->host transfers issued by the train loop").value()
    source = _cycling_factory()
    pulled_on = collections.Counter()

    def factory(consumed, gbs):
        for batch in source(consumed, gbs):
            pulled_on[threading.current_thread().name] += 1
            yield batch

    loop = TrainLoop(cfg, log=lambda m: None)
    loop.train(factory)
    # one sync point per processed step record, none hidden elsewhere
    assert loop.host_sync_points == 8
    evs, torn = read_events(os.path.join(tele, "events.jsonl"))
    assert torn is None
    steps = [e for e in evs if e["kind"] == "step"]
    assert len(steps) == 8
    # compiles only on the warmup step; steady state is recompile-free
    assert "compiles" in steps[0]
    for e in steps[1:]:
        assert "compiles" not in e, e
    # every batch the loop stepped on came off the prefetch queue: the
    # source was read on the worker's thread (ahead of the loop, so at
    # least once a step) and never on the loop's own, and each step
    # journals the pop it waited for
    assert set(pulled_on) == {"batch-prefetcher"}, pulled_on
    assert pulled_on["batch-prefetcher"] >= 8
    for e in steps:
        assert e["data_wait_ms"] >= 0.0, e
    # the host-sync counter is exported for scraping too
    reg = loop.telemetry.metrics
    assert reg.get("train_host_syncs_total").value() - before == 8


# ---------------------------------------------------------------------------
# persistent compilation cache: warm start shrinks the compile bucket


_WARM_CACHE_RUN = """
import json, os, sys
sys.path.insert(0, {repo!r}); sys.path.insert(0, os.path.join({repo!r}, "tests"))
from megatron_tpu.platform import force_cpu
force_cpu(8)
from megatron_tpu.telemetry import recompile_tracker
from megatron_tpu.telemetry.journal import read_events
from megatron_tpu.training.pretrain import TrainLoop
from test_prefetch import _cycling_factory, _tiny_run_cfg
import pathlib
tmp = pathlib.Path({tmp!r})
tele = str(tmp / ("tele_" + {tag!r}))
cfg = _tiny_run_cfg(tmp, {tag!r}, True, train_iters=2,
                    compilation_cache_dir={cache!r}, telemetry_dir=tele)
tr = recompile_tracker()
snap = tr.snapshot()
TrainLoop(cfg, log=lambda m: None).train(_cycling_factory())
delta = tr.delta(snap)
evs, _ = read_events(os.path.join(tele, "events.jsonl"))
run_start = [e for e in evs if e["kind"] == "run_start"][0]
delta["journal_hits"] = sum(e.get("cache_hits", 0)
                            for e in evs if e["kind"] == "step")
delta["journal_cache_dir"] = run_start["compilation_cache_dir"]
delta["journal_async"] = run_start["async_loop"]
print(json.dumps(delta))
"""


@pytest.mark.slow  # two subprocess pretrain starts, ~28s on the 2-core host
def test_warm_compilation_cache_shrinks_compile_bucket(tmp_path):
    """Acceptance: a SECOND PROCESS START with a warm
    --compilation_cache_dir serves the train step from the persistent
    cache — cache hits recorded (tracker counters AND journal step
    records), compile seconds collapse vs the cold start (the goodput
    `compile` bucket a crash-resume restart no longer pays). Real
    subprocess starts: emulating restarts in-process (jax.clear_caches +
    re-latching the cache module) reproducibly corrupts later XLA:CPU
    executions in the shared pytest process (the conftest
    live-executable SIGABRT, order-dependent)."""
    import json
    import subprocess

    cache = str(tmp_path / "xla_cache")
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               # the suite turns the cache off for children (conftest);
               # this test is about the cache
               JAX_ENABLE_COMPILATION_CACHE="true")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)  # the flag must place it
    deltas = {}
    for tag in ("cold", "warm"):
        code = _WARM_CACHE_RUN.format(repo=REPO, tmp=str(tmp_path),
                                      tag=tag, cache=cache)
        r = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, cwd=REPO,
                           timeout=420)
        assert r.returncode == 0, r.stderr[-3000:]
        deltas[tag] = json.loads(r.stdout.strip().splitlines()[-1])
        assert deltas[tag]["journal_cache_dir"] == cache
        assert deltas[tag]["journal_async"] is True
    cold, warm = deltas["cold"], deltas["warm"]
    assert cold["cache_misses"] > 0 and cold["compiles"] > 0
    assert warm["cache_hits"] > 0
    assert warm["cache_misses"] == 0
    # the warm-start discriminators are cache_hits and the compile
    # SECONDS (retrieval vs real XLA compile):
    assert warm["compile_seconds"] < 0.5 * cold["compile_seconds"], (
        cold, warm)
    # the warm run's journal says WHY its compile bucket collapsed: the
    # train step's compile landed as cache hits on step records
    assert warm["journal_hits"] > 0


# ---------------------------------------------------------------------------
# CLI flags


def test_async_loop_flags_parse_into_config():
    from megatron_tpu.arguments import args_to_run_config, parse_args

    base = ["--num_layers", "2", "--hidden_size", "64",
            "--num_attention_heads", "4"]
    t = args_to_run_config(parse_args(base)).training
    assert t.async_loop and t.prefetch_depth == 2 and t.metrics_lag == 1
    assert t.compilation_cache_dir is None

    t = args_to_run_config(parse_args(base + [
        "--no_async_loop", "--prefetch_depth", "4", "--metrics_lag", "3",
        "--compilation_cache_dir", "/tmp/xc"])).training
    assert not t.async_loop
    assert t.prefetch_depth == 4 and t.metrics_lag == 3
    assert t.compilation_cache_dir == "/tmp/xc"

    with pytest.raises(ValueError, match="metrics_lag"):
        TrainingConfig(metrics_lag=-1).validate()
    with pytest.raises(ValueError, match="prefetch_depth"):
        TrainingConfig(prefetch_depth=-2).validate()
