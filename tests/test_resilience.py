"""Fault-tolerance tests: divergence sentinel, fault-injection harness,
multi-signal handler, and the REAL crash/recovery acceptance paths —
subprocess training runs killed mid-save and poisoned with NaN windows
(ISSUE 2: crash-safe training).

Since ISSUE 5 the subprocess runs here exercise the ASYNC goodput loop by
default (background prefetcher + lagged metrics): the kill/resume and
rollback bitwise assertions below double as the prefetcher-x-resilience
interplay acceptance — no sample lost or duplicated across a
prefetch-queue rebuild. The --no_async_loop oracle differentials live in
tests/test_prefetch.py (in-process) and the slow-marked subprocess parity
test at the bottom of this file."""

import json
import os
import re
import signal
import subprocess
import sys

import numpy as np
import pytest

from megatron_tpu.training import resilience
from megatron_tpu.training.resilience import DivergenceSentinel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- sentinel unit tests -----------------------------------------------------


def test_sentinel_nonfinite_patience():
    s = DivergenceSentinel(patience=3, spike_factor=0.0)
    assert s.observe(1.0) is None
    assert s.observe(float("nan")) is None
    assert s.observe(2.0, skipped=True) is None  # skipped counts as bad...
    assert s.observe(1.0) is None                # ...but a good step resets
    assert s.observe(float("inf")) is None
    assert s.observe(None, skipped=True) is None
    trip = s.observe(float("nan"))
    assert trip and "3 consecutive" in trip
    s.reset()
    assert s.observe(float("nan")) is None


def test_sentinel_streak_override_survives_restart():
    """The optimizer's checkpointed skip streak overrides the host counter:
    a resume that lands mid-NaN (or a crash loop faster than patience)
    keeps accumulating instead of restarting from zero."""
    s = DivergenceSentinel(patience=50, spike_factor=0.0)
    # fresh sentinel after a restart; the restored state already carries 49
    # consecutive skips
    trip = s.observe(float("nan"), skipped=True, streak=50)
    assert trip and "50 consecutive" in trip
    s.reset()
    assert s.observe(float("nan"), streak=10) is None
    assert s.nonfinite_streak == 10
    assert s.observe(1.0, streak=0) is None  # finite step resets as usual
    assert s.nonfinite_streak == 0


def test_sentinel_disabled():
    s = DivergenceSentinel(patience=0, spike_factor=0.0)
    for _ in range(50):
        assert s.observe(float("nan")) is None


def test_sentinel_loss_spike():
    s = DivergenceSentinel(patience=0, spike_factor=2.0, spike_patience=3,
                           warmup_steps=5, ema_alpha=0.5)
    for _ in range(10):
        assert s.observe(1.0) is None
    ema_before = s.ema
    assert s.observe(5.0) is None  # spike 1
    assert s.observe(5.0) is None  # spike 2
    assert s.ema == ema_before     # spikes are NOT folded into the EMA
    assert s.observe(1.0) is None  # recovery resets the spike streak
    assert s.observe(5.0) is None
    assert s.observe(5.0) is None
    trip = s.observe(5.0)
    assert trip and "loss_spike_factor" in trip
    # no trip during warmup regardless of ratio
    s2 = DivergenceSentinel(patience=0, spike_factor=2.0, spike_patience=1,
                            warmup_steps=100)
    for loss in (1.0, 100.0, 1.0, 100.0):
        assert s2.observe(loss) is None


# -- fault harness -----------------------------------------------------------


def test_fault_env_parsing(monkeypatch):
    monkeypatch.setenv(resilience.FAULT_ENV,
                       "kill_during_save:4, nan_loss:3:2,slow_save:250")
    assert resilience.fault_args("kill_during_save") == (4,)
    assert resilience.fault_args("nan_loss") == (3, 2)
    assert resilience.fault_args("nope") is None
    assert resilience.fault_active("kill_during_save", 4)
    assert not resilience.fault_active("kill_during_save", 5)
    assert [i for i in range(8) if resilience.fault_active("nan_loss", i)] \
        == [3, 4]
    monkeypatch.setenv(resilience.FAULT_ENV, "nan_loss:7")
    assert [i for i in range(10) if resilience.fault_active("nan_loss", i)] \
        == [7]
    monkeypatch.setenv(resilience.FAULT_ENV, "bad:spec:x")
    with pytest.raises(ValueError, match="malformed"):
        resilience.fault_args("bad")
    monkeypatch.setenv(resilience.FAULT_ENV, "")
    assert resilience.fault_args("nan_loss") is None


def test_poison_batch_makes_loss_nonfinite():
    batch = {"tokens": np.ones((2, 4), np.int64),
             "labels": np.ones((2, 4), np.int64),
             "loss_mask": np.ones((2, 4), np.float32)}
    out = resilience.poison_batch(batch)
    assert np.isinf(out["loss_mask"]).any()
    assert np.isfinite(batch["loss_mask"]).all()  # original untouched
    # masked-mean loss through an inf mask is non-finite
    losses = np.ones((2, 4), np.float32)
    loss = float((losses * out["loss_mask"]).sum() / out["loss_mask"].sum())
    assert not np.isfinite(loss)


def test_new_fault_kinds_parse_and_fire(monkeypatch):
    monkeypatch.setenv(resilience.FAULT_ENV,
                       "preempt_at:4,hang_step:6,corrupt_step:8")
    assert resilience.fault_active("preempt_at", 4)
    assert not resilience.fault_active("preempt_at", 5)
    assert resilience.fault_active("hang_step", 6)
    assert resilience.fault_active("corrupt_step", 8)
    assert not resilience.fault_active("corrupt_step", 4)


def test_maybe_signal_delivers_sigterm(monkeypatch):
    """preempt_at self-delivers a REAL SIGTERM that the run's own handler
    sees — a notice, not maybe_kill's unmaskable death."""
    from megatron_tpu.training.signal_handler import DistributedSignalHandler

    monkeypatch.setenv(resilience.FAULT_ENV, "preempt_at:7")
    with DistributedSignalHandler() as h:
        resilience.maybe_signal("preempt_at", 6)  # not armed for 6
        assert h.signals_received() == ()
        assert h.first_signal() is None
        resilience.maybe_signal("preempt_at", 7)
        assert h.signals_received() == (signal.SIGTERM,)
        signum, arrived = h.first_signal()
        assert signum == signal.SIGTERM and arrived > 0


def test_batch_fingerprint_identity():
    rng = np.random.default_rng(0)
    a = {"tokens": rng.integers(0, 9, (2, 4)),
         "labels": rng.integers(0, 9, (2, 4))}
    # key-insertion order must not matter; content must
    b = {"labels": a["labels"].copy(), "tokens": a["tokens"].copy()}
    assert resilience.batch_fingerprint(a) == resilience.batch_fingerprint(b)
    c = {"tokens": a["tokens"].copy(), "labels": a["labels"].copy()}
    c["tokens"][0, 0] += 1
    assert resilience.batch_fingerprint(a) != resilience.batch_fingerprint(c)
    # poisoning after fingerprinting never changes the identity (the loop
    # fingerprints BEFORE host_batch_faults)
    fp = resilience.batch_fingerprint(a)
    resilience.poison_batch(dict(a, loss_mask=np.ones((2, 4), np.float32)))
    assert resilience.batch_fingerprint(a) == fp


def test_tree_bitwise_mismatch():
    a = {"x": np.array([1.0, np.nan], np.float32),
         "y": {"z": np.array([0.0], np.float32)}}
    same = {"x": a["x"].copy(), "y": {"z": a["y"]["z"].copy()}}
    assert resilience.tree_bitwise_mismatch(a, same) == []  # NaN == NaN bits
    neg = {"x": a["x"].copy(), "y": {"z": np.array([-0.0], np.float32)}}
    bad = resilience.tree_bitwise_mismatch(a, neg)
    assert len(bad) == 1 and "z" in bad[0]  # -0.0 differs BITWISE from 0.0


def test_step_watchdog_unit():
    fired = []
    wd = resilience.StepWatchdog(0.15, lambda age: fired.append(age),
                                 poll_s=0.02).start()
    try:
        import time as _t

        # clock starts at the first beat: no fire while un-beaten (the
        # initial-compile exemption)
        _t.sleep(0.4)
        assert not fired
        # regular beats keep it alive
        for _ in range(5):
            wd.beat()
            _t.sleep(0.05)
        assert not fired
        # silence past the deadline fires exactly once
        _t.sleep(0.5)
        assert len(fired) == 1 and fired[0] >= 0.15
        _t.sleep(0.3)
        assert len(fired) == 1  # single-shot
    finally:
        wd.stop()


# -- signal handler ----------------------------------------------------------


def test_signal_handler_records_multiple_signals():
    from megatron_tpu.training.signal_handler import DistributedSignalHandler

    with DistributedSignalHandler(signals=(signal.SIGUSR1,)) as h:
        assert h.signals_received() == ()
        os.kill(os.getpid(), signal.SIGUSR1)
        assert h.signals_received() == (signal.SIGUSR1,)
    # legacy single-sig ctor still works
    with DistributedSignalHandler(sig=signal.SIGUSR2) as h:
        os.kill(os.getpid(), signal.SIGUSR2)
        assert h.signals_received() == (signal.SIGUSR2,)


def test_signal_handler_second_signal_forces_exit():
    """A wedged flush can't block termination: the second signal os._exits
    with 128+signum. Needs a subprocess (os._exit would kill pytest)."""
    sh_path = os.path.join(REPO, "megatron_tpu", "training",
                           "signal_handler.py")
    script = f"""
import importlib.util, os, signal, sys, time
# load the module file directly: the package import would drag in jax,
# which is ~8s of interpreter start for a test about signal delivery
spec = importlib.util.spec_from_file_location("sh", {sh_path!r})
sh = importlib.util.module_from_spec(spec); spec.loader.exec_module(sh)
DistributedSignalHandler = sh.DistributedSignalHandler
with DistributedSignalHandler() as h:
    os.kill(os.getpid(), signal.SIGTERM)
    assert h.signals_received() == (signal.SIGTERM,)
    print("first recorded", flush=True)
    os.kill(os.getpid(), signal.SIGTERM)   # simulates a wedged flush
    time.sleep(30)
    print("NOT REACHED", flush=True)
"""
    out = subprocess.run([sys.executable, "-c", script],
                         env={**os.environ, "JAX_PLATFORMS": "cpu"},
                         capture_output=True, text=True, timeout=120)
    assert "first recorded" in out.stdout
    assert "NOT REACHED" not in out.stdout
    assert out.returncode == 128 + signal.SIGTERM
    assert "forcing exit" in out.stderr


# -- subprocess crash/recovery acceptance ------------------------------------


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    from tools import preprocess_data

    tmp = tmp_path_factory.mktemp("corpus")
    rng = np.random.default_rng(0)
    jsonl = tmp / "docs.jsonl"
    with open(jsonl, "w") as f:
        for _ in range(150):
            n = int(rng.integers(20, 60))
            f.write(json.dumps({"text": " ".join(
                str(int(x)) for x in rng.integers(0, 97, n))}) + "\n")
    prefix = str(tmp / "corpus")
    preprocess_data.main(["--input", str(jsonl), "--output_prefix", prefix,
                          "--tokenizer_type", "null", "--vocab_size", "97",
                          "--append_eod"])
    return prefix


def _run_pretrain(corpus, save, extra=(), fault=None, train_iters=8,
                  timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    # NB: never give these subprocesses a shared persistent XLA compile
    # cache: the fault harness SIGKILLs runs mid-flight, which can tear a
    # cache write and crash every later run that loads the entry (observed
    # as glibc heap corruption). Each run compiles from scratch.
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop(resilience.FAULT_ENV, None)
    if fault:
        env[resilience.FAULT_ENV] = fault
    return subprocess.run([
        sys.executable, os.path.join(REPO, "pretrain_gpt.py"),
        "--num_layers", "2", "--hidden_size", "32",
        "--num_attention_heads", "4", "--vocab_size", "128",
        "--seq_length", "32", "--use_rms_norm", "--glu_activation", "swiglu",
        "--fp32", "--micro_batch_size", "2", "--global_batch_size", "4",
        "--train_iters", str(train_iters), "--log_interval", "1",
        "--lr", "1e-3", "--lr_decay_style", "constant",
        "--data_path", corpus, "--split", "95,5,0",
        "--eval_interval", "100", "--save", save, "--load", save,
        "--save_interval", "2", *extra],
        env=env, capture_output=True, text=True, cwd=REPO, timeout=timeout)


def _losses_by_iteration(stdout):
    out = {}
    for m in re.finditer(r"iteration (\d+)/\d+ \|.*?lm loss: ([0-9.einf-]+)",
                         stdout):
        out[int(m.group(1))] = m.group(2)
    return out


@pytest.mark.slow  # 42s (3 subprocess runs) measured cacheless (PR 4
# re-budget); tier-1 keeps the rollback + abort subprocess runs and the
# in-process kill-free differentials (tests/test_prefetch.py)
def test_kill_during_save_resume_bitwise(tmp_path, corpus):
    """Acceptance: a run SIGKILLed mid-save (fault harness) leaves an
    uncommitted staging dir and an intact last checkpoint; the restart
    falls back to it (here through a garbage tracker too) and its
    post-resume loss curve is bitwise-identical to an uninterrupted run."""
    from megatron_tpu.training import checkpointing

    # A: uninterrupted reference run
    ref = _run_pretrain(corpus, str(tmp_path / "ref"))
    assert ref.returncode == 0, ref.stderr[-3000:]
    ref_losses = _losses_by_iteration(ref.stdout)
    assert set(ref_losses) == set(range(1, 9))

    # B1: killed while finalizing the iteration-4 checkpoint
    save = str(tmp_path / "crash")
    b1 = _run_pretrain(corpus, save, fault="kill_during_save:4")
    assert b1.returncode == -signal.SIGKILL, (b1.returncode, b1.stderr[-2000:])
    assert "kill_during_save firing" in b1.stderr
    # iteration 2 committed; iteration 4 left as an uncommitted staging dir
    assert checkpointing.read_tracker(save) == 2
    assert os.path.exists(
        checkpointing.checkpoint_dir(save, 4) + checkpointing.STAGING_SUFFIX)
    assert checkpointing.list_valid_checkpoints(save) == [2]

    # simulate the tracker itself torn by the crash: resume must FALL BACK
    with open(os.path.join(save, checkpointing.TRACKER), "w") as f:
        f.write("")

    # B2: restart resumes from the last committed checkpoint and finishes
    b2 = _run_pretrain(corpus, save)
    assert b2.returncode == 0, b2.stderr[-3000:]
    assert "falling back to iteration 2" in b2.stderr
    assert "removed uncommitted staging dirs: ['iter_0000004.tmp']" in b2.stderr
    assert not os.path.exists(
        checkpointing.checkpoint_dir(save, 4) + checkpointing.STAGING_SUFFIX)
    assert "loaded checkpoint at iteration 2" in b2.stdout
    b2_losses = _losses_by_iteration(b2.stdout)
    assert set(b2_losses) == set(range(3, 9))
    # bitwise-identical post-resume loss curve at the same iterations
    for it in range(3, 9):
        assert b2_losses[it] == ref_losses[it], (
            f"iteration {it}: resumed {b2_losses[it]} != "
            f"uninterrupted {ref_losses[it]}")
    assert checkpointing.read_tracker(save) == 8


def test_nan_window_aborts_without_rollback(tmp_path, corpus):
    """Acceptance: an injected NaN-loss window trips the sentinel into a
    clean abort — non-zero exit with a diagnostic — without
    --rollback_on_divergence."""
    out = _run_pretrain(corpus, str(tmp_path / "abort"),
                        extra=("--divergence_patience", "3"),
                        fault="nan_loss:3:4")
    assert out.returncode != 0
    assert "divergence sentinel tripped" in out.stdout
    assert "DivergenceError" in out.stderr
    assert "consecutive non-finite" in out.stderr
    # it tripped at iteration 5 (3 poisoned steps from 3) and went no further
    assert 8 not in _losses_by_iteration(out.stdout)


@pytest.mark.slow  # 3 tiny subprocess pretrain runs, ~60s on the 2-core host
def test_async_loop_subprocess_parity_with_kill_and_resume(tmp_path, corpus):
    """Oracle differential at the CLI level (ISSUE 5 acceptance): an async
    (default) run SIGKILLed mid-flight and resumed must reproduce, bitwise,
    the loss curve of an UNINTERRUPTED --no_async_loop run — the prefetch
    queue dies with the process and is rebuilt at the checkpoint's
    consumed_samples watermark with no sample loss or duplication."""
    ref = _run_pretrain(corpus, str(tmp_path / "sync_ref"),
                        extra=("--no_async_loop",))
    assert ref.returncode == 0, ref.stderr[-3000:]
    ref_losses = _losses_by_iteration(ref.stdout)
    assert set(ref_losses) == set(range(1, 9))

    save = str(tmp_path / "async_crash")
    k = _run_pretrain(corpus, save, fault="kill_at:6")
    assert k.returncode == -signal.SIGKILL, (k.returncode, k.stderr[-2000:])
    losses = _losses_by_iteration(k.stdout)
    # the pre-kill iterations the crashed async run DID report match the
    # synchronous oracle bitwise
    for it, v in losses.items():
        assert v == ref_losses[it], (it, v, ref_losses[it])

    r = _run_pretrain(corpus, save)
    assert r.returncode == 0, r.stderr[-3000:]
    # resumes from whatever save had COMMITTED at kill time (the iter-4
    # async save may still be in flight when kill_at:6 lands — falling
    # back to 2 is the correct crash semantics, and parity must hold
    # from either watermark)
    m = re.search(r"loaded checkpoint at iteration (\d+)", r.stdout)
    assert m and int(m.group(1)) in (2, 4), r.stdout[-2000:]
    losses.update(_losses_by_iteration(r.stdout))
    assert set(losses) >= set(range(1, 9)) - {5}  # 5 may die un-reported
    for it in sorted(set(losses) & set(ref_losses)):
        assert losses[it] == ref_losses[it], (
            f"iteration {it}: async kill/resume {losses[it]} != "
            f"sync oracle {ref_losses[it]}")
    from megatron_tpu.training import checkpointing

    assert checkpointing.read_tracker(save) == 8


def test_preemption_notice_checkpoint_and_exit(tmp_path, corpus):
    """Acceptance (ISSUE 11): a SIGTERM preemption notice at an exact step
    (preempt_at fault) takes the expedited path — committed checkpoint
    bypassing --save_interval, `preemption` journal event inside
    --preempt_save_timeout, exit 0 — and the checkpoint is tagged so
    retention can never prune it."""
    from megatron_tpu.training import checkpointing
    from megatron_tpu.telemetry.journal import read_events

    save = str(tmp_path / "pre")
    tele = str(tmp_path / "tele")
    out = _run_pretrain(corpus, save, fault="preempt_at:3",
                        extra=("--telemetry_dir", tele,
                               "--preempt_save_timeout", "120",
                               # save_interval=2 would save at 2 anyway;
                               # prove the bypass with an interval the run
                               # never reaches
                               "--save_interval", "100"))
    assert out.returncode == 0, (out.returncode, out.stderr[-3000:])
    assert "preempt_at firing at iteration 3" in out.stderr
    assert "expedited synchronous save" in out.stdout
    assert "preemption checkpoint committed at iteration 3" in out.stdout
    # the notice ended the run: nothing past iteration 3
    losses = _losses_by_iteration(out.stdout)
    assert set(losses) == {1, 2, 3}
    # committed + tagged; the tag survives into verify's manifest read
    assert checkpointing.read_tracker(save) == 3
    ckpt = checkpointing.checkpoint_dir(save, 3)
    assert checkpointing.verify_checkpoint(ckpt, deep=True)[0]
    assert checkpointing.checkpoint_tags(ckpt) == ("preemption",)
    evs, _ = read_events(os.path.join(tele, "events.jsonl"))
    pre = [e for e in evs if e["kind"] == "preemption"]
    assert len(pre) == 1
    assert pre[0]["iteration"] == 3 and pre[0]["signal"] == "SIGTERM"
    assert 0 < pre[0]["notice_to_commit_ms"] < 120 * 1000
    # satellite: run_end tells preemption from operator interrupt
    run_end = [e for e in evs if e["kind"] == "run_end"][-1]
    assert run_end["received_signal"] == "SIGTERM"


@pytest.mark.slow  # one ~7s subprocess run; the deadline machinery is
# unit-covered by test_step_watchdog_unit and the tier-1 preemption run
def test_preempt_save_timeout_forces_exit(tmp_path, corpus):
    """A preemption save wedged past --preempt_save_timeout (here: the
    barrier on a slow_save-delayed in-flight async commit) force-exits
    PREEMPT_TIMEOUT_EXIT_CODE with `preemption_timeout` journaled instead
    of overstaying the notice window."""
    from megatron_tpu.telemetry.journal import read_events

    tele = str(tmp_path / "tele")
    out = _run_pretrain(corpus, str(tmp_path / "wedge"),
                        fault="slow_save:8000,preempt_at:3",
                        extra=("--telemetry_dir", tele,
                               "--preempt_save_timeout", "0.5"))
    assert out.returncode == resilience.PREEMPT_TIMEOUT_EXIT_CODE, (
        out.returncode, out.stderr[-3000:])
    assert "exceeded --preempt_save_timeout" in out.stderr
    evs, _ = read_events(os.path.join(tele, "events.jsonl"))
    assert [e for e in evs if e["kind"] == "preemption_timeout"]
    assert not [e for e in evs if e["kind"] == "preemption"]


def test_hang_step_watchdog_bundle_and_abort(tmp_path, corpus):
    """Acceptance (ISSUE 11): a hung step (hang_step fault) is ended by
    the --step_timeout_s watchdog — flight-recorder bundle on disk,
    `hang_detected` journaled, clean HANG_EXIT_CODE abort — NOT by the
    test runner's timeout kill."""
    from megatron_tpu.telemetry.journal import read_events

    tele = str(tmp_path / "tele")
    out = _run_pretrain(corpus, str(tmp_path / "hang"),
                        fault="hang_step:3", train_iters=6,
                        extra=("--telemetry_dir", tele,
                               "--step_timeout_s", "2"))
    assert out.returncode == resilience.HANG_EXIT_CODE, (
        out.returncode, out.stderr[-3000:])
    assert "hang_step firing at iteration 3" in out.stderr
    assert "step watchdog" in out.stdout
    bundles_dir = os.path.join(tele, "flight_bundles")
    bundles = os.listdir(bundles_dir)
    assert len(bundles) == 1
    bundle = os.path.join(bundles_dir, bundles[0])
    assert os.path.exists(os.path.join(bundle, "stacks.txt"))
    assert os.path.exists(os.path.join(bundle, "meta.json"))
    with open(os.path.join(bundle, "stacks.txt")) as f:
        # the hung thread's stack is in the bundle — the evidence a
        # timeout kill would have destroyed
        assert "maybe_hang" in f.read()
    evs, _ = read_events(os.path.join(tele, "events.jsonl"))
    hangs = [e for e in evs if e["kind"] == "hang_detected"]
    assert hangs and hangs[0]["iteration"] == 3


def test_replay_check_detects_corrupt_step(tmp_path):
    """Acceptance (ISSUE 11): the --replay_check_interval SDC sentinel.
    In-process pair on one tiny model: a clean run replays
    bitwise-identical; with corrupt_step armed the same run journals
    `sdc_detected` naming the mismatching leaf and aborts (SDCError)."""
    import jax

    from megatron_tpu.config import (
        ModelConfig, OptimizerConfig, RunConfig, TrainingConfig,
    )
    from megatron_tpu.telemetry.journal import read_events
    from megatron_tpu.training.pretrain import TrainLoop

    model = ModelConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4, num_kv_heads=4,
        ffn_hidden_size=64, vocab_size=64, seq_length=16,
        params_dtype="float32").validate()
    rng = np.random.default_rng(0)
    # conftest's 8-fake-device CPU mesh: gbs 8 = micro 1 x dp 8
    proto = {"tokens": rng.integers(0, 64, (8, 16)).astype(np.int64),
             "labels": rng.integers(0, 64, (8, 16)).astype(np.int64),
             "loss_mask": np.ones((8, 16), np.float32)}

    def factory(consumed, gbs):
        def gen():
            while True:
                yield proto
        return gen()

    def run(tele, fault):
        os.environ.pop(resilience.FAULT_ENV, None)
        if fault:
            os.environ[resilience.FAULT_ENV] = fault
        try:
            cfg = RunConfig(
                model=model,
                optimizer=OptimizerConfig(lr=1e-3,
                                          lr_decay_style="constant"),
                training=TrainingConfig(
                    micro_batch_size=1, global_batch_size=8, train_iters=4,
                    log_interval=1 << 30, seed=0, telemetry_dir=str(tele),
                    replay_check_interval=2))
            loop = TrainLoop(cfg, log=lambda m: None)
            loop.train(factory)
        finally:
            os.environ.pop(resilience.FAULT_ENV, None)
        evs, _ = read_events(os.path.join(str(tele), "events.jsonl"))
        return evs

    evs = run(tmp_path / "clean", None)
    checks = [(e["iteration"], e["ok"]) for e in evs
              if e["kind"] == "replay_check"]
    assert checks == [(2, True), (4, True)]
    assert not [e for e in evs if e["kind"] == "sdc_detected"]

    with pytest.raises(resilience.SDCError, match="iteration 2"):
        run(tmp_path / "sdc", "corrupt_step:2")
    evs, _ = read_events(os.path.join(str(tmp_path / "sdc"),
                                      "events.jsonl"))
    sdc = [e for e in evs if e["kind"] == "sdc_detected"]
    assert len(sdc) == 1 and sdc[0]["iteration"] == 2
    assert sdc[0]["leaves"] and "params" in sdc[0]["leaves"][0]
    assert [e for e in evs if e["kind"] == "fault_injection"
            and e["fault"] == "corrupt_step"]
    # jax still healthy after the corruption round-trip
    assert np.isfinite(float(jax.numpy.sum(jax.numpy.ones(3))))


@pytest.mark.slow  # ~5s subprocess run; the sentinel itself is tier-1
# via the in-process test above — this covers only the CLI wiring + exit
def test_replay_check_cli_corrupt_step(tmp_path, corpus):
    from megatron_tpu.telemetry.journal import read_events

    tele = str(tmp_path / "tele")
    out = _run_pretrain(corpus, str(tmp_path / "sdc"),
                        fault="corrupt_step:4",
                        extra=("--telemetry_dir", tele,
                               "--replay_check_interval", "2"))
    assert out.returncode != 0
    assert "SDCError" in out.stderr
    evs, _ = read_events(os.path.join(tele, "events.jsonl"))
    sdc = [e for e in evs if e["kind"] == "sdc_detected"]
    assert sdc and sdc[0]["iteration"] == 4 and sdc[0]["leaves"]


def test_nan_window_rollback_and_continue(tmp_path, corpus):
    """Acceptance: with --rollback_on_divergence the same NaN window rolls
    back to the last good checkpoint, fast-forwards past the poison window,
    and the run completes."""
    out = _run_pretrain(corpus, str(tmp_path / "roll"),
                        extra=("--divergence_patience", "3",
                               "--rollback_on_divergence",
                               "--keep_latest_k", "2"),
                        fault="nan_loss:3:3")
    assert out.returncode == 0, out.stderr[-3000:]
    assert "rolled back to checkpoint at iteration 4" in out.stdout
    assert "post-rollback fast-forward" in out.stdout
    assert "iteration 8/8" in out.stdout
    losses = _losses_by_iteration(out.stdout)
    # post-rollback iterations trained for real, with finite losses
    for it in (6, 7, 8):
        assert float(losses[it]) == float(losses[it])  # not NaN
    from megatron_tpu.training import checkpointing

    save = str(tmp_path / "roll")
    assert checkpointing.read_tracker(save) == 8
    # keep_latest_k=2 retention pruned the older checkpoints
    assert len(checkpointing.list_valid_checkpoints(save)) <= 2
