"""Elastic resume tests (ISSUE 11): a run preempted at one topology and
resumed at another must continue SAMPLE-EXACTLY — the global batch is the
invariant, the gradient-accumulation split is the free variable.

Evidence chain: --log_data_fingerprint journals a crc32 of every host
batch (`data_crc` on step records), so two runs consumed the same sample
IDs in the same order iff their per-iteration fingerprints match; losses
then agree to reduction-order tolerance (the accumulation split changes
the summation order, nothing else).

The tier-1 test exercises the accumulation re-derivation in-process
(micro-batch change on the conftest mesh, no subprocess startup cost);
the dp=4 -> dp=2 subprocess matrix — the acceptance scenario — is
slow-marked (4 tiny pretrain subprocesses at 4/2/3 fake CPU devices,
~16s measured solo on the 2-core host, weather-dependent).
"""

import json
import os
import re
import signal
import subprocess
import sys

import numpy as np
import pytest

from megatron_tpu.training import resilience

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _step_records(tele):
    from megatron_tpu.telemetry.journal import read_events

    evs, _ = read_events(os.path.join(str(tele), "events.jsonl"))
    return evs, {e["iteration"]: e for e in evs if e["kind"] == "step"}


# -- tier-1: accumulation re-derivation, in-process --------------------------


def test_elastic_resume_microbatch_change_sample_exact(tmp_path):
    """Preempt at micro_batch=2 (accumulation 1 on the 8-device mesh),
    resume at micro_batch=1 (accumulation 2): identical per-step batch
    fingerprints and losses allclose to an uninterrupted oracle — plus
    the `elastic_resume` journal record of the re-derivation."""
    from megatron_tpu.config import (
        ModelConfig, OptimizerConfig, RunConfig, TrainingConfig,
    )
    from megatron_tpu.training.pretrain import TrainLoop

    model = ModelConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4, num_kv_heads=4,
        ffn_hidden_size=64, vocab_size=64, seq_length=16,
        params_dtype="float32").validate()
    rng = np.random.default_rng(0)
    data = rng.integers(0, 64, (256, 17)).astype(np.int64)

    def factory(consumed, gbs):
        # pure function of the consumed_samples watermark — the sampler
        # contract the elastic resume leans on
        def gen():
            i = consumed
            while i + gbs <= len(data):
                text = data[i:i + gbs]
                yield {"tokens": text[:, :-1], "labels": text[:, 1:],
                       "loss_mask": np.ones((gbs, 16), np.float32)}
                i += gbs
        return gen()

    save = str(tmp_path / "ckpt")

    def run(tele, micro, iters, load=False, fault=None, profile=False):
        os.environ.pop(resilience.FAULT_ENV, None)
        if fault:
            os.environ[resilience.FAULT_ENV] = fault
        try:
            cfg = RunConfig(
                model=model,
                optimizer=OptimizerConfig(lr=1e-3,
                                          lr_decay_style="constant"),
                training=TrainingConfig(
                    # conftest's 8-fake-device CPU mesh: dp=8, so
                    # gbs 16 = micro 2 x dp 8 (accum 1) resumes as
                    # micro 1 x dp 8 (accum 2)
                    micro_batch_size=micro, global_batch_size=16,
                    train_iters=iters, log_interval=1 << 30, seed=0,
                    save=(save if load or fault else None),
                    load=(save if load else None),
                    telemetry_dir=str(tele), log_data_fingerprint=True,
                    # a window deliberately left OPEN across the preempt
                    # iteration: the expedited path must flush it
                    profile=profile, profile_step_start=2,
                    profile_step_end=1 << 30,
                    profile_dir=str(tele / "trace"),
                    preempt_save_timeout=120.0))
            loop = TrainLoop(cfg, log=lambda m: None)
            loop.train(factory)
        finally:
            os.environ.pop(resilience.FAULT_ENV, None)
        return _step_records(tele)

    # oracle: uninterrupted at micro_batch=2
    _, oracle = run(tmp_path / "oracle", micro=2, iters=8)
    assert set(oracle) == set(range(1, 9))
    # preempted at iteration 4 (SIGTERM notice -> committed checkpoint),
    # with a --profile window still open when the notice lands
    evs_pre, pre = run(tmp_path / "pre", micro=2, iters=8,
                       fault="preempt_at:4", profile=True)
    assert max(pre) == 4
    from megatron_tpu.training import checkpointing

    assert checkpointing.read_tracker(save) == 4
    # the expedited path closed the trace BEFORE spending grace on the
    # save: journaled as an abort-with-flush, and the file is readable
    aborted = [e for e in evs_pre if e["kind"] == "profile_aborted"]
    assert len(aborted) == 1
    assert aborted[0]["reason"] == "preemption"
    assert aborted[0]["flushed"] is True
    from megatron_tpu.telemetry.tracing import find_xplane_files

    assert find_xplane_files(str(tmp_path / "pre" / "trace"))
    # resume at micro_batch=1: accumulation 2 -> 4, same global batch
    evs, res = run(tmp_path / "res", micro=1, iters=8, load=True)
    elastic = [e for e in evs if e["kind"] == "elastic_resume"]
    assert len(elastic) == 1
    assert elastic[0]["from_micro_batch"] == 2
    assert elastic[0]["to_micro_batch"] == 1
    assert elastic[0]["accum_from"] == 1 and elastic[0]["accum_to"] == 2
    assert set(res) == set(range(5, 9))
    for it in range(5, 9):
        # sample-exact: identical batch identity per step...
        assert res[it]["data_crc"] == oracle[it]["data_crc"], it
        assert res[it]["consumed_samples"] == oracle[it]["consumed_samples"]
        # ...and losses agree to reduction-order tolerance (the
        # accumulation split changes summation order, nothing else)
        np.testing.assert_allclose(res[it]["loss"], oracle[it]["loss"],
                                   rtol=2e-4, atol=1e-6)
    # the preempted prefix matched the oracle too (same topology there)
    for it in range(1, 5):
        assert pre[it]["data_crc"] == oracle[it]["data_crc"]


def test_global_batch_indivisible_by_new_dp_is_loud():
    """Satellite (ISSUE 11): resuming with a global batch the new
    topology cannot preserve must be a loud ValueError naming the valid
    accumulation choices — never a silent batch-size drift."""
    from megatron_tpu.training.microbatches import MicroBatchCalculator

    # gbs % dp == 0 but micro doesn't divide the per-rank share: the
    # error names the micro_batch_size values that DO work at this dp
    with pytest.raises(ValueError) as e:
        MicroBatchCalculator(micro_batch_size=3, target_global_batch=16,
                             data_parallel=2)
    msg = str(e.value)
    assert "micro_batch_size from [1, 2, 4, 8]" in msg
    assert "invariant" in msg
    # gbs % dp != 0: no micro size can help — the error says to pick a
    # dividing dp degree instead
    with pytest.raises(ValueError) as e:
        MicroBatchCalculator(micro_batch_size=1, target_global_batch=16,
                             data_parallel=3)
    msg = str(e.value)
    assert "data-parallel degree dividing 16" in msg
    assert "[1, 2, 4, 8, 16]" in msg
    # divisible geometries stay silent
    MicroBatchCalculator(micro_batch_size=2, target_global_batch=16,
                         data_parallel=2)


# -- slow: the dp=4 -> dp=2 subprocess acceptance matrix ---------------------


def _run_elastic(corpus, save, tele, n_devices, extra=(), fault=None,
                 train_iters=8, micro=1, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop(resilience.FAULT_ENV, None)
    if fault:
        env[resilience.FAULT_ENV] = fault
    return subprocess.run([
        sys.executable, os.path.join(REPO, "pretrain_gpt.py"),
        "--num_layers", "2", "--hidden_size", "32",
        "--num_attention_heads", "4", "--vocab_size", "128",
        "--seq_length", "32", "--use_rms_norm", "--glu_activation", "swiglu",
        "--fp32", "--micro_batch_size", str(micro),
        "--global_batch_size", "8",
        "--train_iters", str(train_iters), "--log_interval", "1",
        "--lr", "1e-3", "--lr_decay_style", "constant",
        "--data_path", corpus, "--split", "95,5,0",
        "--eval_interval", "100", "--save", save, "--load", save,
        "--save_interval", "100", "--preempt_save_timeout", "120",
        "--telemetry_dir", tele, "--log_data_fingerprint", *extra],
        env=env, capture_output=True, text=True, cwd=REPO, timeout=timeout)


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    from tools import preprocess_data

    tmp = tmp_path_factory.mktemp("corpus")
    rng = np.random.default_rng(0)
    jsonl = tmp / "docs.jsonl"
    with open(jsonl, "w") as f:
        for _ in range(200):
            n = int(rng.integers(20, 60))
            f.write(json.dumps({"text": " ".join(
                str(int(x)) for x in rng.integers(0, 97, n))}) + "\n")
    prefix = str(tmp / "corpus")
    preprocess_data.main(["--input", str(jsonl), "--output_prefix", prefix,
                          "--tokenizer_type", "null", "--vocab_size", "97",
                          "--append_eod"])
    return prefix


@pytest.mark.slow  # 4 subprocess pretrain runs at 4/2/3 fake devices,
# ~16s measured solo; the accumulation re-derivation itself is tier-1
# via the in-process micro-batch variant above
def test_elastic_resume_dp4_to_dp2_sample_exact(tmp_path, corpus):
    """Acceptance (ISSUE 11): train at dp=4, preempt at step 4, resume at
    dp=2 — per-step sample IDs identical (batch fingerprints) and losses
    allclose to the uninterrupted dp=4 oracle; a dp that cannot preserve
    the global batch fails loudly."""
    from megatron_tpu.training import checkpointing

    # A: uninterrupted dp=4 oracle
    ref = _run_elastic(corpus, str(tmp_path / "ref"),
                       str(tmp_path / "ref_tele"), n_devices=4)
    assert ref.returncode == 0, ref.stderr[-3000:]
    _, oracle = _step_records(tmp_path / "ref_tele")
    assert set(oracle) == set(range(1, 9))

    # B: dp=4, preempted by a SIGTERM notice at step 4
    save = str(tmp_path / "elastic")
    b = _run_elastic(corpus, save, str(tmp_path / "b_tele"), n_devices=4,
                     fault="preempt_at:4")
    assert b.returncode == 0, (b.returncode, b.stderr[-3000:])
    assert checkpointing.read_tracker(save) == 4
    assert "preemption" in checkpointing.checkpoint_tags(
        checkpointing.checkpoint_dir(save, 4))

    # C: resume the same run at dp=2 (accumulation 2 -> 4)
    c = _run_elastic(corpus, save, str(tmp_path / "c_tele"), n_devices=2)
    assert c.returncode == 0, (c.returncode, c.stderr[-3000:])
    assert "elastic resume" in c.stdout
    assert re.search(r"data_parallel=4.*resuming at data_parallel=2",
                     c.stdout)
    evs, resumed = _step_records(tmp_path / "c_tele")
    elastic = [e for e in evs if e["kind"] == "elastic_resume"]
    assert elastic and elastic[0]["from_dp"] == 4
    assert elastic[0]["to_dp"] == 2
    assert elastic[0]["accum_from"] == 2 and elastic[0]["accum_to"] == 4
    assert set(resumed) == set(range(5, 9))
    for it in range(5, 9):
        assert resumed[it]["data_crc"] == oracle[it]["data_crc"], it
        assert (resumed[it]["consumed_samples"]
                == oracle[it]["consumed_samples"])
        np.testing.assert_allclose(resumed[it]["loss"], oracle[it]["loss"],
                                   rtol=2e-4, atol=1e-6)
    assert checkpointing.read_tracker(save) == 8

    # D: dp=3 cannot preserve global_batch=8 — loud refusal, no drift
    d = _run_elastic(corpus, save, str(tmp_path / "d_tele"), n_devices=3,
                     timeout=180)
    assert d.returncode != 0
    assert "data-parallel degree dividing 8" in (d.stderr + d.stdout)


@pytest.mark.slow  # 3 subprocess pretrain runs at 4/4/2 fake devices,
# ~20s; the orbax reshard path was only dp-acceptance-tested before
# (ISSUE 12 satellite) — this pins tp-change resume
def test_elastic_resume_tp2_to_tp1_sample_exact(tmp_path, corpus):
    """Model-parallel elastic resume: train at tp=2 (4 devices, dp=2),
    preempt, resume at tp=1 (2 devices, dp=2 — accumulation unchanged,
    only the tensor sharding moves). The orbax layer reshards on load;
    per-step sample fingerprints must be identical and losses allclose
    (tp changes matmul partial-sum order, nothing else), with the tp
    change journaled as `elastic_resume`."""
    from megatron_tpu.training import checkpointing

    tp2 = ("--tensor_model_parallel_size", "2")
    ref = _run_elastic(corpus, str(tmp_path / "ref"),
                       str(tmp_path / "ref_tele"), n_devices=4, extra=tp2)
    assert ref.returncode == 0, ref.stderr[-3000:]
    _, oracle = _step_records(tmp_path / "ref_tele")
    assert set(oracle) == set(range(1, 9))

    save = str(tmp_path / "elastic")
    b = _run_elastic(corpus, save, str(tmp_path / "b_tele"), n_devices=4,
                     extra=tp2, fault="preempt_at:4")
    assert b.returncode == 0, (b.returncode, b.stderr[-3000:])
    assert checkpointing.read_tracker(save) == 4

    # vocab padding is tp-dependent (pad_vocab_size: divisible_by * tp),
    # so a naive tp-change resume is a LOUD refusal naming the drift —
    # never a silent shape reinterpretation
    bad = _run_elastic(corpus, save, str(tmp_path / "bad_tele"),
                       n_devices=2, timeout=180)
    assert bad.returncode != 0
    assert "vocab_size: checkpoint=256 current=128" in bad.stderr

    # the recipe: hold the PADDED vocab fixed across the tp change
    c = _run_elastic(corpus, save, str(tmp_path / "c_tele"), n_devices=2,
                     extra=("--make_vocab_size_divisible_by", "256"))
    assert c.returncode == 0, (c.returncode, c.stderr[-3000:])
    assert "elastic resume" in c.stdout
    assert "tp 2->1" in c.stdout
    evs, resumed = _step_records(tmp_path / "c_tele")
    elastic = [e for e in evs if e["kind"] == "elastic_resume"]
    assert elastic and elastic[0]["from_tp"] == 2
    assert elastic[0]["to_tp"] == 1
    assert elastic[0]["from_dp"] == 2 and elastic[0]["to_dp"] == 2
    assert set(resumed) == set(range(5, 9))
    for it in range(5, 9):
        assert resumed[it]["data_crc"] == oracle[it]["data_crc"], it
        assert (resumed[it]["consumed_samples"]
                == oracle[it]["consumed_samples"])
        np.testing.assert_allclose(resumed[it]["loss"], oracle[it]["loss"],
                                   rtol=5e-4, atol=1e-5)
    assert checkpointing.read_tracker(save) == 8


@pytest.mark.slow  # 3 subprocess pretrain runs at 2/2/1 fake devices,
# ~20s (ISSUE 12 satellite) — pins pp-change resume through the same
# reshard path
def test_elastic_resume_pp2_to_pp1_sample_exact(tmp_path, corpus):
    """Pipeline-parallel elastic resume: train at pp=2 (2 devices, dp=1),
    preempt, resume unpipelined on 1 device. Sample order invariant;
    losses allclose (the pipeline schedule changes accumulation/summation
    order only); `elastic_resume` journals the pp change."""
    from megatron_tpu.training import checkpointing

    pp2 = ("--pipeline_model_parallel_size", "2")
    ref = _run_elastic(corpus, str(tmp_path / "ref"),
                       str(tmp_path / "ref_tele"), n_devices=2, extra=pp2)
    assert ref.returncode == 0, ref.stderr[-3000:]
    _, oracle = _step_records(tmp_path / "ref_tele")
    assert set(oracle) == set(range(1, 9))

    save = str(tmp_path / "elastic")
    b = _run_elastic(corpus, save, str(tmp_path / "b_tele"), n_devices=2,
                     extra=pp2, fault="preempt_at:4")
    assert b.returncode == 0, (b.returncode, b.stderr[-3000:])
    assert checkpointing.read_tracker(save) == 4

    c = _run_elastic(corpus, save, str(tmp_path / "c_tele"), n_devices=1)
    assert c.returncode == 0, (c.returncode, c.stderr[-3000:])
    assert "elastic resume" in c.stdout
    assert "pp 2->1" in c.stdout
    evs, resumed = _step_records(tmp_path / "c_tele")
    elastic = [e for e in evs if e["kind"] == "elastic_resume"]
    assert elastic and elastic[0]["from_pp"] == 2
    assert elastic[0]["to_pp"] == 1
    assert set(resumed) == set(range(5, 9))
    for it in range(5, 9):
        assert resumed[it]["data_crc"] == oracle[it]["data_crc"], it
        np.testing.assert_allclose(resumed[it]["loss"], oracle[it]["loss"],
                                   rtol=5e-4, atol=1e-5)
    assert checkpointing.read_tracker(save) == 8


def test_preempted_checkpoint_survives_pruning(tmp_path):
    """Satellite (ISSUE 11): prune_checkpoints never removes the newest
    preemption-tagged checkpoint regardless of --keep_latest_k; older
    preemption checkpoints age out normally."""
    from megatron_tpu.training import checkpointing

    save = str(tmp_path / "ckpt")
    os.makedirs(save)

    def fake_ckpt(it, tags=()):
        path = checkpointing.checkpoint_dir(save, it)
        os.makedirs(path)
        with open(os.path.join(path, "meta.json"), "w") as f:
            f.write("{}")
        checkpointing.write_manifest(path, it, tags=tags)
        with open(os.path.join(save, checkpointing.TRACKER), "w") as f:
            f.write(str(it))

    fake_ckpt(1, tags=("preemption",))
    fake_ckpt(2)
    fake_ckpt(3, tags=("preemption",))
    fake_ckpt(4)
    fake_ckpt(5)
    assert checkpointing.checkpoint_tags(
        checkpointing.checkpoint_dir(save, 3)) == ("preemption",)
    pruned = checkpointing.prune_checkpoints(save, keep_latest_k=1)
    # 5 is kept (newest + tracker target), 3 is kept (newest preemption);
    # 1 — an OLDER preemption checkpoint — ages out with 2 and 4
    assert pruned == [1, 2, 4]
    assert checkpointing.committed_iterations(save) == [3, 5]
    # dry_run reports without deleting
    assert checkpointing.prune_checkpoints(save, 1, dry_run=True) == []


def test_checkpoint_util_verify_prints_preemption_tag(tmp_path, capsys):
    from megatron_tpu.training import checkpointing

    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import checkpoint_util
    finally:
        sys.path.pop(0)

    save = str(tmp_path / "ckpt")
    path = checkpointing.checkpoint_dir(save, 7)
    os.makedirs(path)
    with open(os.path.join(path, "meta.json"), "w") as f:
        f.write("{}")
    checkpointing.write_manifest(path, 7, tags=("preemption",))
    with open(os.path.join(save, checkpointing.TRACKER), "w") as f:
        f.write("7")
    checkpoint_util.main(["verify", "--load", save])
    out = capsys.readouterr().out
    assert "[tags: preemption]" in out


def test_signal_name_constant_matches():
    # the expedited path keys off SIGTERM by number; a platform where
    # that assumption breaks should fail loudly here, not silently in
    # production
    assert signal.SIGTERM == 15
