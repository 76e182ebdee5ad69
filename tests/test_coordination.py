"""Multi-host coordination tests (ISSUE 12): the cross-process agreement
seam (training/coordination.py) and the four protocols wired through the
train loop — signal agreement (one host's SIGTERM drains ALL hosts),
coordinated abort (peer death/poison -> PEER_ABORT_EXIT_CODE, not a
wedged collective), two-phase checkpoint commit (no tracker flips unless
every host staged), and the restart/resume barrier.

Three layers of evidence:
  * in-process units over the FileBackend (two coordinators, one dir);
  * REAL 2-process jax.distributed drills over the KV backend
    (the shared `jax_cluster` conftest harness — the coordination
    service works for real on CPU; only cross-process XLA computations
    don't, see tests/test_multihost.py);
  * REAL two-host CLI acceptance: two pretrain_gpt.py processes sharing
    only a --coordination_dir (one single-device JAX process per "host",
    replicated data/seed — exactly the file-backend cluster shape),
    driven by the per-host faults preempt_host/kill_host/kill_during_save.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from megatron_tpu.training import checkpointing, coordination, resilience
from megatron_tpu.training.coordination import (
    ClusterCoordinator, CommitAborted, CoordinationError, FileBackend,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- backend + protocol units (FileBackend, in-process) -----------------------


def _pair(tmp_path, timeout=1.0, poll=0.02):
    d = str(tmp_path / "coord")
    a = ClusterCoordinator(FileBackend(d), 0, 2,
                           peer_death_timeout_s=timeout, poll_s=poll)
    b = ClusterCoordinator(FileBackend(d), 1, 2,
                           peer_death_timeout_s=timeout, poll_s=poll)
    return a, b


def _concurrently(fa, fb):
    out = {}
    t = threading.Thread(target=lambda: out.update(a=fa()))
    t.start()
    out["b"] = fb()
    t.join()
    return out["a"], out["b"]


def test_file_backend_atomic_records(tmp_path):
    be = FileBackend(str(tmp_path / "c"))
    assert be.get_all("sig") == {}
    be.put("sig/0", "hello")
    be.put("sig/0", "world")  # overwrite
    be.put("sig/1", "x")
    assert be.get_all("sig") == {"0": "world", "1": "x"}
    be.delete("sig/0")
    be.delete("sig/0")  # idempotent
    assert be.get_all("sig") == {"1": "x"}


def test_topology_barrier_and_mismatch(tmp_path):
    a, b = _pair(tmp_path)
    ra, rb = _concurrently(lambda: a.topology_barrier(5),
                           lambda: b.topology_barrier(5))
    assert sorted(ra) == sorted(rb) == [0, 1]
    # a lone host times out with the missing hosts named
    lone = ClusterCoordinator(FileBackend(str(tmp_path / "solo")), 0, 2,
                              peer_death_timeout_s=1, poll_s=0.02)
    with pytest.raises(CoordinationError, match=r"hosts \[1\] missing"):
        lone.topology_barrier(0.3)
    # world-size disagreement is loud, not a hang
    d3 = str(tmp_path / "mismatch")
    c0 = ClusterCoordinator(FileBackend(d3), 0, 2, poll_s=0.02)
    c1 = ClusterCoordinator(FileBackend(d3), 1, 3, poll_s=0.02)
    c1._put("topo/1", num_hosts=3)

    with pytest.raises(CoordinationError, match="disagreement"):
        c0.topology_barrier(5)


def test_signal_agreement_union_and_exit_iteration(tmp_path):
    a, b = _pair(tmp_path)
    assert b.cluster_signals() == {} and b.notice_host() is None
    a.publish_signals(["SIGTERM"])
    a.publish_signals(["SIGTERM"])  # idempotent
    assert b.cluster_signals()[0]["signals"] == ["SIGTERM"]
    assert b.notice_host() == 0
    # hosts at different iterations agree on the MAX (nobody steps back)
    (ta, na), (tb, nb) = _concurrently(
        lambda: a.agree_exit_iteration(5, 5),
        lambda: b.agree_exit_iteration(3, 5))
    assert (ta, na) == (tb, nb) == (5, 0)


def test_completion_ack_resolves_late_notice(tmp_path):
    """A host that reaches train_iters publishes a NON-BLOCKING exit ack;
    a preemption notice published AFTER it left the loop still resolves
    the drainer's agreement — to the completer's final iteration — rather
    than waiting on a host that will never run another pass."""
    a, b = _pair(tmp_path)
    a.ack_exit(50)  # completer: records its position, does NOT wait
    b.publish_signals(["SIGTERM"])  # the notice lands a moment later
    target, nh = b.agree_exit_iteration(47, 5)
    assert (target, nh) == (50, 1)


def test_commit_reattempt_needs_fresh_votes(tmp_path):
    """A re-save of the SAME iteration (divergence rollback re-traverses
    committed iterations) must wait for the peers' votes for THIS
    attempt — stale votes from the earlier commit never satisfy it."""
    a, b = _pair(tmp_path)
    _concurrently(lambda: a.commit_barrier(7, "a0", 5),
                  lambda: b.commit_barrier(7, "b0", 5))  # attempt 0
    _concurrently(lambda: a.commit_barrier(7, "a1", 5),
                  lambda: b.commit_barrier(7, "b1", 5))  # attempt 1: new votes
    # one-sided re-attempt: two generations of leftover votes exist, and
    # none of them count — the lone voter aborts
    with pytest.raises(CommitAborted, match="attempt 2"):
        a.commit_barrier(7, "a2", 0.4)


def test_two_phase_commit_agreement_and_abort(tmp_path):
    a, b = _pair(tmp_path)
    _concurrently(lambda: a.commit_barrier(7, "ca", 5),
                  lambda: b.commit_barrier(7, "cb", 5))
    # one-sided staging: the lone voter ABORTS (tracker never flips)
    with pytest.raises(CommitAborted, match="iteration 8"):
        a.commit_barrier(8, "ca", 0.4)
    # a peer's poison record aborts the wait immediately, with the cause
    b.publish_abort("hang", iteration=9)
    t0 = time.monotonic()
    with pytest.raises(CommitAborted, match="hang"):
        a.commit_barrier(9, "ca", 30.0)
    assert time.monotonic() - t0 < 5.0


def test_peer_abort_and_heartbeat_death(tmp_path, monkeypatch):
    a, b = _pair(tmp_path, timeout=0.3)
    b.heartbeat()
    assert a.check_peers() is None
    # a peer SEEN heartbeating that goes silent past the timeout is a
    # peer_death verdict
    deadline = time.monotonic() + 5
    verdict = None
    while verdict is None and time.monotonic() < deadline:
        verdict = a.check_peers()
        time.sleep(0.05)
    assert verdict == {"host": 1, "cause": "peer_death",
                       "detail": verdict["detail"]}
    # a peer that has NEVER heartbeat is judged against the STARTUP
    # window (its process may still be booting), not the steady-state
    # death window
    a2 = ClusterCoordinator(FileBackend(str(tmp_path / "n")), 0, 2,
                            peer_death_timeout_s=0.1, poll_s=0.02)
    monkeypatch.setenv(coordination.STARTUP_TIMEOUT_ENV, "0.4")
    t0 = time.monotonic()
    v = None
    while v is None and time.monotonic() < t0 + 5:
        v = a2.dead_peer()
        time.sleep(0.03)
    assert v == 1
    assert time.monotonic() - t0 >= 0.35  # 0.1s death window NOT applied
    # an explicit poison record wins over silence and names its cause
    b2 = ClusterCoordinator(a.backend, 1, 2, peer_death_timeout_s=0.3,
                            poll_s=0.02)
    b2.publish_abort("sdc", iteration=4)
    v = a.check_peers()
    assert v["host"] == 1 and v["cause"] == "sdc"
    # own abort record is never a PEER abort
    assert b2.peer_abort() is None


def test_stale_incarnation_records_are_invisible(tmp_path):
    """A crashed-and-restarted host's old SIGTERM/abort records must be
    dead on arrival — the file backend's directory outlives processes."""
    a, b = _pair(tmp_path)
    b.publish_abort("hang")
    b.publish_signals(["SIGTERM"])
    assert a.peer_abort() is not None
    # host 1 restarts: new boot nonce, old records filtered out
    ClusterCoordinator(a.backend, 1, 2, poll_s=0.02)
    assert a.peer_abort() is None
    assert a.cluster_signals() == {}


def test_resume_agreement_intersection(tmp_path):
    a, b = _pair(tmp_path)
    ra, rb = _concurrently(lambda: a.agree_resume_iteration([2, 4, 6], 5),
                           lambda: b.agree_resume_iteration([2, 4], 5))
    assert ra == rb == 4  # newest valid EVERYWHERE, not anyone's tracker
    a2, b2 = _pair(tmp_path / "n2")
    ra, rb = _concurrently(lambda: a2.agree_resume_iteration([2], 5),
                           lambda: b2.agree_resume_iteration([], 5))
    assert ra is rb is None  # empty intersection: fresh start everywhere


def test_broadcast_and_published_value(tmp_path):
    a, b = _pair(tmp_path)
    got, _ = _concurrently(
        lambda: b.broadcast(None, root=0, key="cfg", timeout_s=5),
        lambda: a.broadcast({"interval": 40}, root=0, key="cfg"))
    assert got == {"interval": 40}
    a.publish_value("cadence", 37)
    assert b.read_value("cadence") == 37
    assert b.read_value("cadence", host=1) is None


def test_sideband_watchdog_fires_on_poison(tmp_path):
    a, b = _pair(tmp_path, timeout=5.0, poll=0.02)
    fired = []
    a.start_watchdog(fired.append)
    try:
        time.sleep(0.1)
        assert not fired
        b.publish_abort("hang", iteration=3)
        deadline = time.monotonic() + 5
        while not fired and time.monotonic() < deadline:
            time.sleep(0.02)
        assert fired and fired[0]["cause"] == "hang"
        # and it heartbeat while watching
        assert a._fresh("hb").get(0) is not None
    finally:
        a.stop_watchdog()


def test_single_process_gets_no_coordinator(tmp_path, monkeypatch):
    """process_count()==1 with no host-identity env: for_training returns
    None — the byte-identical single-host contract."""
    from megatron_tpu.config import TrainingConfig

    monkeypatch.delenv(coordination.COORD_HOST_ENV, raising=False)
    monkeypatch.delenv(coordination.COORD_NUM_HOSTS_ENV, raising=False)
    t = TrainingConfig(coordination_dir=str(tmp_path / "c"))
    assert coordination.for_training(t, log=lambda m: None) is None
    # env identity + dir => file backend coordinator, heartbeating from
    # construction (the startup barriers judge liveness by this, long
    # before the train loop finishes building its model)
    monkeypatch.setenv(coordination.COORD_HOST_ENV, "1")
    monkeypatch.setenv(coordination.COORD_NUM_HOSTS_ENV, "2")
    c = coordination.for_training(t, log=lambda m: None)
    assert isinstance(c.backend, FileBackend) and (c.host, c.num_hosts) == (1, 2)
    assert c._fresh("hb").get(1) is not None  # immediate first beat
    assert c._watchdog is not None  # publish-only sideband running
    c.close()
    # half-set env is a loud error, not a silent solo run
    monkeypatch.delenv(coordination.COORD_NUM_HOSTS_ENV)
    with pytest.raises(ValueError, match="must be set together"):
        coordination.for_training(t, log=lambda m: None)


# -- per-host faults + cadence tuner units ------------------------------------


def test_host_faults_parse_and_target_one_host(monkeypatch):
    monkeypatch.setenv(resilience.FAULT_ENV,
                       "kill_host:1:4,preempt_host:0:3")
    assert resilience.host_fault_active("kill_host", 1, 4)
    assert not resilience.host_fault_active("kill_host", 0, 4)
    assert not resilience.host_fault_active("kill_host", 1, 5)
    assert resilience.host_fault_active("preempt_host", 0, 3)
    # preempt_host self-delivers a real SIGTERM only on the named host
    from megatron_tpu.training.signal_handler import DistributedSignalHandler

    with DistributedSignalHandler() as h:
        resilience.maybe_signal_host(1, 3)  # wrong host: nothing
        assert h.signals_received() == ()
        resilience.maybe_signal_host(0, 3)
        assert h.signals_received() == (signal.SIGTERM,)


def test_cadence_tuner_formula_and_retune():
    t = resilience.CheckpointCadenceTuner(grace_s=100.0, floor_steps=5)
    assert t.interval() is None  # no step sample yet
    for _ in range(10):
        t.note_step(1.0)
    for _ in range(10):
        t.note_commit(10.0)
    # (grace 100 - p95 commit 10) / p50 step 1 = 90
    assert t.interval() == 90
    r = t.retune()
    assert r["to_interval"] == 90 and r["from_interval"] is None
    assert t.retune() is None  # unchanged: no re-journal
    # commit latency grows -> interval shrinks, floor clamps
    for _ in range(50):
        t.note_commit(99.5)
    assert t.interval() == 5
    assert t.retune()["to_interval"] == 5
    # seeding from a journal adopts commit + preemption latencies
    t2 = resilience.CheckpointCadenceTuner(grace_s=20.0, floor_steps=2)
    n = t2.seed_from_journal([
        {"kind": "checkpoint_commit", "seconds": 4.0},
        {"kind": "preemption", "save_latency_ms": 6000.0},
        {"kind": "step"},
    ])
    assert n == 2
    t2.note_step(2.0)
    # p95 of [4, 6] = 6 -> (20 - 6) / 2 = 7
    assert t2.interval() == 7
    with pytest.raises(ValueError, match="positive"):
        resilience.CheckpointCadenceTuner(grace_s=0.0)


def test_save_interval_auto_flag_wiring():
    from megatron_tpu.arguments import args_to_run_config, parse_args

    base = ["--num_layers", "2", "--hidden_size", "32",
            "--num_attention_heads", "4", "--vocab_size", "64",
            "--seq_length", "16", "--micro_batch_size", "1",
            "--global_batch_size", "1", "--train_iters", "1", "--fp32"]
    cfg = args_to_run_config(parse_args(
        base + ["--save_interval", "auto", "--save_interval_floor", "7",
                "--coordination_dir", "/tmp/c",
                "--peer_death_timeout_s", "9"]))
    t = cfg.training
    assert t.save_interval is None and t.save_interval_auto
    assert t.save_interval_floor == 7
    assert t.coordination_dir == "/tmp/c"
    assert t.peer_death_timeout_s == 9.0
    cfg = args_to_run_config(parse_args(base + ["--save_interval", "3"]))
    assert cfg.training.save_interval == 3
    assert not cfg.training.save_interval_auto
    with pytest.raises(SystemExit):
        args_to_run_config(parse_args(base + ["--save_interval",
                                              "sometimes"]))


# -- two-phase commit through checkpointing._finalize -------------------------


class _StubCoordinator:
    """num_hosts>1 coordinator double for _finalize: records votes,
    optionally refuses agreement."""

    def __init__(self, agree=True):
        self.num_hosts = 2
        self.host = 0
        self.votes = []
        self.agree = agree

    def commit_barrier(self, iteration, crc, timeout_s=None):
        self.votes.append((iteration, crc))
        if not self.agree:
            raise CommitAborted(f"stub refused iteration {iteration}")


def _stage_fake_checkpoint(save, iteration):
    stage = checkpointing.checkpoint_dir(str(save), iteration) \
        + checkpointing.STAGING_SUFFIX
    os.makedirs(os.path.join(stage, "state"))
    with open(os.path.join(stage, "state", "blob"), "w") as f:
        f.write("bytes")
    return stage


def test_finalize_two_phase_commit_and_abort(tmp_path):
    save = tmp_path / "ckpt"
    # agreement: vote carries the per-host manifest crc, tracker flips
    stage = _stage_fake_checkpoint(save, 3)
    coord = _StubCoordinator(agree=True)
    path = checkpointing._finalize(str(save), stage, 3, 30, None, None,
                                   coordinator=coord)
    assert os.path.isdir(path) and checkpointing.read_tracker(str(save)) == 3
    assert len(coord.votes) == 1 and coord.votes[0][0] == 3
    assert len(coord.votes[0][1]) == 8  # crc32 hex of the manifest
    # refusal: CommitAborted propagates, tracker UNFLIPPED, staging kept
    stage = _stage_fake_checkpoint(save, 5)
    bad = _StubCoordinator(agree=False)
    with pytest.raises(CommitAborted):
        checkpointing._finalize(str(save), stage, 5, 50, None, None,
                                coordinator=bad)
    assert checkpointing.read_tracker(str(save)) == 3
    assert os.path.isdir(stage)
    assert checkpointing.list_valid_checkpoints(str(save)) == [3]


def test_saver_journals_commit_abort(tmp_path):
    """AsyncCheckpointSaver surfaces a refused commit as `commit_abort`
    in the journal and re-raises at the next wait()."""
    import jax.numpy as jnp

    from megatron_tpu.config import OptimizerConfig
    from megatron_tpu.training.optimizer import init_train_state

    class _Journal:
        def __init__(self):
            self.events = []

        def emit(self, kind, **fields):
            self.events.append({"kind": kind, **fields})

        def flush(self):
            pass

    state = init_train_state(
        OptimizerConfig(lr=1e-3, lr_decay_style="constant"),
        {"w": jnp.ones((2,), jnp.float32)})
    journal = _Journal()
    saver = checkpointing.AsyncCheckpointSaver(
        str(tmp_path / "s"), journal=journal,
        coordinator=_StubCoordinator(agree=False))
    saver.save(state, 1, 10)
    with pytest.raises(CommitAborted):
        saver.wait()
    kinds = [e["kind"] for e in journal.events]
    assert kinds == ["checkpoint_begin", "commit_abort"]
    assert journal.events[1]["iteration"] == 1
    assert checkpointing.read_tracker(str(tmp_path / "s")) is None


def test_event_counters_on_metrics_registry(tmp_path):
    """Satellite: preemption/hang/SDC/elastic-resume/peer-abort events
    move Prometheus counters transparently through RunTelemetry.emit —
    and through the saver-facing journal_sink."""
    from megatron_tpu import telemetry
    from megatron_tpu.config import TrainingConfig
    from megatron_tpu.telemetry.metrics import MetricsRegistry

    reg = MetricsRegistry()
    tcfg = TrainingConfig(telemetry_dir=str(tmp_path / "tele"))
    rt = telemetry.for_training(tcfg, log=lambda m: None, registry=reg)
    try:
        rt.emit("preemption", iteration=3, notice_host=0)
        rt.emit("peer_abort", host=1, cause="hang")
        rt.emit("peer_abort", host=1, cause="peer_death")
        rt.emit("elastic_resume", from_dp=4, to_dp=2)
        rt.emit("hang_detected", iteration=5)
        rt.emit("sdc_detected", iteration=6)
        rt.journal_sink().emit("commit_abort", iteration=7, reason="x")
        rt.emit("cadence_retune", to_interval=40)
        text = reg.render()
    finally:
        rt.close()
    for needle in ("train_preemptions_total 1",
                   "train_peer_aborts_total 2",
                   "train_elastic_resumes_total 1",
                   "train_hangs_total 1",
                   "train_sdc_total 1",
                   "train_commit_aborts_total 1",
                   "train_cadence_retunes_total 1"):
        assert needle in text, (needle, text)
    # the sink ALSO journaled (the saver path writes events, not just
    # counters)
    from megatron_tpu.telemetry.journal import read_events

    evs, _ = read_events(os.path.join(str(tmp_path / "tele"),
                                      "events.jsonl"))
    assert [e for e in evs if e["kind"] == "commit_abort"]


def test_telemetry_report_merges_hosts(tmp_path):
    """Satellite: one command over N per-host journals — preemption
    notices by notice_host, peer aborts by (host, cause), commit
    aborts."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)

    def write(path, events):
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            for e in events:
                f.write(json.dumps(e) + "\n")

    j0 = str(tmp_path / "h0" / "events.jsonl")
    j1 = str(tmp_path / "h1" / "events.jsonl")
    write(j0, [
        {"ts": 1, "kind": "run_start", "host": 0, "num_hosts": 2},
        {"ts": 2, "kind": "preemption", "iteration": 4, "notice_host": 1},
        {"ts": 3, "kind": "commit_abort", "iteration": 6, "host": 0},
    ])
    write(j1, [
        {"ts": 1, "kind": "run_start", "host": 1, "num_hosts": 2},
        {"ts": 2, "kind": "preemption", "iteration": 4, "notice_host": 1},
        {"ts": 3, "kind": "peer_abort", "host": 0, "cause": "hang"},
        {"ts": 4, "kind": "cadence_retune", "to_interval": 40},
    ])
    summary = telemetry_report.summarize(
        telemetry_report.load_journals([j0, j1]))
    co = summary["coordination"]
    assert co["hosts"] == [0, 1]
    # ONE cluster preemption journaled by BOTH hosts dedups to one
    # notice (identity = notice_host + iteration); per-host observations
    # (peer_abort) stay counted as observations
    assert co["preemption_notices_by_host"] == {"host 1": 1}
    assert co["peer_aborts"] == {"host 0: hang": 1}
    assert co["commit_aborts"]["total"] == 1
    assert co["commit_aborts"]["iterations"] == [6]
    assert co["cadence_retunes"]["last_interval"] == 40
    text = telemetry_report.render(summary)
    assert "peer aborts" in text and "host 0: hang: 1" in text
    assert "preemption notices" in text


# -- REAL 2-process jax.distributed KV-backend drill --------------------------


_KV_DRILL = r"""
import time
from megatron_tpu.training.coordination import (
    ClusterCoordinator, CommitAborted, KVBackend)

c = ClusterCoordinator(KVBackend(), pid, 2, peer_death_timeout_s=10,
                       poll_s=0.05)
c.topology_barrier(60)
print(f"P{pid} topo ok", flush=True)

# signal agreement: the notice lands on host 0 only; host 1 reads the
# cluster union and both agree on the max acked iteration
if pid == 0:
    c.publish_signals(["SIGTERM"])
deadline = time.monotonic() + 30
while not c.cluster_signals():
    assert time.monotonic() < deadline, "union never arrived"
    time.sleep(0.05)
assert c.notice_host() == 0
target, nh = c.agree_exit_iteration(3 + pid, 30)
assert (target, nh) == (4, 0), (target, nh)
print(f"P{pid} exit agreement ok", flush=True)

# two-phase commit: both staged -> both proceed
c.commit_barrier(7, f"crc{pid}", 30)
print(f"P{pid} commit ok", flush=True)
# one-sided staging aborts (host 1 deliberately never votes for 9)
if pid == 0:
    try:
        c.commit_barrier(9, "crc0", 1.0)
        print("P0 COMMIT-9-DID-NOT-ABORT", flush=True)
    except CommitAborted:
        print("P0 commit 9 aborted as required", flush=True)

# host-data broadcast over the KV store (no XLA collective involved)
val = c.broadcast({"interval": 40} if pid == 0 else None, root=0,
                  key="cfg", timeout_s=30)
assert val == {"interval": 40}, val
print(f"P{pid} broadcast ok", flush=True)

# poison record visibility (LAST: a poison record aborts commit
# barriers by design, so nothing protocol-shaped can follow it)
if pid == 1:
    c.publish_abort("hang", iteration=5)
if pid == 0:
    deadline = time.monotonic() + 30
    v = None
    while v is None and time.monotonic() < deadline:
        v = c.peer_abort()
        time.sleep(0.05)
    assert v and v["cause"] == "hang" and v["host"] == 1, v
    print("P0 poison ok", flush=True)

# exit rendezvous over plain records (each publishes done, waits for the
# peer's) so neither tears down the coordination service under the other
c.publish_value("done", True)
deadline = time.monotonic() + 60
while c.read_value("done", host=1 - pid) is None:
    assert time.monotonic() < deadline, "peer never finished"
    time.sleep(0.05)
print(f"P{pid} DRILL-OK", flush=True)
"""


def test_kv_backend_two_process_drill(jax_cluster):
    """All four protocols over the REAL jax.distributed KV store between
    two CPU processes — the backend a real cluster uses, with zero extra
    infrastructure."""
    results = jax_cluster(_KV_DRILL, nprocs=2, devices_per_proc=1,
                          timeout=240)
    for i, (rc, out) in enumerate(results):
        assert rc == 0, f"worker {i} failed:\n{out}"
        assert f"P{i} DRILL-OK" in out
    assert "P0 commit 9 aborted as required" in results[0][1]
    assert "COMMIT-9-DID-NOT-ABORT" not in results[0][1]
    assert "P0 poison ok" in results[0][1]


# -- two-host CLI acceptance --------------------------------------------------


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


def _host_cmd(corpus, save, tele, coord_dir, train_iters, save_interval,
              extra=()):
    cmd = [
        sys.executable, os.path.join(REPO, "pretrain_gpt.py"),
        "--num_layers", "2", "--hidden_size", "32",
        "--num_attention_heads", "4", "--vocab_size", "128",
        "--seq_length", "32", "--use_rms_norm", "--glu_activation", "swiglu",
        "--fp32", "--micro_batch_size", "2", "--global_batch_size", "4",
        "--train_iters", str(train_iters), "--log_interval", "1",
        "--lr", "1e-3", "--lr_decay_style", "constant",
        "--data_path", corpus, "--split", "95,5,0",
        "--eval_interval", "10000", "--save", save, "--load", save,
        "--save_interval", str(save_interval),
        "--telemetry_dir", tele,
        "--preempt_save_timeout", "120", *extra]
    if coord_dir:
        cmd += ["--coordination_dir", coord_dir]
    return cmd


def _run_two_hosts(corpus, base, coord_dir, fault_by_host=None,
                   train_iters=8, save_interval=2, extra=(),
                   peer_death_timeout="3", timeout=300):
    """Two pretrain_gpt.py processes = two single-device 'hosts' sharing
    only the coordination dir (replicated data/seed). Returns
    [(rc, stdout+stderr), ...] per host."""
    procs = []
    for host in range(2):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.pop(resilience.FAULT_ENV, None)
        env[coordination.COORD_HOST_ENV] = str(host)
        env[coordination.COORD_NUM_HOSTS_ENV] = "2"
        env[coordination.STARTUP_TIMEOUT_ENV] = "120"
        fault = (fault_by_host or {}).get(host)
        if fault:
            env[resilience.FAULT_ENV] = fault
        cmd = _host_cmd(corpus, os.path.join(base, f"save{host}"),
                        os.path.join(base, f"tele{host}"), coord_dir,
                        train_iters, save_interval,
                        extra=tuple(extra)
                        + ("--peer_death_timeout_s", peer_death_timeout))
        procs.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=REPO))
    # drain BOTH pipes concurrently: a sequential communicate() lets the
    # not-yet-waited host fill its 64KB stdout pipe and block in print
    # mid-pass — its main thread then never reaches the signal check
    # while its sideband keeps heartbeating, which reads as a live host
    # ignoring the cluster (a real debugging episode, not a hypothetical)
    chunks = [[] for _ in procs]
    readers = [threading.Thread(target=lambda p=p, c=c: c.append(
        p.stdout.read()), daemon=True) for p, c in zip(procs, chunks)]
    for r in readers:
        r.start()
    out = []
    deadline = time.monotonic() + timeout
    for p, c, r in zip(procs, chunks, readers):
        try:
            p.wait(timeout=max(deadline - time.monotonic(), 1.0))
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
        r.join(timeout=30)
        out.append((p.returncode, c[0] if c else ""))
    return out


def _events(base, host):
    from megatron_tpu.telemetry.journal import read_events

    evs, _ = read_events(os.path.join(base, f"tele{host}", "events.jsonl"))
    return evs


def test_sigterm_one_host_drains_both(tmp_path, corpus):
    """Acceptance (ISSUE 12): a SIGTERM delivered to ONE host
    (preempt_host:0:3) drains and checkpoints BOTH hosts — both exit 0
    with one cluster-consistent committed checkpoint tagged `preemption`,
    and both journals record the same notice_host and commit."""
    base = str(tmp_path)
    coord_dir = os.path.join(base, "coord")
    results = _run_two_hosts(
        corpus, base, coord_dir,
        # the notice lands on host 0 ONLY; host 1 must learn of it
        # through the agreement seam. The notice fires past the compile
        # (iteration 25) and train_iters is far beyond reach, so both
        # hosts are mid-run when they drain — the agreed iteration is
        # whatever the slower host had acked, never the end of the run.
        fault_by_host={0: "preempt_host:0:25", 1: "preempt_host:0:25"},
        # the 2-core host runs both compiles concurrently (and tier-1 may
        # have background load): heartbeat cadence degrades badly during
        # the overlap, so the death window must be startup-grade here —
        # peer death is the NEXT test's subject
        train_iters=3000, save_interval=10000, peer_death_timeout="90")
    for host, (rc, out) in enumerate(results):
        # captured; surfaces BOTH hosts' tails when any assert fails
        print(f"===== host {host} rc={rc} =====\n{out[-4000:]}")
    for host, (rc, out) in enumerate(results):
        assert rc == 0, f"host {host}: rc={rc}\n{out[-4000:]}"
        assert "preemption notice: expedited synchronous save" in out, (
            host, out[-3000:])
    assert "preempt_host firing on host 0" in results[0][1]
    assert "preempt_host firing" not in results[1][1]

    # ONE cluster-consistent committed checkpoint: same iteration on both
    # hosts, both tagged, both deep-verified
    trackers = [checkpointing.read_tracker(os.path.join(base, f"save{h}"))
                for h in range(2)]
    assert trackers[0] == trackers[1] and trackers[0] is not None, trackers
    assert trackers[0] >= 25  # at or past the notice step, never before
    assert trackers[0] < 3000  # and nowhere near normal completion
    for h in range(2):
        ckpt = checkpointing.checkpoint_dir(
            os.path.join(base, f"save{h}"), trackers[h])
        assert checkpointing.verify_checkpoint(ckpt, deep=True)[0]
        assert "preemption" in checkpointing.checkpoint_tags(ckpt)

    # both journals: `preemption` with the SAME notice_host and iteration
    pres = []
    for h in range(2):
        evs = _events(base, h)
        pre = [e for e in evs if e["kind"] == "preemption"]
        assert len(pre) == 1, (h, [e["kind"] for e in evs])
        assert pre[0]["notice_host"] == 0
        assert pre[0]["host"] == h
        pres.append(pre[0])
        run_end = [e for e in evs if e["kind"] == "run_end"][-1]
        assert run_end["received_signal"] == "SIGTERM"
    assert pres[0]["iteration"] == pres[1]["iteration"] == trackers[0]

    # --perfetto round-trip (ISSUE 13): BOTH hosts' real journals render
    # as one schema-valid timeline — two host processes, step spans, and
    # the cluster preemption visible as an instant on each
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import telemetry_report
    finally:
        sys.path.pop(0)
    from test_telemetry import validate_trace_events

    out_json = os.path.join(base, "cluster.perfetto.json")
    trace = telemetry_report.write_perfetto(
        [os.path.join(base, f"tele{h}", "events.jsonl")
         for h in range(2)], out_json)
    assert validate_trace_events(trace)
    assert os.path.exists(out_json)
    procs = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert any("host 0" in p for p in procs)
    assert any("host 1" in p for p in procs)
    assert {e["pid"] for e in trace["traceEvents"]} == {0, 1}
    for pid in (0, 1):
        assert any(e["ph"] == "X" and e["name"].startswith("step ")
                   and e["pid"] == pid for e in trace["traceEvents"])
        assert any(e["ph"] == "i" and e["name"] == "preemption"
                   and e["pid"] == pid for e in trace["traceEvents"])


def test_sigkill_one_host_peer_abort_within_timeout(tmp_path, corpus):
    """Acceptance (ISSUE 12): SIGKILL of one host mid-run → the survivor
    exits PEER_ABORT_EXIT_CODE with a `peer_abort` journal event within
    --peer_death_timeout_s — not a test-timeout kill."""
    base = str(tmp_path)
    coord_dir = os.path.join(base, "coord")
    t0 = time.monotonic()
    results = _run_two_hosts(
        corpus, base, coord_dir,
        fault_by_host={1: "kill_host:1:4"},
        # long enough that the survivor is still mid-run when the
        # detection window closes (the kill lands after both compiles,
        # so steady-state heartbeats make 4s a safe window)
        train_iters=2000, save_interval=100000, peer_death_timeout="4",
        extra=("--log_interval", "100"), timeout=240)
    wall = time.monotonic() - t0
    rc1, out1 = results[1]
    assert rc1 == -signal.SIGKILL, (rc1, out1[-2000:])
    assert "kill_host firing on host 1" in out1
    rc0, out0 = results[0]
    assert rc0 == resilience.PEER_ABORT_EXIT_CODE, (rc0, out0[-4000:])
    assert "peer abort: host 1 (peer_death)" in out0
    evs = _events(base, 0)
    pa = [e for e in evs if e["kind"] == "peer_abort"]
    assert len(pa) == 1
    assert pa[0]["host"] == 1 and pa[0]["cause"] == "peer_death"
    assert pa[0]["observed_by"] == 0
    # bounded reaction: well inside the run, nowhere near the 240s kill
    assert wall < 180, wall


def test_kill_during_save_never_half_commits(tmp_path, corpus):
    """Acceptance (ISSUE 12, two-phase commit proof): kill_during_save on
    ONE of two hosts leaves NO half-committed checkpoint — the survivor's
    commit aborts (its tracker never flips), resume on both hosts falls
    back to the SAME older valid checkpoint, and the post-resume loss
    curve is bitwise-identical to an uninterrupted oracle."""
    # oracle: coordination adds no math/data — a plain single-process
    # uninterrupted run is the curve both hosts must reproduce
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop(resilience.FAULT_ENV, None)
    oracle = subprocess.run(
        _host_cmd(corpus, str(tmp_path / "oracle"),
                  str(tmp_path / "oracle_tele"), None, 8, 2),
        env=env, capture_output=True, text=True, cwd=REPO, timeout=300)
    assert oracle.returncode == 0, oracle.stderr[-3000:]
    ref = _losses_by_iteration(oracle.stdout)
    assert set(ref) == set(range(1, 9))

    base = str(tmp_path / "cluster")
    os.makedirs(base)
    coord_dir = os.path.join(base, "coord")
    results = _run_two_hosts(
        corpus, base, coord_dir,
        fault_by_host={1: "kill_during_save:4"},
        train_iters=8, save_interval=2, peer_death_timeout="5")
    rc1, out1 = results[1]
    assert rc1 == -signal.SIGKILL, (rc1, out1[-2000:])
    rc0, out0 = results[0]
    # two designed no-half-commit verdicts race on the survivor: the
    # sideband's peer-death exit (76) vs the commit barrier's own
    # dead-peer CommitAborted (loud error exit) — both watch the same
    # heartbeat staleness, whichever polls first wins. Either way the
    # tracker never flipped.
    assert rc0 in (resilience.PEER_ABORT_EXIT_CODE, 1), (rc0, out0[-4000:])
    assert ("peer abort: host 1" in out0
            or "commit ABORTED" in out0), out0[-4000:]
    evs0 = _events(base, 0)
    assert [e for e in evs0 if e["kind"] in ("peer_abort", "commit_abort")]
    # NO half-commit anywhere: iteration 4 is not valid on either host
    for h in range(2):
        save = os.path.join(base, f"save{h}")
        assert checkpointing.list_valid_checkpoints(save) == [2], h
        assert checkpointing.read_tracker(save) == 2, h

    # resume: both hosts agree on the SAME older checkpoint and complete
    resumed = _run_two_hosts(corpus, base, os.path.join(base, "coord2"),
                             train_iters=8, save_interval=2,
                             peer_death_timeout="10")
    for h, (rc, out) in enumerate(resumed):
        assert rc == 0, f"host {h}: rc={rc}\n{out[-4000:]}"
        assert "loaded checkpoint at iteration 2" in out, (h, out[-3000:])
        # bitwise-identical post-resume loss curve vs the oracle
        losses = _losses_by_iteration(out)
        assert set(losses) == set(range(3, 9)), (h, sorted(losses))
        for it in range(3, 9):
            assert losses[it] == ref[it], (h, it, losses[it], ref[it])
        assert checkpointing.read_tracker(
            os.path.join(base, f"save{h}")) == 8


def _losses_by_iteration(stdout):
    import re

    out = {}
    for m in re.finditer(r"iteration (\d+)/\d+ \|.*?lm loss: ([0-9.einf-]+)",
                         stdout):
        out[int(m.group(1))] = m.group(2)
    return out


def test_save_interval_auto_in_process(tmp_path):
    """--save_interval auto end-to-end: with a grace window too small for
    any budget the cadence clamps to the floor deterministically, saves
    land every `floor` steps, and the retune is journaled."""
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
    proto = {"tokens": rng.integers(0, 64, (8, 16)).astype(np.int64),
             "labels": rng.integers(0, 64, (8, 16)).astype(np.int64),
             "loss_mask": np.ones((8, 16), np.float32)}

    def factory(consumed, gbs):
        def gen():
            while True:
                yield proto
        return gen()

    tele = tmp_path / "tele"
    cfg = RunConfig(
        model=model,
        optimizer=OptimizerConfig(lr=1e-3, lr_decay_style="constant"),
        training=TrainingConfig(
            micro_batch_size=1, global_batch_size=8, train_iters=11,
            log_interval=1 << 30, seed=0, telemetry_dir=str(tele),
            save=str(tmp_path / "ckpt"),
            save_interval_auto=True, save_interval_floor=4,
            # grace smaller than any step: budget 0 => floor cadence
            preempt_save_timeout=1e-6))
    loop = TrainLoop(cfg, log=lambda m: None)
    loop.train(factory)
    evs, _ = read_events(os.path.join(str(tele), "events.jsonl"))
    retunes = [e for e in evs if e["kind"] == "cadence_retune"]
    assert retunes and retunes[0]["to_interval"] == 4
    assert retunes[0]["floor"] == 4
    commits = sorted(e["iteration"] for e in evs
                     if e["kind"] == "checkpoint_commit")
    # every floor-th step, plus the end-of-run save
    assert commits == [4, 8, 11], commits
    # mutual exclusion with a fixed interval is validated loudly
    with pytest.raises(ValueError, match="mutually exclusive"):
        TrainingConfig(save_interval=5, save_interval_auto=True).validate()


def test_save_interval_auto_refused_on_coordinated_runs(tmp_path,
                                                        monkeypatch):
    """Per-host-measured cadences cannot agree on exact future save
    iterations; the combination must be a loud startup error, never a
    desynchronized two-phase commit."""
    from megatron_tpu.config import (
        ModelConfig, OptimizerConfig, RunConfig, TrainingConfig,
    )
    from megatron_tpu.training.pretrain import TrainLoop

    monkeypatch.setenv(coordination.COORD_HOST_ENV, "0")
    monkeypatch.setenv(coordination.COORD_NUM_HOSTS_ENV, "2")
    model = ModelConfig(
        num_layers=2, hidden_size=32, num_attention_heads=4, num_kv_heads=4,
        ffn_hidden_size=64, vocab_size=64, seq_length=16,
        params_dtype="float32").validate()
    cfg = RunConfig(
        model=model,
        optimizer=OptimizerConfig(lr=1e-3, lr_decay_style="constant"),
        training=TrainingConfig(
            micro_batch_size=1, global_batch_size=8, train_iters=2,
            save=str(tmp_path / "ckpt"), save_interval_auto=True,
            coordination_dir=str(tmp_path / "coord")))
    with pytest.raises(ValueError, match="not supported on coordinated"):
        TrainLoop(cfg, log=lambda m: None)
