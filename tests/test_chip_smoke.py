"""chip_smoke.py off the chip: its rehearsal mode at toy size on the CPU
(same control flow, same entry points, kernels interpreted), and the ways
it must fail — no TPU, a failing phase, a directory that is not a
checkout. What it proves ON the chip is in CHANGES.md / PERF.md."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd=REPO, script=SMOKE, n_devices=1, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    return subprocess.run([sys.executable, script] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _lines(stdout):
    return [json.loads(line) for line in stdout.splitlines() if line.strip()]


@pytest.mark.slow  # ~50 s: six child processes (trainer, server...).
# Tier-1 runs against its time limit on this host (ROADMAP D10), so the
# end-to-end rehearsal is in the slow set; tier-1 keeps the parent's
# contract (the three tests at the end) and the no-TPU / no-checkout exits.
# Run it before any chip call: pytest -m slow tests/test_chip_smoke.py
def test_rehearsal_drives_the_main_path(tmp_path):
    """Every phase in order through the real CLIs (trainer, preprocess,
    server), each line naming the device it really ran on, the last line
    the contract's object and nothing else."""
    r = _run(["--rehearse", "--workdir", str(tmp_path / "work")])
    assert r.returncode == 0, r.stderr[-3000:]
    lines = _lines(r.stdout)
    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert lines[-1] == {"ok": True, "device": cpu}
    phases = lines[:-1]
    assert [p["phase"] for p in phases] == [
        "device", "kernels", "data", "train", "serve"]
    assert all(p["ok"] for p in phases)
    by = {p["phase"]: p for p in phases}
    assert all(by[p]["device"] == cpu for p in by if p != "data")
    assert by["device"]["setup"]["block_until_ready_waits"] is True
    assert set(by["kernels"]["max_rel_err"]) == {
        "forward", "gradient", "decode", "decode_mq5",
        "paged_decode_page16", "paged_decode_page128"}
    assert by["data"]["native_helpers"] == "built"
    train = by["train"]
    assert train["recompiles_after_first_step"] == 0
    assert train["losses"][-1] < train["losses"][0]
    # interpreted kernels: pallas_call equations, no TPU custom calls
    assert train["kernels_in_step"]["pallas_calls_in_jaxpr"] >= 3
    serve = by["serve"]
    assert serve["decode_recompiles"] == 0
    assert serve["weights_version"] == train["checkpoint_iteration"]
    assert serve["kernels_in_step"]["pallas_calls_in_jaxpr"] >= 1
    assert serve["concurrent"] >= 2 and serve["drained"]


@pytest.mark.slow  # ~40 s: two toy trainings on four virtual devices
def test_rehearsal_four_devices(tmp_path):
    """--chips 4 runs the sharded trainer and its unsharded baseline and
    no other phase; the last line counts four devices."""
    r = _run(["--rehearse", "--chips", "4", "--workdir",
              str(tmp_path / "work")], n_devices=4)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = _lines(r.stdout)
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    assert [p["phase"] for p in lines[:-1]] == [
        "data", "train_sharded", "train_baseline", "compare"]
    sharded, base, cmp_ = lines[1], lines[2], lines[3]
    assert sharded["devices_holding_state"] == [0, 1, 2, 3]
    assert base["devices_holding_state"] == [0]
    assert sharded["smallest_shard_fraction"] == 0.25
    assert sharded["kernels_in_step"]["pallas_calls_in_jaxpr"] >= 3
    assert sharded["compiled"]["collectives"]["all-gather"] > 0
    assert cmp_["max_loss_diff_first3"] < 0.05


def test_without_a_tpu_it_fails_and_prints_no_result(tmp_path):
    """No --rehearse, no TPU: non-zero exit and an empty stdout — nothing
    a reader could take for a result, and no path onto the CPU."""
    r = _run(["--workdir", str(tmp_path / "work")])
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_alone_in_a_directory_it_fails(tmp_path):
    """The script without the program has nothing to drive."""
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    r = _run([], cwd=str(tmp_path), script=str(tmp_path / "chip_smoke.py"))
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "not in a checkout" in r.stderr


def _canned_run(phases, dev):
    def run(size, work, seed, rehearse, emit):
        for name in phases:
            emit({"phase": name, "ok": True, "device": dev, "seconds": 0.0})
        return dev
    return run


def test_last_line_is_the_contract_object(monkeypatch, tmp_path, capsys):
    """Phases pass: exit 0, and the LAST line is exactly
    {"ok": true, "device": {platform, kind, count}} as the phases
    reported the device — nothing else in it."""
    sys.path.insert(0, REPO)
    import chip_smoke

    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    phases = ["device", "kernels", "data", "train", "serve"]
    monkeypatch.setattr(chip_smoke, "run_one_chip", _canned_run(phases, tpu))
    rc = chip_smoke.main(["--workdir", str(tmp_path / "w")])
    out = capsys.readouterr().out
    assert rc == 0
    assert [l["phase"] for l in _lines(out)[:-1]] == phases
    assert out.splitlines()[-1] == json.dumps({"ok": True, "device": tpu})


def test_four_chip_option_needs_four_devices(monkeypatch, tmp_path, capsys):
    """--chips 4 runs the four-chip path only (never run_one_chip) and
    fails unless JAX reported four devices; with four, the count in the
    last line is 4."""
    sys.path.insert(0, REPO)
    import chip_smoke

    def never(*a, **k):
        raise AssertionError("--chips 4 ran a one-chip phase")

    monkeypatch.setattr(chip_smoke, "run_one_chip", never)
    for count, want_rc in ((4, 0), (1, 1)):
        dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": count}
        monkeypatch.setattr(chip_smoke, "run_four_chips", _canned_run(
            ["data", "train_sharded", "train_baseline", "compare"], dev))
        rc = chip_smoke.main(["--chips", "4", "--workdir",
                              str(tmp_path / f"w{count}")])
        last = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert rc == want_rc
        assert last == ({"ok": True, "device": dev} if count == 4 else {
            "ok": False, "after": ["data", "train_sharded",
                                   "train_baseline", "compare"]})


def test_a_failing_phase_fails_the_run(monkeypatch, tmp_path, capsys):
    """Any phase failing: non-zero exit code, the phase lines printed so
    far stay, and the last line is not the contract's ok object."""
    sys.path.insert(0, REPO)
    import chip_smoke

    cpu = {"platform": "cpu", "kind": "cpu", "count": 1}

    def failing_run(size, work, seed, rehearse, emit):
        emit({"phase": "device", "ok": True, "device": cpu})
        raise chip_smoke.PhaseFailed("loss did not fall: [10.4, 10.4]")

    monkeypatch.setattr(chip_smoke, "run_one_chip", failing_run)
    rc = chip_smoke.main(["--rehearse", "--workdir", str(tmp_path / "w")])
    out = capsys.readouterr()
    assert rc == 1
    lines = _lines(out.out)
    assert lines[0]["phase"] == "device"
    assert lines[-1] == {"ok": False, "after": ["device"]}
    assert "loss did not fall" in out.err
