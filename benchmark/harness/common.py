"""What both drivers share: the run's directory, the child that holds the
chip, and the record that per-layer readers read."""

from __future__ import annotations

import dataclasses
import os
import shutil
import subprocess
import sys
from typing import Any, Callable, Dict, List, Optional

from benchmark.harness.spec import REPO, Cell


WRONG_DEVICE_EXIT = 3  # benchmark/harness/child.py: not the TPU asked for


class RunFailed(RuntimeError):
    """The run cannot give a result line (no TPU, a dead child, ...)."""

    def __init__(self, message: str, code: int = 1):
        super().__init__(message)
        self.code = code


@dataclasses.dataclass
class Run:
    """One run of one cell, as the readers of benchmark/layer_metrics/
    see it. Lists are of what happened INSIDE the measured window."""

    cell: Cell
    seconds: float
    device: Dict[str, Any]                 # platform, kind, count
    memory_peak_bytes: int
    setup_s: float
    end_to_end: Dict[str, Callable[[], float]]
    attempted: int
    failed: int
    problems: List[str]                    # why `correct` is false
    # each number `correct` compared, beside its limit: {name: {"value",
    # and "at_most" or "at_least"}}; the line's last key
    compared: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    compiles_in_window: int = 0            # any at all voids the run
    steps: List[dict] = dataclasses.field(default_factory=list)
    requests: List[dict] = dataclasses.field(default_factory=list)
    engine_requests: List[dict] = dataclasses.field(default_factory=list)
    trace: Optional[Dict[str, Any]] = None  # trace/reduce.py, traced run
    peaks: Optional[Dict[str, Any]] = None  # None only in a rehearsal
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)


def fresh_run_dir(cell_name: str) -> str:
    """runs/benchmark/<cell> in the checkout (.gitignore lists runs/),
    emptied: every run makes its inputs anew from the seed."""
    path = os.path.join(REPO, "runs", "benchmark", cell_name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def child_env(chips: int, rehearse: bool) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    if rehearse:
        # the Pallas kernels dispatch (interpreted) on a CPU host only
        # when asked; and a CPU host has one device unless told otherwise
        env.setdefault("MEGATRON_TPU_FLASH_INTERPRET", "1")
        if env.get("JAX_PLATFORMS", "").startswith("cpu"):
            env["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={chips}")
    return env


def start_child(module: str, plan_path: str, log_path: str, chips: int,
                rehearse: bool) -> subprocess.Popen:
    log = open(log_path, "w")  # noqa: SIM115 - closed with the child
    proc = subprocess.Popen(
        [sys.executable, "-m", module, plan_path], stdout=log,
        stderr=subprocess.STDOUT, cwd=REPO, env=child_env(chips, rehearse))
    proc._benchmark_log = log  # type: ignore[attr-defined]
    return proc


def finish_child(proc: subprocess.Popen, timeout: float, log_path: str,
                 what: str) -> None:
    """Wait for the child to end (kill it past `timeout`); RunFailed on
    anything but exit code 0."""
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        rc = -9
    finally:
        proc._benchmark_log.close()  # type: ignore[attr-defined]
    if rc != 0:
        with open(log_path, errors="replace") as f:
            tail = f.read()[-4000:]
        raise RunFailed(f"{what} exited {rc}; log tail:\n{tail}",
                        code=rc if rc == WRONG_DEVICE_EXIT else 1)
