"""What the two children that hold the chip share: reading the plan,
refusing the wrong device, the benchmark's listeners, writing the result."""

from __future__ import annotations

import json
import os
import sys

from benchmark.harness import compile_watch, devices, profiler

WRONG_DEVICE_EXIT = 3


def begin(plan_path: str) -> tuple:
    """(plan, device summary). Exits with WRONG_DEVICE_EXIT, before
    anything else touches the device, where JAX does not report the TPU
    chips the cell asks for (and the run is no rehearsal)."""
    with open(plan_path) as f:
        plan = json.load(f)
    try:
        found = devices.check_devices(plan["chips"], plan["rehearse"])
    except devices.WrongDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        sys.exit(WRONG_DEVICE_EXIT)
    compile_watch.install(os.path.join(plan["run_dir"], "compiles.jsonl"))
    profiler.without_python_tracer()
    return plan, found


def write_result(run_dir: str, result: dict) -> None:
    """result.json, whole or not at all: the parent reads it only after
    this process has ended, but a kill must not leave half a file."""
    tmp = os.path.join(run_dir, "result.json.tmp")
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, os.path.join(run_dir, "result.json"))
