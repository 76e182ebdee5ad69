#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
                             --trace <0|1>

The cell names a configuration and a traffic mix; both are data files
(benchmark/configs/, benchmark/traffic/), and the mix's "driver" picks
the module that runs it: benchmark/harness/<kind>_driver.py, where <kind>
is the driver's name up to its first "_" (train; serve_open and
serve_closed share serve). The configuration names its architecture, one
file of benchmark/reference/: the plain reference `correct` is held to
and the translation of the sizes into the program's flags. With --trace 0 the line holds the cell's
end-to-end metrics, with --trace 1 its per-layer metrics (each read by
benchmark/layer_metrics/<reader>.py), `device.busy_s`/`window_s` and a
`breakdown`. Each number `correct` compared stands beside its limit in the
line's last key, `compared`, and in the last lines of standard error.
Weights, data and requests are made from --seed. The program
under test runs in a child that alone holds the chip(s); this process
never initialises a JAX backend.

Exit code 0 and a last line of JSON on success. No TPU, fewer chips than
the cell asks for, a device that is not in benchmark/peaks.json, or a
checkout that lacks the program: a non-zero exit code and no result line.
--rehearse (tests, JAX_PLATFORMS=cpu) runs the same control flow on
whatever JAX finds and names that platform in the line.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

STARTED = time.time()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import common, peaks, spec, stats  # noqa: E402


def _value(metric: dict, read) -> dict | None:
    """{"value", "unit"} of one metric, or None where it has no value in
    this run (a reader that found nothing to read)."""
    try:
        value = read()
    except stats.TooFewSamples as e:
        print(f"benchmark: {metric['name']}: {e}", file=sys.stderr)
        return None
    if value is None:
        return None
    return {"value": float(value), "unit": metric["unit"]}


def result_line(run: common.Run, trace: bool) -> dict:
    cell = run.cell
    metrics = {}
    if trace:
        for m in cell.per_layer():
            reader = cell.reader(m["name"])
            got = _value(m, lambda reader=reader: reader(run))
            if got is not None:
                metrics[m["name"]] = got
    else:
        for m in cell.end_to_end():
            read = ((lambda: run.setup_s) if m["name"] == "setup_s"
                    else run.end_to_end.get(m["name"]))
            if read is None:
                raise common.RunFailed(
                    f"the {cell.traffic['driver']} driver has no "
                    f"end-to-end metric {m['name']!r}")
            got = _value(m, read)
            if got is None:
                run.problems.append(f"{m['name']} has no value")
            else:
                metrics[m["name"]] = got
    device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
    line = {"correct": not run.problems, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    if run.problems:
        line["problems"] = run.problems
    line["workload"] = cell.name
    line["extras"] = run.extras
    return line


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--spec", default=os.path.join(REPO, "BENCHMARK.json"),
                   help="another BENCHMARK.json (tests: a cell made of "
                        "files in a temporary directory)")
    p.add_argument("--rehearse", action="store_true",
                   help="run on whatever backend JAX finds (never a "
                        "measurement: the line names the platform)")
    args = p.parse_args(argv)

    missing = [f for f in ("pretrain_gpt.py", "megatron_tpu", os.path.join(
        "tools", "run_text_generation_server.py"))
        if not os.path.exists(os.path.join(REPO, f))]
    if missing:
        print(f"benchmark: not in a checkout of the program (missing "
              f"{missing}): there is nothing to measure", file=sys.stderr)
        return 2
    try:
        cell = spec.Cell(args.spec, args.workload)
        kind = cell.traffic["driver"].split("_", 1)[0]
        driver = importlib.import_module(f"benchmark.harness.{kind}_driver")
        run = driver.run(cell, args.seed, args.seconds, bool(args.trace),
                         args.rehearse, STARTED)
        if run.compiles_in_window:
            raise common.RunFailed(
                f"{run.compiles_in_window} compilation(s) inside the "
                "measured window: warm-up missed a shape, and the numbers "
                "hold compile time")
        if run.device["platform"] == "tpu" or not args.rehearse:
            run.peaks = peaks.peaks_for(run.device["kind"])
        if args.trace and run.trace is None and not args.rehearse:
            raise common.RunFailed(
                "the traced run holds no operation on a device plane")
        line = result_line(run, bool(args.trace))
    except common.RunFailed as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return e.code
    except (spec.SpecError, peaks.UnknownDevice) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    if args.rehearse:
        line["rehearsal"] = True
    # each number `correct` compared beside its limit: the last lines on
    # standard error, and the line's last key
    for name, got in run.compared.items():
        print(f"benchmark: compared {name} "
              + " ".join(f"{k} {v}" for k, v in got.items()),
              file=sys.stderr)
    line["compared"] = run.compared
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
