"""A traced training run read by the names the program gives its work
(names.py says how they arrive and which partition the step).

    per_run(path)          own device time of each region, of every scope
                           and of each kernel, inside the runs of the step
                           program that the trace holds whole, per run, mean
                           of devices
    read(path)["passes"]   each whole `train-pass` with the time its
                           `metrics-fetch` and `batch-generator` spans took
    journal(file)          the records of a run's tele/events.jsonl
    of_run, region_ms, scope_ms, kernel_ms, roofline_pct, step_program:
    what the readers of <path>/layer_metrics/ call, given the harness's
    `run`. A reader of a scope or a kernel a later PR adds is a new file
    of a few lines: `named.scope_ms(run, "router")`,
    `named.kernel_ms(run, "grouped_matmul")`, and for a roofline share
    `named.roofline_pct(run, "label", "grouped_matmul")` with the kernel's
    needed work in <path>/kernel_costs/grouped_matmul.py.

Every operation's own time goes to exactly one region, so the regions sum
to the busy time of a whole run; a scope's time is that of the operations
whose name stack holds it at any depth, so scopes overlap (`mlp` holds
`router`, `rematted_computation` cuts across regions). A program without
the region names (a parent commit) gives None, never a partition with
everything under `other`.

Readers import this module by name, so the decode of one trace, a few
seconds of pure-Python protobuf walking, is shared by all of them.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

from benchmark.harness.spec import REPO
from benchmark.harness.trace import kernel_cost, reduce, xplane
from benchmark.harness.trace.names import (
    DATA, FETCH, HOST_PLANE, OTHER, PASS, REGIONS, kernel_of,
    name_stacks, region_of, tokens,
)


def run_files(run) -> Tuple[str, str]:
    """(trace dir, journal file) of a run, where the harness made them."""
    run_dir = os.path.join(REPO, "runs", "benchmark", run.cell.name)
    return (os.path.join(run_dir, "trace"),
            os.path.join(run_dir, "tele", "events.jsonl"))


def _inside(segs: List[reduce.Interval], runs: List[reduce.Interval],
            starts: List[int]) -> int:
    """Picoseconds of an operation's own segments inside the whole runs."""
    if not segs or not runs:
        return 0
    at = bisect.bisect_right(starts, segs[0][0]) - 1
    if at >= 0 and segs[-1][1] <= runs[at][1]:
        return reduce.total(segs)          # wholly inside one run
    return reduce.total(segs) - reduce.total(reduce.subtract(segs, runs))


def reduce_device(plane: xplane.Plane, tf_ops: Dict[str, str]
                  ) -> Dict[str, Any]:
    """One device: picoseconds inside its whole runs by region, by scope
    (every part of every name stack) and by kernel (with each kernel
    call's HLO text)."""
    whole = reduce.whole_runs(reduce._line(plane, reduce.MODULE_LINE))
    runs = reduce.merge((m.start_ps, m.end_ps) for m in whole)
    starts = [s for s, _ in runs]
    regions = {name: 0 for name in REGIONS + (OTHER,)}
    scopes: Dict[str, int] = {}
    kernels: Dict[str, Dict[str, Any]] = {}
    named = False
    for ev, segs in reduce.self_segments(reduce._line(plane,
                                                      reduce.OP_LINE)):
        own = _inside(segs, runs, starts)
        if not own:
            continue
        parts = tokens(tf_ops.get(ev.name, ""))
        region = region_of(parts)
        named = named or region != OTHER
        regions[region] += own
        for part in set(parts):
            scopes[part] = scopes.get(part, 0) + own
        kernel = kernel_of(parts) if reduce.is_kernel(ev) else None
        if kernel is not None:
            k = kernels.setdefault(kernel, {"ps": 0, "calls": {}})
            k["ps"] += own
            k["calls"][ev.name] = k["calls"].get(ev.name, 0) + 1
    return {"runs": len(whole), "named": named, "regions": regions,
            "scopes": scopes, "kernels": kernels}


def host_passes(plane: xplane.Plane) -> List[Dict[str, Any]]:
    """The whole `train-pass` spans of the loop thread's line, in order:
    their step number and the picoseconds of the pass, of the lagged
    `metrics-fetch` (the host waits for the device there) and of
    `batch-generator` (it waits for data) inside it."""
    out: List[Dict[str, Any]] = []
    for line in plane.lines:
        passes = sorted((ev for ev in line.events if ev.name == PASS),
                        key=lambda ev: ev.start_ps)
        for p in passes:
            waits = {FETCH: 0, DATA: 0}
            for ev in line.events:
                if (ev.name in waits and ev.start_ps >= p.start_ps
                        and ev.end_ps <= p.end_ps):
                    waits[ev.name] += ev.duration_ps
            out.append({"step_num": p.stats.get("step_num"),
                        "pass_ps": p.duration_ps,
                        "fetch_ps": waits[FETCH], "data_ps": waits[DATA]})
    return out


@functools.lru_cache(maxsize=4)
def _read(path: str, stamp: float) -> Dict[str, Any]:
    t0 = time.monotonic()
    devices: Dict[str, Dict[str, Any]] = {}
    passes: List[Dict[str, Any]] = []
    want = lambda n: n in (reduce.OP_LINE, reduce.MODULE_LINE)  # noqa: E731
    for name, buf in xplane.capture_planes(path):
        if reduce._DEVICE_PLANE.match(name):
            devices[name] = reduce_device(
                xplane.decode_plane(buf, want), name_stacks(buf))
        elif name == HOST_PLANE:
            passes += host_passes(xplane.decode_plane(buf))
    return {"devices": devices, "passes": passes,
            "decode_s": time.monotonic() - t0}


def read(path: str) -> Dict[str, Any]:
    """The trace under `path`, decoded once per file state."""
    files = xplane.find_xplane_files(path)
    stamp = max((os.path.getmtime(f) for f in files), default=0.0)
    return _read(os.path.abspath(path), stamp)


def per_run(path: str) -> Optional[Dict[str, Any]]:
    """Seconds a whole run of the step program, mean over the devices
    that hold one: `regions` (they sum to the run's busy time), `scopes`
    (every part of a name stack, by name), and per kernel its seconds and
    its calls a run ({HLO text: calls}). None where no device holds a
    whole run, or the program carries none of the region names."""
    devices = [d for d in read(path)["devices"].values() if d["runs"]]
    if not devices or not any(d["named"] for d in devices):
        return None
    n = len(devices)
    mean = lambda get: sum(get(d) / d["runs"]  # noqa: E731
                           for d in devices) / n * reduce.PS
    kernels: Dict[str, Dict[str, Any]] = {}
    for name in {k for d in devices for k in d["kernels"]}:
        calls: Dict[str, float] = {}
        for d in devices:
            for text, count in d["kernels"].get(name, {"calls": {}})[
                    "calls"].items():
                calls[text] = calls.get(text, 0.0) + count / d["runs"] / n
        kernels[name] = {
            "s": mean(lambda d: d["kernels"].get(name, {"ps": 0})["ps"]),
            "calls": calls}
    return {"devices": n, "runs": min(d["runs"] for d in devices),
            "regions": {r: mean(lambda d: d["regions"][r])
                        for r in REGIONS + (OTHER,)},
            "scopes": {name: mean(lambda d: d["scopes"].get(name, 0))
                       for name in {s for d in devices for s in d["scopes"]}},
            "kernels": kernels}


def of_run(run) -> Optional[Dict[str, Any]]:
    """per_run() of a run's own trace; None for a run that was not traced,
    before the disk is touched (a made-up run in a test must not read the
    files an earlier real run of the cell left)."""
    if run.trace is None:
        return None
    return per_run(run_files(run)[0])


def region_ms(run, *regions: str) -> Optional[float]:
    """Milliseconds a step under the named regions."""
    got = of_run(run)
    return None if got is None else 1e3 * sum(got["regions"][r]
                                              for r in regions)


def scope_ms(run, name: str) -> Optional[float]:
    """Milliseconds a step of the operations whose name stack holds
    `name` at any depth (a `jax.named_scope` of the program, or one of
    JAX's own parts); 0.0 where the program is named and no operation
    sits under it."""
    got = of_run(run)
    return None if got is None else 1e3 * got["scopes"].get(name, 0.0)


def kernel_ms(run, *kernels: str) -> Optional[float]:
    """Milliseconds a step in the custom calls of the Pallas kernels of
    those names (`pallas_call(name=)`); None where none of them ran."""
    got = of_run(run)
    if got is None or not any(k in got["kernels"] for k in kernels):
        return None
    return 1e3 * sum(got["kernels"].get(k, {"s": 0.0})["s"]
                     for k in kernels)


def roofline_pct(run, label: str, *kernels: str) -> Optional[float]:
    """The named kernels' share of their roofline (kernel_cost.roofline:
    needed work by each kernel's own file of <path>/kernel_costs/, from
    the calls' shapes and the cell's configuration, over the run's
    peaks); what it was computed from, with the bound that applies, goes
    to the line's `extras.roofline[label]`. None in a rehearsal (no
    peaks)."""
    got = of_run(run) if run.peaks is not None else None
    if got is None:
        return None
    roof = kernel_cost.roofline(got["kernels"], kernels, run.cell.kernel_cost,
                                run.cell.config, run.peaks)
    if roof is None:
        return None
    run.extras.setdefault("roofline", {})[label] = roof
    return roof["pct"]


def journal(path: str) -> List[dict]:
    """The records of a telemetry journal (tele/events.jsonl), in order;
    empty where the file is not there."""
    try:
        with open(path) as f:
            return [json.loads(line) for line in f if line.strip()]
    except FileNotFoundError:
        return []


def step_program(run) -> Optional[dict]:
    """The last `step_program` record of a run's journal: what the
    compiler says the traced step program needs on one chip
    (`argument_bytes`, `temp_bytes`, `output_bytes`, `alias_bytes`; the
    trainer writes it after a run that opened a trace window). None for
    a run that journalled no step, before the disk is touched."""
    if not run.steps:
        return None
    programs = [r for r in journal(run_files(run)[1])
                if r.get("kind") == "step_program"]
    return programs[-1] if programs else None
