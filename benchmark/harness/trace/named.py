"""A traced training run read by the names the program gives its work.

The program names its regions with `jax.named_scope` (`embed`, `attention`,
`mlp`, `head_loss`, `optimizer`) and its Pallas kernels by `name=` and a
scope of the same string (`flash_fwd`, `flash_bwd_dq`, ...). On a TPU the
names arrive in each operation's event METADATA, as the stat `tf_op`: the
jaxpr name stack, "jit(train_step)/while/body/closed_call/transpose(jvp())/
.../attention/flash_bwd_dq/pallas_call:". The loop's host phases arrive on
the `/host:CPU` plane as `jax.profiler` annotations: one `train-pass` span
a pass of the train loop, the loop's timers nested inside it.

    per_run(path)          own device time of each region, of recomputation
                           and of each kernel, inside the runs of the step
                           program that the trace holds whole, per run, mean
                           of devices
    read(path)["passes"]   each whole `train-pass` with the time its
                           `metrics-fetch` and `batch-generator` spans took
    journal(file)          the records of a run's tele/events.jsonl
    of_run, region_ms, kernel_ms, roofline_pct: what the readers of
    benchmark/layer_metrics/ call, given the harness's `run`

Every operation's own time goes to exactly one region, so the regions sum
to the busy time of a whole run. A program without the names (a parent
commit) gives None, never a partition with everything under `other`.

Readers import this module by name, so the decode of one trace, a few
seconds of pure-Python protobuf walking, is shared by all of them.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

from benchmark.harness.spec import REPO
from benchmark.harness.trace import kernel_cost, proto, reduce, xplane

REGIONS = ("optimizer", "head_loss", "attention", "mlp", "embed")
OTHER = "other"
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_decode",
           "paged_flash_decode")
RECOMPUTED = "rematted_computation"
PASS = "train-pass"
FETCH, DATA = "metrics-fetch", "batch-generator"
HOST_PLANE = "/host:CPU"

_EVENT_MD_NAME, _EVENT_MD_STATS = 2, 5          # XEventMetadata
_PLANE_EVENT_MD, _PLANE_STAT_MD = 4, 5          # XPlane
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")  # jvp(x), transpose(jvp(x))


def run_files(run) -> Tuple[str, str]:
    """(trace dir, journal file) of a run, where the harness made them."""
    run_dir = os.path.join(REPO, "runs", "benchmark", run.cell.name)
    return (os.path.join(run_dir, "trace"),
            os.path.join(run_dir, "tele", "events.jsonl"))


def tokens(tf_op: str) -> List[str]:
    """The name stack's parts, outermost first, with the wrappers that
    differentiation and transposition put around a scope's name taken
    off: "a/transpose(jvp(attention))/mul:" -> ["a", "attention", "mul"]."""
    out = []
    for part in tf_op.rstrip(":").split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        out.append(part)
    return out


def region_of(parts: List[str]) -> str:
    """The innermost region scope the operation sits under, else `other`."""
    for part in reversed(parts):
        if part in REGIONS:
            return part
    return OTHER


def kernel_of(parts: List[str]) -> Optional[str]:
    for part in reversed(parts):
        if part in KERNELS:
            return part
    return None


def metadata_tf_ops(plane_buf: bytes) -> Dict[str, str]:
    """Event name -> `tf_op`, from the plane's event metadata (the stats
    an event's NAME carries, which harness/trace/xplane.py leaves out: it
    decodes only the stats of each event)."""
    stat_names: Dict[int, str] = {}
    entries: List[bytes] = []
    for fn, wt, v in proto.fields(plane_buf):
        if wt != proto.WIRE_LEN:
            continue
        if fn == _PLANE_STAT_MD:
            key, md = xplane._map_entry(v)
            stat_names[key] = xplane._metadata_name(md)
        elif fn == _PLANE_EVENT_MD:
            entries.append(xplane._map_entry(v)[1])
    out: Dict[str, str] = {}
    for md in entries:
        name, tf_op = "", None
        for fn, wt, v in proto.fields(md):
            if wt != proto.WIRE_LEN:
                continue
            if fn == _EVENT_MD_NAME:
                name = proto.to_text(v)
            elif fn == _EVENT_MD_STATS:
                key, value = xplane._decode_stat(v, stat_names)
                if key == "tf_op" and isinstance(value, str):
                    tf_op = value
        if tf_op is not None:
            out[name] = tf_op
    return out


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
    """One device: picoseconds inside its whole runs by region, in
    recomputation, and by kernel (with each kernel call's HLO text)."""
    whole = reduce.whole_runs(reduce._line(plane, reduce.MODULE_LINE))
    runs = reduce.merge((m.start_ps, m.end_ps) for m in whole)
    starts = [s for s, _ in runs]
    regions = {name: 0 for name in REGIONS + (OTHER,)}
    recomputed = 0
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
        if RECOMPUTED in parts:
            recomputed += own
        kernel = kernel_of(parts) if reduce.is_kernel(ev) else None
        if kernel is not None:
            k = kernels.setdefault(kernel, {"ps": 0, "calls": {}})
            k["ps"] += own
            k["calls"][ev.name] = k["calls"].get(ev.name, 0) + 1
    return {"runs": len(whole), "named": named, "regions": regions,
            "recomputed_ps": recomputed, "kernels": kernels}


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
    for f in xplane.find_xplane_files(path):
        with open(f, "rb") as fh:
            data = fh.read()
        for buf in xplane.raw_planes(data):
            name = xplane.plane_name(buf)
            if reduce._DEVICE_PLANE.match(name):
                devices[name] = reduce_device(
                    xplane.decode_plane(buf, want), metadata_tf_ops(buf))
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
    that hold one: `regions` (they sum to the run's busy time),
    `recomputed`, and per kernel its seconds and its calls a run
    ({HLO text: calls}). None where no device holds a whole run, or the
    program carries none of the region names."""
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
            "recomputed": mean(lambda d: d["recomputed_ps"]),
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


def kernel_ms(run, *kernels: str) -> Optional[float]:
    """Milliseconds a step in the named kernels' custom calls; None where
    none of them ran by that name."""
    got = of_run(run)
    if got is None or not any(k in got["kernels"] for k in kernels):
        return None
    return 1e3 * sum(got["kernels"].get(k, {"s": 0.0})["s"]
                     for k in kernels)


def roofline_pct(run, label: str, *kernels: str) -> Optional[float]:
    """The named kernels' share of their roofline (kernel_cost.roofline:
    needed work from the calls' own shapes, the window from the cell's
    configuration, the run's peaks); what it was computed from, with the
    bound that applies, goes to the line's `extras.roofline[label]`. None
    in a rehearsal (no peaks)."""
    got = of_run(run) if run.peaks is not None else None
    if got is None:
        return None
    roof = kernel_cost.roofline(got["kernels"], kernels,
                                run.cell.config.get("sliding_window"),
                                run.peaks)
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
