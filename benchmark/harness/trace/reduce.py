"""From a device trace to numbers: busy time, kernel time, exposed
collective time, the operations that took most time and the longest idle
gaps. Picoseconds inside, seconds out.

A device is a plane named "/device:TPU:<n>"; its operations are the
events of its "XLA Ops" line (whole-program envelopes on "XLA Modules"
and "Steps" are not operations: counted as such they would make every
device busy all the time). Events nest (a `while` holds its body), so an
operation's own time is its duration minus the operations inside it.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

from benchmark.harness.trace import xplane

PS = 1e-12
KERNEL_TARGET = "tpu_custom_call"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)")

_PROGRAM_ID = re.compile(r"\(\d+\)$")  # "jit_train_step(1234)": the id varies

Interval = Tuple[int, int]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The parts of merged intervals `a` that merged `b` does not cover."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < e:
            out.append((at, e))
    return out


def self_segments(events: List[xplane.Event]
                  ) -> List[Tuple[xplane.Event, List[Interval]]]:
    """Each event of one line with the intervals in which it, and no
    event nested inside it, runs."""
    order = sorted(events, key=lambda ev: (ev.start_ps, -ev.end_ps))
    out: List[Tuple[xplane.Event, List[Interval]]] = []
    stack: List[Tuple[xplane.Event, List[Interval], int]] = []

    def close(upto: int) -> None:
        while stack and stack[-1][0].end_ps <= upto:
            ev, segs, at = stack.pop()
            if at < ev.end_ps:
                segs.append((at, ev.end_ps))
            out.append((ev, segs))

    for ev in order:
        close(ev.start_ps)
        if stack:
            parent, segs, at = stack[-1]
            if ev.start_ps > at:
                segs.append((at, ev.start_ps))
            stack[-1] = (parent, segs, max(at, ev.end_ps))
        stack.append((ev, [], ev.start_ps))
    close(1 << 62)
    return out


def short_name(name: str) -> str:
    """On a TPU an operation's event is named by its whole HLO text
    ("%fusion.4 = bf16[8,128]{...} fusion(...), kind=..."): keep the
    instruction's name, its result's type and shape, and its opcode."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:120]
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rhs)
    opcode = re.search(r"[}\])]\s([a-z][a-z0-9\-]*)\(", rhs)
    parts = [lhs.lstrip("%"), shape.group(1) if shape else "",
             opcode.group(1) if opcode else "",
             "tpu_custom_call" if KERNEL_TARGET in rhs else ""]
    return " ".join(p for p in parts if p)[:120]


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.match(name.lstrip("%")))


def is_kernel(ev: xplane.Event) -> bool:
    """A Pallas (Mosaic) kernel: a custom call whose target is
    tpu_custom_call. The five flash kernels have no names of their own
    yet, so they are one number (kernel_ms_per_step) until the tracing
    issue names them."""
    return KERNEL_TARGET in ev.name


def whole_runs(modules: List[xplane.Event]) -> List[xplane.Event]:
    """The runs of the program that took most of the trace (the train
    step, in a training cell), without the first and the last of them:
    the trace starts and stops between two host calls, in the middle of
    whatever the device is running, so those two may be cut."""
    by_program: Dict[str, List[xplane.Event]] = {}
    for m in modules:
        by_program.setdefault(_PROGRAM_ID.sub("", m.name), []).append(m)
    if not by_program:
        return []
    runs = max(by_program.values(),
               key=lambda evs: sum(ev.duration_ps for ev in evs))
    return sorted(runs, key=lambda ev: ev.start_ps)[1:-1]


def device_planes(path: str) -> Dict[int, xplane.Plane]:
    want = lambda n: bool(_DEVICE_PLANE.match(n))  # noqa: E731
    lines = lambda n: n in (OP_LINE, MODULE_LINE)  # noqa: E731
    out: Dict[int, xplane.Plane] = {}
    for f in xplane.find_xplane_files(path):
        for plane in xplane.load_planes(f, want, lines):
            out[int(_DEVICE_PLANE.match(plane.name).group(1))] = plane
    return out


def _line(plane: xplane.Plane, name: str) -> List[xplane.Event]:
    return [ev for ln in plane.lines if ln.name == name
            for ev in ln.events if ev.duration_ps > 0]


def reduce_device(plane: xplane.Plane) -> Dict[str, Any]:
    ops = self_segments(_line(plane, OP_LINE))
    busy = merge((ev.start_ps, ev.end_ps) for ev, _ in ops)
    by_name: Dict[str, int] = {}
    kernel = collective = 0
    compute_segs: List[Interval] = []
    collective_segs: List[Interval] = []
    kernel_segs: List[Interval] = []
    for ev, segs in ops:
        own = total(segs)
        key = short_name(ev.name)
        by_name[key] = by_name.get(key, 0) + own
        if is_collective(ev.name):
            collective += own
            collective_segs += segs
        else:
            compute_segs += segs
            if is_kernel(ev):
                kernel += own
                kernel_segs += segs
    exposed = total(subtract(merge(collective_segs), merge(compute_segs)))
    # an idle gap is named by the program that ends it: the host was
    # getting that program's launch ready (the tracing issue puts host
    # spans on this clock; until then this is all the trace knows)
    modules = sorted(_line(plane, MODULE_LINE), key=lambda ev: ev.end_ps)
    ends = [m.end_ps for m in modules]
    runs = whole_runs(modules)
    kernels = merge(kernel_segs)
    outside = subtract(kernels, merge((m.start_ps, m.end_ps) for m in runs))
    gaps: List[Tuple[str, int]] = []
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        at = bisect.bisect_right(ends, s1)  # first program ending after s1
        name = ("before " + _PROGRAM_ID.sub("", modules[at].name)
                if at < len(modules) else "unattributed")
        gaps.append((name, s1 - e0))
    return {"busy": busy, "busy_ps": total(busy), "kernel_ps": kernel,
            "collective_ps": collective, "collective_exposed_ps": exposed,
            "runs": len(runs),
            "kernel_in_runs_ps": total(kernels) - total(outside),
            "by_name": by_name, "gaps": gaps,
            "span": (busy[0][0], busy[-1][1]) if busy else None}


def reduce_trace(path: str, top: int = 10) -> Optional[Dict[str, Any]]:
    """The trace under `path` as the numbers the per-layer readers take,
    or None when no device plane holds an operation."""
    devices = {k: reduce_device(p) for k, p in device_planes(path).items()}
    devices = {k: d for k, d in devices.items() if d["span"]}
    if not devices:
        return None
    start = min(d["span"][0] for d in devices.values())
    end = max(d["span"][1] for d in devices.values())
    n = len(devices)
    by_name: Dict[str, int] = {}
    gaps: Dict[str, int] = {}
    for d in devices.values():
        for name, ps in d["by_name"].items():
            by_name[name] = by_name.get(name, 0) + ps
    worst = max(devices.values(),
                key=lambda d: d["collective_exposed_ps"])
    # the first device's gaps, summed by the program that ended them
    first = devices[min(devices)]
    for name, ps in first["gaps"]:
        gaps[name] = gaps.get(name, 0) + ps
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    per_run = [d["kernel_in_runs_ps"] / d["runs"] for d in devices.values()
               if d["runs"]]
    return {
        "devices": n,
        "window_s": (end - start) * PS,
        "busy_s": sum(d["busy_ps"] for d in devices.values()) / n * PS,
        "kernel_s": sum(d["kernel_ps"] for d in devices.values()) / n * PS,
        "runs": min(d["runs"] for d in devices.values()),
        "kernel_s_per_run": (sum(per_run) / len(per_run) * PS
                             if per_run else None),
        "collective_exposed_worst_s": worst["collective_exposed_ps"] * PS,
        "device_ops": [[name, ps / n * PS] for name, ps in rank(by_name)],
        "idle_gaps": [[name, ps * PS] for name, ps in rank(gaps)],
    }
