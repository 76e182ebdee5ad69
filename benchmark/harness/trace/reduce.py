"""From a device trace to numbers: busy time, exposed collective time,
the operations that took most time and the longest idle gaps.
Picoseconds inside, seconds out.

A device is a plane named "/device:TPU:<n>"; its operations are the
events of its "XLA Ops" line (whole-program envelopes on "XLA Modules"
and "Steps" are not operations: counted as such they would make every
device busy all the time). Events nest (a `while` holds its body), so an
operation's own time is its duration minus the operations inside it.
Operations and idle gaps are labelled by the program's own names
(names.py): the region and kernel of the operation's name stack, the
host span that was open as the gap began.
"""

from __future__ import annotations

import bisect
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from benchmark.harness.trace import names, xplane

PS = 1e-12
KERNEL_TARGET = names.KERNEL_TARGET
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


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.match(name.lstrip("%")))


def is_kernel(ev: xplane.Event) -> bool:
    """A Pallas (Mosaic) kernel: a custom call whose target is
    tpu_custom_call."""
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


def reduce_device(plane: xplane.Plane,
                  tf_ops: Optional[Dict[str, str]] = None,
                  spans: Sequence[xplane.Event] = ()) -> Dict[str, Any]:
    """One device. `tf_ops` is names.name_stacks() of its plane, `spans`
    names.gap_spans() of the host plane; without them every operation is
    under `other` and every gap under the program that ends it."""
    tf_ops = tf_ops or {}
    ops = self_segments(_line(plane, OP_LINE))
    busy = merge((ev.start_ps, ev.end_ps) for ev, _ in ops)
    by_name: Dict[str, int] = {}
    collective = 0
    compute_segs: List[Interval] = []
    collective_segs: List[Interval] = []
    for ev, segs in ops:
        own = total(segs)
        key = names.op_label(ev.name, tf_ops.get(ev.name, ""))
        by_name[key] = by_name.get(key, 0) + own
        if is_collective(ev.name):
            collective += own
            collective_segs += segs
        else:
            compute_segs += segs
    exposed = total(subtract(merge(collective_segs), merge(compute_segs)))
    modules = sorted(_line(plane, MODULE_LINE), key=lambda ev: ev.end_ps)
    ends = [m.end_ps for m in modules]
    starts = [ev.start_ps for ev in spans]

    def gap_name(opens_ps: int, closes_ps: int) -> str:
        """What the host was doing as the device fell idle: the innermost
        span (they are sorted by start, outer first) that covers the
        moment the gap opens; where none does, the program whose run ends
        the gap (the host was getting its launch ready)."""
        for i in range(bisect.bisect_right(starts, opens_ps) - 1, -1, -1):
            if spans[i].end_ps > opens_ps:
                return spans[i].name
        at = bisect.bisect_right(ends, closes_ps)
        return ("before " + _PROGRAM_ID.sub("", modules[at].name)
                if at < len(modules) else "unattributed")

    gaps = [(gap_name(e0, s1), s1 - e0)
            for (_, e0), (s1, _) in zip(busy, busy[1:])]
    return {"busy": busy, "busy_ps": total(busy),
            "collective_ps": collective, "collective_exposed_ps": exposed,
            "runs": len(whole_runs(modules)),
            "by_name": by_name, "gaps": gaps,
            "span": (busy[0][0], busy[-1][1]) if busy else None}


def reduce_trace(path: str, top: int = 10) -> Optional[Dict[str, Any]]:
    """The trace under `path` as the numbers the per-layer readers take,
    or None when no device plane holds an operation."""
    planes: Dict[int, bytes] = {}
    spans: List[xplane.Event] = []
    lines = lambda n: n in (OP_LINE, MODULE_LINE)  # noqa: E731
    for name, buf in xplane.capture_planes(path):
        if _DEVICE_PLANE.match(name):
            planes[int(_DEVICE_PLANE.match(name).group(1))] = buf
        elif name == names.HOST_PLANE:
            spans = names.gap_spans(xplane.decode_plane(buf))
    return summarize({k: reduce_device(xplane.decode_plane(buf, lines),
                                       names.name_stacks(buf), spans)
                      for k, buf in planes.items()}, top)


def summarize(devices: Dict[int, Dict[str, Any]], top: int = 10
              ) -> Optional[Dict[str, Any]]:
    """reduce_device() of each device as one record: seconds, means over
    the devices that held an operation; None where none did."""
    devices = {k: d for k, d in devices.items() if d["span"]}
    if not devices:
        return None
    n = len(devices)
    by_name: Dict[str, int] = {}
    gaps: Dict[str, int] = {}
    for d in devices.values():
        for name, ps in d["by_name"].items():
            by_name[name] = by_name.get(name, 0) + ps
    worst = max(devices.values(),
                key=lambda d: d["collective_exposed_ps"])
    # the first device's gaps, summed by what the host was doing
    first = devices[min(devices)]
    for name, ps in first["gaps"]:
        gaps[name] = gaps.get(name, 0) + ps
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "devices": n,
        # each device's own window, first operation to last: devices start
        # and stop a program some microseconds apart, and a window over
        # all of them would book that skew as idle time
        "window_s": sum(d["span"][1] - d["span"][0]
                        for d in devices.values()) / n * PS,
        "busy_s": sum(d["busy_ps"] for d in devices.values()) / n * PS,
        "runs": min(d["runs"] for d in devices.values()),
        "collective_exposed_worst_s": worst["collective_exposed_ps"] * PS,
        "device_ops": [[name, ps / n * PS] for name, ps in rank(by_name)],
        "idle_gaps": [[name, ps * PS] for name, ps in rank(gaps)],
    }
