"""Read the profiler's `.xplane.pb` (tensorflow/tsl xplane.proto) with the
wire decoder beside this file. A copy, cut down, of the program's
megatron_tpu/telemetry/tracing/xplane.py: the benchmark keeps its own so
that the reduction from trace to metrics cannot move under it.

    XSpace.planes: XPlane     "/device:TPU:0", "/host:CPU", ...
      lines: XLine            one per device stream / host thread
        events: XEvent        metadata_id -> name, offset_ps, duration_ps
          stats: XStat        hlo_category, ...
      event_metadata, stat_metadata: interned names

Only planes the caller asks for are decoded past their name: a host plane
with a Python tracer's events can be a hundred times the device planes.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, List, NamedTuple, Optional

from benchmark.harness.trace import proto

XPLANE_SUFFIX = ".xplane.pb"

_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_LINES = 2, 3
_PLANE_EVENT_MD, _PLANE_STAT_MD, _PLANE_STATS = 4, 5, 6
_LINE_ID, _LINE_NAME, _LINE_TS_NS, _LINE_EVENTS = 1, 2, 3, 4
_LINE_DISPLAY_NAME = 11
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DUR_PS, _EVENT_STATS = 1, 2, 3, 4
_STAT_MD_ID = 1
_STAT_DOUBLE, _STAT_UINT64, _STAT_INT64 = 2, 3, 4
_STAT_STR, _STAT_BYTES, _STAT_REF = 5, 6, 7
_MD_ID, _MD_NAME = 1, 2


class Event(NamedTuple):
    name: str
    start_ps: int
    duration_ps: int
    stats: Dict[str, Any]

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.duration_ps


class Line(NamedTuple):
    name: str
    events: List[Event]


class Plane(NamedTuple):
    name: str
    lines: List[Line]
    stats: Dict[str, Any]


def _metadata_name(buf: bytes) -> str:
    for fn, wt, v in proto.fields(buf):
        if fn == _MD_NAME and wt == proto.WIRE_LEN:
            return proto.to_text(v)
    return ""


def _map_entry(buf: bytes) -> tuple:
    key, value = 0, b""
    for fn, wt, v in proto.fields(buf):
        if fn == 1 and wt == proto.WIRE_VARINT:
            key = proto.to_signed(v)
        elif fn == 2 and wt == proto.WIRE_LEN:
            value = v
    return key, value


def _decode_stat(buf: bytes, stat_names: Dict[int, str]) -> tuple:
    name, value = "", None
    for fn, wt, v in proto.fields(buf):
        if fn == _STAT_MD_ID and wt == proto.WIRE_VARINT:
            name = stat_names.get(proto.to_signed(v), str(v))
        elif fn == _STAT_DOUBLE:
            value = proto.to_double(v)
        elif fn == _STAT_UINT64 and wt == proto.WIRE_VARINT:
            value = v
        elif fn == _STAT_INT64 and wt == proto.WIRE_VARINT:
            value = proto.to_signed(v)
        elif fn == _STAT_STR:
            value = proto.to_text(v)
        elif fn == _STAT_BYTES:
            value = v
        elif fn == _STAT_REF and wt == proto.WIRE_VARINT:
            # an interned string: the id of a stat_metadata entry whose
            # NAME is the payload
            value = stat_names.get(proto.to_signed(v), str(v))
    return name, value


def _decode_event(buf: bytes, ts_ps: int, event_names: Dict[int, str],
                  stat_names: Dict[int, str]) -> Event:
    name, offset_ps, dur_ps = "", 0, 0
    stats: Dict[str, Any] = {}
    for fn, wt, v in proto.fields(buf):
        if fn == _EVENT_MD_ID and wt == proto.WIRE_VARINT:
            name = event_names.get(proto.to_signed(v), str(v))
        elif fn == _EVENT_OFFSET_PS and wt == proto.WIRE_VARINT:
            offset_ps = proto.to_signed(v)
        elif fn == _EVENT_DUR_PS and wt == proto.WIRE_VARINT:
            dur_ps = proto.to_signed(v)
        elif fn == _EVENT_STATS and wt == proto.WIRE_LEN:
            k, value = _decode_stat(v, stat_names)
            stats[k] = value
    return Event(name, ts_ps + offset_ps, max(dur_ps, 0), stats)


def _decode_line(buf: bytes, event_names, stat_names,
                 want_line: Callable[[str], bool]) -> Optional[Line]:
    name, display, ts_ns = "", "", 0
    raw_events: List[bytes] = []
    for fn, wt, v in proto.fields(buf):
        if fn == _LINE_NAME and wt == proto.WIRE_LEN:
            name = proto.to_text(v)
        elif fn == _LINE_DISPLAY_NAME and wt == proto.WIRE_LEN:
            display = proto.to_text(v)
        elif fn == _LINE_TS_NS and wt == proto.WIRE_VARINT:
            ts_ns = proto.to_signed(v)
        elif fn == _LINE_EVENTS and wt == proto.WIRE_LEN:
            raw_events.append(v)
    name = display or name
    if not want_line(name):
        return Line(name, [])
    ts_ps = ts_ns * 1000
    return Line(name, [_decode_event(e, ts_ps, event_names, stat_names)
                       for e in raw_events])


def plane_name(buf: bytes) -> str:
    for fn, wt, v in proto.fields(buf):
        if fn == _PLANE_NAME and wt == proto.WIRE_LEN:
            return proto.to_text(v)
    return ""


def decode_plane(buf: bytes,
                 want_line: Callable[[str], bool] = lambda _n: True
                 ) -> Plane:
    name = ""
    event_names: Dict[int, str] = {}
    stat_names: Dict[int, str] = {}
    raw_lines: List[bytes] = []
    raw_stats: List[bytes] = []
    # the metadata tables may come after the lines that use them
    for fn, wt, v in proto.fields(buf):
        if fn == _PLANE_NAME and wt == proto.WIRE_LEN:
            name = proto.to_text(v)
        elif fn == _PLANE_LINES and wt == proto.WIRE_LEN:
            raw_lines.append(v)
        elif fn == _PLANE_EVENT_MD and wt == proto.WIRE_LEN:
            key, md = _map_entry(v)
            event_names[key] = _metadata_name(md)
        elif fn == _PLANE_STAT_MD and wt == proto.WIRE_LEN:
            key, md = _map_entry(v)
            stat_names[key] = _metadata_name(md)
        elif fn == _PLANE_STATS and wt == proto.WIRE_LEN:
            raw_stats.append(v)
    lines = [_decode_line(ln, event_names, stat_names, want_line)
             for ln in raw_lines]
    stats = dict(_decode_stat(r, stat_names) for r in raw_stats)
    return Plane(name, lines, stats)


def raw_planes(data: bytes) -> List[bytes]:
    return [v for fn, wt, v in proto.fields(data)
            if fn == _SPACE_PLANES and wt == proto.WIRE_LEN]


def capture_planes(path: str):
    """(name, undecoded plane) of every plane of the newest capture under
    `path`: the caller decodes those it wants, as far as it wants."""
    for f in find_xplane_files(path):
        with open(f, "rb") as fh:
            data = fh.read()
        for buf in raw_planes(data):
            yield plane_name(buf), buf


def load_planes(path: str,
                want_plane: Callable[[str], bool] = lambda _n: True,
                want_line: Callable[[str], bool] = lambda _n: True
                ) -> List[Plane]:
    with open(path, "rb") as f:
        data = f.read()
    return [decode_plane(buf, want_line) for buf in raw_planes(data)
            if want_plane(plane_name(buf))]


def find_xplane_files(path: str) -> List[str]:
    """The xplane files of the newest capture under `path` (jax.profiler
    nests each as <dir>/plugins/profile/<session>/<host>.xplane.pb), or
    `path` itself if it is one."""
    if os.path.isfile(path):
        return [path]
    hits = [os.path.join(root, f) for root, _dirs, files in os.walk(path)
            for f in files if f.endswith(XPLANE_SUFFIX)]
    if not hits:
        return []
    latest = max(os.path.dirname(h) for h in hits)
    return sorted(h for h in hits if os.path.dirname(h) == latest)
