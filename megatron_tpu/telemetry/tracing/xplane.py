"""XSpace/XPlane schema walk over the wire decoder (stdlib only).

The schema (tensorflow/tsl ``profiler/protobuf/xplane.proto``) is the
on-disk format every XLA profiler backend emits — ``jax.profiler``
writes one ``<host>.xplane.pb`` per host under
``<logdir>/plugins/profile/<session>/``. Shape:

    XSpace
      planes: XPlane          "/host:CPU", "/device:TPU:0", ...
        lines: XLine          one per thread / device stream
          events: XEvent      metadata_id -> name, offset_ps, duration_ps
            stats: XStat      hlo_op / hlo_module / program_id / ...
        event_metadata: map<id, XEventMetadata>   (interned event names,
                                                   and stats of their own)
        stat_metadata:  map<id, XStatMetadata>    (interned stat names
                                                   AND str ref values)

On a TPU an operation's event is named by its whole HLO text, and what
says where in the PROGRAM it came from sits on the event's metadata, not
on the event: ``tf_op`` (the jaxpr name stack, which holds the
``jax.named_scope`` names: ".../transpose(jvp(attention))/flash_bwd/
pallas_call:"), ``hlo_category``, ``flops``, ``source``. The walker hands
them out as stats of every event of that name; a stat of the event itself
wins over one of the same name on its metadata.

Events carry times as ``line.timestamp_ns`` + ``offset_ps``; this
walker resolves both the name interning and the timebase so consumers
see plain (name, start_ps, duration_ps, stats-dict) tuples. Unknown
fields are skipped by construction (the wire layer yields them, we
ignore them), so schema additions in newer toolchains don't break
reading.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional

from megatron_tpu.telemetry.tracing import proto

XPLANE_SUFFIX = ".xplane.pb"


@dataclasses.dataclass
class XStat:
    name: str
    value: Any  # int, float, str, or bytes


@dataclasses.dataclass
class XEvent:
    name: str
    start_ps: int        # absolute within the trace timebase
    duration_ps: int     # 0 for instant/counter events
    stats: Dict[str, Any]

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.duration_ps


@dataclasses.dataclass
class XLine:
    id: int
    name: str
    timestamp_ns: int
    events: List[XEvent]


@dataclasses.dataclass
class XPlane:
    name: str
    lines: List[XLine]
    stats: Dict[str, Any]
    event_names: Dict[int, str]
    stat_names: Dict[int, str]


@dataclasses.dataclass
class XSpace:
    planes: List[XPlane]
    hostnames: List[str]

    def plane(self, name: str) -> Optional[XPlane]:
        for p in self.planes:
            if p.name == name:
                return p
        return None


# -- schema field numbers (xplane.proto) --------------------------------------

_SPACE_PLANES, _SPACE_HOSTNAMES = 1, 4
_PLANE_NAME, _PLANE_LINES = 2, 3
_PLANE_EVENT_MD, _PLANE_STAT_MD, _PLANE_STATS = 4, 5, 6
_LINE_ID, _LINE_NAME, _LINE_TS_NS, _LINE_EVENTS = 1, 2, 3, 4
_LINE_DISPLAY_NAME = 11
_EVENT_MD_ID, _EVENT_OFFSET_PS, _EVENT_DUR_PS, _EVENT_STATS = 1, 2, 3, 4
_STAT_MD_ID = 1
_STAT_DOUBLE, _STAT_UINT64, _STAT_INT64 = 2, 3, 4
_STAT_STR, _STAT_BYTES, _STAT_REF = 5, 6, 7
_MD_ID, _MD_NAME, _MD_STATS = 1, 2, 5


def _metadata_name(buf: bytes) -> (int, str):
    mid, name = 0, ""
    for fn, wt, v in proto.fields(buf):
        if fn == _MD_ID and wt == proto.WIRE_VARINT:
            mid = proto.to_signed(v)
        elif fn == _MD_NAME and wt == proto.WIRE_LEN:
            name = proto.to_text(v)
    return mid, name


def _map_entry(buf: bytes) -> (int, bytes):
    """map<int64, Message> entries encode as {key=1, value=2}."""
    key, value = 0, b""
    for fn, wt, v in proto.fields(buf):
        if fn == 1 and wt == proto.WIRE_VARINT:
            key = proto.to_signed(v)
        elif fn == 2 and wt == proto.WIRE_LEN:
            value = v
    return key, value


def _decode_stat(buf: bytes, stat_names: Dict[int, str]) -> XStat:
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
            # interned string: the value is a stat_metadata id whose NAME
            # is the payload (how xplane dedups repeated hlo_op strings)
            value = stat_names.get(proto.to_signed(v), str(v))
    return XStat(name=name, value=value)


def _decode_event(buf: bytes, ts_ps: int, event_names: Dict[int, str],
                  stat_names: Dict[int, str],
                  event_stats: Dict[int, Dict[str, Any]]) -> XEvent:
    name, offset_ps, dur_ps, md_id = "", 0, 0, None
    stats: Dict[str, Any] = {}
    for fn, wt, v in proto.fields(buf):
        if fn == _EVENT_MD_ID and wt == proto.WIRE_VARINT:
            md_id = proto.to_signed(v)
            name = event_names.get(md_id, str(v))
        elif fn == _EVENT_OFFSET_PS and wt == proto.WIRE_VARINT:
            offset_ps = proto.to_signed(v)
        elif fn == _EVENT_DUR_PS and wt == proto.WIRE_VARINT:
            dur_ps = proto.to_signed(v)
        elif fn == _EVENT_STATS and wt == proto.WIRE_LEN:
            s = _decode_stat(v, stat_names)
            stats[s.name] = s.value
    if md_id in event_stats:
        stats = {**event_stats[md_id], **stats}
    return XEvent(name=name, start_ps=ts_ps + offset_ps,
                  duration_ps=max(dur_ps, 0), stats=stats)


def _decode_line(buf: bytes, event_names: Dict[int, str],
                 stat_names: Dict[int, str],
                 event_stats: Dict[int, Dict[str, Any]]) -> XLine:
    line_id, name, display, ts_ns = 0, "", "", 0
    raw_events: List[bytes] = []
    for fn, wt, v in proto.fields(buf):
        if fn == _LINE_ID and wt == proto.WIRE_VARINT:
            line_id = proto.to_signed(v)
        elif fn == _LINE_NAME and wt == proto.WIRE_LEN:
            name = proto.to_text(v)
        elif fn == _LINE_DISPLAY_NAME and wt == proto.WIRE_LEN:
            display = proto.to_text(v)
        elif fn == _LINE_TS_NS and wt == proto.WIRE_VARINT:
            ts_ns = proto.to_signed(v)
        elif fn == _LINE_EVENTS and wt == proto.WIRE_LEN:
            raw_events.append(v)
    ts_ps = ts_ns * 1000
    events = [_decode_event(e, ts_ps, event_names, stat_names, event_stats)
              for e in raw_events]
    return XLine(id=line_id, name=display or name, timestamp_ns=ts_ns,
                 events=events)


def _decode_plane(buf: bytes) -> XPlane:
    # two passes: metadata tables first (they may appear AFTER the lines
    # that reference them in the serialized stream)
    name = ""
    event_names: Dict[int, str] = {}
    stat_names: Dict[int, str] = {}
    raw_lines: List[bytes] = []
    raw_stats: List[bytes] = []
    raw_event_md: Dict[int, bytes] = {}
    for fn, wt, v in proto.fields(buf):
        if fn == _PLANE_NAME and wt == proto.WIRE_LEN:
            name = proto.to_text(v)
        elif fn == _PLANE_LINES and wt == proto.WIRE_LEN:
            raw_lines.append(v)
        elif fn == _PLANE_EVENT_MD and wt == proto.WIRE_LEN:
            key, md = _map_entry(v)
            event_names[key] = _metadata_name(md)[1]
            raw_event_md[key] = md
        elif fn == _PLANE_STAT_MD and wt == proto.WIRE_LEN:
            key, md = _map_entry(v)
            stat_names[key] = _metadata_name(md)[1]
        elif fn == _PLANE_STATS and wt == proto.WIRE_LEN:
            raw_stats.append(v)
    # the stats an event NAME carries (stat names are all known by now)
    event_stats: Dict[int, Dict[str, Any]] = {}
    for key, md in raw_event_md.items():
        found = [_decode_stat(v, stat_names)
                 for fn, wt, v in proto.fields(md)
                 if fn == _MD_STATS and wt == proto.WIRE_LEN]
        if found:
            event_stats[key] = {st.name: st.value for st in found}
    lines = [_decode_line(ln, event_names, stat_names, event_stats)
             for ln in raw_lines]
    stats = {s.name: s.value
             for s in (_decode_stat(r, stat_names) for r in raw_stats)}
    return XPlane(name=name, lines=lines, stats=stats,
                  event_names=event_names, stat_names=stat_names)


def decode_xspace(data: bytes) -> XSpace:
    planes: List[XPlane] = []
    hostnames: List[str] = []
    for fn, wt, v in proto.fields(data):
        if fn == _SPACE_PLANES and wt == proto.WIRE_LEN:
            planes.append(_decode_plane(v))
        elif fn == _SPACE_HOSTNAMES and wt == proto.WIRE_LEN:
            hostnames.append(proto.to_text(v))
    return XSpace(planes=planes, hostnames=hostnames)


def load_xspace(path: str) -> XSpace:
    with open(path, "rb") as f:
        return decode_xspace(f.read())


def find_xplane_files(path: str, latest_session_only: bool = True
                      ) -> List[str]:
    """xplane files under ``path`` (a trace dir, a session dir, or one
    ``.xplane.pb`` file). ``jax.profiler`` nests each capture as
    ``<dir>/plugins/profile/<session>/<host>.xplane.pb``; with
    ``latest_session_only`` a logdir holding several captures yields the
    newest session only (one report covers one capture, all hosts)."""
    if os.path.isfile(path):
        return [path]
    hits: List[str] = []
    for root, _dirs, files in os.walk(path):
        for f in files:
            if f.endswith(XPLANE_SUFFIX):
                hits.append(os.path.join(root, f))
    if not hits:
        return []
    if latest_session_only:
        # session dir names are profiler timestamps (YYYY_MM_DD_HH_MM_SS):
        # lexicographic max is the newest capture
        latest = max(os.path.dirname(h) for h in hits)
        hits = [h for h in hits if os.path.dirname(h) == latest]
    return sorted(hits)
