#!/usr/bin/env python3
"""Cut a traced training run to a few WHOLE runs of its step program, with
the names the readers of harness/trace/named.py find their way by.

    python3 benchmark/tools/cut_named_trace.py <trace dir or .xplane.pb> \\
        --out benchmark/fixtures/x.xplane.pb [--runs 2] [--devices 2]

cut_trace.py keeps the first events of every line, so its recordings hold
the operations of the first, cut, run only, and no host plane. This keeps,
of each of the first --devices device planes (all by default), the
`XLA Modules` and `XLA Ops` lines over --runs whole runs from the middle of the trace: the operations inside them, the
envelopes of the run before and the run after (whole_runs() drops a
trace's first and last run as cut, and must still find them to drop), and
of the event metadata the entries those events use, with their `tf_op`
and `hlo_category` and nothing else (a kernel's HLO text whole, any other
cut to 120 characters). Of `/host:CPU` it keeps the lines that hold the
program's spans (`train-pass`, the prefetcher's `batch-transfer`), over
the same stretch of time, events and their stats whole. Every other plane
and line goes. Works on the wire format; prints what it kept.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import Dict, List, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.trace import named, proto, reduce, xplane  # noqa: E402
from benchmark.tools.cut_trace import _field  # noqa: E402

SPAN_LINES = (named.PASS, "batch-transfer")
KEPT_STATS = ("tf_op", "hlo_category")
NAME_CHARS = 120

# field numbers of xplane.proto
_SPACE_PLANES = 1
_PLANE_LINES, _PLANE_EVENT_MD, _PLANE_STAT_MD = 3, 4, 5
_LINE_NAME, _LINE_TS_NS, _LINE_EVENTS, _LINE_DISPLAY = 2, 3, 4, 11
_EV_MD_ID, _EV_OFFSET_PS, _EV_DUR_PS = 1, 2, 3
_MD_NAME, _MD_STATS = 2, 5
_STAT_MD_ID = 1

Window = Tuple[int, int]


def _line_head(buf: bytes) -> Tuple[str, int]:
    """(name, timestamp in ps) of a line, without decoding its events."""
    name = display = ""
    ts_ns = 0
    for fn, wt, v in proto.fields(buf):
        if fn == _LINE_NAME and wt == proto.WIRE_LEN:
            name = proto.to_text(v)
        elif fn == _LINE_DISPLAY and wt == proto.WIRE_LEN:
            display = proto.to_text(v)
        elif fn == _LINE_TS_NS and wt == proto.WIRE_VARINT:
            ts_ns = proto.to_signed(v)
    return display or name, ts_ns * 1000


def _event_head(buf: bytes) -> Tuple[int, int, int]:
    md = offset = dur = 0
    for fn, wt, v in proto.fields(buf):
        if wt != proto.WIRE_VARINT:
            continue
        if fn == _EV_MD_ID:
            md = v
        elif fn == _EV_OFFSET_PS:
            offset = proto.to_signed(v)
        elif fn == _EV_DUR_PS:
            dur = proto.to_signed(v)
    return md, offset, dur


def _cut_line(buf: bytes, keep: List[Window], bare: bool,
              used: Set[int]) -> Tuple[bytes, int]:
    """The line with only the events that lie inside one of `keep`; `bare`
    drops each kept event's stats. Adds the metadata ids kept to `used`."""
    _name, ts_ps = _line_head(buf)
    out, kept = [], 0
    for fn, wt, v in proto.fields(buf):
        if fn == _LINE_EVENTS and wt == proto.WIRE_LEN:
            md, offset, dur = _event_head(v)
            start = ts_ps + offset
            if not any(s <= start and start + dur <= e for s, e in keep):
                continue
            used.add(md)
            kept += 1
            if bare:
                v = b"".join(_field(f, w, x) for f, w, x in proto.fields(v)
                             if f in (_EV_MD_ID, _EV_OFFSET_PS, _EV_DUR_PS))
        out.append(_field(fn, wt, v))
    return b"".join(out), kept


def _slim_metadata(entry: bytes, stat_names: Dict[int, str]) -> bytes:
    """One event_metadata map entry with its name cut (unless a kernel's)
    and only KEPT_STATS of its stats."""
    key, md = xplane._map_entry(entry)
    out = []
    for fn, wt, v in proto.fields(md):
        if fn == _MD_NAME and wt == proto.WIRE_LEN:
            text = proto.to_text(v)
            if reduce.KERNEL_TARGET not in text:
                v = text[:NAME_CHARS].encode()
        elif fn == _MD_STATS and wt == proto.WIRE_LEN:
            stat_id = next((x for f, w, x in proto.fields(v)
                            if f == _STAT_MD_ID), None)
            if stat_names.get(stat_id) not in KEPT_STATS:
                continue
        out.append(_field(fn, wt, v))
    return (_field(1, proto.WIRE_VARINT, key)
            + _field(2, proto.WIRE_LEN, b"".join(out)))


def _cut_plane(buf: bytes, windows: Dict[str, List[Window]],
               bare: bool, want_line) -> Tuple[bytes, Dict[str, int]]:
    """The plane with the wanted lines cut to their windows (`windows`
    by line name, "" for any other) and its metadata to what they use."""
    stat_names = {}
    for fn, wt, v in proto.fields(buf):
        if fn == _PLANE_STAT_MD and wt == proto.WIRE_LEN:
            key, md = xplane._map_entry(v)
            stat_names[key] = xplane._metadata_name(md)
    used: Set[int] = set()
    fields, kept = [], {}
    for fn, wt, v in proto.fields(buf):
        if fn == _PLANE_LINES and wt == proto.WIRE_LEN:
            name, _ts = _line_head(v)
            if not want_line(name, v):
                continue
            v, n = _cut_line(v, windows.get(name, windows[""]), bare, used)
            kept[name] = kept.get(name, 0) + n
        fields.append((fn, wt, v))
    out = []
    for fn, wt, v in fields:
        if fn == _PLANE_EVENT_MD and wt == proto.WIRE_LEN:
            if xplane._map_entry(v)[0] not in used:
                continue
            if bare:
                v = _slim_metadata(v, stat_names)
        out.append(_field(fn, wt, v))
    return b"".join(out), kept


def _windows(plane: xplane.Plane, runs: int) -> Dict[str, List[Window]]:
    """Of one device plane: the whole runs to keep (operations), and with
    them the run before and the run after (module envelopes, host spans)."""
    modules = reduce._line(plane, reduce.MODULE_LINE)
    whole = reduce.whole_runs(modules)
    if len(whole) < runs:
        raise SystemExit(f"{plane.name}: the trace holds {len(whole)} whole "
                         f"run(s) of its step program, fewer than {runs}")
    first = (len(whole) - runs) // 2
    kept = whole[first:first + runs]
    same = sorted((m for m in modules if reduce._PROGRAM_ID.sub("", m.name)
                   == reduce._PROGRAM_ID.sub("", kept[0].name)),
                  key=lambda m: m.start_ps)
    at = same.index(kept[0])
    before, after = same[at - 1], same[at + runs]
    return {reduce.OP_LINE: [(kept[0].start_ps, kept[-1].end_ps)],
            "": [(before.start_ps, after.end_ps)]}


def _holds_program_spans(host_plane: bytes):
    """want_line for the host plane: the lines with a SPAN_LINES event."""
    names = {key: xplane._metadata_name(md) for key, md in (
        xplane._map_entry(v) for fn, wt, v in proto.fields(host_plane)
        if fn == _PLANE_EVENT_MD and wt == proto.WIRE_LEN)}

    def want(_name: str, line: bytes) -> bool:
        events = xplane._decode_line(line, names, {}, lambda _n: True).events
        return any(ev.name in SPAN_LINES for ev in events)

    return want


def cut(data: bytes, runs: int, n_devices: int = 0
        ) -> Tuple[bytes, List[str]]:
    device_lines = (reduce.OP_LINE, reduce.MODULE_LINE)
    planes = [v for fn, wt, v in proto.fields(data)
              if fn == _SPACE_PLANES and wt == proto.WIRE_LEN]
    devices = sorted((p for p in planes
                      if reduce._DEVICE_PLANE.match(xplane.plane_name(p))),
                     key=xplane.plane_name)[:n_devices or None]
    per_device = [_windows(xplane.decode_plane(
        p, lambda n: n in device_lines), runs) for p in devices]
    # the host spans are kept over every device's stretch of time
    host_window = [(min(w[""][0][0] for w in per_device),
                    max(w[""][0][1] for w in per_device))]
    out, report = [], []
    for fn, wt, v in proto.fields(data):
        if fn != _SPACE_PLANES or wt != proto.WIRE_LEN:
            out.append(_field(fn, wt, v))
            continue
        name = xplane.plane_name(v)
        if v in devices:
            v, kept = _cut_plane(
                v, per_device[devices.index(v)], True,
                lambda n, _buf: n in device_lines)
        elif name == named.HOST_PLANE:
            v, kept = _cut_plane(v, {"": host_window}, False,
                                 _holds_program_spans(v))
        else:
            continue
        report.append(f"{name}: {len(v)} bytes, events kept {kept}")
        out.append(_field(fn, wt, v))
    return b"".join(out), report


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace")
    p.add_argument("--out", required=True)
    p.add_argument("--runs", type=int, default=2)
    p.add_argument("--devices", type=int, default=0,
                   help="keep only the first N device planes (0: all)")
    args = p.parse_args(argv)
    files = xplane.find_xplane_files(args.trace)
    if not files:
        print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
        return 1
    with open(files[0], "rb") as f:
        small, report = cut(f.read(), args.runs, args.devices)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "wb") as f:
        f.write(small)
    print("\n".join(report))
    print(f"wrote {args.out}: {len(small)} bytes")
    got = named.per_run(args.out)
    print("per run:", None if got is None else
          {k: got[k] for k in ("devices", "runs", "regions", "recomputed")})
    print("host passes:", named.read(args.out)["passes"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
