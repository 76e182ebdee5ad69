#!/usr/bin/env python3
"""Cut a profiler trace small enough to check in, and say what it holds.

    python3 benchmark/tools/cut_trace.py <trace dir or .xplane.pb> \\
        [--out benchmark/fixtures/x.xplane.pb] [--events 400]

Works on the wire format: keeps the device planes whole in their metadata
(names are interned there) and the first --events events of each of their
lines, and drops every other plane. Prints each plane's lines with their
event counts, and the commonest event names of each kept line, so that a
reader sees how the device names its operations before writing code
against them.
"""

from __future__ import annotations

import argparse
import collections
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness.trace import proto, xplane  # noqa: E402


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte = n & 0x7F
        n >>= 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, wire_type: int, value) -> bytes:
    tag = _varint((number << 3) | wire_type)
    if wire_type == proto.WIRE_LEN:
        return tag + _varint(len(value)) + value
    if wire_type == proto.WIRE_VARINT:
        return tag + _varint(value)
    return tag + value  # fixed32 / fixed64: raw bytes


def _cut_line(buf: bytes, keep: int) -> bytes:
    out, seen = [], 0
    for fn, wt, v in proto.fields(buf):
        if fn == 4 and wt == proto.WIRE_LEN:  # XLine.events
            seen += 1
            if seen > keep:
                continue
        out.append(_field(fn, wt, v))
    return b"".join(out)


def _event_names_used(line: bytes) -> set:
    used = set()
    for fn, wt, v in proto.fields(line):
        if fn == 4 and wt == proto.WIRE_LEN:  # XLine.events
            used.update(x for f, w, x in proto.fields(v)
                        if f == 1 and w == proto.WIRE_VARINT)
    return used


def _cut_plane(buf: bytes, keep: int) -> bytes:
    """First `keep` events of each line, and of the interned event names
    (on a TPU each is an operation's whole HLO text) only those used."""
    fields = [(f, w, _cut_line(x, keep) if f == 3 else x)
              for f, w, x in proto.fields(buf)]
    used = set().union(*[_event_names_used(x) for f, _w, x in fields
                         if f == 3] or [set()])
    return b"".join(
        _field(f, w, x) for f, w, x in fields
        if f != 4 or next(proto.fields(x))[2] in used)  # map entry's key


def cut(data: bytes, keep: int) -> bytes:
    out = []
    for fn, wt, v in proto.fields(data):
        if fn == 1 and wt == proto.WIRE_LEN:  # XSpace.planes
            if not xplane.plane_name(v).startswith("/device:"):
                continue
            v = _cut_plane(v, keep)
        out.append(_field(fn, wt, v))
    return b"".join(out)


def describe(path: str, top: int = 12) -> None:
    for plane in xplane.load_planes(path):
        print(f"plane {plane.name!r} stats={list(plane.stats)[:8]}")
        for line in plane.lines:
            print(f"  line {line.name!r}: {len(line.events)} events")
            if not plane.name.startswith("/device:"):
                continue
            names = collections.Counter()
            for ev in line.events:
                names[ev.name] += ev.duration_ps
            for name, ps in names.most_common(top):
                ev = next(e for e in line.events if e.name == name)
                print(f"    {ps / 1e9:10.3f} ms  {name[:90]!r} "
                      f"stats={dict(list(ev.stats.items())[:6])}")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace")
    p.add_argument("--out", default=None)
    p.add_argument("--events", type=int, default=400)
    args = p.parse_args(argv)
    files = xplane.find_xplane_files(args.trace)
    if not files:
        print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
        return 1
    describe(files[0])
    if args.out:
        with open(files[0], "rb") as f:
            small = cut(f.read(), args.events)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "wb") as f:
            f.write(small)
        print(f"wrote {args.out}: {len(small)} bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
