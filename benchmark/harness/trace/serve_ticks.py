"""A traced serving run's host plane read by the engine's own spans.

The engine's loop puts every tick on the profiler's clock (the program's
inference/engine.py: a `serve-tick` step marker around one `step()`, and
inside it the `tick-*` phases, `page-evict`, `page-preempt`; of them
`tick-read` alone waits for the device). This file reads the loop thread's
line, the one that holds `serve-tick`, with `xplane`'s helpers and edits
none of them:

    of_run(run) -> [{"step_num": the engine's step number,
                     "tick_ps":  the tick's duration,
                     "read_ps":  its `tick-read` spans, summed,
                     "top_ps":   its outermost program spans, summed (the
                                 phases: what they leave is the glue),
                     "spans":    {name: picoseconds} of the program's
                                 spans inside it, nested ones too}, ...]

in order, or None: an untraced run (one that kept no `serve_request`
record), a trace with no host plane, or a program that marks no tick (a parent commit, a training cell). A span is
recorded only if the capture held it whole, so every tick here is whole.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, List, Optional

from benchmark.harness.trace import named, xplane
from benchmark.harness.trace.names import HOST_PLANE

TICK = "serve-tick"
READ = "tick-read"
_PROGRAM_SPANS = ("tick-", "page-")


def host_ticks(plane: xplane.Plane) -> List[Dict[str, Any]]:
    for line in plane.lines:
        ticks = sorted((ev for ev in line.events if ev.name == TICK),
                       key=lambda ev: ev.start_ps)
        if not ticks:
            continue
        # outer before inner where two start together
        spans = sorted((ev for ev in line.events
                        if ev.name.startswith(_PROGRAM_SPANS)),
                       key=lambda ev: (ev.start_ps, -ev.end_ps))
        out, at = [], 0
        for tick in ticks:
            inside: Dict[str, int] = {}
            top, top_end = 0, tick.start_ps
            while at < len(spans) and spans[at].start_ps < tick.start_ps:
                at += 1
            while at < len(spans) and spans[at].end_ps <= tick.end_ps:
                ev = spans[at]
                inside[ev.name] = inside.get(ev.name, 0) + ev.duration_ps
                if ev.start_ps >= top_end:   # under no other span
                    top += ev.duration_ps
                    top_end = ev.end_ps
                at += 1
            out.append({"step_num": tick.stats.get("step_num"),
                        "tick_ps": tick.duration_ps,
                        "read_ps": inside.get(READ, 0), "top_ps": top,
                        "spans": inside})
        return out
    return []


@functools.lru_cache(maxsize=2)
def _read(path: str, stamp: float) -> List[Dict[str, Any]]:
    for name, buf in xplane.capture_planes(path):
        if name == HOST_PLANE:
            return host_ticks(xplane.decode_plane(buf))
    return []


def of_run(run) -> Optional[List[Dict[str, Any]]]:
    if not run.engine_requests:
        # an untraced run, or a made-up one in a test: before the disk is
        # touched (named.of_run says why). Not `run.trace`: a rehearsal's
        # trace has no device plane and this reader needs none
        return None
    path = named.run_files(run)[0]
    files = xplane.find_xplane_files(path)
    stamp = max((os.path.getmtime(f) for f in files), default=0.0)
    return _read(os.path.abspath(path), stamp) or None
