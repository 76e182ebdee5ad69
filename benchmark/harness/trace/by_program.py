"""A traced serving run read one PROGRAM at a time.

`named.per_run` reads the program that took most of the trace
(`reduce.whole_runs`): the train step in a training cell, the decode step
in a served one. A served cell's engine runs two programs, tick by tick,
`jit_decode_step` and `jit_chunk_step`, and a reader of the second has to
select it by name. This file does that with `xplane`'s, `reduce`'s and
`named`'s helpers and edits none of them:

    of_run(run, "jit_chunk_step") -> {
        "runs":    how many whole runs of it the trace holds,
        "run_ms":  each one's duration on the module line,
        "scopes":  {name: seconds a run}: own time of the operations whose
                   name stack holds the name, inside those runs,
        "kernels": {name: {"s": seconds a run, "calls": {HLO text: calls a
                   run}}}, as named.per_run gives them}

or None: an untraced run, a trace with no device plane, or no whole run of
that program in it (a parent commit, another engine). The first and the
last run of a program in the trace are left out, as `whole_runs` leaves
them: the trace may have cut them.
"""

from __future__ import annotations

import functools
import os
from typing import Any, Dict, Optional

from benchmark.harness import stats
from benchmark.harness.trace import named, reduce, xplane
from benchmark.harness.trace.names import kernel_of, name_stacks, tokens


def _device(plane: xplane.Plane, tf_ops: Dict[str, str]) -> Dict[str, Any]:
    by_program: Dict[str, list] = {}
    for m in reduce._line(plane, reduce.MODULE_LINE):
        by_program.setdefault(reduce._PROGRAM_ID.sub("", m.name),
                              []).append(m)
    ops = reduce.self_segments(reduce._line(plane, reduce.OP_LINE))
    out: Dict[str, Any] = {}
    for program, modules in by_program.items():
        whole = sorted(modules, key=lambda ev: ev.start_ps)[1:-1]
        if not whole:
            continue
        runs = reduce.merge((m.start_ps, m.end_ps) for m in whole)
        starts = [s for s, _ in runs]
        scopes: Dict[str, int] = {}
        kernels: Dict[str, Dict[str, Any]] = {}
        for ev, segs in ops:
            own = named._inside(segs, runs, starts)
            if not own:
                continue
            parts = tokens(tf_ops.get(ev.name, ""))
            for part in set(parts):
                scopes[part] = scopes.get(part, 0) + own
            # by the name stack alone (it ends in the kernel's
            # `pallas_call`): XLA may wrap a kernel's custom call in a
            # fusion with what feeds it (`ssm_scan` with the lane repeat
            # of B and C), and the event is then that fusion's
            kernel = kernel_of(parts)
            if kernel is not None:
                k = kernels.setdefault(kernel, {"ps": 0, "calls": {}})
                k["ps"] += own
                k["calls"][ev.name] = k["calls"].get(ev.name, 0) + 1
        n = len(whole)
        out[program] = {
            "runs": n,
            "run_ms": [m.duration_ps * reduce.PS * 1e3 for m in whole],
            "scopes": {s: ps * reduce.PS / n for s, ps in scopes.items()},
            "kernels": {k: {"s": v["ps"] * reduce.PS / n,
                            "calls": {t: c / n
                                      for t, c in v["calls"].items()}}
                        for k, v in kernels.items()}}
    return out


@functools.lru_cache(maxsize=2)
def _read(path: str, stamp: float) -> Dict[str, Any]:
    """The first device plane that holds a module line (a served cell is
    one chip), by program."""
    want = lambda n: n in (reduce.OP_LINE, reduce.MODULE_LINE)  # noqa: E731
    for name, buf in xplane.capture_planes(path):
        if reduce._DEVICE_PLANE.match(name):
            got = _device(xplane.decode_plane(buf, want), name_stacks(buf))
            if got:
                return got
    return {}


def of_run(run, program: str) -> Optional[Dict[str, Any]]:
    if run.trace is None:
        return None   # before the disk is touched (named.of_run says why)
    path = named.run_files(run)[0]
    files = xplane.find_xplane_files(path)
    stamp = max((os.path.getmtime(f) for f in files), default=0.0)
    return _read(os.path.abspath(path), stamp).get(program)


def run_ms_p50(run, program: str) -> Optional[float]:
    """Median duration of the program's whole runs, milliseconds."""
    got = of_run(run, program)
    return None if got is None else stats.median(got["run_ms"])


def scope_ms(run, program: str, scope: str) -> Optional[float]:
    """Milliseconds a run of `program` under `scope`; None where the
    program did not run or no operation of it carries the scope."""
    got = of_run(run, program)
    if got is None or scope not in got["scopes"]:
        return None
    return 1e3 * got["scopes"][scope]
