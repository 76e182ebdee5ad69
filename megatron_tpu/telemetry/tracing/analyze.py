"""Trace analysis: comm/compute split, exposed time, contract check.

Five results out of one pass over classified op events (stdlib only):

  * **op table** — per-op count/total time, top-K by time, plus the
    busy-time split compute / collective / infeed / host and per-step
    wall stats from the ``PjitFunction`` dispatch markers.
  * **exposed collective time** — per collective mnemonic, total time
    vs. time NOT overlapped by any concurrent compute on the same
    plane (interval subtraction). This is the Flash Communication
    measurement (arXiv 2412.04964): only the exposed fraction is worth
    compressing/re-routing, overlapped comm is already free.

  * **own time by scope** — the module's op self time under each of the
    program's ``jax.named_scope`` regions (``events.REGION_SCOPES``, the
    innermost wins, the rest is ``other``), and the Pallas kernels by
    name; what they do not cover is what a refactor left unnamed.
  * **idle gaps by host span** — each stretch in which the (first)
    device runs nothing, under the innermost event of the loop thread
    (the host line that holds step annotations: ``train-pass`` with the
    loop's timers and the runtime's dispatch events inside it) at the
    moment the device ran dry: what the host was doing instead.

Events on one line NEST (XLA:CPU wraps a layer scan's body in one big
``while.N`` event containing the per-iteration ops; the python line
wraps execution in dispatch spans), so every sum here uses SELF time —
an instant belongs to the innermost event covering it. Without that, a
collective inside a ``while`` would count as "hidden" under its own
enclosing loop event, and the while's duration would double-count all
its children in the compute bucket.
  * **measured vs. expected** — collective event counts joined against
    a golden comm contract (``analysis/golden/*.json``): the manifest
    pins per-execution counts, the trace yields totals, and the number
    of executions (devices x profiled steps) must reconcile them op-for-
    op. The runtime enforcement of the static promise PR 5 made — plus
    the manifest's byte volumes give effective bus bandwidth.

Static HLO counts are per device-execution of the compiled module;
collectives INSIDE runtime loops (a microbatch scan) execute more often
than they appear in the module text, which reports as a per-op
execution-ratio mismatch rather than being silently absorbed — configs
whose collectives all sit at top level (ulysses_cp2: no scan) reconcile
exactly.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Tuple

from megatron_tpu.analysis.taxonomy import (
    COLLECTIVE_PRIMITIVES, is_collective_done_half,
)
from megatron_tpu.telemetry.tracing.events import (
    DEVICE_PLANE_PREFIX, KIND_COLLECTIVE, KIND_COMPUTE, KIND_HOST,
    KIND_INFEED, OP_CLASSES, REGION_SCOPES, OpEvent, innermost_scope,
    kernel_of, modules, op_class, scope_tokens, step_markers,
)

PS_PER_S = 1e12


# -- interval arithmetic ------------------------------------------------------


def merge_intervals(intervals: Iterable[Tuple[int, int]]
                    ) -> List[Tuple[int, int]]:
    """Union of [start, end) intervals as a sorted disjoint list."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap_ps(start: int, end: int,
               merged: List[Tuple[int, int]],
               starts: Optional[List[int]] = None) -> int:
    """Length of [start, end) covered by a merged interval list."""
    if end <= start or not merged:
        return 0
    if starts is None:
        starts = [s for s, _ in merged]
    i = max(bisect.bisect_right(starts, start) - 1, 0)
    covered = 0
    while i < len(merged):
        s, e = merged[i]
        if s >= end:
            break
        covered += max(0, min(e, end) - max(s, start))
        i += 1
    return covered


# -- report dataclasses -------------------------------------------------------


def self_segments(events_on_line: List[OpEvent]
                  ) -> List[Tuple[OpEvent, List[Tuple[int, int]], int]]:
    """(event, self-intervals, self_ps) per event of ONE line.

    Containment nesting via a sweep stack: an event starting inside the
    previous event's span is its child; a parent's self time is its span
    minus the union of its children's spans (clamped to the parent).
    Zero-duration marker events neither nest nor mask anything."""
    zero = [e for e in events_on_line if e.duration_ps <= 0]
    evs = sorted((e for e in events_on_line if e.duration_ps > 0),
                 key=lambda e: (e.start_ps, -e.end_ps))
    children: Dict[int, List[Tuple[int, int]]] = {}
    stack: List[OpEvent] = []
    for e in evs:
        while stack and stack[-1].end_ps <= e.start_ps:
            stack.pop()
        if stack:
            p = stack[-1]
            children.setdefault(id(p), []).append(
                (e.start_ps, min(e.end_ps, p.end_ps)))
        stack.append(e)
    out = []
    for e in evs:
        covered = merge_intervals(children.get(id(e), ()))
        segs: List[Tuple[int, int]] = []
        cursor = e.start_ps
        for s, c_end in covered:
            if s > cursor:
                segs.append((cursor, s))
            cursor = max(cursor, c_end)
        if cursor < e.end_ps:
            segs.append((cursor, e.end_ps))
        out.append((e, segs, sum(b - a for a, b in segs)))
    # zero-duration events still count (op counts, markers) — they just
    # own no time and mask nothing
    out.extend((e, [], 0) for e in zero)
    return out


@dataclasses.dataclass
class OpAgg:
    name: str
    kind: str
    count: int
    total_ps: int       # summed event spans (children included)
    self_ps: int        # summed self time (what the op itself ran)
    detail: str = ""    # TPU: result shape and opcode

    @property
    def total_s(self) -> float:
        return self.total_ps / PS_PER_S

    @property
    def self_s(self) -> float:
        return self.self_ps / PS_PER_S


@dataclasses.dataclass
class CollectiveAgg:
    op: str               # base mnemonic ("all-reduce")
    count: int
    total_ps: int
    exposed_ps: int

    @property
    def exposed_frac(self) -> float:
        return self.exposed_ps / self.total_ps if self.total_ps else 0.0


@dataclasses.dataclass
class TraceReport:
    module: Optional[str]                 # module the op table covers
    wall_s: float                         # span of the module's op events
    busy_s: Dict[str, float]              # kind -> summed event seconds
    ops: List[OpAgg]                      # per-op aggregation, by time desc
    collectives: List[CollectiveAgg]      # per-mnemonic comm split
    steps: Dict[str, Dict[str, float]]    # step marker -> wall stats (ms)
    all_modules: Dict[str, float]         # module -> total op seconds
    scopes: Dict[str, float] = dataclasses.field(default_factory=dict)
    #   region scope (or "other") -> op self seconds; empty where the
    #   trace carries none of the program's region names
    scope_classes: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)
    #   the same seconds by class of work (events.op_class, from the
    #   profiler's hlo_category): region -> class -> seconds, and under
    #   UNNAMED_SCOPE the part of "other" that has no name stack at all
    kernels: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)             # kernel name -> count, self_s
    idle_gaps: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)             # host span -> count, total_s, max_s
    loop_thread: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)             # loop-thread event -> count,
    #   total_s, self_s: where the thread that dispatches the steps spent
    #   its own time, the program's spans and the runtime's events alike

    @property
    def compute_s(self) -> float:
        return self.busy_s.get(KIND_COMPUTE, 0.0)

    @property
    def collective_s(self) -> float:
        return self.busy_s.get(KIND_COLLECTIVE, 0.0)

    @property
    def exposed_collective_s(self) -> float:
        return sum(c.exposed_ps for c in self.collectives) / PS_PER_S

    def collective_counts(self) -> Dict[str, int]:
        return {c.op: c.count for c in self.collectives}

    def to_dict(self, top: int = 15) -> Dict[str, Any]:
        return {
            "module": self.module,
            "wall_s": round(self.wall_s, 6),
            "busy_s": {k: round(v, 6) for k, v in sorted(self.busy_s.items())},
            "exposed_collective_s": round(self.exposed_collective_s, 6),
            "top_ops": [
                {"name": o.name, "detail": o.detail, "kind": o.kind,
                 "count": o.count,
                 "self_s": round(o.self_s, 6),
                 "total_s": round(o.total_s, 6)}
                for o in self.ops[:top]],
            "collectives": [
                {"op": c.op, "count": c.count,
                 "total_s": round(c.total_ps / PS_PER_S, 6),
                 "exposed_s": round(c.exposed_ps / PS_PER_S, 6),
                 "exposed_frac": round(c.exposed_frac, 4)}
                for c in self.collectives],
            "steps": self.steps,
            "modules": {m: round(s, 6)
                        for m, s in sorted(self.all_modules.items())},
            "scopes": {k: round(v, 6) for k, v in self.scopes.items()},
            "scope_classes": {
                scope: {c: round(v, 6) for c, v in by_class.items()}
                for scope, by_class in self.scope_classes.items()},
            "kernels": self.kernels,
            "idle_gaps": self.idle_gaps[:top],
            "loop_thread": self.loop_thread[:top],
        }


# -- the analysis pass --------------------------------------------------------


def _percentile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return float("nan")
    idx = min(len(sorted_vals) - 1,
              max(0, round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


OTHER_SCOPE = "other"
UNNAMED_SCOPE = "(unnamed)"   # operations with no name stack: part of "other"


def idle_gaps_by_host_span(events: List[OpEvent]) -> List[Dict[str, Any]]:
    """The first TPU device's idle gaps (between the union of its op
    intervals), summed under the innermost loop-thread event that covers
    the moment each gap opens. The loop thread is the host line that
    holds step annotations; the profiler puts host and device on one
    clock. Empty without a device plane; every gap falls under
    ``<no host span>`` where no line is annotated. Largest total first."""
    ops = [e for e in events if e.kind != KIND_HOST
           and e.plane.startswith(DEVICE_PLANE_PREFIX)]
    if not ops:
        return []
    device = min(e.plane for e in ops)
    busy = merge_intervals((e.start_ps, e.end_ps) for e in ops
                           if e.plane == device)
    loop_lines = {(e.plane, e.line) for e in events
                  if e.step_num is not None}
    spans = sorted((e for e in events if e.kind == KIND_HOST
                    and (e.plane, e.line) in loop_lines
                    and e.duration_ps > 0),
                   key=lambda e: (e.start_ps, -e.end_ps))
    starts = [e.start_ps for e in spans]
    out: Dict[str, Dict[str, Any]] = {}
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        name = "<no host span>"
        # the latest-started span still open at gap_start is the innermost
        for i in range(bisect.bisect_right(starts, gap_start) - 1, -1, -1):
            if spans[i].end_ps > gap_start:
                name = spans[i].name
                break
        row = out.setdefault(name, {"span": name, "count": 0,
                                    "total_s": 0.0, "max_s": 0.0})
        gap_s = (gap_end - gap_start) / PS_PER_S
        row["count"] += 1
        row["total_s"] += gap_s
        row["max_s"] = max(row["max_s"], gap_s)
    rows = sorted(out.values(), key=lambda r: -r["total_s"])
    for r in rows:
        r["total_s"], r["max_s"] = round(r["total_s"], 9), round(r["max_s"], 9)
    return rows


def loop_thread_self_time(events: List[OpEvent]) -> List[Dict[str, Any]]:
    """The loop thread's events by name, largest self time first: the
    host line that holds step annotations (`train-pass` with the loop's
    timers; `serve-tick` with the engine's `tick-*` phases) and the
    runtime's dispatch and wait events nested inside them. An instant
    belongs to the innermost event covering it, so the self times sum to
    the line's covered time: under `serve-tick`, `tick-read`'s own is the
    wait for the device and what is left of the tick is the host's work.
    Empty where no host line is annotated."""
    lines: Dict[Tuple[str, str], List[OpEvent]] = {}
    for e in events:
        if e.kind == KIND_HOST and not e.plane.startswith(
                DEVICE_PLANE_PREFIX):
            lines.setdefault((e.plane, e.line), []).append(e)
    out: Dict[str, Dict[str, Any]] = {}
    for line_events in lines.values():
        if not any(e.step_num is not None for e in line_events):
            continue
        for e, _segs, self_ps in self_segments(line_events):
            row = out.setdefault(e.name, {"span": e.name, "count": 0,
                                          "total_s": 0.0, "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += e.duration_ps / PS_PER_S
            row["self_s"] += self_ps / PS_PER_S
    rows = sorted(out.values(), key=lambda r: -r["self_s"])
    for r in rows:
        r["total_s"], r["self_s"] = round(r["total_s"], 9), round(r["self_s"], 9)
    return rows


def analyze_events(events: List[OpEvent],
                   module: Optional[str] = None) -> TraceReport:
    """Aggregate classified events into a TraceReport.

    module: restrict the op table / split / exposed computation to one
    hlo module (e.g. "jit_train_step"); default is the module with the
    most op time — a train-loop trace also carries the odd reshard or
    metrics program, and mixing them would blur the step's numbers.
    """
    per_module = {m: ps / PS_PER_S for m, ps in modules(events).items()}
    if module is None and per_module:
        module = max(per_module, key=per_module.get)

    # one nesting pass per (plane, line): self time for every event, and
    # the compute SELF segments feeding the exposure union
    by_line: Dict[Tuple[str, str], List[OpEvent]] = {}
    for e in events:
        by_line.setdefault((e.plane, e.line), []).append(e)

    busy_ps: Dict[str, int] = {KIND_HOST: 0}
    per_op: Dict[Tuple[str, str], OpAgg] = {}
    compute_segs: Dict[str, List[Tuple[int, int]]] = {}  # plane -> segs
    coll_events: Dict[str, List[OpEvent]] = {}           # plane -> events
    xla_span: List[int] = []  # [min_start, max_end] of the module's ops
    scope_ps: Dict[str, int] = {}
    class_ps: Dict[str, Dict[str, int]] = {}
    kernels: Dict[str, Dict[str, float]] = {}
    for (plane, _line), line_events in by_line.items():
        for e, segs, self_ps in self_segments(line_events):
            if e.kind == KIND_HOST:
                busy_ps[KIND_HOST] += self_ps
                continue
            # compute from ANY module hides comm — overlap is overlap
            # regardless of which program the concurrent work belongs to
            if e.kind == KIND_COMPUTE:
                compute_segs.setdefault(plane, []).extend(segs)
            if module is not None and e.module != module:
                continue
            busy_ps[e.kind] = busy_ps.get(e.kind, 0) + self_ps
            agg = per_op.get((e.name, e.kind))
            if agg is None:
                per_op[(e.name, e.kind)] = OpAgg(
                    e.name, e.kind, 1, e.duration_ps, self_ps, e.detail)
            else:
                agg.count += 1
                agg.total_ps += e.duration_ps
                agg.self_ps += self_ps
            parts = scope_tokens(e.tf_op)
            region = innermost_scope(parts, REGION_SCOPES) or OTHER_SCOPE
            scope_ps[region] = scope_ps.get(region, 0) + self_ps
            kernel = (kernel_of(parts) if "custom-call" in e.detail
                      else None)
            if kernel is not None:
                k = kernels.setdefault(kernel, {"count": 0, "self_s": 0.0})
                k["count"] += 1
                k["self_s"] += self_ps / PS_PER_S
            cls = op_class(e.name, e.category, kernel is not None)
            for scope in ((region,) if e.tf_op
                          else (region, UNNAMED_SCOPE)):
                class_ps.setdefault(
                    scope, dict.fromkeys(OP_CLASSES, 0))[cls] += self_ps
            if e.kind == KIND_COLLECTIVE and e.collective:
                coll_events.setdefault(plane, []).append(e)
            if not xla_span:
                xla_span = [e.start_ps, e.end_ps]
            else:
                xla_span[0] = min(xla_span[0], e.start_ps)
                xla_span[1] = max(xla_span[1], e.end_ps)
    busy = {k: v / PS_PER_S for k, v in busy_ps.items()}

    collectives: Dict[str, CollectiveAgg] = {}
    for plane, evs in coll_events.items():
        compute_union = merge_intervals(compute_segs.get(plane, ()))
        starts = [s for s, _ in compute_union]
        for e in evs:
            hidden = overlap_ps(e.start_ps, e.end_ps, compute_union, starts)
            agg = collectives.get(e.collective)
            if agg is None:
                agg = collectives[e.collective] = CollectiveAgg(
                    e.collective, 0, 0, 0)
            # async pairs: the -done half's time is communication (the
            # wait) but the PAIR counts once, like the static contracts
            if not is_collective_done_half(e.name):
                agg.count += 1
            agg.total_ps += e.duration_ps
            agg.exposed_ps += e.duration_ps - hidden

    steps: Dict[str, Dict[str, float]] = {}
    for name, marks in step_markers(events).items():
        ms = sorted(m.duration_ps / 1e9 for m in marks)
        steps[name] = {
            "count": len(ms),
            "p50_ms": round(_percentile(ms, 0.5), 3),
            "max_ms": round(ms[-1], 3),
            "total_ms": round(sum(ms), 3),
        }

    if set(scope_ps) <= {OTHER_SCOPE}:
        # without the program's names: no tables (an unnamed kernel's
        # stack closes in `pallas_call` behind one of JAX's own parts)
        scope_ps = class_ps = kernels = {}
    for k in kernels.values():
        k["self_s"] = round(k["self_s"], 6)

    wall_s = (xla_span[1] - xla_span[0]) / PS_PER_S if xla_span else 0.0
    return TraceReport(
        module=module,
        wall_s=wall_s,
        busy_s=busy,
        ops=sorted(per_op.values(), key=lambda o: -o.self_ps),
        collectives=sorted(collectives.values(), key=lambda c: -c.total_ps),
        steps=steps,
        all_modules=per_module,
        scopes={k: v / PS_PER_S for k, v in sorted(
            scope_ps.items(), key=lambda kv: -kv[1])},
        scope_classes={
            scope: {c: ps / PS_PER_S for c, ps in by_class.items()}
            for scope, by_class in class_ps.items()},
        kernels=dict(sorted(kernels.items(),
                            key=lambda kv: -kv[1]["self_s"])),
        idle_gaps=idle_gaps_by_host_span(events),
        loop_thread=loop_thread_self_time(events),
    )


# -- golden-contract comparison ----------------------------------------------

#: jaxpr collective primitive -> the HLO mnemonic its thunk traces as
#: (for manifests without an ``hlo`` section: can_compile=False configs)
_JAXPR_TO_HLO = {
    "psum": "all-reduce", "pmax": "all-reduce", "pmin": "all-reduce",
    "ppermute": "collective-permute",
    "pbroadcast": "collective-broadcast",
    "all_gather": "all-gather", "all_to_all": "all-to-all",
    "reduce_scatter": "reduce-scatter", "psum_scatter": "reduce-scatter",
    "pgather": "all-gather", "ragged_all_to_all": "ragged-all-to-all",
}


def expected_collectives(manifest: Dict[str, Any]
                         ) -> Tuple[Dict[str, int], Dict[str, int], str]:
    """(per-execution counts, per-execution bytes, level) pinned by a
    golden manifest. The ``hlo`` section (post-GSPMD static op counts —
    what the runtime thunks execute once per device per step, loops
    aside) is authoritative when present; jaxpr-only manifests map their
    explicit primitives onto HLO mnemonics."""
    hlo = manifest.get("hlo", {}).get("collectives")
    if hlo is not None:
        counts = {op: int(v["count"]) for op, v in hlo.items()}
        bytes_ = {op: int(v.get("total_bytes", 0)) for op, v in hlo.items()}
        return counts, bytes_, "hlo"
    counts: Dict[str, int] = {}
    bytes_: Dict[str, int] = {}
    for key, v in manifest.get("jaxpr", {}).get("collectives", {}).items():
        prim = key.split("[", 1)[0]
        if prim not in COLLECTIVE_PRIMITIVES:
            continue
        op = _JAXPR_TO_HLO.get(prim, prim)
        counts[op] = counts.get(op, 0) + int(v["count"])
        bytes_[op] = bytes_.get(op, 0) + int(v.get("total_bytes", 0))
    return counts, bytes_, "jaxpr"


@dataclasses.dataclass
class ContractComparison:
    config: str
    level: str                      # hlo | jaxpr
    executions: Optional[int]       # devices x steps reconciling the counts
    rows: List[Dict[str, Any]]      # one per op: expected/measured/ok
    problems: List[str]
    bandwidth: Dict[str, Dict[str, float]]  # op -> bytes/bandwidth stats

    @property
    def matches(self) -> bool:
        return not self.problems

    def to_dict(self) -> Dict[str, Any]:
        return {"config": self.config, "level": self.level,
                "executions": self.executions, "matches": self.matches,
                "rows": self.rows, "problems": self.problems,
                "bandwidth": self.bandwidth}


def compare_contract(report: TraceReport, manifest: Dict[str, Any],
                     config: str,
                     executions: Optional[int] = None
                     ) -> ContractComparison:
    """measured-vs-expected collective counts for one golden contract.

    The manifest pins per-execution counts; the trace yields totals over
    (devices x profiled steps) executions. With ``executions`` given the
    check is direct; otherwise it is inferred from the first op and must
    reconcile EVERY op (integer, identical) — a collective the contract
    doesn't know, a missing one, or inconsistent ratios (a collective
    inside a runtime loop) all land in ``problems``."""
    expected, exp_bytes, level = expected_collectives(manifest)
    measured = report.collective_counts()
    problems: List[str] = []
    inferred = executions
    if inferred is None:
        # anchor on the SMALLEST divisible ratio across ops: loop-carried
        # collectives run MORE often than the static count, never less,
        # so the minimum is the true execution count and the inflated
        # ops get flagged (anchoring on whichever op sorts first would
        # invert the attribution when a loop-carried op sorts early)
        ratios = [measured[op] // n for op, n in expected.items()
                  if n > 0 and measured.get(op, 0) > 0
                  and measured[op] % n == 0]
        if ratios:
            inferred = min(ratios)
    rows: List[Dict[str, Any]] = []
    for op in sorted(set(expected) | set(measured)):
        exp, got = expected.get(op, 0), measured.get(op, 0)
        want_total = exp * inferred if inferred else None
        ok = (got == want_total if want_total is not None
              else exp == 0 and got == 0)
        rows.append({"op": op, "expected_per_exec": exp,
                     "measured_total": got,
                     "expected_total": want_total, "ok": ok})
        if not ok:
            if exp == 0:
                problems.append(
                    f"{config}: UNEXPECTED collective {op}: measured "
                    f"{got}, contract pins none")
            elif got == 0:
                problems.append(
                    f"{config}: collective {op} NEVER RAN: contract "
                    f"expects {exp} per execution")
            else:
                problems.append(
                    f"{config}: {op}: measured {got} != expected "
                    f"{exp} x {inferred} executions (loop-carried "
                    f"collective, or the wrong module/trace?)")
    if inferred is None and any(expected.values()):
        problems.append(f"{config}: could not reconcile an execution "
                        "count from the measured totals")

    # effective bus bandwidth: the manifest's per-execution byte volume
    # over the measured time — `exposed` is the number Flash-Communication
    # compression would have to beat
    bandwidth: Dict[str, Dict[str, float]] = {}
    if inferred:
        per_coll = {c.op: c for c in report.collectives}
        for op, nbytes in sorted(exp_bytes.items()):
            c = per_coll.get(op)
            if c is None or not nbytes:
                continue
            total = nbytes * inferred
            bandwidth[op] = {
                "bytes_total": total,
                "bus_gbps": round(total / max(c.total_ps / PS_PER_S, 1e-12)
                                  / 1e9, 4),
                "exposed_gbps": round(
                    total / max(c.exposed_ps / PS_PER_S, 1e-12) / 1e9, 4),
            }
    return ContractComparison(config=config, level=level,
                              executions=inferred, rows=rows,
                              problems=problems, bandwidth=bandwidth)
