"""Typed, classified op events out of a decoded XSpace (stdlib only).

Two kinds of trace, told apart by the plane an event sits on:

  * **XLA:CPU** (what tier-1 exercises): op events live on host
    thread-pool lines and carry ``hlo_op``/``hlo_module`` stats; an event
    with one of them is an **XLA op**.
  * **TPU**: on a ``/device:TPU:<n>`` plane only the ``XLA Ops`` line
    holds operations. ``XLA Modules`` and ``Steps`` hold whole-program
    ENVELOPES (classified as compute they would cover the plane and zero
    out every collective's exposed time), ``Async XLA Ops`` repeats the
    async halves (``copy-done.14`` with an ``hlo_op`` stat: counted, they
    read as hundreds of milliseconds of "infeed") and ``TC Overlay`` is a
    view. An operation's event is named by its whole HLO text and carries
    no module: it is shown by instruction name, result shape and opcode,
    and takes the module of the ``XLA Modules`` envelope it runs inside.

Ops split collective / infeed / compute by HLO instruction name against
``analysis/taxonomy.py`` (the vocabulary the golden comm contracts count).
Everything else is **host** activity. ``PjitFunction(fn)`` host events,
host events with a ``step_num`` stat (``jax.profiler.StepTraceAnnotation``:
the train loop's ``train-pass``) and the device's ``Steps`` envelopes are
the step markers the analyzer derives per-step wall from.

The program's names (docs/observability.md "Runtime traces") arrive in
``tf_op``, the jaxpr name stack: ``scope_tokens`` takes it apart,
``REGION_SCOPES`` are the ``jax.named_scope`` names the model puts there,
and ``kernel_of`` finds a Pallas kernel by the part in front of the stack's
closing ``pallas_call`` (``pallas_call(name=)`` puts it there, whatever the
kernel is called: there is no list of kernels to keep). ``hlo_category``,
the profiler's own word for what an operation is, gives its class of work
(``op_class``): the column ``tools/trace_report.py`` prints beside each
scope, the same classes the benchmark reads a traced run by.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Optional

from megatron_tpu.analysis.taxonomy import (
    HLO_COLLECTIVE_OPS, collective_base, is_transfer,
)
from megatron_tpu.telemetry.tracing.xplane import XSpace

KIND_COMPUTE = "compute"
KIND_COLLECTIVE = "collective"
KIND_INFEED = "infeed"
KIND_HOST = "host"

#: python dispatch events naming the jitted callable — the step markers
PJIT_RE = re.compile(r"^PjitFunction\((.+)\)$")

DEVICE_PLANE_PREFIX = "/device:TPU:"
_DEVICE_OP_LINE = "XLA Ops"
_DEVICE_MODULE_LINE = "XLA Modules"
DEVICE_STEP_LINE = "Steps"

#: jax.named_scope names in the program, innermost wins
#: (models/transformer.py, models/language_model.py,
#: training/train_step.py)
REGION_SCOPES = ("optimizer", "head_loss", "attention", "mlp", "embed")
PALLAS = "pallas_call"

#: classes of work, by the profiler's `hlo_category` (`op_class`)
CLASS_KERNEL, CLASS_MATMUL = "kernel", "matmul"
CLASS_COLLECTIVE, CLASS_COLLECTIVE_FUSED = "collective", "collective_fused"
CLASS_ELEMENTWISE, CLASS_DATA_MOVEMENT = "elementwise", "data_movement"
CLASS_REST = "rest"
OP_CLASSES = (CLASS_KERNEL, CLASS_MATMUL, CLASS_COLLECTIVE,
              CLASS_COLLECTIVE_FUSED, CLASS_ELEMENTWISE,
              CLASS_DATA_MOVEMENT, CLASS_REST)
_ELEMENTWISE = frozenset({"loop fusion", "custom fusion", "input fusion",
                          "non-fusion elementwise", "reduce",
                          "reduce-window"})
_DATA_MOVEMENT = frozenset({
    "data formatting", "broadcast", "copy", "copy-start", "copy-done",
    "async-start", "async-done", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "gather", "scatter",
    "transpose", "reshape", "iota"})

_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")  # jvp(x), transpose(jvp(x))
_PROGRAM_ID = re.compile(r"\(\d+\)$")             # jit_train_step(1234)
_HLO_RESULT = re.compile(r"\(?([a-z0-9]+\[[0-9,]*\])")
_HLO_OPCODE = re.compile(r"[}\])]\s([a-z][a-z0-9\-]*)\(")


@dataclasses.dataclass
class OpEvent:
    name: str
    kind: str            # compute | collective | transfer | host
    start_ps: int
    duration_ps: int
    plane: str
    line: str
    module: Optional[str] = None      # hlo_module ("jit_train_step")
    program_id: Optional[int] = None
    collective: Optional[str] = None  # base mnemonic ("all-reduce")
    detail: str = ""                  # TPU op: "bf16[8,128] fusion"
    tf_op: Optional[str] = None       # jaxpr name stack, with the scopes
    category: Optional[str] = None    # the profiler's hlo_category
    step_num: Optional[int] = None    # host StepTraceAnnotation

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.duration_ps


def scope_tokens(tf_op: Optional[str]) -> List[str]:
    """The name stack's parts, outermost first, without the wrappers that
    differentiation puts around a scope's name:
    "a/transpose(jvp(attention))/mul:" -> ["a", "attention", "mul"]."""
    out: List[str] = []
    for part in (tf_op or "").rstrip(":").split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        out.append(part)
    return out


def split_hlo_text(text: str) -> tuple:
    """("fusion.4", "bf16[8,128] fusion") from a TPU op event's name, its
    whole HLO text "%fusion.4 = bf16[8,128]{1,0:T(8,128)} fusion(...)"; a
    name that is no HLO text comes back whole."""
    lhs, sep, rhs = text.partition(" = ")
    if not sep:
        return text, ""
    shape = _HLO_RESULT.match(rhs)
    opcode = _HLO_OPCODE.search(rhs)
    detail = " ".join(m.group(1) for m in (shape, opcode) if m)
    return lhs.lstrip("%"), detail


def innermost_scope(parts: List[str], names) -> Optional[str]:
    """The innermost of `names` among a name stack's parts, or None."""
    return next((p for p in reversed(parts) if p in names), None)


def kernel_of(parts: List[str]) -> Optional[str]:
    """The `name=` of the `pallas_call` a name stack ends in, else None:
    [..., "attention", "flash_fwd", "pallas_call"] -> "flash_fwd"."""
    if len(parts) >= 2 and parts[-1] == PALLAS:
        return parts[-2]
    return None


def op_class(name: str, category: Optional[str], kernel: bool = False) -> str:
    """An operation's class of work from its instruction's `name`
    ("all-gather.3", "fusion.12") and the profiler's `hlo_category` of
    it: a Pallas kernel (`kernel`: the caller found it by `kernel_of` and
    its custom call); a collective, by name or by category; a fusion
    whose category names a collective, or one the compiler runs beside
    other work ("async-collective-start"), which communicates though its
    name says `fusion`; a matmul (a convolution, alone or as a fusion's
    root); elementwise; data movement; the rest (loops' own time, sorts,
    a category this table does not know)."""
    category = category or ""
    if kernel:
        return CLASS_KERNEL
    if collective_base(name) or collective_base(category):
        # by category too: a collective the program wrote itself is named
        # after its primitive (`reduce_scatter.13`)
        return CLASS_COLLECTIVE
    if ((category.endswith(" fusion")
         and category.startswith(HLO_COLLECTIVE_OPS))
            or name.startswith("async-collective-")):
        return CLASS_COLLECTIVE_FUSED
    if category.startswith("convolution") or category == "dot":
        return CLASS_MATMUL
    if category in _ELEMENTWISE:
        return CLASS_ELEMENTWISE
    if category in _DATA_MOVEMENT:
        return CLASS_DATA_MOVEMENT
    return CLASS_REST


def _op_event(name: str, ev, plane: str, line: str, module,
              detail: str = "") -> OpEvent:
    """An XLA op's event, its kind from the instruction's name."""
    base = collective_base(name)
    pid = ev.stats.get("program_id")
    tf_op = ev.stats.get("tf_op")
    category = ev.stats.get("hlo_category")
    return OpEvent(
        name=name,
        kind=(KIND_COLLECTIVE if base else KIND_INFEED if is_transfer(name)
              else KIND_COMPUTE),
        start_ps=ev.start_ps, duration_ps=ev.duration_ps, plane=plane,
        line=line, module=module if isinstance(module, str) else None,
        program_id=pid if isinstance(pid, int) else None, collective=base,
        detail=detail, tf_op=tf_op if isinstance(tf_op, str) else None,
        category=category if isinstance(category, str) else None)


def _host_event(ev, plane: str, line: str) -> OpEvent:
    step = ev.stats.get("step_num")
    return OpEvent(name=ev.name, kind=KIND_HOST, start_ps=ev.start_ps,
                   duration_ps=ev.duration_ps, plane=plane, line=line,
                   step_num=step if isinstance(step, int) else None)


def _device_plane_events(plane, out: List[OpEvent]) -> None:
    """A /device:TPU: plane: operations from "XLA Ops" alone, each under
    the module whose "XLA Modules" envelope holds it; every other line's
    events are host-kind markers."""
    envelopes = sorted((ev for line in plane.lines
                        if line.name == _DEVICE_MODULE_LINE
                        for ev in line.events), key=lambda ev: ev.start_ps)
    starts = [ev.start_ps for ev in envelopes]
    for line in plane.lines:
        for ev in line.events:
            if line.name != _DEVICE_OP_LINE:
                out.append(_host_event(ev, plane.name, line.name))
                continue
            name, detail = split_hlo_text(ev.name)
            module = ev.stats.get("hlo_module")
            at = bisect.bisect_right(starts, ev.start_ps) - 1
            if not isinstance(module, str) and at >= 0 and (
                    ev.start_ps < envelopes[at].end_ps):
                module = _PROGRAM_ID.sub("", envelopes[at].name)
            out.append(_op_event(name, ev, plane.name, line.name, module,
                                 detail))


def classify_xspace(space: XSpace) -> List[OpEvent]:
    """Every event in the space as a classified OpEvent, time-sorted."""
    out: List[OpEvent] = []
    for plane in space.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            _device_plane_events(plane, out)
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = ev.stats
                if "hlo_module" in stats or "hlo_op" in stats:
                    name = stats.get("hlo_op")
                    out.append(_op_event(
                        name if isinstance(name, str) and name else ev.name,
                        ev, plane.name, line.name, stats.get("hlo_module")))
                else:
                    out.append(_host_event(ev, plane.name, line.name))
    out.sort(key=lambda e: (e.start_ps, e.end_ps))
    return out


def op_events(events: Iterable[OpEvent]) -> List[OpEvent]:
    """XLA op events only (compute + collective + transfer)."""
    return [e for e in events if e.kind != KIND_HOST]


def modules(events: Iterable[OpEvent]) -> Dict[str, int]:
    """module name -> total op picoseconds, for dominant-module picking."""
    out: Dict[str, int] = {}
    for e in events:
        if e.kind != KIND_HOST and e.module:
            out[e.module] = out.get(e.module, 0) + e.duration_ps
    return out


def step_markers(events: Iterable[OpEvent]) -> Dict[str, List[OpEvent]]:
    """Step markers: ``PjitFunction(fn)`` dispatch events grouped by fn,
    host step annotations (``train-pass``) by name, and a device's
    "Steps"-line envelopes under the line's name.

    The runtime emits the python dispatch span twice (a python-level and
    a C++ TraceMe with the same name, one nested in the other), so a
    marker contained within the previously kept marker of the same name
    is folded — one span per actual dispatch."""
    out: Dict[str, List[OpEvent]] = {}
    for e in events:
        if e.kind == KIND_HOST:
            m = PJIT_RE.match(e.name)
            if e.duration_ps <= 0:
                continue
            if m:
                out.setdefault(m.group(1), []).append(e)
            elif e.step_num is not None:
                out.setdefault(e.name, []).append(e)
            elif (e.line == DEVICE_STEP_LINE
                    and e.plane.startswith(DEVICE_PLANE_PREFIX)):
                # one envelope a step, named by its number: one marker
                out.setdefault(DEVICE_STEP_LINE, []).append(e)
    deduped: Dict[str, List[OpEvent]] = {}
    for name, marks in out.items():
        marks.sort(key=lambda e: (e.start_ps, -e.end_ps))
        kept: List[OpEvent] = []
        for e in marks:
            if kept and e.end_ps <= kept[-1].end_ps:
                continue  # nested duplicate of the same dispatch
            kept.append(e)
        deduped[name] = kept
    return deduped
