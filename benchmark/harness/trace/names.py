"""How the program's names arrive in a trace, and what they are.

On a TPU each operation's event METADATA carries the stat `tf_op`: the
jaxpr name stack, "jit(train_step)/while/body/closed_call/transpose(jvp())/
.../attention/flash_bwd_dq/flash_bwd_dq/pallas_call:". Its parts are the
program's `jax.named_scope`s (any of them: `scope_ms` of named.py reads
whichever a reader names), JAX's own (`while`, `checkpoint`,
`rematted_computation`) and, last, the primitive. A Pallas kernel is named
by the part in front of a closing `pallas_call`: `pallas_call(name=)` puts
it there, whatever the kernel is called. The loop's host phases arrive on
the `/host:CPU` plane as `jax.profiler` annotations.

REGIONS partition a training step (every operation goes to the innermost
of them in its stack, else to `other`); they label the ledger's
`breakdown` and are what `region_ms` sums. A scope inside a region (a
router, a dispatch) needs no entry here.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

from benchmark.harness.trace import proto, xplane

REGIONS = ("optimizer", "head_loss", "attention", "mlp", "embed")
OTHER = "other"
PALLAS = "pallas_call"
KERNEL_TARGET = "tpu_custom_call"
HOST_PLANE = "/host:CPU"
PASS = "train-pass"
FETCH, DATA, SAVE = "metrics-fetch", "batch-generator", "save-checkpoint"
# the host spans an idle gap of the device is booked under: the innermost
# of these that covers the moment the gap opens
GAP_SPANS = (DATA, FETCH, SAVE, PASS)

_EVENT_MD_NAME, _EVENT_MD_STATS = 2, 5          # XEventMetadata
_PLANE_EVENT_MD, _PLANE_STAT_MD = 4, 5          # XPlane
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")  # jvp(x), transpose(jvp(x))
_SHAPE = re.compile(r"\b([a-z][a-z0-9]*)\[([0-9,]*)\]")
_OPCODE = re.compile(r"[}\])]\s([a-z][a-z0-9\-]*)\(")


def tokens(tf_op: str) -> List[str]:
    """The name stack's parts, outermost first, with the wrappers that
    differentiation and transposition put around a scope's name taken
    off: "a/transpose(jvp(attention))/mul:" -> ["a", "attention", "mul"]."""
    out = []
    for part in tf_op.rstrip(":").split("/"):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        out.append(part)
    return out


def region_of(parts: List[str]) -> str:
    """The innermost region scope the operation sits under, else `other`."""
    for part in reversed(parts):
        if part in REGIONS:
            return part
    return OTHER


def kernel_of(parts: List[str]) -> Optional[str]:
    """The `name=` of the `pallas_call` the stack ends in, else None."""
    if len(parts) >= 2 and parts[-1] == PALLAS:
        return parts[-2]
    return None


def op_label(hlo_text: str, tf_op: str) -> str:
    """An operation as the ledger's breakdown names it:
    "<region>[/<kernel>]: <opcode> <shape>" from its name stack and its
    event's name (on a TPU the whole HLO text, "%fusion.4 = bf16[8,128]
    {...} fusion(...), kind=..."; the instruction's own name and number
    are left out, so that operations doing the same thing under one scope
    merge; of several results the largest is shown). "mlp: fusion
    bf16[4096,28672]", "attention/flash_fwd: tpu_custom_call
    bf16[1,32,4096,128]"."""
    parts = tokens(tf_op)
    where = region_of(parts)
    kernel = kernel_of(parts)
    if kernel is not None:
        where += "/" + kernel
    lhs, sep, rhs = hlo_text.partition(" = ")
    if not sep:
        return f"{where}: {hlo_text}"[:120]
    opcode = _OPCODE.search(rhs)
    what = (KERNEL_TARGET if KERNEL_TARGET in rhs
            else opcode.group(1) if opcode
            else re.sub(r"\.\d+$", "", lhs.lstrip("%")))
    # the results stand in front of the opcode (a text cut short by the
    # profiler ends inside them)
    results = _SHAPE.findall(rhs[:opcode.start() + 1] if opcode else rhs)
    shape = max(results, key=_elements, default=None)
    return f"{where}: {what} {'%s[%s]' % shape if shape else ''}".rstrip()[:120]


def _elements(shape: tuple) -> int:
    n = 1
    for d in shape[1].split(","):
        n *= int(d or 1)
    return n


def name_stacks(plane_buf: bytes) -> Dict[str, str]:
    """Event name -> `tf_op`, from the plane's event metadata (the stats
    an event's NAME carries, which xplane.py leaves out: it decodes only
    the stats of each event)."""
    stat_names: Dict[int, str] = {}
    entries: List[bytes] = []
    for fn, wt, v in proto.fields(plane_buf):
        if wt != proto.WIRE_LEN:
            continue
        if fn == _PLANE_STAT_MD:
            key, md = xplane._map_entry(v)
            stat_names[key] = xplane._metadata_name(md)
        elif fn == _PLANE_EVENT_MD:
            entries.append(xplane._map_entry(v)[1])
    out: Dict[str, str] = {}
    for md in entries:
        name, tf_op = "", None
        for fn, wt, v in proto.fields(md):
            if wt != proto.WIRE_LEN:
                continue
            if fn == _EVENT_MD_NAME:
                name = proto.to_text(v)
            elif fn == _EVENT_MD_STATS:
                key, value = xplane._decode_stat(v, stat_names)
                if key == "tf_op" and isinstance(value, str):
                    tf_op = value
        if tf_op is not None:
            out[name] = tf_op
    return out


def gap_spans(plane: xplane.Plane) -> List[xplane.Event]:
    """The GAP_SPANS events of the host plane's loop thread (the line that
    holds `train-pass`), outer before inner where they start together."""
    for line in plane.lines:
        if any(ev.name == PASS for ev in line.events):
            return sorted((ev for ev in line.events
                           if ev.name in GAP_SPANS and ev.duration_ps > 0),
                          key=lambda ev: (ev.start_ps, -ev.end_ps))
    return []
