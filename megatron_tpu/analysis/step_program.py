"""What a compiled step program says about itself (stdlib only): where
its collectives stand, and which of its instructions lost their name.

    collectives(text)            one record for every collective
                                 instruction the step reaches: plain, the
                                 `-start` half of an asynchronous pair, or
                                 inside a fused computation
    unnamed_instructions(text)   opcode and result of every instruction
                                 that does work and carries no `op_name`
    kernel_calls(text)           for each named Pallas kernel, its calls
                                 and how many of them are recomputation
    flash_k_operands(text)       for each flash training kernel, the shape
                                 its calls take K in: [B, Hkv, S, D] where
                                 the kernels read K and V by KV head, the
                                 query heads' where they were broadcast
    relaid_arrays(text, n)       every instruction that writes an array of
                                 n bytes or more a second time and computes
                                 nothing: a `copy` into another layout, a
                                 slice taken out for its consumer to read

`text` is `compiled.as_text()`: the program after GSPMD and the chip
compiler's fusion, which is what a device trace times. The chip compiler
fuses a collective with the operation in front of or behind it (a
reduce-scatter with the matmul that feeds it, an all-reduce with the slice
that follows): the trace then shows one `fusion`, and only this walk says
that it communicates. An instruction's name stack (`op_name`) is its own,
else that of the instruction that calls the computation it stands in: the
compiler leaves the name on the fusion. Read by the trainer's
`step_program` journal record (training/pretrain.py) and by
tests/test_chip_compile.py (`relaid_arrays` by its guard on the served
steps' weights alone); the record is the cross-check for the classes
a trace is read by (docs/observability.md "Runtime traces").
"""

from __future__ import annotations

import collections
import math
import re
from typing import Any, Dict, List, Optional, Tuple

from megatron_tpu.analysis.taxonomy import (
    HLO_COLLECTIVE_OPS, HLO_DTYPE_BITS,
)
from megatron_tpu.telemetry.tracing.events import (
    REGION_SCOPES, innermost_scope, kernel_of, scope_tokens,
)

OTHER = "other"
KERNEL_TARGET = "tpu_custom_call"
# the scope `jax.checkpoint` puts what the backward pass computes again under
REMATTED = "rematted_computation"

_COMPUTATION = re.compile(r"^(ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(?P<name>[\w.\-]+) = (?P<results>.*?[\]})]) "
    r"(?P<opcode>[a-z][a-z0-9\-]*)\(")
_COLLECTIVE = re.compile(
    r"^(" + "|".join(HLO_COLLECTIVE_OPS) + r")(-start|-done)?$")
_RESULT = re.compile(r"\b(pred|[a-z]+\d+(?:e\dm\d\w*)?)\[([\d,]*)\]")
_CALLED = re.compile(r"\b(calls|to_apply|body|condition|branch_computations)"
                     r"=\{?((?:%[\w.\-]+(?:, )?)+)\}?")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_GROUPS = re.compile(r"replica_groups=(\{\{[\d,]*\}|\[[\d,]+\]<=)")
_CHANNEL = re.compile(r"channel_id=(\d+)")
_ASYNC_FUSION = re.compile(r"async[-_]collective")
_IDENTIFIER = re.compile(r"^[A-Za-z_]\w*$")
_LAYOUT = re.compile(r"\]\{([\d,]*)")
_OPERAND = re.compile(r"\(%([\w.\-]+)")
_FUSED = re.compile(r"\bcalls=%([\w.\-]+)")
# what takes a part of an array out and does nothing else to it
_SLICES = frozenset({"slice", "dynamic-slice"})
# instructions that move or compute nothing of their own
_NO_WORK = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id", "opt-barrier"})


def _elements(dims: str) -> int:
    return math.prod(int(d) for d in dims.split(",") if d)


def _largest_result(results: str) -> Tuple[str, str, int]:
    """(dtype, dims, bytes) of the largest array among an instruction's
    results (a `-start` half returns its operand beside its result)."""
    best = ("", "", 0)
    for dtype, dims in _RESULT.findall(results):
        nbytes = _elements(dims) * HLO_DTYPE_BITS.get(dtype, 32) // 8
        if nbytes >= best[2]:
            best = (dtype, dims, nbytes)
    return best


def _group_size(line: str) -> int:
    """Devices in one replica group: `{{0,1},{2,3}}` or the iota form
    `[2,2]<=[4]` (groups x size); 0 where the instruction names none (a
    collective-permute names pairs)."""
    m = _GROUPS.search(line)
    if not m:
        return 0
    text = m.group(1)
    if text.startswith("{{"):
        return len([i for i in text[2:-1].split(",") if i])
    return int(text[1:text.index("]")].split(",")[-1])


def scope_of(op_name: str) -> Tuple[str, str]:
    """(region, scope) of a name stack: the innermost region scope, else
    `other`; and the innermost part in front of the primitive that is a
    plain name (a `jax.named_scope`, or JAX's own `while`, `shard_map`),
    "" for an empty stack."""
    parts = scope_tokens(op_name)
    region = innermost_scope(parts, REGION_SCOPES) or OTHER
    scope = next((p for p in reversed(parts[:-1]) if _IDENTIFIER.match(p)),
                 "")
    return region, scope


class Program:
    """The computations of a compiled module's text, and for each how
    often a step runs it (the product of the trip counts of the loops
    around it), whether a loop is around it, whether it stands inside a
    fusion, whether only a reduction applies it, and the `op_name` it
    inherits from its caller."""

    def __init__(self, text: str):
        self.lines: Dict[str, List[str]] = {}
        cur: Optional[str] = None
        entry: Optional[str] = None
        for line in text.splitlines():
            if cur is None:
                m = _COMPUTATION.match(line)
                if m:
                    cur = m.group(2)
                    self.lines[cur] = []
                    entry = cur if m.group(1) else entry
            elif line.startswith("}"):
                cur = None
            else:
                self.lines[cur].append(line)
        trips = {}  # a scan's condition compares its counter with a constant
        for name, lines in self.lines.items():
            limits = [int(c) for line in lines for c in
                      re.findall(r"s32\[\][^ ]* constant\((\d+)\)", line)]
            if limits and any("direction=LT" in line for line in lines):
                trips[name] = max(limits)
        edges = collections.defaultdict(list)
        waiting: Dict[str, int] = collections.Counter()
        for name, lines in self.lines.items():
            for line in lines:
                called = {k: re.findall(r"%([\w.\-]+)", v)
                          for k, v in _CALLED.findall(line)}
                n = trips.get((called.get("condition") or [None])[0], 1)
                op = _OP_NAME.search(line)
                for key, callees in called.items():
                    for callee in callees:
                        edges[name].append((
                            callee, n if key == "body" else 1,
                            key == "body", op.group(1) if op else "",
                            " fusion(" in line, key == "to_apply"))
                        waiting[callee] += 1
        self.times: Dict[str, int] = collections.defaultdict(int)
        self.looped: Dict[str, bool] = collections.defaultdict(bool)
        self.fused: Dict[str, bool] = collections.defaultdict(bool)
        self.applied: Dict[str, bool] = collections.defaultdict(bool)
        self.caller_op: Dict[str, str] = {}
        if entry is None:
            return
        self.times[entry] = 1
        ready = [entry]
        while ready:
            name = ready.pop()
            for callee, n, loop, op, fusion, applied in edges[name]:
                self.times[callee] += self.times[name] * n
                self.looped[callee] |= self.looped[name] or loop
                self.fused[callee] |= self.fused[name] or fusion
                self.applied[callee] |= self.applied[name] or applied
                self.caller_op.setdefault(
                    callee, op or self.caller_op.get(name, ""))
                waiting[callee] -= 1
                if not waiting[callee]:
                    ready.append(callee)

    def instructions(self):
        """(computation, line, name, results, opcode) of every instruction
        the step reaches."""
        for comp, lines in self.lines.items():
            if not self.times[comp]:
                continue
            for line in lines:
                m = _INSTRUCTION.match(line)
                if m:
                    yield (comp, line, m.group("name"), m.group("results"),
                           m.group("opcode"))

    def op_name(self, comp: str, line: str) -> str:
        own = _OP_NAME.search(line)
        return (own.group(1) if own and own.group(1)
                else self.caller_op.get(comp, ""))


def collectives(text: str) -> List[Dict[str, Any]]:
    """Every collective of a compiled program that a step reaches (the
    `-done` half of a pair is its `-start`'s): `region` and `scope` of
    its name stack, `kind`, `fused` (it stands inside a fused
    computation: a trace shows the fusion, not the collective), `async`
    (a `-start` half, or inside a fusion the chip compiler runs beside
    other work: `async-collective-start`, `async_collective_fusion`),
    `result` and `result_bytes` of its largest result, `group_size`,
    `times` a step, and `links`: the chip compiler carries one
    asynchronous collective through a chain of fusions (each holds a copy
    of the instruction beside the matmul it hides behind, all on one
    channel), so the copies on one channel are one record, and `links`
    says through how many fusions its time is spread."""
    program = Program(text)
    out: List[Dict[str, Any]] = []
    by_channel: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for comp, line, name, results, opcode in program.instructions():
        m = _COLLECTIVE.match(opcode)
        if not m or m.group(2) == "-done":
            continue
        region, scope = scope_of(program.op_name(comp, line))
        dtype, dims, nbytes = _largest_result(results)
        is_async = m.group(2) == "-start" or bool(_ASYNC_FUSION.search(comp))
        channel = _CHANNEL.search(line)
        rec = by_channel.get((m.group(1), channel.group(1))) if (
            channel and program.fused[comp]) else None
        if rec is not None:
            rec["links"] += 1
            rec["async"] = rec["async"] or is_async
            rec["times"] = max(rec["times"], program.times[comp])
            continue
        rec = {"region": region, "scope": scope, "kind": m.group(1),
               "fused": program.fused[comp], "async": is_async,
               "result": f"{dtype}[{dims}]", "result_bytes": nbytes,
               "group_size": _group_size(line),
               "times": program.times[comp], "links": 1}
        if channel:
            by_channel[(m.group(1), channel.group(1))] = rec
        out.append(rec)
    return out


def unnamed_instructions(text: str, top: int = 64) -> List[Dict[str, Any]]:
    """The instructions that do work of their own (no parameter, tuple or
    bitcast; not inside a fusion, which is timed as one; not a
    reduction's scalar function; not a Pallas kernel) and carry no
    `op_name`, their own or their caller's: a trace books them under no
    region and no scope at all. Merged by opcode and result, the `top`
    that move most bytes a step first: `opcode`, `result`, `count` (in
    the text), `times` (a step)."""
    program = Program(text)
    merged: Dict[Tuple[str, str], Dict[str, Any]] = {}
    for comp, line, name, results, opcode in program.instructions():
        if (opcode in _NO_WORK or program.fused[comp]
                or program.applied[comp] or KERNEL_TARGET in line
                or program.op_name(comp, line)):
            continue
        dtype, dims, nbytes = _largest_result(results)
        rec = merged.setdefault((opcode, f"{dtype}[{dims}]"), {
            "opcode": opcode, "result": f"{dtype}[{dims}]", "count": 0,
            "times": 0, "_bytes": 0})
        rec["count"] += 1
        rec["times"] += program.times[comp]
        rec["_bytes"] += nbytes * program.times[comp]
    ranked = sorted(merged.values(), key=lambda r: -r["_bytes"])[:top]
    return [{k: v for k, v in r.items() if k != "_bytes"} for r in ranked]


def kernel_calls(text: str) -> Dict[str, Dict[str, int]]:
    """The Pallas kernels of a compiled program by the name a trace finds
    them under (the part in front of the name stack's closing
    `pallas_call`): `calls`, the custom calls the step reaches as the
    text holds them (a scanned layer's once), `rematted`, how many of
    them stand under `rematted_computation` (a forward kernel that the
    backward pass runs a second time because the layer's checkpoint kept
    none of its results), and `times` a step. Empty where the kernels
    are interpreted: there is no custom call to find."""
    program = Program(text)
    out: Dict[str, Dict[str, int]] = {}
    for comp, line, name, results, opcode in program.instructions():
        if opcode != "custom-call" or KERNEL_TARGET not in line:
            continue
        parts = scope_tokens(program.op_name(comp, line))
        rec = out.setdefault(kernel_of(parts) or "", {
            "calls": 0, "rematted": 0, "times": 0})
        rec["calls"] += 1
        rec["rematted"] += REMATTED in parts
        rec["times"] += program.times[comp]
    return dict(sorted(out.items()))


# the training flash kernels (ops/pallas/flash_template.py) take the
# position offset, q, K, V, ...: K is their third operand, and a Pallas
# custom call states its operands' shapes
_FLASH_TRAINING = frozenset({"flash_fwd", "flash_bwd", "flash_bwd_dq",
                             "flash_bwd_dkv"})
_OPERAND_SHAPES = re.compile(
    r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}")


def flash_k_operands(text: str) -> Dict[str, List[str]]:
    """For each training flash kernel of a compiled program (by the name
    a trace finds it under), the shapes its calls take K in, as
    `dtype[dims]`, sorted, each once. The static counter of the kernels'
    GQA addressing: a call that reads K and V by KV head takes
    `bf16[B,Hkv,S,D]` (`bf16[2,4,8192,128]` in the benchmark's Mellum
    cell, `bf16[1,8,4096,128]` in `train_mistral7b_seq4k`), one whose K
    was broadcast in front of it the query heads' `bf16[B,Hq,S,D]`. Empty
    where the kernels are interpreted: there is no custom call to find."""
    program = Program(text)
    out: Dict[str, set] = {}
    for comp, line, name, results, opcode in program.instructions():
        if opcode != "custom-call" or KERNEL_TARGET not in line:
            continue
        kernel = kernel_of(scope_tokens(program.op_name(comp, line)))
        stated = _OPERAND_SHAPES.search(line)
        if kernel in _FLASH_TRAINING and stated:
            dtype, dims = _RESULT.findall(stated.group(1))[2]
            out.setdefault(kernel, set()).add(f"{dtype}[{dims}]")
    return {kernel: sorted(shapes) for kernel, shapes in sorted(out.items())}


def _layout(results: str) -> str:
    """`{2,1,0}` of `bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)S(1)}`: the
    dimensions from the fastest-varying to the slowest; "" where the text
    states none."""
    m = _LAYOUT.search(results)
    return "{" + m.group(1) + "}" if m else ""


def relaid_arrays(text: str, min_bytes: int) -> List[Dict[str, Any]]:
    """The instructions a step reaches that write `min_bytes` or more and
    compute nothing: a `copy` (the same elements in another layout), and a
    slice that is a result of its own (`slice` / `dynamic-slice`, bare or
    the only work of a fusion: a layer's weight taken out of its stack
    into memory of its own, where the product that needs it could have
    taken the stack and the index). Not counted: what stands inside a
    fusion (a slice fused into its consumer is read in place and written
    nowhere), and the asynchronous pairs (`copy-start`, `slice-start`: the
    compiler's prefetch into fast memory beside other work, same layout).
    Each record: `name`, `kind` ("copy" or "slice"), `result`, `layout`
    and, of a copy, the operand's `from_layout`, `bytes`, `times` a step,
    `region` and `scope` of its name stack; most bytes a step first. With
    `min_bytes` the smallest of a layer's matrices, an empty list says no
    weight is moved but into the product that reads it."""
    program = Program(text)
    parsed = {comp: [m for m in map(_INSTRUCTION.match, lines) if m]
              for comp, lines in program.lines.items()}
    slicing = set()   # the computations that slice and do nothing else
    for comp, found in parsed.items():
        ops = {m.group("opcode") for m in found}
        if ops & _SLICES and ops <= _SLICES | _NO_WORK:
            slicing.add(comp)
    out: List[Dict[str, Any]] = []
    for comp, line, name, results, opcode in program.instructions():
        if program.fused[comp]:
            continue
        called = _FUSED.search(line) if opcode == "fusion" else None
        if opcode == "copy":
            kind = "copy"
        elif opcode in _SLICES or (called and called.group(1) in slicing):
            kind = "slice"
        else:
            continue
        dtype, dims, nbytes = _largest_result(results)
        if nbytes < min_bytes:
            continue
        region, scope = scope_of(program.op_name(comp, line))
        rec = {"name": name, "kind": kind, "result": f"{dtype}[{dims}]",
               "layout": _layout(results), "bytes": nbytes,
               "times": program.times[comp], "region": region,
               "scope": scope}
        if kind == "copy":
            operand = _OPERAND.search(line[line.index(" copy("):]).group(1)
            rec["from_layout"] = next(
                (_layout(m.group("results")) for m in parsed[comp]
                 if m.group("name") == operand), "")
        out.append(rec)
    return sorted(out, key=lambda r: -r["bytes"] * r["times"])
