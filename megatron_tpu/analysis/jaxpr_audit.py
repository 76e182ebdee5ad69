"""Jaxpr auditor: walk a closed jaxpr and report its communication and
tracing-discipline facts.

What it extracts (docs/static_analysis.md):

  * **collectives** — every explicit collective primitive (psum,
    ppermute, all_to_all, all_gather, reduce/psum_scatter, pmax, ...)
    with its mesh axes, per-call payload bytes, and a static call count
    that multiplies through enclosing ``lax.scan`` trip counts (a
    ppermute inside a T-tick pipeline scan counts T times). GSPMD-
    inserted collectives don't exist at jaxpr level — see
    ``hlo_collectives`` for the post-partitioning view.
  * **host callbacks** — pure_callback / io_callback / debug_callback /
    outside_call equations. The train step and engine decode step must
    have ZERO (tests/test_analysis.py asserts it).
  * **scalar_carries** — rank-0 inexact scan carries INSIDE shard_map
    bodies: jax 0.4.37's shard_map partial-eval mis-names rank-0
    residuals of differentiated bodies (the [1]-shaped-carry rule in
    training/pipeline.py), so the repo convention is audited here.
  * **manual_constraints** — sharding_constraint equations inside
    shard_map bodies whose spec touches a manually-bound axis (rejected
    at lowering by this toolchain; ``parallel/sharding.py constrain``
    must have skipped them).
  * **promotions** — convert_element_type equations widening bf16/f16
    to f32 above a byte threshold (silent upcasts double comm and
    memory; intentional ones get allowlisted per audit call site).

Donation is audited from ``jax.stages.Lowered.args_info`` (see
``audit_donation``), not from the jaxpr — jaxprs don't carry it.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

# The op vocabulary lives in analysis/taxonomy.py (stdlib-only) so the
# runtime trace analyzer (telemetry/tracing) classifies profiler events
# against the SAME names without paying a jax import; re-exported here
# because the audit API predates the split.
from megatron_tpu.analysis.taxonomy import (  # noqa: F401
    CALLBACK_PRIMITIVES, COLLECTIVE_PRIMITIVES, HLO_COLLECTIVE_OPS,
    HLO_DTYPE_BITS, is_low_bit_dtype, wire_bytes_per_call,
)


@dataclasses.dataclass
class CollectiveOp:
    primitive: str
    axes: Tuple[str, ...]
    shape: Tuple[int, ...]
    dtype: str
    bytes_per_call: int     # per-device payload of one call
    calls: int              # static count (scan trip counts multiplied in)
    context: str            # e.g. "shard_map/scan"
    in_while: bool = False  # trip count unknown => calls is per-iteration
    axis_size: int = 0      # participating devices (0 = unknown mesh)

    @property
    def key(self) -> str:
        shape = "x".join(map(str, self.shape))
        return (f"{self.primitive}[{','.join(self.axes)}] "
                f"{self.dtype}[{shape}] @{self.context}")

    @property
    def compressed(self) -> bool:
        """Low-bit transport (the quant/ pattern): the payload rides as
        int8/uint8/fp8, not bf16/f32."""
        return is_low_bit_dtype(self.dtype)

    @property
    def wire_bytes(self) -> int:
        """Estimated interconnect bytes per call (taxonomy wire model —
        an all-reduce moves ~2x its payload, a gather (n-1)/n of it)."""
        return wire_bytes_per_call(self.primitive, self.bytes_per_call,
                                   self.axis_size)


@dataclasses.dataclass
class Callback:
    primitive: str
    context: str


@dataclasses.dataclass
class ScalarCarry:
    dtype: str
    context: str


@dataclasses.dataclass
class ManualConstraint:
    spec: str
    axes: Tuple[str, ...]
    context: str


@dataclasses.dataclass
class Promotion:
    old_dtype: str
    new_dtype: str
    shape: Tuple[int, ...]
    bytes_out: int
    calls: int
    context: str


@dataclasses.dataclass
class AuditReport:
    name: str
    collectives: List[CollectiveOp] = dataclasses.field(default_factory=list)
    callbacks: List[Callback] = dataclasses.field(default_factory=list)
    scalar_carries: List[ScalarCarry] = dataclasses.field(
        default_factory=list)
    manual_constraints: List[ManualConstraint] = dataclasses.field(
        default_factory=list)
    promotions: List[Promotion] = dataclasses.field(default_factory=list)

    def collective_summary(self) -> Dict[str, Dict[str, int]]:
        """Aggregate by CollectiveOp.key -> {count, bytes_per_call,
        total_bytes, wire_bytes_per_call, total_wire_bytes, compressed}
        (the golden-manifest payload). ``compressed`` marks low-bit
        transport; wire bytes use the taxonomy interconnect model."""
        out: Dict[str, Dict[str, int]] = {}
        for c in self.collectives:
            e = out.setdefault(c.key, {
                "count": 0,
                "bytes_per_call": c.bytes_per_call,
                "total_bytes": 0,
                "wire_bytes_per_call": c.wire_bytes,
                "total_wire_bytes": 0,
                "compressed": c.compressed,
            })
            e["count"] += c.calls
            e["total_bytes"] += c.calls * c.bytes_per_call
            e["total_wire_bytes"] += c.calls * c.wire_bytes
        return dict(sorted(out.items()))

    def total_collective_bytes(self) -> int:
        return sum(c.calls * c.bytes_per_call for c in self.collectives)

    def total_wire_bytes(self) -> int:
        """Estimated interconnect bytes of one program execution — the
        number the compressed-vs-dense contract ratio is taken over."""
        return sum(c.calls * c.wire_bytes for c in self.collectives)


def _aval_bytes(aval) -> int:
    try:
        import numpy as np

        return int(np.prod(aval.shape, dtype="int64")
                   * np.dtype(aval.dtype).itemsize)
    except (TypeError, ValueError, AttributeError):
        return 0  # abstract tokens / opaque avals carry no payload


def _axis_tuple(v) -> Tuple[str, ...]:
    if v is None:
        return ()
    if isinstance(v, (list, tuple, frozenset, set)):
        out: List[str] = []
        for x in v:
            out.extend(_axis_tuple(x))
        return tuple(out)
    return (str(v),)


def _collective_axes(eqn) -> Tuple[str, ...]:
    for k in ("axis_name", "axes", "axis_index_groups_axis", "named_axes"):
        if k in eqn.params and eqn.params[k] is not None:
            axes = _axis_tuple(eqn.params[k])
            # psum params 'axes' may include positional ints — drop them
            return tuple(a for a in axes if not a.isdigit())
    return ()


def _subjaxprs(params) -> List[Tuple[str, Any]]:
    """(param_name, jaxpr) for every (Closed)Jaxpr in an eqn's params."""
    found: List[Tuple[str, Any]] = []

    def visit(name, v):
        if hasattr(v, "jaxpr") and hasattr(getattr(v, "jaxpr"), "eqns"):
            found.append((name, v.jaxpr))     # ClosedJaxpr
        elif hasattr(v, "eqns"):
            found.append((name, v))            # raw Jaxpr
        elif isinstance(v, (tuple, list)):
            for i, item in enumerate(v):
                visit(f"{name}[{i}]", item)

    for k, v in params.items():
        visit(k, v)
    return found


@dataclasses.dataclass
class _Ctx:
    multiplier: int = 1
    manual_axes: Tuple[str, ...] = ()
    axis_sizes: Optional[Dict[str, int]] = None  # from enclosing shard_map
    path: str = ""
    in_while: bool = False

    def push(self, seg: str, **kw) -> "_Ctx":
        return dataclasses.replace(
            self, path=f"{self.path}/{seg}" if self.path else seg, **kw)

    def collective_axis_size(self, axes: Tuple[str, ...]) -> int:
        """Devices participating in a collective over `axes`: the
        product of the enclosing mesh's sizes for them. No named axes
        (positional-only psum) = 1 (no interconnect traffic); a named
        axis with no known mesh = 0 (unknown — wire model falls back to
        the payload)."""
        if not axes:
            return 1
        if not self.axis_sizes:
            return 0
        n = 1
        for a in axes:
            if a not in self.axis_sizes:
                return 0
            n *= int(self.axis_sizes[a])
        return n


def audit_jaxpr(closed_jaxpr, name: str = "jaxpr",
                promotion_threshold_bytes: int = 1 << 12) -> AuditReport:
    """Walk a (closed) jaxpr; see module docstring for what's reported."""
    report = AuditReport(name=name)
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    _walk(jaxpr, _Ctx(), report, promotion_threshold_bytes)
    return report


def _walk(jaxpr, ctx: _Ctx, report: AuditReport, promo_thresh: int) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim in COLLECTIVE_PRIMITIVES:
            axes = _collective_axes(eqn)
            for ov in eqn.outvars:
                report.collectives.append(CollectiveOp(
                    primitive=prim,
                    axes=axes,
                    shape=tuple(getattr(ov.aval, "shape", ())),
                    dtype=str(getattr(ov.aval, "dtype", "?")),
                    bytes_per_call=_aval_bytes(ov.aval),
                    calls=ctx.multiplier,
                    context=ctx.path or "top",
                    in_while=ctx.in_while,
                    axis_size=ctx.collective_axis_size(axes),
                ))
        elif prim in CALLBACK_PRIMITIVES:
            report.callbacks.append(Callback(prim, ctx.path or "top"))
        elif prim == "sharding_constraint":
            _check_constraint(eqn, ctx, report)
        elif prim == "convert_element_type":
            _check_promotion(eqn, ctx, report, promo_thresh)

        if prim == "shard_map":
            manual = _shard_map_manual_axes(eqn)
            sizes = _shard_map_axis_sizes(eqn)
            for pname, sub in _subjaxprs(eqn.params):
                _walk(sub, ctx.push("shard_map", manual_axes=manual,
                                    axis_sizes=sizes),
                      report, promo_thresh)
            continue
        if prim == "scan":
            length = int(eqn.params.get("length", 1))
            _check_scan_carries(eqn, ctx, report)
            for pname, sub in _subjaxprs(eqn.params):
                _walk(sub, ctx.push("scan", multiplier=ctx.multiplier
                                    * max(length, 1)),
                      report, promo_thresh)
            continue
        if prim == "while":
            for pname, sub in _subjaxprs(eqn.params):
                _walk(sub, ctx.push("while", in_while=True), report,
                      promo_thresh)
            continue
        if prim == "cond":
            for pname, sub in _subjaxprs(eqn.params):
                _walk(sub, ctx.push("cond"), report, promo_thresh)
            continue
        # pjit / remat / custom_* / closed_call / anything else that
        # carries sub-jaxprs: transparent traversal
        for pname, sub in _subjaxprs(eqn.params):
            _walk(sub, ctx, report, promo_thresh)


def _shard_map_manual_axes(eqn) -> Tuple[str, ...]:
    """The axes the body is manual over: the equation's own
    `manual_axes` (jax 0.9), in mesh order. A shard_map that names no
    axes is manual over the whole mesh."""
    mesh = eqn.params.get("mesh")
    names = tuple(getattr(mesh, "axis_names", ()) or ())
    manual = eqn.params.get("manual_axes")
    if manual is None:
        return names
    manual = set(map(str, manual))
    return tuple(n for n in names if str(n) in manual)


def _shard_map_axis_sizes(eqn) -> Dict[str, int]:
    """axis name -> size from the shard_map's (abstract) mesh, for the
    wire-byte model."""
    mesh = eqn.params.get("mesh")
    shape = getattr(mesh, "shape", None)
    try:
        return {str(k): int(v) for k, v in dict(shape or {}).items()}
    except (TypeError, ValueError):
        return {}


def _check_scan_carries(eqn, ctx: _Ctx, report: AuditReport) -> None:
    if not ctx.manual_axes:
        return  # the rank-0 hazard is specific to shard_map bodies
    import numpy as np

    num_consts = int(eqn.params.get("num_consts", 0))
    num_carry = int(eqn.params.get("num_carry", 0))
    for var in eqn.invars[num_consts:num_consts + num_carry]:
        aval = getattr(var, "aval", None)
        if aval is None or getattr(aval, "shape", None) != ():
            continue
        try:
            inexact = np.issubdtype(np.dtype(aval.dtype), np.inexact)
        except TypeError:
            continue
        if inexact:
            report.scalar_carries.append(ScalarCarry(
                str(aval.dtype), (ctx.path or "top") + "/scan"))


def _check_constraint(eqn, ctx: _Ctx, report: AuditReport) -> None:
    if not ctx.manual_axes:
        return
    sharding = eqn.params.get("sharding")
    spec = getattr(sharding, "spec", None)
    spec_axes = set()
    if spec is not None:
        for part in spec:
            if part is None:
                continue
            for a in (part if isinstance(part, tuple) else (part,)):
                spec_axes.add(str(a))
    hit = tuple(sorted(spec_axes & set(map(str, ctx.manual_axes))))
    if hit or spec is None:
        report.manual_constraints.append(ManualConstraint(
            spec=str(spec), axes=hit, context=ctx.path or "top"))


def _check_promotion(eqn, ctx: _Ctx, report: AuditReport,
                     thresh: int) -> None:
    import numpy as np

    new = eqn.params.get("new_dtype")
    src = getattr(eqn.invars[0], "aval", None)
    if src is None or new is None:
        return
    old = getattr(src, "dtype", None)
    if old is None:
        return
    if str(old) not in ("bfloat16", "float16") or str(new) != "float32":
        return
    out = eqn.outvars[0].aval
    size = _aval_bytes(out)
    if size * ctx.multiplier >= thresh:
        report.promotions.append(Promotion(
            str(old), str(new), tuple(out.shape), size, ctx.multiplier,
            ctx.path or "top"))


# ---------------------------------------------------------------------------
# donation (from a Lowered, not the jaxpr)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DonationReport:
    donated: List[str]
    undonated: List[Tuple[str, int]]    # (path, bytes)

    def undonated_over(self, min_bytes: int,
                       allow: Sequence[str] = ()) -> List[Tuple[str, int]]:
        """Non-donated inputs above min_bytes whose path matches no
        allowlist regex (allow entries document intentional inputs —
        the batch, eval params...)."""
        pats = [re.compile(p) for p in allow]
        return [(p, b) for p, b in self.undonated
                if b >= min_bytes and not any(r.search(p) for r in pats)]


def audit_donation(lowered) -> DonationReport:
    """Donation coverage from ``jit(...).lower(...)``'s args_info."""
    donated: List[str] = []
    undonated: List[Tuple[str, int]] = []
    flat, _ = jax.tree_util.tree_flatten_with_path(lowered.args_info)
    for path, info in flat:
        label = jax.tree_util.keystr(path)
        size = _aval_bytes(info)
        if getattr(info, "donated", False):
            donated.append(label)
        else:
            undonated.append((label, size))
    return DonationReport(donated=donated, undonated=undonated)


# ---------------------------------------------------------------------------
# HLO-level collective counting (post-SPMD-partitioning)
# ---------------------------------------------------------------------------

_HLO_LINE = re.compile(
    r"=\s*(?P<shapes>\([^)]*\)|\S+)\s+"
    r"(?P<op>" + "|".join(HLO_COLLECTIVE_OPS) + r")(?:-start)?\(")
_HLO_SHAPE = re.compile(
    r"(?P<dtype>pred|[a-z]+\d+(?:e\dm\d)?)\[(?P<dims>[\d,]*)\]")
_HLO_DTYPE_BITS = HLO_DTYPE_BITS


def hlo_collectives(compiled_text: str) -> Dict[str, Dict[str, int]]:
    """Count collective ops (and their result bytes) in a compiled HLO
    module's text — the view that includes GSPMD-inserted collectives.
    ``-done`` halves of async pairs are skipped so an op counts once.

    Returns {op: {"count": n, "total_bytes": b}} with bytes summed over
    result shapes (tuple results: every element)."""
    out: Dict[str, Dict[str, int]] = {}
    for line in compiled_text.splitlines():
        if "-done(" in line or " = " not in line:
            continue
        m = _HLO_LINE.search(line)
        if not m:
            continue
        op = m.group("op")
        size = 0
        for sm in _HLO_SHAPE.finditer(m.group("shapes")):
            dims = [int(d) for d in sm.group("dims").split(",") if d]
            n = 1
            for d in dims:
                n *= d
            size += n * _HLO_DTYPE_BITS.get(sm.group("dtype"), 32) // 8
        e = out.setdefault(op, {"count": 0, "total_bytes": 0})
        e["count"] += 1
        e["total_bytes"] += size
    return dict(sorted(out.items()))
