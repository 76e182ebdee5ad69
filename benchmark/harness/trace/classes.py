"""What a region's time is made of: a traced training run as one table,
region x class of work, that closes exactly to the regions' sums.

named.py gives one number a region (norm, projections, rotary, the
kernels' wrapper, residual and collectives together). Every operation's
event metadata also carries the profiler's own word for what the operation
is, `hlo_category` ("convolution fusion", "loop fusion", "all-reduce-scatter
fusion", "data formatting", ...), and the program names the parts of its
regions one level down (`attn_qkv`, `attn_out`, `mlp_in`, ...,
`layer_stack`, `micro_batches`). This module walks the trace once more, by
the same rules as named.py (own time inside the whole runs of the step
program; the innermost region of the name stack), and books each
operation's own time under

    (region, class)    Σ classes of a region == named's region, to the
                       picosecond on every device
    (scope, class)     every part of the name stack in front of the
                       primitive that is a plain name (scopes overlap)
    scope, in no region    what a loop's scope holds beside the layers
    class, no name stack   the part of `other` that carries no `tf_op`

CLASSES, decided in this order:

    kernel            a Pallas custom call (`tpu_custom_call`)
    collective        the event's name is a collective's (reduce.
                      _COLLECTIVE; `-start` and `-done` included), or
                      its category is one's ("reduce-scatter": the
                      instruction of a collective the program wrote
                      itself is named after its primitive,
                      `%reduce_scatter.13`, which no name rule matches)
    collective_fused  a fusion whose `hlo_category` names a collective
                      ("all-reduce-scatter fusion": the chip compiler
                      fused a reduce-scatter into the operation beside
                      it), or one it runs beside other work
                      (`async-collective-start` / `-done`): it
                      communicates, and by its name is a `fusion`, so
                      `collective_exposed_pct` does not see it
    matmul            "convolution fusion", a bare convolution or dot
    elementwise       "loop fusion", "custom fusion", "input fusion",
                      "non-fusion elementwise", "reduce", "reduce-window"
    data_movement     "data formatting", broadcasts, copies, slices,
                      concatenates, the asynchronous copies' halves
    rest              loops' own time, sorts, calls; and every category
                      this table does not know, which is then listed by
                      name under `unclassified`, never dropped

    per_run(path)    seconds a whole run, mean of devices: the tables
                     above, per class the categories it was made of, the
                     compiler's own `flops` count by (region, class)
                     where the trace carries it, and by (region, kind,
                     fused) of collectives calls a run, result bytes a
                     call and seconds
    of_run, region_class_ms, class_ms, outside_ms, unnamed_ms,
    matmul_roofline_pct: what the readers of <path>/layer_metrics/ call,
    given the harness's `run`; the first of them to read a run puts the
    whole table on the line's `extras.step_classes` and the collectives'
    record on `extras.collectives`.

A program without the region names (named.per_run gives None) gives None
here too. A program without the scopes one level down (the parent of the
PR that added them) gives the classes, which need only the regions, and
None for what reads a scope no operation carries.
"""

from __future__ import annotations

import functools
import os
import re
import time
from typing import Any, Dict, List, Optional, Tuple

from benchmark.harness.trace import named, proto, reduce, xplane
from benchmark.harness.trace.names import (
    OTHER, REGIONS, _OPCODE, _SHAPE, region_of, tokens,
)

KERNEL, MATMUL = "kernel", "matmul"
COLLECTIVE, COLLECTIVE_FUSED = "collective", "collective_fused"
ELEMENTWISE, DATA_MOVEMENT, REST = "elementwise", "data_movement", "rest"
CLASSES = (KERNEL, MATMUL, COLLECTIVE, COLLECTIVE_FUSED, ELEMENTWISE,
           DATA_MOVEMENT, REST)
GLUE = (ELEMENTWISE, DATA_MOVEMENT, REST)   # neither kernel, matmul nor
#                                             collective

_BY_CATEGORY = {
    **dict.fromkeys(("loop fusion", "custom fusion", "input fusion",
                     "non-fusion elementwise", "reduce", "reduce-window"),
                    ELEMENTWISE),
    **dict.fromkeys((
        "data formatting", "broadcast", "copy", "copy-start", "copy-done",
        "async-start", "async-done", "slice", "dynamic-slice",
        "dynamic-update-slice", "concatenate", "pad", "gather", "scatter",
        "transpose", "reshape", "iota"), DATA_MOVEMENT),
    **dict.fromkeys(("while", "conditional", "call", "custom-call", "sort",
                     "rng", "constant", "tuple", "get-tuple-element",
                     "parameter", "bitcast"), REST),
}
_ASYNC_FUSION = re.compile(r"^%?async-collective-(start|done)")
_IDENTIFIER = re.compile(r"^[A-Za-z_]\w*$")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
             "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
             "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_EVENT_MD_NAME, _EVENT_MD_STATS = 2, 5          # XEventMetadata
_PLANE_EVENT_MD, _PLANE_STAT_MD = 4, 5          # XPlane
_WANTED = ("tf_op", "hlo_category", "flops")


def event_stats(plane_buf: bytes) -> Dict[str, Dict[str, Any]]:
    """Event name -> {`tf_op`, `hlo_category`, `flops`} (those it has),
    from the plane's event metadata: names.name_stacks() with the two
    other stats an operation's NAME carries."""
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
    out: Dict[str, Dict[str, Any]] = {}
    for md in entries:
        name, stats = "", {}
        for fn, wt, v in proto.fields(md):
            if wt != proto.WIRE_LEN:
                continue
            if fn == _EVENT_MD_NAME:
                name = proto.to_text(v)
            elif fn == _EVENT_MD_STATS:
                key, value = xplane._decode_stat(v, stat_names)
                if key in _WANTED and value is not None:
                    stats[key] = value
        if stats:
            out[name] = stats
    return out


def class_of(event_name: str, category: Optional[str]) -> Tuple[str, bool]:
    """(class, known) of an operation from its event's name (on a TPU the
    whole HLO text) and its `hlo_category`; `known` is False for a
    category this table has no entry for (its time is under `rest`)."""
    category = category or ""
    if reduce.KERNEL_TARGET in event_name:
        return KERNEL, True
    # a category that starts like a collective's name is one's, or, with
    # " fusion" behind it, that of a fusion that holds one
    fusion = category.endswith(" fusion")
    by_category = reduce.is_collective(category)
    if reduce.is_collective(event_name) or (by_category and not fusion):
        return COLLECTIVE, True
    if (by_category and fusion) or _ASYNC_FUSION.match(event_name):
        return COLLECTIVE_FUSED, True
    if category.startswith("convolution") or category == "dot":
        return MATMUL, True
    known = _BY_CATEGORY.get(category)
    return (known, True) if known else (REST, False)


def collective_kind(event_name: str, category: Optional[str]) -> str:
    """What a collective operation is, as `extras.collectives` names it:
    "all-gather" (with its `-start` and `-done`), "all-reduce-scatter"
    (a fusion's category without the word), "async-collective"."""
    category = category or ""
    if _ASYNC_FUSION.match(event_name):
        return "async-collective"
    if category.endswith(" fusion"):
        return category[:-len(" fusion")]
    plain = (reduce._COLLECTIVE.match(event_name.lstrip("%"))
             or reduce._COLLECTIVE.match(category))
    return plain.group(1) if plain else category


def result_bytes(event_name: str) -> int:
    """Bytes of the largest array among an operation's results, from its
    event's name; 0 where the name is no HLO text."""
    _lhs, sep, rhs = event_name.partition(" = ")
    if not sep:
        return 0
    opcode = _OPCODE.search(rhs)
    best = 0
    for dtype, dims in _SHAPE.findall(rhs[:opcode.start() + 1] if opcode
                                      else rhs):
        n = _ITEMSIZE.get(dtype, 0)
        for d in dims.split(","):
            n *= int(d or 1)
        best = max(best, n)
    return best


def _add(table: Dict[Any, int], key: Any, ps: int) -> None:
    table[key] = table.get(key, 0) + ps


def reduce_device(plane: xplane.Plane, stats: Dict[str, Dict[str, Any]]
                  ) -> Dict[str, Any]:
    """One device: picoseconds inside its whole runs by (region, class),
    by (scope, class), by scope under no region, by class without a name
    stack, by (class, category), by unknown category; the compiler's
    FLOP by (region, class); and the collectives by (region, kind,
    fused)."""
    whole = reduce.whole_runs(reduce._line(plane, reduce.MODULE_LINE))
    runs = reduce.merge((m.start_ps, m.end_ps) for m in whole)
    starts = [s for s, _ in runs]
    table: Dict[Tuple[str, str], int] = {
        (r, c): 0 for r in REGIONS + (OTHER,) for c in CLASSES}
    scopes: Dict[Tuple[str, str], int] = {}
    outside: Dict[str, int] = {}
    unnamed = {c: 0 for c in CLASSES}
    categories: Dict[Tuple[str, str], int] = {}
    unknown: Dict[str, int] = {}
    flops: Dict[Tuple[str, str], float] = {}
    collectives: Dict[Tuple[str, str, bool], Dict[str, float]] = {}
    named_ = False
    for ev, segs in reduce.self_segments(reduce._line(plane,
                                                      reduce.OP_LINE)):
        own = named._inside(segs, runs, starts)
        if not own:
            continue
        md = stats.get(ev.name, {})
        tf_op = md.get("tf_op") or ""
        category = md.get("hlo_category")
        parts = tokens(tf_op)
        region = region_of(parts)
        named_ = named_ or region != OTHER
        cls, known = class_of(ev.name, category)
        table[(region, cls)] += own
        _add(categories, (cls, category or ""), own)
        if not known:
            _add(unknown, category or "", own)
        if not tf_op:
            unnamed[cls] += own
        for part in {p for p in parts[:-1] if _IDENTIFIER.match(p)}:
            _add(scopes, (part, cls), own)
            if region == OTHER:
                _add(outside, part, own)
        if "flops" in md:
            key = (region, cls)
            flops[key] = flops.get(key, 0.0) + float(md["flops"])
        if cls in (COLLECTIVE, COLLECTIVE_FUSED):
            c = collectives.setdefault(
                (region, collective_kind(ev.name, category),
                 cls == COLLECTIVE_FUSED),
                {"ps": 0, "calls": 0, "bytes": 0})
            c["ps"] += own
            if "-done" not in ev.name.partition(" = ")[0]:
                c["calls"] += 1           # a pair is one call
                c["bytes"] += result_bytes(ev.name)
    return {"runs": len(whole), "named": named_, "table": table,
            "scopes": scopes, "outside": outside, "unnamed": unnamed,
            "categories": categories, "unknown": unknown, "flops": flops,
            "collectives": collectives}


@functools.lru_cache(maxsize=4)
def _read(path: str, stamp: float) -> Dict[str, Any]:
    t0 = time.monotonic()
    want = lambda n: n in (reduce.OP_LINE, reduce.MODULE_LINE)  # noqa: E731
    devices = {name: reduce_device(xplane.decode_plane(buf, want),
                                   event_stats(buf))
               for name, buf in xplane.capture_planes(path)
               if reduce._DEVICE_PLANE.match(name)}
    got = {"devices": devices, "decode_s": time.monotonic() - t0}
    got["per_run"] = _per_run(got)
    return got


def read(path: str) -> Dict[str, Any]:
    """The trace under `path`, decoded once per file state."""
    files = xplane.find_xplane_files(path)
    stamp = max((os.path.getmtime(f) for f in files), default=0.0)
    return _read(os.path.abspath(path), stamp)


def per_run(path: str) -> Optional[Dict[str, Any]]:
    """_per_run() of the trace under `path`: one decode and one reduction
    per file state, whichever reader asks first."""
    return read(path)["per_run"]


def _per_run(got: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Seconds a whole run of the step program, mean over the devices
    that hold one (as named.per_run): `regions` {region: {class: s}},
    `scopes` {scope: {class: s}}, `outside` {scope: s under no region},
    `unnamed` {class: s}, `categories` {class: {category: s}},
    `unclassified` {category: s}, `flops` {region: {class: FLOP a run by
    the compiler's count}}, `collectives` [{region, kind, fused, s,
    calls, bytes_per_call}], and `decode_s`. None where no device holds a
    whole run or the program carries none of the region names."""
    devices = [d for d in got["devices"].values() if d["runs"]]
    if not devices or not any(d["named"] for d in devices):
        return None
    n = len(devices)

    def mean(field: str, scale: float = reduce.PS) -> Dict[Any, float]:
        keys = {k for d in devices for k in d[field]}
        return {k: sum(d[field].get(k, 0) / d["runs"] for d in devices)
                / n * scale for k in keys}

    def nested(flat: Dict[Tuple[str, str], float]
               ) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        for (a, b), v in flat.items():
            out.setdefault(a, {})[b] = v
        return out

    collectives = []
    for key in sorted({k for d in devices for k in d["collectives"]},
                      key=lambda k: (k[0], k[1], k[2])):
        each = [d["collectives"].get(key, {"ps": 0, "calls": 0, "bytes": 0})
                for d in devices]
        calls = sum(c["calls"] / d["runs"] for c, d in zip(each, devices)) / n
        total = sum(c["bytes"] / d["runs"] for c, d in zip(each, devices)) / n
        collectives.append({
            "region": key[0], "kind": key[1], "fused": key[2],
            "s": sum(c["ps"] / d["runs"]
                     for c, d in zip(each, devices)) / n * reduce.PS,
            "calls": calls,
            "bytes_per_call": total / calls if calls else 0.0})
    return {"devices": n, "runs": min(d["runs"] for d in devices),
            "regions": nested(mean("table")),
            "scopes": nested(mean("scopes")),
            "outside": mean("outside"), "unnamed": mean("unnamed"),
            "categories": nested(mean("categories")),
            "unclassified": mean("unknown"),
            "flops": nested(mean("flops", 1.0)),
            "collectives": collectives, "decode_s": got["decode_s"]}


def _extras(got: Dict[str, Any], peaks: Optional[dict]) -> Dict[str, Any]:
    """The tables as the result line carries them: milliseconds a step,
    zero cells left out."""
    ms = lambda row: {k: 1e3 * v for k, v in sorted(  # noqa: E731
        row.items()) if v}
    rows = lambda t: {k: ms(row) for k, row in sorted(  # noqa: E731
        t.items()) if any(row.values())}
    step = sum(sum(row.values()) for row in got["regions"].values())
    link = (peaks or {}).get("interconnect_bits_per_s")
    return {
        "step_classes": {
            "classes": list(CLASSES), "unit": "ms a step",
            "devices": got["devices"], "runs": got["runs"],
            "step_ms": 1e3 * step, "regions": rows(got["regions"]),
            "scopes": rows(got["scopes"]),
            "outside_regions": ms(got["outside"]),
            "unnamed": ms(got["unnamed"]),
            "categories": rows(got["categories"]),
            "unclassified": ms(got["unclassified"]),
            "decode_s": got["decode_s"]},
        "collectives": {
            "what": "own time of collective operations inside the whole "
                    "runs, mean of devices; gb_per_s is result bytes a "
                    "call x calls over that time, not wire bytes",
            "interconnect_gb_per_s": link / 8e9 if link else None,
            "by_region_kind": [
                {"region": c["region"], "kind": c["kind"],
                 "fused": c["fused"], "ms_per_step": 1e3 * c["s"],
                 "calls_per_step": c["calls"],
                 "result_bytes_per_call": c["bytes_per_call"],
                 "gb_per_s": (c["bytes_per_call"] * c["calls"] / c["s"]
                              / 1e9 if c["s"] else None)}
                for c in got["collectives"]]}}


def of_run(run) -> Optional[Dict[str, Any]]:
    """per_run() of a run's own trace, and the tables on the run's
    extras; None for a run that was not traced, before the disk is
    touched."""
    if run.trace is None:
        return None
    got = per_run(named.run_files(run)[0])
    if got is not None and "step_classes" not in run.extras:
        run.extras.update(_extras(got, run.peaks))
    return got


def region_class_ms(run, region: str, *classes: str) -> Optional[float]:
    """Milliseconds a step of the operations of those classes in a
    region."""
    got = of_run(run)
    return None if got is None else 1e3 * sum(
        got["regions"][region].get(c, 0.0) for c in classes)


def class_ms(run, *classes: str) -> Optional[float]:
    """Milliseconds a step of the operations of those classes, in every
    region and in none."""
    got = of_run(run)
    return None if got is None else 1e3 * sum(
        row.get(c, 0.0) for row in got["regions"].values() for c in classes)


def outside_ms(run, scope: str) -> Optional[float]:
    """Milliseconds a step of the operations whose name stack holds
    `scope` and no region: what a loop's scope holds beside the layers it
    runs. None where no operation at all carries the scope (a program
    from before it was named)."""
    got = of_run(run)
    if got is None or scope not in got["scopes"]:
        return None
    return 1e3 * got["outside"].get(scope, 0.0)


def unnamed_ms(run) -> Optional[float]:
    """Milliseconds a step of the operations with no name stack at all."""
    got = of_run(run)
    return None if got is None else 1e3 * sum(got["unnamed"].values())


def parallel_of(cell) -> Tuple[int, int]:
    """(tensor-parallel, data-parallel) sizes of a training cell: TP from
    the mix's own flag, DP the chips that are left (the training cells
    shard no other way)."""
    flags = list(cell.traffic.get("flags", ()))
    tp = (int(flags[flags.index("--tensor_model_parallel_size") + 1])
          if "--tensor_model_parallel_size" in flags else 1)
    return tp, max(cell.chips // tp, 1)


def matmul_roofline_pct(run, label: str, region: str) -> Optional[float]:
    """The share of the MXU's peak that the matmuls of a region reach:
    the least time the chip could take for what they NEED to do on one
    device (`kernel_costs/<label>.py`: `needed((tokens a replica a step,
    TP), itemsize, config)`, forward and both backward products, nothing
    computed again) over the time the trace shows for the region's class
    `matmul`. The record, with the compiler's own count of the FLOP those
    operations run (the trace's `flops` stat, recomputation included,
    where the trace carries it) beside the needed, goes to the line's
    `extras.roofline[label]`. None in a rehearsal (no peaks), on an
    untraced run, or where the cost file is missing or the region ran no
    matmul."""
    got = of_run(run) if run.peaks is not None else None
    needed = run.cell.kernel_cost(label)
    if got is None or needed is None:
        return None
    seconds = got["regions"][region].get(MATMUL, 0.0)
    mix = run.cell.traffic
    tp, dp = parallel_of(run.cell)
    work = needed((mix["global_batch_size"] * mix["seq_length"] // dp, tp),
                  2, run.cell.config)
    if not seconds or work is None:
        return None
    compute_s = work[0] / run.peaks["bf16_flops_per_s"]
    memory_s = work[1] / run.peaks["hbm_bytes_per_s"]
    roof = {"pct": 100.0 * max(compute_s, memory_s) / seconds,
            "bound": "compute" if compute_s >= memory_s else "memory",
            "needed_flop": work[0], "needed_bytes": work[1],
            "needed_ms": 1e3 * max(compute_s, memory_s),
            "measured_ms": 1e3 * seconds,
            "compiler_flop": got["flops"].get(region, {}).get(MATMUL)}
    run.extras.setdefault("roofline", {})[label] = roof
    return roof["pct"]
