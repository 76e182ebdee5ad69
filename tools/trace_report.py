#!/usr/bin/env python
"""trace_report: read jax.profiler xplane traces, print the comm/compute
story (docs/observability.md "Runtime traces").

    python tools/trace_report.py runs/profile              # trace logdir
    python tools/trace_report.py host0.xplane.pb           # one file
    python tools/trace_report.py DIR --module jit_train_step --top 20
    python tools/trace_report.py DIR --contract ulysses_cp2
    python tools/trace_report.py DIR --format json

Works on any ``--profile`` window, serving ``/admin/profile`` capture,
or SIGUSR1 window — CPU and
TPU alike (XLA:CPU xplanes carry real op events, so the whole pipeline
is testable without a chip).

Prints the per-op table, the compute / collective / infeed busy split
with per-collective total vs. EXPOSED time (not overlapped by compute —
the Flash Communication number), per-step wall from the step markers,
own time by the program's named scopes, split by class of work (matmul,
kernel, collective plain and fused, elementwise, data movement, rest: from
the profiler's ``hlo_category``, the table the benchmark reads) with a row
for what carries no name at all and the kernels by name, the
device's idle gaps by the host span under each (both where the trace
carries the names: a TPU trace of a program with the scopes and the
loop's annotations), and with ``--contract NAME`` the measured-vs-expected
collective counts against the golden comm manifest
(``megatron_tpu/analysis/golden/NAME.json``) plus effective bus
bandwidth from the manifest's byte volumes.

Like tools/jaxlint.py, modules load by file path: reading a trace never
imports jax (or megatron_tpu), so this runs on a laptop holding nothing
but the ``.pb`` files scp'd off a pod.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import types
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
_PKG = _REPO / "megatron_tpu"

#: load order respects intra-package imports (taxonomy first).
#: quant.policy is stdlib-only like taxonomy: deriving a comm policy
#: from a trace must not need jax either.
_MODULES = (
    ("megatron_tpu.analysis.taxonomy", _PKG / "analysis" / "taxonomy.py"),
    ("megatron_tpu.quant.policy", _PKG / "quant" / "policy.py"),
    ("megatron_tpu.telemetry.tracing.proto",
     _PKG / "telemetry" / "tracing" / "proto.py"),
    ("megatron_tpu.telemetry.tracing.xplane",
     _PKG / "telemetry" / "tracing" / "xplane.py"),
    ("megatron_tpu.telemetry.tracing.events",
     _PKG / "telemetry" / "tracing" / "events.py"),
    ("megatron_tpu.telemetry.tracing.analyze",
     _PKG / "telemetry" / "tracing" / "analyze.py"),
)

GOLDEN_DIR = _PKG / "analysis" / "golden"


def _load_tracing():
    """The tracing modules WITHOUT importing the megatron_tpu package
    (whose __init__ pulls jax). Parent package names are pre-registered
    as empty namespace modules so the absolute imports inside the
    tracing modules short-circuit on sys.modules. When the REAL package
    is already imported (in-process/test use), the normal import system
    is used instead."""
    real_pkg = getattr(sys.modules.get("megatron_tpu"), "__file__", None)
    if real_pkg:
        loaded = {name: importlib.import_module(name)
                  for name, _ in _MODULES}
    else:
        if "megatron_tpu" not in sys.modules:
            for pkg in ("megatron_tpu", "megatron_tpu.analysis",
                        "megatron_tpu.quant",
                        "megatron_tpu.telemetry",
                        "megatron_tpu.telemetry.tracing"):
                mod = types.ModuleType(pkg)
                mod.__path__ = []  # mark as package
                sys.modules[pkg] = mod
        loaded = {}
        for name, path in _MODULES:
            if name in sys.modules and hasattr(sys.modules[name],
                                               "__file__"):
                loaded[name] = sys.modules[name]
                continue
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[name] = mod
            parent, _, leaf = name.rpartition(".")
            setattr(sys.modules[parent], leaf, mod)
            spec.loader.exec_module(mod)
            loaded[name] = mod
    return (loaded["megatron_tpu.telemetry.tracing.xplane"],
            loaded["megatron_tpu.telemetry.tracing.events"],
            loaded["megatron_tpu.telemetry.tracing.analyze"],
            loaded["megatron_tpu.quant.policy"])


def _fmt_s(s: float) -> str:
    if s >= 1.0:
        return f"{s:.3f}s"
    if s >= 1e-3:
        return f"{s * 1e3:.3f}ms"
    return f"{s * 1e6:.1f}us"


def render_text(report, comparison, top: int, files) -> str:
    lines = [f"trace: {len(files)} xplane file(s), module "
             f"{report.module or '<none>'} "
             f"(others: "
             + (", ".join(m for m in sorted(report.all_modules)
                          if m != report.module) or "none") + ")"]
    lines.append(
        f"busy split: compute {_fmt_s(report.compute_s)} | "
        f"collective {_fmt_s(report.collective_s)} "
        f"(exposed {_fmt_s(report.exposed_collective_s)}) | "
        f"infeed {_fmt_s(report.busy_s.get('infeed', 0.0))} | "
        f"op wall {_fmt_s(report.wall_s)}")
    if report.collectives:
        lines.append("collectives (total vs exposed = not hidden under "
                     "compute):")
        for c in report.collectives:
            lines.append(
                f"  {c.op:<20} x{c.count:<6} total "
                f"{_fmt_s(c.total_ps / 1e12):>10}  exposed "
                f"{_fmt_s(c.exposed_ps / 1e12):>10} "
                f"({100 * c.exposed_frac:.1f}%)")
    if report.steps:
        lines.append("steps (jit dispatch spans, step annotations, a "
                     "device's Steps envelopes):")
        for name, st in sorted(report.steps.items(),
                               key=lambda kv: -kv[1]["total_ms"]):
            lines.append(f"  {name:<32} x{st['count']:<5} "
                         f"p50 {st['p50_ms']}ms  max {st['max_ms']}ms")
    if report.scopes:
        lines.append("own time by scope (the program's jax.named_scope "
                     "regions; kernels by name):")
        whole = sum(report.scopes.values()) or 1.0
        # the same time by class of work (the profiler's hlo_category:
        # events.op_class), the table the benchmark's readers take
        table = report.scope_classes
        classes = [c for c in next(iter(table.values()), {})
                   if any(row[c] for row in table.values())]
        lines.append(f"  {'':<20} {'':>10}  {'':>8}"
                     + "".join(f" {c[:12]:>12}" for c in classes))
        rows = list(report.scopes.items()) + [
            (name, sum(row.values())) for name, row in table.items()
            if name not in report.scopes]
        for name, sec in rows:
            row = table.get(name, {})
            lines.append(f"  {name:<20} {_fmt_s(sec):>10}  "
                         f"({100 * sec / whole:5.1f}%)" + "".join(
                             f" {_fmt_s(row.get(c, 0.0)):>12}"
                             for c in classes))
        for name, k in report.kernels.items():
            lines.append(f"    kernel {name:<15} {_fmt_s(k['self_s']):>10}"
                         f"  x{k['count']}")
    if report.idle_gaps:
        lines.append("idle gaps of the first device, by the innermost "
                     "loop-thread span as each opens:")
        for g in report.idle_gaps[:top]:
            lines.append(f"  {g['span']:<32} x{g['count']:<6} total "
                         f"{_fmt_s(g['total_s']):>10}  max "
                         f"{_fmt_s(g['max_s']):>10}")
    if report.loop_thread:
        lines.append("loop thread by own time (the host line that holds "
                     "the step annotations; the program's spans and the "
                     "runtime's events):")
        for g in report.loop_thread[:top]:
            lines.append(f"  {g['span']:<40} x{g['count']:<6} own "
                         f"{_fmt_s(g['self_s']):>10}  total "
                         f"{_fmt_s(g['total_s']):>10}")
    lines.append(f"top {top} ops by self time:")
    for o in report.ops[:top]:
        lines.append(f"  {o.self_s * 1e3:10.3f}ms  x{o.count:<6} "
                     f"[{o.kind[:4]}] {o.name}"
                     + (f" {o.detail}" if o.detail else ""))
    if comparison is not None:
        lines.append(
            f"contract {comparison.config} ({comparison.level} level, "
            f"{comparison.executions or '?'} executions): "
            + ("measured == expected"
               if comparison.matches else "MISMATCH"))
        for row in comparison.rows:
            lines.append(
                f"  {row['op']:<20} expected {row['expected_per_exec']}"
                f"/exec -> {row['expected_total']}  measured "
                f"{row['measured_total']}  "
                f"{'ok' if row['ok'] else 'MISMATCH'}")
        for p in comparison.problems:
            lines.append(f"  ! {p}")
        for op, bw in comparison.bandwidth.items():
            lines.append(
                f"  {op:<20} {bw['bytes_total']} bytes -> bus "
                f"{bw['bus_gbps']} GB/s (exposed-only "
                f"{bw['exposed_gbps']} GB/s)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="trace logdir, session dir, or one "
                                  "*.xplane.pb file")
    ap.add_argument("--module", default=None,
                    help="hlo module to report (default: most op time)")
    ap.add_argument("--top", type=int, default=15,
                    help="rows in the op table")
    ap.add_argument("--contract", default=None,
                    help="golden comm contract to compare measured "
                         "collective counts against (e.g. ulysses_cp2)")
    ap.add_argument("--executions", type=int, default=None,
                    help="devices x profiled steps for the contract "
                         "check (default: inferred from the counts)")
    ap.add_argument("--all-sessions", action="store_true",
                    help="read every capture session under the logdir, "
                         "not just the newest")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--check", action="store_true",
                    help="with --contract: exit 1 on measured!=expected")
    ap.add_argument("--emit-comm-policy", metavar="OUT.json", default=None,
                    help="derive the compressed-collective site policy "
                         "from this trace's measured per-collective "
                         "EXPOSED fractions (quant/policy.py) and write "
                         "it as JSON — serve it back with "
                         "--serve_comm_policy OUT.json")
    ap.add_argument("--exposed-threshold", type=float, default=0.25,
                    help="exposed fraction at/above which a collective "
                         "kind's sites compress (default 0.25: a "
                         "collective 75%%-hidden under compute is not "
                         "worth the quantization error)")
    args = ap.parse_args(argv)

    xplane, events_mod, analyze, policy_mod = _load_tracing()
    files = xplane.find_xplane_files(
        args.trace, latest_session_only=not args.all_sessions)
    if not files:
        print(f"no *.xplane.pb under {args.trace}", file=sys.stderr)
        return 1
    events = []
    for f in files:
        events.extend(events_mod.classify_xspace(xplane.load_xspace(f)))
    report = analyze.analyze_events(events, module=args.module)

    comparison = None
    if args.contract:
        path = GOLDEN_DIR / f"{args.contract}.json"
        if not path.exists():
            print(f"no golden manifest {path}", file=sys.stderr)
            return 1
        comparison = analyze.compare_contract(
            report, json.loads(path.read_text()), args.contract,
            executions=args.executions)

    if args.emit_comm_policy:
        exposure = {c.op: round(c.exposed_frac, 4)
                    for c in report.collectives}
        policy = policy_mod.policy_from_exposure(
            exposure, threshold=args.exposed_threshold,
            source=f"trace:{args.trace}")
        # per-site exposed fractions: each policy site keyed by ITS
        # collective kind — collective-permute (cp_ring) and all-to-all
        # (cp_a2a) report separately, so a 2D-geometry trace shows which
        # leg is actually exposed
        site_exposure = {
            site: exposure.get(kind, 0.0)
            for site, kind in policy_mod.SITE_COLLECTIVES.items()}
        doc = dict(policy.to_dict(), exposure=exposure,
                   site_exposure=site_exposure)
        with open(args.emit_comm_policy, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"# comm policy -> {args.emit_comm_policy}: "
              + ", ".join(f"{s}={'on' if v else 'off'}"
                          for s, v in sorted(doc["sites"].items())),
              file=sys.stderr)

    if args.format == "json":
        out = {"files": files, "report": report.to_dict(top=args.top)}
        if comparison is not None:
            out["contract"] = comparison.to_dict()
        print(json.dumps(out, indent=1))
    else:
        print(render_text(report, comparison, args.top, files))
    if args.check and comparison is not None and not comparison.matches:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
