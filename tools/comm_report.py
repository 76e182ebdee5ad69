#!/usr/bin/env python
"""comm_report: print (or regenerate) the golden comm-contract tables.

Reads the checked-in manifests in megatron_tpu/analysis/golden/ and
prints the per-config collective count/bytes ledger — the static
communication budget of every audited parallel config. This is the
operational face of the comm contracts (docs/static_analysis.md): run
it before/after a parallelism change to see what moved.

Usage:
    python tools/comm_report.py                    # table from golden
    python tools/comm_report.py --config train_pp2 # one config
    python tools/comm_report.py --check            # rebuild + diff (slow)
    python tools/comm_report.py --regen [name ...] # retrace + rewrite JSON
    python tools/comm_report.py --diff decode_tp2_dense decode_tp2_int8
                                # side-by-side per-collective deltas

--diff prints the per-collective count/byte deltas between two
manifests and the total wire-byte ratio — the dense-vs-compressed
reduction (quant/, docs/performance.md "Compressed collectives") as one
command. --check additionally verifies the pinned compression gates
(contracts.COMPRESSION_GATES: the compressed serving configs must stay
>= 3x below their dense baseline in wire bytes).

Printing golden / --diff needs no jax; --check/--regen trace (and
partly compile) the real programs on the fake CPU mesh.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_REPO = Path(__file__).resolve().parent.parent
GOLDEN_DIR = _REPO / "megatron_tpu" / "analysis" / "golden"


def _fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{n}B"
        n /= 1024
    return f"{n}B"


def _print_manifest(name: str, manifest: dict) -> None:
    j = manifest.get("jaxpr", {})
    colls = j.get("collectives", {})
    hlo = manifest.get("hlo", {}).get("collectives", {})
    print(f"\n== {name} "
          f"(jax {manifest.get('toolchain', {}).get('jax', '?')}) ==")
    print(f"  host_callbacks={j.get('host_callbacks', '?')} "
          f"scalar_carries_in_shard_map="
          f"{j.get('scalar_carries_in_shard_map', '?')} "
          f"manual_axis_constraints={j.get('manual_axis_constraints', '?')}")
    if colls:
        w = max(len(k) for k in colls)
        print(f"  {'jaxpr collective':<{w}}  {'count':>6} "
              f"{'bytes/call':>10} {'total':>10} {'wire':>10}")
        for key, v in colls.items():
            q = " [q]" if v.get("compressed") else ""
            print(f"  {key:<{w}}  {v['count']:>6} "
                  f"{_fmt_bytes(v['bytes_per_call']):>10} "
                  f"{_fmt_bytes(v['total_bytes']):>10} "
                  f"{_fmt_bytes(v.get('total_wire_bytes', 0)):>10}{q}")
        print(f"  {'TOTAL':<{w}}  {'':>6} {'':>10} "
              f"{_fmt_bytes(j.get('total_collective_bytes', 0)):>10} "
              f"{_fmt_bytes(j.get('total_wire_bytes', 0)):>10}")
    else:
        print("  jaxpr collectives: none (contract: stays that way)")
    if hlo:
        print("  hlo (post-GSPMD, static op counts):")
        for op, v in hlo.items():
            print(f"    {op:<20} count={v['count']:>4} "
                  f"bytes={_fmt_bytes(v['total_bytes'])}")
    elif "hlo" in manifest:
        print("  hlo collectives: none")


def _load(name: str) -> dict:
    """A manifest by config name (golden dir) or explicit JSON path."""
    path = Path(name)
    if not path.exists():
        path = GOLDEN_DIR / f"{name}.json"
    if not path.exists():
        raise SystemExit(f"no manifest for {name!r} (looked at {path})")
    return json.loads(path.read_text())


def _diff_manifests(name_a: str, name_b: str) -> int:
    """Side-by-side per-collective count/byte deltas A -> B, plus the
    total wire-byte ratio (the dense-vs-compressed reduction)."""
    a, b = _load(name_a), _load(name_b)
    ca = a.get("jaxpr", {}).get("collectives", {})
    cb = b.get("jaxpr", {}).get("collectives", {})
    keys = sorted(set(ca) | set(cb))
    w = max([len(k) for k in keys] + [16])
    print(f"{'collective':<{w}}  {'count':>11}  {'wire total':>21}")
    print(f"{'':<{w}}  {name_a[:11]:>5}>{name_b[:11]:<5}")
    for k in keys:
        va, vb = ca.get(k), cb.get(k)
        na = va["count"] if va else 0
        nb = vb["count"] if vb else 0
        wa = va.get("total_wire_bytes", 0) if va else 0
        wb = vb.get("total_wire_bytes", 0) if vb else 0
        tag = (" [q]" if ((va or {}).get("compressed")
                          or (vb or {}).get("compressed")) else "")
        print(f"{k:<{w}}  {na:>5}>{nb:<5} "
              f"{_fmt_bytes(wa):>10}>{_fmt_bytes(wb):<10}{tag}")
    ja, jb = a.get("jaxpr", {}), b.get("jaxpr", {})
    ta = ja.get("total_wire_bytes", ja.get("total_collective_bytes", 0))
    tb = jb.get("total_wire_bytes", jb.get("total_collective_bytes", 0))
    print(f"{'TOTAL wire':<{w}}  {'':>11} "
          f"{_fmt_bytes(ta):>10}>{_fmt_bytes(tb):<10}")
    if tb > 0:
        print(f"wire-byte ratio {name_a} / {name_b}: {ta / tb:.2f}x")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", action="append", default=None,
                    help="limit to these config names (repeatable)")
    ap.add_argument("--check", action="store_true",
                    help="rebuild each manifest and diff against golden "
                         "(+ verify the compression gates)")
    ap.add_argument("--regen", nargs="*", metavar="NAME", default=None,
                    help="retrace and REWRITE golden manifests "
                    "(all when no names given)")
    ap.add_argument("--diff", nargs=2, metavar=("A", "B"), default=None,
                    help="print per-collective count/byte deltas between "
                         "two manifests (config names or JSON paths)")
    args = ap.parse_args(argv)

    exclusive = [n for n, v in (("--check", args.check),
                                ("--regen", args.regen is not None),
                                ("--diff", args.diff is not None)) if v]
    if len(exclusive) > 1:
        ap.error(" and ".join(exclusive) + " are mutually exclusive")
    if args.diff is not None:
        return _diff_manifests(*args.diff)
    if args.regen is not None or args.check:
        sys.path.insert(0, str(_REPO))
        from megatron_tpu.platform import force_cpu

        force_cpu(8)  # the contracts are traced on the 8-device CPU mesh
        from megatron_tpu.analysis import contracts

        names = args.regen or args.config or sorted(contracts.CONFIGS)
        if args.check:
            problems = []
            for name in names:
                problems += contracts.check_contract(name, level="all")
            gated = {c for c, d, _ in contracts.COMPRESSION_GATES
                     for c in (c, d)}
            if gated & set(names):
                # the >= 3x dense-vs-compressed wire-byte reduction is
                # part of the contract: a silent revert to dense
                # transport fails --check, not just the manifest diff
                problems += contracts.check_compression_gates()
            for p in problems:
                print(p)
            print("comm contracts:", "OK" if not problems else
                  f"{len(problems)} mismatch(es)")
            return 1 if problems else 0
        for name in names:
            path = contracts.write_manifest(name)
            print(f"wrote {path}")
        return 0

    names = args.config or sorted(
        p.stem for p in GOLDEN_DIR.glob("*.json"))
    if not names:
        print(f"no golden manifests in {GOLDEN_DIR} — generate with "
              "--regen", file=sys.stderr)
        return 1
    for name in names:
        path = GOLDEN_DIR / f"{name}.json"
        if not path.exists():
            print(f"{name}: no golden manifest at {path}", file=sys.stderr)
            return 1
        _print_manifest(name, json.loads(path.read_text()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
