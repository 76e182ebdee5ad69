"""Golden comm contracts: pinned collective counts/bytes per parallel
config, asserted in tier-1.

A contract freezes two views of one audit target
(``analysis/targets.py``):

  * ``jaxpr`` — explicit collectives from the traced program (counts
    multiplied through scan trip counts) plus the tracing-discipline
    facts (host callbacks, rank-0 shard_map scan carries, manual-axis
    sharding constraints). Cheap: no XLA compile.
  * ``hlo`` — collective ops in the compiled SPMD module, which
    includes everything GSPMD *inserted* (the TP all-reduces, ZeRO-1
    reduce-scatter/all-gather...). Costs a compile; targets whose
    shard_map output CHECK-crashes the baked XLA set
    ``can_compile=False`` and pin the jaxpr view only.

A PR that sneaks in a hidden collective — an extra all_gather from a
lost sharding constraint, a psum from a new reduction — changes these
numbers and fails tests/test_analysis.py loudly. This is the
measurement seam ROADMAP item 2 (Flash-Communication-style comm/compute
optimization) builds on: the manifests are the "before" ledger any
compressed-collective change must diff against.

Regenerate after an INTENTIONAL comm change with::

    python tools/comm_report.py --regen [config ...]

and commit the JSON diff — the review then sees exactly which
collectives the change added/removed (docs/static_analysis.md).

Manifests live in ``megatron_tpu/analysis/golden/*.json``; they are
toolchain-pinned (jax/jaxlib recorded inside) like every other golden
in this repo.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

GOLDEN_DIR = Path(__file__).resolve().parent / "golden"


def _targets():
    from megatron_tpu.analysis import targets as T

    return T


#: config name -> zero-arg builder returning an AuditTarget. Geometry is
#: pinned inside targets.py (tiny_model) so the numbers are stable.
CONFIGS: Dict[str, Callable[[], Any]] = {
    # training step, GSPMD tensor parallel + sequence parallel: the
    # all-gather/reduce-scatter ledger Korthikanti SP implies
    "train_tp2_sp": lambda: _targets().train_step_target(
        "train_tp2_sp", dict(tensor_parallel=2, sequence_parallel=True)),
    # training step, 2-stage pipeline: the shard_map ppermute ring
    # (fwd + cooldown via autodiff) is explicit in the jaxpr
    "train_pp2": lambda: _targets().train_step_target(
        "train_pp2", dict(pipeline_parallel=2)),
    # training step, pure DP (derived dp=8 on the fake mesh) with the
    # ZeRO-1 distributed optimizer: GSPMD's derived
    # reduce-scatter / all-gather pattern
    "train_dp8_zero1": lambda: _targets().train_step_target(
        "train_dp8_zero1", dict(), zero1=True, global_batch=8),
    # ring attention fwd+bwd at cp=2 (zig-zag, einsum inner)
    "ring_cp2": lambda: _targets().ring_attention_target("ring_cp2"),
    # ulysses all-to-all attention fwd+bwd at cp=2
    "ulysses_cp2": lambda: _targets().ulysses_attention_target(
        "ulysses_cp2"),
    # dropless expert-parallel MoE dispatch at ep=2 (CPU transport);
    # jaxpr-only — compiling trips the old-XLA sharding remover
    "moe_ep2": lambda: _targets().moe_block_target("moe_ep2"),
    # engine decode step (page-table KV gather): the contract IS "no
    # collectives, no callbacks", and full donation — a hidden
    # all_gather in serving fails here
    "decode_paged": lambda: _targets().paged_decode_step_target(
        "decode_paged"),
    # speculative decode step (model drafter): draft-proposal scan +
    # multi-token verify + in-step accept/reject through the page-table
    # indirection (one table addresses both pools). Zero collectives,
    # zero callbacks, BOTH cache trees (target + draft) donated
    "decode_spec_paged": lambda: _targets().spec_paged_decode_step_target(
        "decode_spec_paged"),
    # serving decode step on a tp=2 mesh with EXPLICIT collectives
    # (quant/collectives.py): the dense baseline ledger — per-layer
    # attn_out/mlp_out psum + the vocab-parallel logits all_gather —
    # that the compressed configs diff against (>= 3x wire-byte
    # reduction, asserted by tools/comm_report.py --check)
    "decode_tp2_dense": lambda: _targets().tp_decode_step_target(
        "decode_tp2_dense", mode="dense"),
    # the same step with int8 compressed collectives: all_to_all +
    # all_gather moving int8 payloads with fp32 scales riding alongside
    "decode_tp2_int8": lambda: _targets().tp_decode_step_target(
        "decode_tp2_int8", mode="int8"),
    # fp8(e4m3) transport variant of the same step
    "decode_tp2_fp8": lambda: _targets().tp_decode_step_target(
        "decode_tp2_fp8", mode="fp8"),
    # context-parallel serving decode on a tp=2 x cp=2 mesh: the TP
    # psum/all_gather ledger PLUS the per-layer ring — (cp-1) ppermute
    # hops moving normalized (out, lse) attention partials between the
    # sequence-striped KV pool shards. jaxpr-only (full-manual
    # shard_map; see moe_ep2)
    "decode_tp2_cp2": lambda: _targets().cp_paged_decode_step_target(
        "decode_tp2_cp2"),
    # the overlapped-ring decode schedule at tp=1 x cp=2: hop l+1's
    # ppermute issues before hop l's merge (double-buffered carry).
    # The ledger keys on op counts, not order — this manifest proves
    # the overlap moves EXACTLY the serial ring's hops and bytes (the
    # perf win is exposed-time only; tools/trace_report.py measures it)
    "decode_cp2_overlap": lambda: _targets().cp_paged_decode_step_target(
        "decode_cp2_overlap", tp=1, cp=2, overlap=True),
    # 2D CP geometry at cp=4 = cp_seq 2 x cp_head 2 (tp=1): per layer a
    # head-scatter all_to_all + head-gather all_gather inside each
    # subgroup, and cp_seq-1 ppermute hops ACROSS subgroups at
    # 1/subgroup payload — the topology-aware ledger (ATTENTION2D/TASP)
    "decode_cp4_2d": lambda: _targets().cp_paged_decode_step_target(
        "decode_cp4_2d", tp=1, cp=4, geometry="2d", subgroup=2),
    # context-parallel chunked prefill at cp=2: one [1, C] prompt chunk
    # scatter-written into the striped pools + ring-attended — the
    # distributed long-prompt prefill ledger
    "prefill_cp2": lambda: _targets().cp_chunk_step_target("prefill_cp2"),
}

#: the compressed-vs-dense pairs --check verifies the wire-byte
#: reduction over (compressed config, dense baseline, minimum ratio)
COMPRESSION_GATES = (
    ("decode_tp2_int8", "decode_tp2_dense", 3.0),
    ("decode_tp2_fp8", "decode_tp2_dense", 3.0),
)


def manifest_path(name: str) -> Path:
    return GOLDEN_DIR / f"{name}.json"


def build_manifest(name: str, include_hlo: bool = True,
                   target: Optional[Any] = None) -> Dict[str, Any]:
    """Trace (and optionally compile) one config; returns the manifest
    dict that ``check_contract`` compares against golden.

    target: audit this AuditTarget instead of the registered builder —
    how tests prove an injected collective trips the contract."""
    from megatron_tpu.analysis import jaxpr_audit

    if target is None:
        if name not in CONFIGS:
            raise KeyError(f"unknown contract config {name!r} "
                           f"(known: {', '.join(sorted(CONFIGS))})")
        target = CONFIGS[name]()
    report = jaxpr_audit.audit_jaxpr(target.jaxpr(), name)
    import jax

    manifest: Dict[str, Any] = {
        "config": name,
        "toolchain": {"jax": jax.__version__},
        "jaxpr": {
            "collectives": report.collective_summary(),
            "total_collective_bytes": report.total_collective_bytes(),
            "total_wire_bytes": report.total_wire_bytes(),
            "host_callbacks": len(report.callbacks),
            "scalar_carries_in_shard_map": len(report.scalar_carries),
            "manual_axis_constraints": len(report.manual_constraints),
        },
    }
    if include_hlo and target.can_compile:
        manifest["hlo"] = {
            "collectives": jaxpr_audit.hlo_collectives(
                target.compiled_text()),
        }
    return manifest


def load_manifest(name: str) -> Dict[str, Any]:
    path = manifest_path(name)
    if not path.exists():
        raise FileNotFoundError(
            f"no golden manifest for {name!r} — generate it with "
            f"'python tools/comm_report.py --regen {name}'")
    return json.loads(path.read_text())


def write_manifest(name: str, include_hlo: bool = True) -> Path:
    GOLDEN_DIR.mkdir(exist_ok=True)
    manifest = build_manifest(name, include_hlo=include_hlo)
    path = manifest_path(name)
    path.write_text(json.dumps(manifest, indent=2, sort_keys=True) + "\n")
    return path


def diff_section(golden: Dict[str, Any], fresh: Dict[str, Any],
                 label: str) -> List[str]:
    """Human-readable mismatches between two collective dicts."""
    out: List[str] = []
    for key in sorted(set(golden) | set(fresh)):
        g, f = golden.get(key), fresh.get(key)
        if g == f:
            continue
        if g is None:
            out.append(f"{label}: NEW collective {key}: {f}")
        elif f is None:
            out.append(f"{label}: collective DISAPPEARED {key}: was {g}")
        else:
            out.append(f"{label}: {key}: golden {g} != current {f}")
    return out


def check_contract(name: str, level: str = "jaxpr",
                   fresh: Optional[Dict[str, Any]] = None) -> List[str]:
    """Compare a freshly-built manifest against golden. Returns [] when
    the contract holds, else one message per mismatch.

    level: "jaxpr" (no compile), "hlo" (compile; skipped when the
    golden has no hlo section), or "all".
    """
    golden = load_manifest(name)
    if fresh is None:
        fresh = build_manifest(
            name, include_hlo=level in ("hlo", "all") and "hlo" in golden)
    problems: List[str] = []
    if level in ("jaxpr", "all"):
        g, f = golden["jaxpr"], fresh["jaxpr"]
        problems += diff_section(g["collectives"], f["collectives"],
                                 f"{name}/jaxpr")
        for scalar in ("host_callbacks", "scalar_carries_in_shard_map",
                       "manual_axis_constraints"):
            if g.get(scalar, 0) != f.get(scalar, 0):
                problems.append(
                    f"{name}/jaxpr: {scalar} golden {g.get(scalar)} != "
                    f"current {f.get(scalar)}")
    if level in ("hlo", "all") and "hlo" in golden:
        if "hlo" not in fresh:
            problems.append(f"{name}/hlo: fresh manifest missing hlo "
                            "section (compile failed or skipped)")
        else:
            problems += diff_section(golden["hlo"]["collectives"],
                                     fresh["hlo"]["collectives"],
                                     f"{name}/hlo")
    return problems


def compression_ratio(compressed: Dict[str, Any],
                      dense: Dict[str, Any]) -> float:
    """dense / compressed wire-byte ratio between two manifests — the
    contract-verified byte reduction (>= the COMPRESSION_GATES floor
    for the shipped configs). Falls back to payload bytes for pre-wire
    manifests."""
    def wire(m):
        j = m.get("jaxpr", {})
        return j.get("total_wire_bytes", j.get("total_collective_bytes", 0))

    c = wire(compressed)
    if c <= 0:
        return 0.0
    return wire(dense) / c


def check_compression_gates(
        fresh: Optional[Dict[str, Dict[str, Any]]] = None) -> List[str]:
    """Verify every COMPRESSION_GATES pair holds (golden manifests, or
    freshly-built ones passed as {name: manifest}). A silent revert of
    the compressed path to dense transport (int8 bytes back to f32)
    collapses the ratio and fails here — the injected-regression test
    drives exactly that."""
    problems: List[str] = []
    for comp_name, dense_name, floor in COMPRESSION_GATES:
        try:
            comp = (fresh or {}).get(comp_name) or load_manifest(comp_name)
            dense = (fresh or {}).get(dense_name) or load_manifest(dense_name)
        except FileNotFoundError as e:
            problems.append(f"compression gate {comp_name}: {e}")
            continue
        ratio = compression_ratio(comp, dense)
        if ratio < floor:
            problems.append(
                f"compression gate: {comp_name} wire bytes are only "
                f"{ratio:.2f}x below {dense_name} (floor {floor}x) — "
                "the compressed path is moving dense-sized payloads")
    return problems
