"""Static-analysis subsystem: AST linter, jaxpr auditor, comm contracts.

Tier-1 gates added by this suite:
  * the linter is CLEAN over megatron_tpu/ (every violation fixed or
    allowlisted with a reason) and each rule provably fires on seeded
    violations;
  * the train step and engine decode step trace with ZERO host
    callbacks and full donation of their mutable state;
  * the golden comm contracts hold at jaxpr level for every config (an
    injected hidden collective fails the check, proven here too).
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from megatron_tpu.analysis import ast_lint, contracts, jaxpr_audit, targets

REPO = Path(__file__).resolve().parent.parent
PKG = REPO / "megatron_tpu"


# ---------------------------------------------------------------------------
# AST linter
# ---------------------------------------------------------------------------


def test_lint_repo_clean():
    """The acceptance gate: megatron_tpu/ lints clean at HEAD."""
    findings = ast_lint.lint_paths([str(PKG)])
    assert findings == [], "\n".join(map(str, findings))


_SEEDED = textwrap.dedent("""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial
    from jax.experimental.shard_map import shard_map as smap

    @partial(jax.jit, donate_argnums=(0,))
    def step(state, batch):
        loss = jnp.sum(state - batch)
        print("loss", loss)
        host = np.asarray(state)
        if jnp.sum(loss) > 0:
            loss = loss * 2.0
        return loss + float(batch)

    def exchange(x):
        return jax.lax.ragged_all_to_all(x, x, x, x, x, x, axis_name="ep")

    def risky():
        try:
            return jax.device_count()
        except Exception:
            return 0
""")


def test_lint_rules_fire(tmp_path):
    f = tmp_path / "seeded.py"
    f.write_text(_SEEDED)
    findings = ast_lint.lint_paths([str(f)])
    rules = {x.rule for x in findings}
    assert {"host-sync", "banned-api", "broad-except",
            "traced-branch"} <= rules, findings
    msgs = "\n".join(map(str, findings))
    assert "print()" in msgs
    assert "np.asarray" in msgs
    assert "float(batch)" in msgs
    assert "ragged_all_to_all" in msgs
    assert "jax.experimental.shard_map" in msgs


def test_lint_traced_detection_via_call_chain(tmp_path):
    """A helper called from a shard_map body is traced transitively."""
    f = tmp_path / "chained.py"
    f.write_text(textwrap.dedent("""
        import jax

        def helper(x):
            return x.item()

        def body(x):
            return helper(x)

        fn = jax.shard_map(body, mesh=None, in_specs=(), out_specs=())
    """))
    findings = ast_lint.lint_paths([str(f)])
    assert any(x.rule == "host-sync" and ".item()" in x.message
               for x in findings), findings


def test_lint_allowlist_requires_reason(tmp_path):
    good = tmp_path / "good.py"
    good.write_text(textwrap.dedent("""
        try:
            pass
        except Exception:  # noqa: BLE001 - degraded mode is intended here
            pass
    """))
    assert ast_lint.lint_paths([str(good)]) == []

    bare = tmp_path / "bare.py"
    bare.write_text(textwrap.dedent("""
        try:
            pass
        except Exception:  # jaxlint: disable=broad-except
            pass
    """))
    findings = ast_lint.lint_paths([str(bare)])
    # the reasonless disable both fails to suppress and is itself flagged
    assert any("without a reason" in x.message for x in findings), findings
    assert any("swallows everything" in x.message for x in findings)


def test_lint_multiline_disable_comment(tmp_path):
    f = tmp_path / "multi.py"
    f.write_text(textwrap.dedent("""
        try:
            pass
        # jaxlint: disable=broad-except - reason spanning a comment
        # block right above the handler
        except Exception:
            pass
    """))
    assert ast_lint.lint_paths([str(f)]) == []


def test_lint_static_idioms_not_flagged(tmp_path):
    """`x is None` guards and static-config branches stay legal in
    traced code (the pipeline/attention idioms)."""
    f = tmp_path / "idioms.py"
    f.write_text(textwrap.dedent("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def fn(x: jnp.ndarray, key=None, mode: str = "causal"):
            if key is not None and x is not None:
                x = x + 1
            if mode == "causal":
                x = x * 2
            return x
    """))
    assert ast_lint.lint_paths([str(f)]) == []


def test_jaxlint_cli(tmp_path):
    """Acceptance: non-zero on a seeded violation, zero on the repo."""
    f = tmp_path / "seeded.py"
    f.write_text(_SEEDED)
    cli = str(REPO / "tools" / "jaxlint.py")
    bad = subprocess.run([sys.executable, cli, str(tmp_path)],
                         capture_output=True, text=True)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "host-sync" in bad.stdout
    clean = subprocess.run([sys.executable, cli],
                           capture_output=True, text=True)
    assert clean.returncode == 0, clean.stdout + clean.stderr


# ---------------------------------------------------------------------------
# jaxpr auditor: detectors provably fire
# ---------------------------------------------------------------------------


def _ctx_mesh():
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.parallel.mesh import build_mesh

    return build_mesh(ParallelConfig(context_parallel=2)).mesh


def test_auditor_counts_scan_collectives():
    mesh = _ctx_mesh()
    from jax.sharding import PartitionSpec as P

    def body(x):
        def tick(c, _):
            return jax.lax.ppermute(c, "context", [(0, 1), (1, 0)]), None

        out, _ = jax.lax.scan(tick, x, None, length=3)
        return out

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("context"),),
                      out_specs=P("context"), check_vma=False)
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    rep = jaxpr_audit.audit_jaxpr(jax.make_jaxpr(fn)(x))
    [c] = rep.collectives
    assert c.primitive == "ppermute" and c.calls == 3
    assert c.axes == ("context",)
    assert c.bytes_per_call == 2 * 8 * 4  # local shard [2, 8] f32


def test_auditor_flags_rank0_scan_carry():
    """The jax 0.4.37 hazard: rank-0 inexact scan carries inside
    shard_map bodies (training/pipeline.py keeps them [1]-shaped)."""
    mesh = _ctx_mesh()
    from jax.sharding import PartitionSpec as P

    def body(x):
        def tick(c, _):
            return c + 1.0, None

        s, _ = jax.lax.scan(tick, jnp.float32(0), None, length=2)
        return x + s

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("context"),),
                      out_specs=P("context"), check_vma=False)
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    rep = jaxpr_audit.audit_jaxpr(jax.make_jaxpr(fn)(x))
    assert len(rep.scalar_carries) == 1
    assert rep.scalar_carries[0].dtype == "float32"

    # the repo convention — [1]-shaped carry — is clean
    def body_ok(x):
        def tick(c, _):
            return c + 1.0, None

        s, _ = jax.lax.scan(tick, jnp.zeros((1,), jnp.float32), None,
                            length=2)
        return x + s[0]

    fn = jax.shard_map(body_ok, mesh=mesh, in_specs=(P("context"),),
                      out_specs=P("context"), check_vma=False)
    rep = jaxpr_audit.audit_jaxpr(jax.make_jaxpr(fn)(x))
    assert rep.scalar_carries == []


def test_auditor_flags_manual_axis_constraint():
    """A with_sharding_constraint naming a manually-bound axis inside a
    shard_map body (this toolchain rejects it at lowering; constrain()
    skips them — the auditor proves none slipped through)."""
    mesh = _ctx_mesh()
    from jax.sharding import NamedSharding, PartitionSpec as P

    def body(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("context")))

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P("context"),),
                      out_specs=P("context"), check_vma=False)
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    rep = jaxpr_audit.audit_jaxpr(jax.make_jaxpr(fn)(x))
    assert len(rep.manual_constraints) == 1
    assert "context" in rep.manual_constraints[0].axes

    # constrain() skips the same spec at trace time — clean audit
    from megatron_tpu.parallel.sharding import constrain

    def body_ok(x):
        return constrain(x, P("context"))

    fn = jax.shard_map(body_ok, mesh=mesh, in_specs=(P("context"),),
                      out_specs=P("context"), check_vma=False)
    rep = jaxpr_audit.audit_jaxpr(jax.make_jaxpr(fn)(x))
    assert rep.manual_constraints == []


def test_auditor_flags_callbacks_and_promotions():
    def fn(x):
        jax.debug.print("x {x}", x=x)
        jax.debug.callback(lambda v: None, x)
        return x.astype(jnp.float32) * 2

    x = jax.ShapeDtypeStruct((64, 64), jnp.bfloat16)
    rep = jaxpr_audit.audit_jaxpr(jax.make_jaxpr(fn)(x),
                                  promotion_threshold_bytes=1024)
    # every way into the host from a traced program is seen, whatever
    # primitive this jax gives it (debug.print: debug_print on 0.9)
    assert [c.primitive for c in rep.callbacks] == [
        "debug_print", "debug_callback"]
    assert len(rep.promotions) == 1
    assert rep.promotions[0].bytes_out == 64 * 64 * 4


def test_auditor_donation_report():
    def f(state, batch):
        return {"w": state["w"] + batch["tokens"].sum()}

    state = {"w": jax.ShapeDtypeStruct((128, 128), jnp.float32)}
    batch = {"tokens": jax.ShapeDtypeStruct((128, 128), jnp.float32)}
    lowered = jax.jit(f, donate_argnums=(0,)).lower(state, batch)
    rep = jaxpr_audit.audit_donation(lowered)
    assert any("w" in p for p in rep.donated)
    over = rep.undonated_over(1, allow=(r"tokens",))
    assert over == [], over  # batch is the only non-donated input


# ---------------------------------------------------------------------------
# production-program audits (the acceptance assertions)
# ---------------------------------------------------------------------------


def test_train_step_audit_clean():
    """Train step (dp8 + ZeRO-1): zero host callbacks, full state
    donation, no rank-0 shard_map carries, no manual-axis constraints,
    no silent half->f32 promotions (the fp32-master design upcasts via
    grad accumulation, not convert-on-activation)."""
    t = contracts.CONFIGS["train_dp8_zero1"]()
    rep = jaxpr_audit.audit_jaxpr(t.jaxpr(), t.name)
    assert rep.callbacks == []
    assert rep.scalar_carries == []
    assert rep.manual_constraints == []

    don = jaxpr_audit.audit_donation(t.lowered())
    # args_info tree: (state, batch); every state leaf must be donated
    state_undonated = [p for p, _ in don.undonated
                       if not any(k in p for k in
                                  ("tokens", "labels", "loss_mask"))]
    assert state_undonated == [], state_undonated
    assert len(don.donated) > 10  # params + masters + moments + scalars


def test_train_step_flash_bwd_audit_clean():
    """The train step with attention routed through the flash template
    (ISSUE 16): the GRADIENT path runs the custom-vjp pallas kernels —
    pallas calls visibly in the step jaxpr (fwd, remat fwd, dq, dk/dv;
    the deterministic form of bench's train_attention_bwd_speedup gate)
    — with the same cleanliness contract as the einsum step: zero host
    callbacks, zero unexpected promotions, full state donation."""
    t = targets.flash_bwd_train_step_target()
    jaxpr = t.jaxpr()
    assert str(jaxpr).count("pallas_call") >= 3  # fwd + bwd kernels

    rep = jaxpr_audit.audit_jaxpr(jaxpr, t.name)
    assert rep.callbacks == []
    assert rep.scalar_carries == []
    assert rep.manual_constraints == []
    assert rep.promotions == [], rep.promotions

    don = jaxpr_audit.audit_donation(t.lowered())
    state_undonated = [p for p, _ in don.undonated
                       if not any(k in p for k in
                                  ("tokens", "labels", "loss_mask"))]
    assert state_undonated == [], state_undonated
    assert len(don.donated) > 10


def test_paged_decode_step_audit_clean():
    """Engine decode step (page-table gather + scatter): zero
    collectives (single-device contract), zero host callbacks, the page
    pools donated; the only tolerated bf16->f32 promotion is
    softmax_fp32's per-layer K upcast (intended numerics, ops/attention.py
    kf = k.astype(f32); here the GATHERED [slots, max_pages*page_size,
    Hkv, D] view, once per layer inside the layer scan) — bounded so a
    new upcast (e.g. the whole pool, or V too) still fails."""
    t = targets.paged_decode_step_target()
    rep = jaxpr_audit.audit_jaxpr(t.jaxpr(), t.name)
    assert rep.collectives == []
    assert rep.callbacks == []
    unexpected = [p for p in rep.promotions
                  if not (p.shape == (4, 32, 2, 8) and p.calls == 4)]
    assert unexpected == [], unexpected
    assert len(rep.promotions) <= 1

    don = jaxpr_audit.audit_donation(t.lowered())
    assert len(don.donated) == 2, don.donated  # the k/v page pools


def test_spec_decode_step_audit_clean():
    """Speculative decode step (model drafter): zero
    collectives, ZERO host callbacks — the draft-proposal scan and the
    exact accept/reject (uniform draws, residual categoricals) must all
    stay on device — and FULL donation of BOTH cache trees (2 target
    k/v stacks + 2 draft k/v stacks). bf16->f32 promotions are bounded
    to the known small intermediates: the per-layer softmax_fp32 K
    upcasts of target and draft (the draft's multiplied through its
    k-step proposal scan), the [N, k+1] verify attention slices, and
    the [N, (k+1,) V] logits rows the accept math scores — anything
    cache-sized is a new silent upcast and fails."""
    t = targets.spec_paged_decode_step_target()
    rep = jaxpr_audit.audit_jaxpr(t.jaxpr(), t.name)
    assert rep.collectives == []
    assert rep.callbacks == []
    # every tolerated promotion is tiny (K-upcast slices, verify rows,
    # logits rows); the full caches/pools would be >= 4*32*2*8 * layers
    import math

    too_big = [p for p in rep.promotions
               if math.prod(p.shape) > 4 * 32 * 2 * 8]
    assert too_big == [], too_big
    assert len(rep.promotions) <= 12, rep.promotions

    don = jaxpr_audit.audit_donation(t.lowered())
    # target k/v stacks + draft k/v stacks
    assert len(don.donated) == 4, don.donated


# ---------------------------------------------------------------------------
# golden comm contracts
# ---------------------------------------------------------------------------

ALL_CONFIGS = sorted(contracts.CONFIGS)


def test_golden_manifests_exist():
    """Acceptance: >= 5 parallel configs pinned."""
    present = [n for n in ALL_CONFIGS if contracts.manifest_path(n).exists()]
    assert len(present) >= 5, present
    assert present == ALL_CONFIGS, "manifest missing — run " \
        "'python tools/comm_report.py --regen'"


@pytest.mark.parametrize("name", ALL_CONFIGS)
def test_golden_contract_jaxpr(name):
    problems = contracts.check_contract(name, level="jaxpr")
    assert problems == [], "\n".join(problems) + \
        "\n(intentional comm change? regen: python tools/comm_report.py " \
        f"--regen {name})"


@pytest.mark.slow  # ~25s: XLA-compiles 5 tiny SPMD programs (the jaxpr
# level above runs in tier-1; this adds the GSPMD-inserted collectives)
@pytest.mark.parametrize("name", [n for n in ALL_CONFIGS
                                  if n not in ("moe_ep2",)])
def test_golden_contract_hlo(name):
    problems = contracts.check_contract(name, level="hlo")
    assert problems == [], "\n".join(problems)


def test_injected_collective_breaks_contract():
    """Acceptance: a hidden extra collective fails the golden check."""
    from jax.sharding import PartitionSpec as P
    from megatron_tpu.ops.ulysses import ulysses_attention
    from megatron_tpu.parallel.mesh import AXIS_CONTEXT

    mesh = _ctx_mesh()
    B, S, Hq, Hkv, D = 2, 32, 4, 2, 8

    def body(q, k, v):
        out = ulysses_attention(q, k, v, inner_impl="xla")
        # the smuggled collective a PR might introduce by accident
        return out + 0.0 * jax.lax.psum(out, AXIS_CONTEXT)

    inner = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, AXIS_CONTEXT),) * 3,
        out_specs=P(None, AXIS_CONTEXT), check_vma=False)

    def fn(q, k, v):
        return jax.grad(lambda q, k, v: inner(q, k, v).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    q = jax.ShapeDtypeStruct((B, S, Hq, D), jnp.float32)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.float32)
    tampered = targets.AuditTarget(name="ulysses_cp2", fn=fn,
                                   args=(q, kv, kv), mesh=mesh)
    fresh = contracts.build_manifest("ulysses_cp2", include_hlo=False,
                                     target=tampered)
    problems = contracts.check_contract("ulysses_cp2", level="jaxpr",
                                        fresh=fresh)
    assert problems, "tampered manifest passed the golden check"
    assert any("psum" in p for p in problems), problems


def test_contract_catches_callback_regression():
    """A host callback smuggled into an audited program trips the
    scalar checks, not just the collective table."""
    t = targets.paged_decode_step_target()

    def with_cb(*args):
        out = t.fn(*args)
        jax.debug.print("tok {t}", t=out[0])
        return out

    tampered = targets.AuditTarget(name="decode_paged", fn=with_cb,
                                   args=t.args)
    fresh = contracts.build_manifest("decode_paged", include_hlo=False,
                                     target=tampered)
    problems = contracts.check_contract("decode_paged", level="jaxpr",
                                        fresh=fresh)
    assert any("host_callbacks" in p for p in problems), problems


# ---------------------------------------------------------------------------
# comm_report CLI
# ---------------------------------------------------------------------------


def test_comm_report_prints_table(capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_comm_report", REPO / "tools" / "comm_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--config", "train_pp2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "train_pp2" in out
    assert "ppermute[pipe]" in out
    assert "host_callbacks=0" in out


# ---------------------------------------------------------------------------
# the journal's step_program.kernel_calls (analysis/step_program.py)
# ---------------------------------------------------------------------------

_STEP_TEXT = """HloModule jit_train_step

%cond (arg: (s32[], bf16[8])) -> pred[] {{
  %arg = (s32[], bf16[8]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant({layers})
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}}

%forward_body (arg.1: (s32[], bf16[8])) -> (s32[], bf16[8]) {{
  %arg.1 = (s32[], bf16[8]) parameter(0)
  %flash_fwd.1 = (bf16[1,32,4096,128]{{3,2,1,0}}, f32[1,32,4096,128]{{3,2,1,0}}) custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={{op_name="jit(train_step)/layer_stack/while/body/checkpoint/attention/attn_core/flash_fwd/pallas_call"}}
  ROOT %t = (s32[], bf16[8]) tuple(%i.1, %x)
}}

%backward_body (arg.2: (s32[], bf16[8])) -> (s32[], bf16[8]) {{
  %arg.2 = (s32[], bf16[8]) parameter(0)
{backward}
  ROOT %t.1 = (s32[], bf16[8]) tuple(%i.2, %x.1)
}}

ENTRY %main (p: bf16[8]) -> bf16[8] {{
  %p = bf16[8] parameter(0)
  %while.1 = (s32[], bf16[8]) while(%init), condition=%cond, body=%forward_body
  %while.2 = (s32[], bf16[8]) while(%init.1), condition=%cond, body=%backward_body
  ROOT %out = bf16[8] get-tuple-element(%while.2), index=1
}}
"""
_KERNEL_LINE = (
    '  %{name}.{n} = {results} custom-call(%q.{n}, %k.{n}), '
    'custom_call_target="tpu_custom_call", metadata={{op_name="jit(train_'
    'step)/layer_stack/transpose(jvp(while))/body/{remat}transpose(jvp('
    'attention))/attn_core/{name}/pallas_call"}}')
_DQ = "bf16[1,32,4096,128]{3,2,1,0}"


@pytest.mark.parametrize("backward, want", [
    # the fused backward: one call a layer whose first result is dq
    ([("flash_bwd", f"({_DQ}, {_DQ}, {_DQ})", "")],
     {"flash_bwd": {"calls": 1, "rematted": 0, "times": 2}}),
    # the split pair, which a sequence too long for the fused kernel runs
    ([("flash_bwd_dq", _DQ, ""), ("flash_bwd_dkv", f"({_DQ}, {_DQ})", "")],
     {"flash_bwd_dkv": {"calls": 1, "rematted": 0, "times": 2},
      "flash_bwd_dq": {"calls": 1, "rematted": 0, "times": 2}}),
    # `full`: the backward pass runs the forward kernel a second time
    ([("flash_fwd", f"({_DQ}, f32[1,32,4096,128]{{3,2,1,0}})",
       "rematted_computation/"),
      ("flash_bwd", f"({_DQ}, {_DQ}, {_DQ})", "")],
     {"flash_bwd": {"calls": 1, "rematted": 0, "times": 2}}),
], ids=["fused", "split_pair", "full_recompute"])
def test_kernel_calls_count_the_backward_a_step_runs(backward, want):
    """What a traced run journals of its kernels: each by the name in
    front of its stack's closing `pallas_call`, a scanned layer's call
    once in the text and `layers` times a step, and under
    `rematted_computation` only a forward that is run again."""
    from megatron_tpu.analysis import step_program

    lines = [_KERNEL_LINE.format(name=name, n=n, results=results,
                                 remat=remat)
             for n, (name, results, remat) in enumerate(backward, 2)]
    text = _STEP_TEXT.format(layers=2, backward="\n".join(lines))
    again = sum(1 for name, _, _ in backward if name == "flash_fwd")
    want = dict(want, flash_fwd={"calls": 1 + again, "rematted": again,
                                 "times": 2 * (1 + again)})
    assert step_program.kernel_calls(text) == dict(sorted(want.items()))


# ---------------------------------------------------------------------------
# step_program.flash_k_operands: the shape the flash kernels take K in
# ---------------------------------------------------------------------------

_K_COMPACT, _K_BROADCAST = "bf16[1,8,4096,128]", "bf16[1,32,4096,128]"
# a flash call as the chip's compiler writes it: the operands by name, their
# shapes under `operand_layout_constraints`
_FLASH_CALL = (
    '  %{name}.{n} = {results} custom-call(%off, %q.{n}, %k.{n}, %v.{n}), '
    'custom_call_target="tpu_custom_call", operand_layout_constraints='
    '{{s32[1]{{0}}, {q}{{3,2,1,0}}, {k}{{3,2,1,0}}, {k}{{3,2,1,0}}}}, '
    'frontend_attributes={{kernel_metadata={{}}}}, metadata={{op_name="jit('
    'train_step)/layer_stack/transpose(jvp(while))/body/transpose(jvp('
    'attention))/attn_core/{name}/pallas_call"}}')


@pytest.mark.parametrize("k, backward", [
    (_K_COMPACT, ["flash_bwd"]), (_K_BROADCAST, ["flash_bwd"]),
    (_K_COMPACT, ["flash_bwd_dq", "flash_bwd_dkv"]),
], ids=["by_kv_head", "broadcast", "split_pair"])
def test_flash_k_operands_say_whether_k_was_broadcast(k, backward):
    """The journal's static counter of the kernels' GQA addressing: each
    training flash kernel by name with the shape of its third operand,
    the KV heads' where the kernels read K and V by KV head and the
    query heads' where K was repeated in front of them; a call that
    states no operand shapes (the text's forward) is not guessed at, and
    the row-statistics kernel takes no K."""
    from megatron_tpu.analysis import step_program

    lines = [_FLASH_CALL.format(name=name, n=n, q=_DQ[:-9], k=k,
                                results=f"({_DQ}, {_DQ})")
             for n, name in enumerate(backward, 2)]
    lines.append(_KERNEL_LINE.format(name="flash_bwd_stats", n=9,
                                     results=_DQ, remat=""))
    text = _STEP_TEXT.format(layers=2, backward="\n".join(lines))
    assert step_program.flash_k_operands(text) == {
        name: [k] for name in sorted(backward)}


# ---------------------------------------------------------------------------
# step_program.relaid_arrays: what a program writes a second time unchanged
# ---------------------------------------------------------------------------

_T = "{2,1,0:T(8,128)(2,1)}"
_SCRATCH = "{2,1,0:T(8,128)(2,1)S(1)}"
_DS = 'metadata={op_name="jit(decode)/layer_stack/while/body/dynamic_slice"}'
_SERVE_TEXT = """HloModule jit_decode, is_scheduled=true

%fused_computation.70 (param_0.398: bf16[8,4096,4096], param_1.417: s32[]) -> bf16[1,4096,4096] {{
  %param_0.398 = bf16[8,4096,4096]{t} parameter(0)
  %param_1.417 = s32[]{{:T(128)}} parameter(1)
  %constant.514 = s32[]{{:T(128)}} constant(0)
  ROOT %dynamic_slice.109 = bf16[1,4096,4096]{s} dynamic-slice(%param_0.398, %param_1.417, %constant.514, %constant.514), dynamic_slice_sizes={{1,4096,4096}}, {ds}
}}

%fused_computation.13 (param_0.406: bf16[8,4096,4096], param_1.422: s32[]) -> bf16[4096,4096] {{
  %param_0.406 = bf16[8,4096,4096]{t} parameter(0)
  %param_1.422 = s32[]{{:T(128)}} parameter(1)
  %constant.516 = s32[]{{:T(128)}} constant(0)
  %dynamic_slice.110 = bf16[1,4096,4096]{t} dynamic-slice(%param_0.406, %param_1.422, %constant.516, %constant.516), dynamic_slice_sizes={{1,4096,4096}}, {ds}
  ROOT %bitcast.191 = bf16[4096,4096]{{1,0:T(8,128)(2,1)}} bitcast(%dynamic_slice.110)
}}

%fused_computation.31 (param_0.408: bf16[64,4096], param_1.423: bf16[8,4096,4096], param_2.334: s32[]) -> bf16[64,4096] {{
  %param_0.408 = bf16[64,4096]{{1,0:T(8,128)(2,1)}} parameter(0)
  %param_1.423 = bf16[8,4096,4096]{t} parameter(1)
  %param_2.334 = s32[]{{:T(128)}} parameter(2)
  %fusion.43 = bf16[4096,4096]{{1,0:T(8,128)(2,1)}} fusion(%param_1.423, %param_2.334), kind=kLoop, calls=%fused_computation.13
  ROOT %convolution.16 = bf16[64,4096]{{1,0:T(8,128)(2,1)}} convolution(%param_0.408, %fusion.43), dim_labels=bf_io->bf, metadata={{op_name="jit(decode)/layer_stack/while/body/closed_call/attention/attn_out/...k,kn->...n/dot_general"}}
}}

%cond (arg: (s32[], bf16[64,4096], bf16[8,4096,4096])) -> pred[] {{
  %arg = (s32[], bf16[64,4096], bf16[8,4096,4096]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant(8)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}}

%body (arg.1: (s32[], bf16[64,4096], bf16[8,4096,4096])) -> (s32[], bf16[64,4096], bf16[8,4096,4096]) {{
  %arg.1 = (s32[], bf16[64,4096], bf16[8,4096,4096]) parameter(0)
  %get-tuple-element.705 = s32[]{{:T(128)}} get-tuple-element(%arg.1), index=0
  %get-tuple-element.706 = bf16[64,4096]{{1,0:T(8,128)(2,1)}} get-tuple-element(%arg.1), index=1
  %get-tuple-element.735 = bf16[8,4096,4096]{t} get-tuple-element(%arg.1), index=2
{layer}
  ROOT %t = (s32[], bf16[64,4096], bf16[8,4096,4096]) tuple(%get-tuple-element.705, %x, %get-tuple-element.735)
}}

ENTRY %main (p: bf16[8,4096,4096]) -> bf16[64,4096] {{
  %p = bf16[8,4096,4096]{t} parameter(0)
  %while.1 = (s32[], bf16[64,4096], bf16[8,4096,4096]) while(%init), condition=%cond, body=%body
  ROOT %out = bf16[64,4096] get-tuple-element(%while.1), index=1
}}
"""
# the parent of PR 55, the served Mistral decode step compiled for the
# described v5e: `wq` sliced out of its stack, copied transposed, and the
# product reads the copy
_WQ_RELAID = """\
  %constant_dynamic-slice_fusion.6 = bf16[1,4096,4096]{s} fusion(%get-tuple-element.735, %get-tuple-element.705), kind=kLoop, calls=%fused_computation.70, {ds}
  %copy.50 = bf16[1,4096,4096]{{1,2,0:T(8,128)(2,1)S(1)}} copy(%constant_dynamic-slice_fusion.6), {ds}
  %bitcast.211 = bf16[32,128,4096]{s} bitcast(%copy.50)
  %x = bf16[64,32,128]{{2,0,1:T(8,128)(2,1)S(1)}} fusion(%bitcast.211, %get-tuple-element.706), kind=kOutput, calls=%fused_computation.47, metadata={{op_name="jit(decode)/layer_stack/while/body/closed_call/attention/attn_qkv/...k,kn->...n/dot_general"}}"""
# `wo` as it has always been: the product takes the stack and the layer,
# the slice stands inside its fusion
_WO_IN_PLACE = """\
  %x = bf16[64,4096]{{1,0:T(8,128)(2,1)}} fusion(%get-tuple-element.706, %get-tuple-element.735, %get-tuple-element.705), kind=kOutput, calls=%fused_computation.31, metadata={{op_name="jit(decode)/layer_stack/while/body/closed_call/attention/attn_out/...k,kn->...n/dot_general"}}"""
_WQ_BYTES = 4096 * 4096 * 2


@pytest.mark.parametrize("layer, min_bytes, want", [
    (_WQ_RELAID, _WQ_BYTES, [
        ("constant_dynamic-slice_fusion.6", "slice", "{2,1,0}", None),
        ("copy.50", "copy", "{1,2,0}", "{2,1,0}")]),
    (_WQ_RELAID, _WQ_BYTES + 1, []),
    (_WO_IN_PLACE, 1 << 20, []),
], ids=["wq_sliced_and_copied", "under_the_size_asked_for", "wo_in_place"])
def test_relaid_arrays_finds_a_weight_moved_without_being_used(
        layer, min_bytes, want):
    """The copy of a layer's weight into another layout and the slice
    that took it out of its stack are found, each with its bytes, its
    layouts and how often a step runs it (the loop's eight trips); a
    slice fused into the product that reads it is nothing written, and
    nothing is found."""
    from megatron_tpu.analysis import step_program

    text = _SERVE_TEXT.format(
        t=_T, s=_SCRATCH, ds=_DS,
        layer=layer.format(s=_SCRATCH, ds=_DS))
    found = step_program.relaid_arrays(text, min_bytes)
    assert [(r["name"], r["kind"], r["layout"], r.get("from_layout"))
            for r in found] == want
    for r in found:
        assert r["result"] == "bf16[1,4096,4096]"
        assert (r["bytes"], r["times"]) == (33_554_432, 8)
        assert (r["region"], r["scope"]) == ("other", "body")
