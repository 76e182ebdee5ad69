"""Compressed-collective subsystem (megatron_tpu/quant/, ISSUE 15).

Four layers of proof, innermost out:

  * primitives: the per-chunk int8/fp8 round-trip honors its documented
    WORST-CASE error bound elementwise (adversarial inputs included) —
    the invariant every parity threshold derives from;
  * collectives: compressed psum / all-gather run on a REAL 2-device
    CPU mesh and agree with the dense ops within the two-stage bound;
    trivial axes fall back to the dense ops exactly;
  * engine: the int8 engine on a tp=2 mesh is greedy-gated against the
    dense engine (>= 99% teacher-forced token match, bounded max logit
    error), pays ZERO decode recompiles after warmup (PR 3 counter),
    and its byte counters realize the >= 3x contract ratio;
  * contracts: the decode_tp2_int8 golden manifest proves the byte
    reduction statically, and a silently-reverted-to-dense engine FAILS
    both the manifest diff and the compression gate.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from megatron_tpu.analysis import contracts, targets
from megatron_tpu.analysis.taxonomy import wire_bytes_per_call
from megatron_tpu.config import ModelConfig, ParallelConfig
from megatron_tpu.quant import (
    CommPolicy, compressed_all_gather, compressed_psum, default_policy,
    dequantize_chunked, effective_chunk, forward_comm_bytes, load_policy,
    make_tp_comm, policy_from_exposure, quantization_error_bound,
    quantize_chunked, resolve_policy,
)

requires_2dev = pytest.mark.skipif(
    len(jax.devices()) < 2, reason="needs >= 2 (fake) devices")


def tiny_cfg(**over):
    kw = dict(num_layers=4, hidden_size=32, num_attention_heads=4,
              num_kv_heads=2, ffn_hidden_size=64, vocab_size=128,
              seq_length=32, params_dtype="float32")
    kw.update(over)
    return ModelConfig(**kw).validate()


def tp2_mesh():
    from megatron_tpu.parallel.mesh import build_mesh

    return build_mesh(ParallelConfig(tensor_parallel=2),
                      devices=jax.devices()[:2])


# ---------------------------------------------------------------------------
# primitives: round-trip error bounds are invariants
# ---------------------------------------------------------------------------


def test_effective_chunk():
    assert effective_chunk(64, 32) == 32
    assert effective_chunk(48, 32) == 24   # largest divisor <= 32
    assert effective_chunk(7, 32) == 7
    assert effective_chunk(7, 3) == 1
    with pytest.raises(ValueError):
        effective_chunk(0, 8)


def _adversarial_inputs():
    rng = np.random.default_rng(0)
    yield rng.normal(size=(4, 3, 64)).astype(np.float32)
    # one huge outlier per chunk: the fine-grained-scale motivation
    x = rng.normal(size=(2, 64)).astype(np.float32)
    x[:, ::16] *= 1e4
    yield x
    yield np.zeros((2, 32), np.float32)
    yield np.full((1, 16), -3.7e3, np.float32)
    yield np.linspace(-1e-6, 1e-6, 32, dtype=np.float32)[None]


@pytest.mark.parametrize("mode,chunk", [("int8", 32), ("int8", 8),
                                        ("fp8", 32), ("fp8", 8)])
def test_round_trip_error_bound(mode, chunk):
    """|x - deq(quant(x))| <= quantization_error_bound(x) ELEMENTWISE,
    on random and adversarial inputs — the unit-tested invariant the
    module docstring derives."""
    for x in _adversarial_inputs():
        c = effective_chunk(x.shape[-1], chunk)
        q, s = quantize_chunked(jnp.asarray(x), c, mode)
        back = np.asarray(dequantize_chunked(q, s, jnp.float32))
        bound = np.asarray(quantization_error_bound(jnp.asarray(x), c,
                                                    mode))
        err = np.abs(back - x)
        assert (err <= bound + 1e-12).all(), \
            f"{mode}/{c}: max excess {np.max(err - bound)}"


def test_quantize_rejects_bad_mode_and_chunk():
    x = jnp.ones((2, 8))
    with pytest.raises(ValueError, match="unknown quantization mode"):
        quantize_chunked(x, 8, "int4")
    with pytest.raises(ValueError, match="does not divide"):
        quantize_chunked(x, 3, "int8")


# ---------------------------------------------------------------------------
# collectives on a real 2-device mesh
# ---------------------------------------------------------------------------


def _psum_via_shard_map(x, mesh, mode, chunk):
    fn = jax.shard_map(
        lambda xl: compressed_psum(xl, "tensor", mode=mode, chunk=chunk),
        mesh=mesh, in_specs=P(None, None, None),
        out_specs=P(), check_vma=False)
    return fn(x)


@requires_2dev
@pytest.mark.parametrize("mode", ["dense", "int8", "fp8"])
def test_compressed_psum_parity(mode):
    """quantize -> all_to_all -> exact local reduce -> all_gather agrees
    with the dense psum within the two-quantization-stage bound (each
    stage bounded by quantization_error_bound; the dense mode is
    exact). The in_spec replicates x, so every device holds the same
    'partial' and psum == tp * x."""
    mesh = tp2_mesh().mesh
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 1, 64)).astype(np.float32))
    chunk = 16
    got = _psum_via_shard_map(x, mesh, mode, chunk)
    want = 2.0 * x
    if mode == "dense":
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
        return
    # stage 1 quantizes each device's partial (== x), errors add over tp
    # peers; stage 2 quantizes the reduced sum
    c = effective_chunk(64 // 2, chunk)
    b1 = 2 * np.asarray(quantization_error_bound(x, c, mode))
    b2 = np.asarray(quantization_error_bound(want + jnp.sign(want) * b1,
                                             c, mode))
    assert (np.abs(np.asarray(got - want)) <= b1 + b2 + 1e-6).all()


@requires_2dev
@pytest.mark.parametrize("mode", ["dense", "int8", "fp8"])
def test_compressed_all_gather_parity(mode):
    mesh = tp2_mesh().mesh
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 64)).astype(np.float32))
    fn = jax.shard_map(
        lambda xl: compressed_all_gather(xl, "tensor", mode=mode,
                                         chunk=16),
        mesh=mesh, in_specs=P(None, "tensor"),
        out_specs=P(), check_vma=False)
    got = np.asarray(fn(x))
    if mode == "dense":
        np.testing.assert_array_equal(got, np.asarray(x))
        return
    c = effective_chunk(32, 16)  # quantized on the [2, 32] local shard
    xs = np.asarray(x).reshape(2, 2, 32)
    bound = np.stack([np.asarray(quantization_error_bound(
        jnp.asarray(xs[:, i]), c, mode)) for i in range(2)], 1)
    assert (np.abs(got - np.asarray(x)).reshape(2, 2, 32)
            <= bound + 1e-7).all()


def test_trivial_axis_falls_back_dense():
    """tp == 1: the wrappers ARE the dense ops (no quantization error,
    no low-bit collectives in the jaxpr)."""
    from megatron_tpu.parallel.mesh import build_mesh

    mesh = build_mesh(ParallelConfig(), devices=jax.devices()[:1]).mesh
    x = jnp.asarray(np.random.default_rng(3).normal(
        size=(2, 8)).astype(np.float32))
    fn = jax.shard_map(
        lambda xl: compressed_psum(xl, "tensor", mode="int8", chunk=4),
        mesh=mesh, in_specs=P(None, None), out_specs=P(),
        check_vma=False)
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))
    jaxpr = str(jax.make_jaxpr(fn)(x))
    assert "all_to_all" not in jaxpr and "int8" not in jaxpr


# ---------------------------------------------------------------------------
# wire-byte model + policy
# ---------------------------------------------------------------------------


def test_wire_bytes_model():
    assert wire_bytes_per_call("psum", 1000, 2) == 1000      # 2*(n-1)/n
    assert wire_bytes_per_call("psum", 1000, 4) == 1500
    assert wire_bytes_per_call("all_gather", 1000, 4) == 750
    assert wire_bytes_per_call("all_to_all", 1000, 2) == 500
    assert wire_bytes_per_call("psum_scatter", 100, 4) == 300
    assert wire_bytes_per_call("ppermute", 1000, 4) == 1000
    assert wire_bytes_per_call("psum", 1000, 1) == 0   # trivial axis
    assert wire_bytes_per_call("psum", 1000, 0) == 1000  # unknown mesh


def test_policy_defaults_and_derivation():
    pol = default_policy()
    assert set(pol.enabled_sites()) == {"attn_out", "mlp_out", "logits",
                                        "cp_ring", "cp_a2a"}
    derived = policy_from_exposure({"all-reduce": 0.8, "all-gather": 0.1},
                                   threshold=0.25)
    assert derived.enabled("attn_out") and derived.enabled("mlp_out")
    assert not derived.enabled("logits")
    # cp_a2a keys on all-to-all exposure, independently of cp_ring
    a2a = policy_from_exposure({"all-to-all": 0.5,
                                "collective-permute": 0.1}, threshold=0.25)
    assert a2a.enabled("cp_a2a") and not a2a.enabled("cp_ring")
    # absent op kinds (never measured / fully hidden) stay dense
    none = policy_from_exposure({}, threshold=0.25)
    assert none.enabled_sites() == ()


def test_policy_load_and_validation(tmp_path):
    p = tmp_path / "pol.json"
    p.write_text(json.dumps({"sites": {"logits": False},
                             "source": "trace:x", "threshold": 0.3}))
    pol = load_policy(str(p))
    assert pol.enabled("attn_out") and not pol.enabled("logits")
    assert pol.threshold == 0.3
    p.write_text(json.dumps({"sites": {"logitz": True}}))
    with pytest.raises(ValueError, match="unknown collective site"):
        load_policy(str(p))
    p.write_text(json.dumps({"sites": {"logits": "yes"}}))
    with pytest.raises(ValueError, match="JSON boolean"):
        load_policy(str(p))
    with pytest.raises(TypeError):
        resolve_policy(42)
    assert isinstance(resolve_policy({"mlp_out": False}), CommPolicy)


def test_make_tp_comm_guards():
    rt = tp2_mesh()
    assert make_tp_comm(None, "int8") is None
    assert make_tp_comm(rt.mesh, "none") is None
    with pytest.raises(ValueError, match="must be one of"):
        make_tp_comm(rt.mesh, "int4")
    # trivial tensor axis: warns + no-op
    from megatron_tpu.parallel.mesh import build_mesh

    solo = build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    with pytest.warns(UserWarning, match="trivial tensor axis"):
        assert make_tp_comm(solo.mesh, "int8") is None
    # divisibility is validated at build, naming the site
    with pytest.raises(ValueError, match="vocab size.*logits"):
        make_tp_comm(rt.mesh, "int8", cfg=tiny_cfg(vocab_size=127))
    with pytest.raises(ValueError, match="MoE"):
        make_tp_comm(rt.mesh, "int8",
                     cfg=tiny_cfg(num_experts=4, moe_top_k=2))
    # a policy disabling the offending site unblocks the build
    tpc = make_tp_comm(rt.mesh, "int8", cfg=tiny_cfg(vocab_size=127),
                       policy={"logits": False})
    assert "logits" not in tpc.sites
    # psum sites also split the OUTPUT width (hidden) across peers: a
    # tp that divides the ffn width but not hidden must still refuse at
    # build, not mid-trace (review finding)
    if len(jax.devices()) >= 3:
        from megatron_tpu.parallel.mesh import build_mesh

        rt3 = build_mesh(ParallelConfig(tensor_parallel=3),
                         devices=jax.devices()[:3])
        with pytest.raises(ValueError, match="hidden size.*mlp_out"):
            make_tp_comm(rt3.mesh, "int8",
                         cfg=tiny_cfg(ffn_hidden_size=48, vocab_size=129),
                         policy={"attn_out": False, "logits": False})


# ---------------------------------------------------------------------------
# engine-level gates (the acceptance criteria)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tp_setup():
    """Shared tp=2 geometry: sharded params + a dense and an int8
    engine (one compile each for the module's engine tests)."""
    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.models.params import init_params, param_specs
    from megatron_tpu.parallel.sharding import shard_tree

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (fake) devices")
    cfg = tiny_cfg()
    rt = tp2_mesh()
    # The gates below were calibrated on ONE random model: PRNGKey(0)
    # under the threefry stream that was jax's default then
    # (jax_threefry_partitionable=False). jax 0.9 flipped that default,
    # which draws a different model from the same key — and on these
    # near-uniform logits (a fifth of the positions have a top-1/top-2
    # gap under 0.01) argmax agreement moves by +-0.02 from draw to
    # draw. The transport arithmetic did not change: the e4m3 cast is
    # bit-identical to the round-to-nearest-even reference and the max
    # logit error on the calibrated model is the recorded 0.0143. So the
    # model is pinned, not the floor lowered.
    with jax.threefry_partitionable(False):
        params = init_params(cfg, jax.random.PRNGKey(0))
    sparams = shard_tree(rt, params, param_specs(cfg))
    dense = InferenceEngine(cfg, sparams, num_slots=4, max_seq_len=32,
                            mesh=rt.mesh)
    comp = InferenceEngine(cfg, sparams, num_slots=4, max_seq_len=32,
                           mesh=rt.mesh, compress_collectives="int8")
    return cfg, rt, sparams, dense, comp


def test_engine_rejects_compress_with_speculative():
    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.inference.speculative import SpecConfig
    from megatron_tpu.models.params import init_params, param_specs
    from megatron_tpu.parallel.sharding import shard_tree

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (fake) devices")
    cfg = tiny_cfg()
    rt = tp2_mesh()
    sparams = shard_tree(rt, init_params(cfg, jax.random.PRNGKey(0)),
                         param_specs(cfg))
    with pytest.raises(ValueError, match="speculative"):
        InferenceEngine(cfg, sparams, num_slots=2, max_seq_len=32,
                        mesh=rt.mesh, compress_collectives="int8",
                        speculative=SpecConfig(k=2, drafter="ngram"))


def test_teacher_forced_parity_gate(tp_setup):
    """THE numeric acceptance gate: per-position greedy agreement of the
    compressed forward against the dense one on identical context
    (teacher-forced — chain-level comparison would charge every
    post-divergence position to quantization). int8 >= 99% argmax
    match; fp8 (2^-4 relative transport error) >= 95% on this
    adversarial near-uniform-logit random model; both with a bounded
    max logit error. Deterministic on CPU: same weights, same math,
    every run."""
    from megatron_tpu.models.language_model import lm_forward

    cfg, rt, sparams, dense, comp = tp_setup
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size,
                                    (8, 32)).astype(np.int32))
    with jax.sharding.set_mesh(rt.mesh):
        ld = jax.jit(lambda p, t: lm_forward(cfg, p, t))(sparams, toks)
        li = jax.jit(lambda p, t: lm_forward(
            cfg, p, t, tp_comm=comp.tp_comm))(sparams, toks)
        fp8_tpc = make_tp_comm(rt.mesh, "fp8", cfg=cfg)
        lf = jax.jit(lambda p, t: lm_forward(
            cfg, p, t, tp_comm=fp8_tpc))(sparams, toks)
    agree_i = float(jnp.mean(jnp.argmax(ld, -1) == jnp.argmax(li, -1)))
    agree_f = float(jnp.mean(jnp.argmax(ld, -1) == jnp.argmax(lf, -1)))
    err_i = float(jnp.max(jnp.abs(ld - li)))
    err_f = float(jnp.max(jnp.abs(ld - lf)))
    assert agree_i >= 0.99, f"int8 token match {agree_i}"
    assert agree_f >= 0.95, f"fp8 token match {agree_f}"
    # bounded max logit error (measured 0.0024 / 0.0145 at this pinned
    # geometry; 4x headroom so only a real numerics regression trips)
    assert err_i <= 0.01, err_i
    assert err_f <= 0.06, err_f


def test_compressed_engine_serves_with_zero_recompiles(tp_setup):
    """End-to-end through the real engines: greedy traffic drains on
    both, ZERO decode recompiles after warmup on the compressed engine
    AND on the dense mesh engine (the cache-sharding pin — mesh engines
    used to pay one), and the live byte counters realize the >= 3x
    contract ratio."""
    cfg, rt, sparams, dense, comp = tp_setup
    rng = np.random.default_rng(1)
    prompts = rng.integers(1, cfg.vocab_size, (4, 8)).astype(np.int32)
    lengths = np.full((4,), 8, np.int32)
    a = dense.generate(prompts, lengths, max_new_tokens=12)
    b = comp.generate(prompts, lengths, max_new_tokens=12)
    # drive a second round so post-warmup recompiles would be visible
    dense.generate(prompts, lengths, max_new_tokens=12)
    comp.generate(prompts, lengths, max_new_tokens=12)
    assert comp.stats["decode_recompiles"] == 0
    assert dense.stats["decode_recompiles"] == 0
    # identical prefill context => the first generated token agrees
    # (chain-level identity is not promised — the gate is teacher-forced)
    assert (a.tokens[:, 8] == b.tokens[:, 8]).all()
    ratio = (comp.stats["comm_dense_bytes"]
             / max(comp.stats["comm_compressed_bytes"], 1))
    assert ratio >= 3.0, ratio
    # counters advance by the static per-tick price
    want = forward_comm_bytes(cfg, comp.tp_comm, 4, 1)
    t0 = comp.stats["comm_compressed_bytes"]
    comp.generate(prompts[:1], lengths[:1], max_new_tokens=3)
    delta = comp.stats["comm_compressed_bytes"] - t0
    # 2 decode ticks (first token comes from prefill) + one prefill chunk
    pre = forward_comm_bytes(cfg, comp.tp_comm, 1,
                             comp.prefill_chunk)["compressed"]
    assert delta == 2 * want["compressed"] + pre, (delta, want, pre)


def test_comm_policy_journal_and_report(tp_setup, tmp_path):
    """The comm_policy journal record lands once per engine build and
    tools/telemetry_report.py renders the compression ratio off it."""
    from megatron_tpu.inference.engine import InferenceEngine
    from megatron_tpu.telemetry.journal import (
        EventJournal, set_global_journal,
    )

    cfg, rt, sparams, _, _ = tp_setup
    path = tmp_path / "events.jsonl"
    j = EventJournal(str(path))
    set_global_journal(j)
    try:
        eng = InferenceEngine(cfg, sparams, num_slots=2, max_seq_len=32,
                              mesh=rt.mesh, compress_collectives="int8",
                              comm_policy={"logits": False})
        assert "logits" not in eng.tp_comm.sites
    finally:
        set_global_journal(None)
        j.close()
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_telemetry_report", os.path.join(repo, "tools",
                                          "telemetry_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    summary = mod.summarize(mod.load_journal(str(path)))
    comm = summary["serving"]["comm"]
    assert comm["mode"] == "int8" and comm["tp"] == 2
    assert comm["sites"] == ["attn_out", "mlp_out"]
    assert comm["compression_ratio"] >= 3.0
    rendered = mod.render(summary)
    assert "compressed collectives (int8" in rendered


@pytest.mark.slow  # ~15s: compiles a chunk + decode step on a mesh
def test_compressed_engine_at_small_pages(tp_setup):
    """Pages of 8 under chunks of 16: chunk-prefill and decode both
    route the compressed collectives, greedy first token agrees with
    the dense engine, zero recompiles, counters advance."""
    from megatron_tpu.inference.engine import InferenceEngine

    cfg, rt, sparams, _, _ = tp_setup
    kw = dict(num_slots=2, max_seq_len=32, page_size=8, prefill_chunk=16,
              mesh=rt.mesh)
    dense = InferenceEngine(cfg, sparams, **kw)
    comp = InferenceEngine(cfg, sparams, **kw, compress_collectives="int8")
    rng = np.random.default_rng(2)
    prompts = rng.integers(1, cfg.vocab_size, (2, 8)).astype(np.int32)
    lengths = np.full((2,), 8, np.int32)
    a = dense.generate(prompts, lengths, max_new_tokens=6)
    b = comp.generate(prompts, lengths, max_new_tokens=6)
    assert (a.tokens[:, 8] == b.tokens[:, 8]).all()
    assert comp.stats["decode_recompiles"] == 0
    assert comp.stats["comm_compressed_bytes"] > 0
    assert (comp.stats["comm_dense_bytes"]
            >= 3 * comp.stats["comm_compressed_bytes"])


# ---------------------------------------------------------------------------
# contracts: the byte reduction is pinned, and a silent revert fails
# ---------------------------------------------------------------------------


def test_golden_compression_gates_hold():
    """The committed manifests prove >= 3x wire-byte reduction for both
    compressed configs (the acceptance floor)."""
    assert contracts.check_compression_gates() == []
    dense = contracts.load_manifest("decode_tp2_dense")
    int8 = contracts.load_manifest("decode_tp2_int8")
    assert contracts.compression_ratio(int8, dense) >= 3.0
    # the compressed manifest really moves low-bit payloads
    colls = int8["jaxpr"]["collectives"]
    assert any(v.get("compressed") for v in colls.values())
    assert any("int8" in k for k in colls)


def test_silent_dense_revert_fails_contract():
    """Injected regression (acceptance): rebuild the decode_tp2_int8
    manifest from an engine that silently reverted to dense transport —
    the golden diff AND the compression gate both fail loudly."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (fake) devices")
    reverted = targets.tp_decode_step_target("decode_tp2_int8",
                                             mode="dense")
    fresh = contracts.build_manifest("decode_tp2_int8", include_hlo=False,
                                     target=reverted)
    problems = contracts.check_contract("decode_tp2_int8", level="jaxpr",
                                        fresh=fresh)
    assert problems, "dense-reverted manifest passed the golden check"
    assert any("int8" in p or "psum" in p for p in problems), problems
    gate = contracts.check_compression_gates(
        fresh={"decode_tp2_int8": fresh})
    assert gate and "compression gate" in gate[0], gate


def test_comm_report_diff_cli(capsys):
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_comm_report_diff", os.path.join(repo, "tools", "comm_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--diff", "decode_tp2_dense", "decode_tp2_int8"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "wire-byte ratio decode_tp2_dense / decode_tp2_int8: 3.2" in out
    assert "[q]" in out
    # the flag trio is mutually exclusive
    with pytest.raises(SystemExit):
        mod.main(["--diff", "a", "b", "--check"])
