"""Context-parallel serving (inference/context_parallel/): striped page
pool units, compressed ring-permute transport, and the engine parity
gates — greedy traffic through the CP engine (chunked distributed
prefill, sequence-striped paged KV, ring-attention decode) must be
token-identical to the one-shot loop's on one device, with logprob parity
and zero decode recompiles after warmup, through radix prefix hits and
mid-prefill preempt/resume."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ParallelConfig
from megatron_tpu.inference.context_parallel import (
    ContextParallelEngine, StripedPagePool,
)
from megatron_tpu.inference.engine import Request
from megatron_tpu.inference.generation import generate_tokens
from megatron_tpu.inference.paging.pool import SCRATCH_PAGE
from megatron_tpu.models import presets
from megatron_tpu.models.params import init_params, param_specs
from megatron_tpu.parallel.mesh import build_mesh
from megatron_tpu.parallel.sharding import shard_tree
from megatron_tpu.quant.collectives import (
    cp_ring_comm_bytes, make_cp_comm, ring_permute,
)

CFG = presets.tiny(vocab_size=64, seq_length=64)
PARAMS = init_params(CFG, jax.random.PRNGKey(0))


class OneShot:
    """The reference: `generate_tokens`' loop over a dense whole-row cache
    on one device, which shares no engine code."""

    def generate(self, prompts, lengths, max_new_tokens, temperature=0.0):
        return generate_tokens(CFG, PARAMS, prompts, lengths,
                               max_new_tokens=max_new_tokens,
                               temperature=temperature)

    def run(self, prompt, n=6):
        from _engine_lookahead_cases import one_shot

        return one_shot(CFG, PARAMS)(np.asarray(prompt, np.int32), n, {})


# ---------------------------------------------------------------------------
# striped page pool


def test_striped_pool_ownership_and_striping():
    # 8 pages over cp=2: rank 0 owns 1..3 (0 is scratch), rank 1 owns 4..7
    pool = StripedPagePool(8, 2)
    assert pool.pages_per_rank == 4
    assert pool.free_pages_by_rank() == [3, 4]
    pages = pool.alloc(4)  # logical 0..3 -> ranks 0,1,0,1
    assert [pool.owner(p) for p in pages] == [0, 1, 0, 1]
    assert pool.free_pages_by_rank() == [1, 2]
    # logical_start continues the stripe mid-sequence
    more = pool.alloc(2, logical_start=4)  # logical 4,5 -> ranks 0,1
    assert [pool.owner(p) for p in more] == [0, 1]


def test_striped_pool_all_or_nothing_per_rank():
    pool = StripedPagePool(8, 2)
    # rank 0 has 3 usable pages: an alloc needing 4 even-logical pages
    # must fail WITHOUT draining rank 1
    assert pool.alloc(7) is None
    assert pool.free_pages_by_rank() == [3, 4]
    # 6 logical pages = 3 per rank fits exactly
    pages = pool.alloc(6)
    assert pages is not None
    assert pool.free_pages_by_rank() == [0, 1]
    # release returns each page to its owner's free list
    pool.release(pages)
    assert pool.free_pages_by_rank() == [3, 4]


def test_striped_pool_misuse_raises():
    pool = StripedPagePool(8, 2)
    with pytest.raises(ValueError):
        StripedPagePool(9, 2)  # not divisible by cp
    (p,) = pool.alloc(1)
    pool.release([p])
    with pytest.raises(ValueError):
        pool.release([p])  # double release
    # scratch page is never tracked
    pool.retain([SCRATCH_PAGE])
    pool.release([SCRATCH_PAGE])


# ---------------------------------------------------------------------------
# ring transport + byte model


def test_ring_permute_dense_and_int8():
    from jax.sharding import PartitionSpec as P

    rt = build_mesh(ParallelConfig(context_parallel=2),
                    devices=jax.devices()[:2])
    perm = [(0, 1), (1, 0)]
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 4, 32)), jnp.float32)

    def run(mode):
        body = lambda s: ring_permute(s, "context", perm, mode=mode,  # noqa: E731
                                      chunk=16)
        # under jit, as the engines call it: a shard_map that is manual
        # over only some mesh axes has no eager form in jax 0.9
        return jax.jit(jax.shard_map(
            body, mesh=rt.mesh, in_specs=(P("context"),),
            out_specs=P("context"), axis_names={"context"},
            check_vma=False))(x)

    want = jnp.roll(x, 1, axis=0)  # shard r receives shard r-1's rows
    np.testing.assert_array_equal(np.asarray(run("dense")), np.asarray(want))
    got = np.asarray(run("int8"))
    # per-chunk symmetric int8: bounded roundtrip error, not identity
    err = np.max(np.abs(got - np.asarray(want)))
    assert 0 < err <= np.max(np.abs(np.asarray(x))) / 127 + 1e-6
    # the wire really moves int8 payloads
    body = lambda s: ring_permute(s, "context", perm, mode="int8")  # noqa: E731
    fn = jax.shard_map(body, mesh=rt.mesh, in_specs=(P("context"),),
                       out_specs=P("context"), axis_names={"context"},
                       check_vma=False)
    assert "i8[" in str(jax.make_jaxpr(fn)(x))


def test_cp_ring_byte_model():
    rt = build_mesh(ParallelConfig(context_parallel=2),
                    devices=jax.devices()[:2])
    dense = make_cp_comm(rt.mesh, "dense", cfg=CFG)
    int8 = make_cp_comm(rt.mesh, "int8", cfg=CFG)
    b_dense = cp_ring_comm_bytes(CFG, dense, 2, 1)
    b_int8 = cp_ring_comm_bytes(CFG, int8, 2, 1)
    assert b_dense["dense"] == b_dense["compressed"]
    assert b_int8["dense"] == b_dense["dense"]
    assert 0 < b_int8["compressed"] < b_int8["dense"]
    # the policy can pin cp_ring dense: byte model collapses to dense
    gated = make_cp_comm(rt.mesh, "int8", cfg=CFG,
                         policy={"cp_ring": False})
    assert not gated.compresses() and gated.wire_mode() == "dense"
    b_gated = cp_ring_comm_bytes(CFG, gated, 2, 1)
    assert b_gated["compressed"] == b_gated["dense"]
    # cp=1 / mode none build no transport
    solo = build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    assert make_cp_comm(solo.mesh, "int8", cfg=CFG) is None
    assert make_cp_comm(rt.mesh, "none", cfg=CFG) is None


# ---------------------------------------------------------------------------
# engine parity gates (real tiny model, cp=2 mesh)


@pytest.fixture(scope="module")
def cp_setup():
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (fake) devices")
    rt = build_mesh(ParallelConfig(context_parallel=2),
                    devices=jax.devices()[:2])
    sparams = shard_tree(rt, PARAMS, param_specs(CFG))
    dense = OneShot()
    cpe = ContextParallelEngine(CFG, sparams, num_slots=2, max_seq_len=64,
                                page_size=8, prefill_chunk=8, mesh=rt.mesh)
    return rt, dense, cpe


def _req(prompt, n=6):
    return Request(prompt=np.asarray(prompt, np.int32), max_new_tokens=n)


def _run(eng, prompt, n=6):
    req = eng.submit(_req(prompt, n))
    eng.run_until_idle()
    assert req.error is None, req.error
    return req


def test_cp_parity_multichunk_ragged(cp_setup):
    """A 13-token prompt: 2 chunks, neither aligned to page_size * cp —
    the ragged tail crosses a shard boundary mid-page. Token-identical
    with full logprob parity."""
    _, dense, cpe = cp_setup
    prompts = np.asarray([[3, 7, 11, 2, 9, 4, 1, 8, 5, 6, 2, 3, 7]],
                         np.int32)
    lengths = np.asarray([13], np.int32)
    a = dense.generate(prompts, lengths, max_new_tokens=8, temperature=0.0)
    b = cpe.generate(prompts, lengths, max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    np.testing.assert_allclose(a.logprobs, b.logprobs, rtol=1e-5, atol=1e-5)
    assert cpe.stats["cp_ring_steps"] > 0


def test_cp_parity_radix_hit_mid_shard(cp_setup):
    """Two requests sharing a 3-page (24-token) prefix: the second
    aliases cached pages whose stripe ends mid-shard (page 3 of the
    follow-up starts on rank 1). Exactness must survive the alias."""
    _, dense, cpe = cp_setup
    prefix = list(range(5, 29))  # 24 tokens = 3 full pages
    tail_a, tail_b = [30, 31], [40, 41, 42]
    _run(cpe, prefix + tail_a)
    hits0 = cpe.stats["prefix_hits"]
    got = _run(cpe, prefix + tail_b)
    assert cpe.stats["prefix_hits"] > hits0
    want = dense.run(prefix + tail_b)
    assert got.generated == want.generated
    np.testing.assert_allclose(got.logprobs, want.logprobs,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got.prompt_logprobs, want.prompt_logprobs,
                               rtol=1e-5, atol=1e-5)


def test_cp_parity_preempt_resume_mid_prefill(cp_setup):
    """Preempt a CP request while its chunked prefill is mid-flight: the
    resume recomputes through the striped pools and must finish with
    exactly the tokens it would have produced without the preemption."""
    _, dense, cpe = cp_setup
    prompt = [int(t) for t in
              np.random.default_rng(7).integers(1, 64, 40)]
    req = cpe.submit(_req(prompt, 6))
    cpe.step()  # admit + first chunk
    cpe.step()  # second chunk (prompt is 5 chunks of 8)
    assert cpe.prefill_queue.peek() is not None  # mid-prefill
    pre0 = cpe.stats["preemptions"]
    assert cpe._preempt_one()
    assert cpe.stats["preemptions"] == pre0 + 1
    cpe.run_until_idle()
    assert req.error is None, req.error
    want = dense.run(prompt, 6)
    assert req.generated == want.generated
    np.testing.assert_allclose(req.logprobs, want.logprobs,
                               rtol=1e-5, atol=1e-5)


def test_cp_zero_decode_recompiles_after_warmup(cp_setup):
    """Order-dependent on the parity tests above having driven real
    traffic: the decode step must have compiled exactly once."""
    _, _, cpe = cp_setup
    assert cpe.stats["decode_recompiles"] == 0


# ---------------------------------------------------------------------------
# construction validation + host-side table building


def test_cp_engine_rejects_bad_geometry(cp_setup):
    rt, _, _ = cp_setup
    with pytest.raises(ValueError, match="requires a mesh"):
        ContextParallelEngine(CFG, PARAMS, mesh=None)
    solo = build_mesh(ParallelConfig(), devices=jax.devices()[:1])
    with pytest.raises(ValueError, match="cp == 1"):
        ContextParallelEngine(CFG, PARAMS, mesh=solo.mesh)
    with pytest.raises(ValueError, match="ring transport"):
        ContextParallelEngine(CFG, PARAMS, mesh=rt.mesh,
                              max_seq_len=64, cp_collectives="none")


def test_cp_engine_rounds_pool_to_cp_multiple(cp_setup):
    rt, _, _ = cp_setup
    sparams = shard_tree(rt, PARAMS, param_specs(CFG))
    eng = ContextParallelEngine(CFG, sparams, num_slots=2, max_seq_len=64,
                                page_size=8, prefill_chunk=8, mesh=rt.mesh,
                                num_pages=11)
    assert eng.num_pages == 12 and eng.pool.pages_per_rank == 6


def test_make_cp_comm_2d_validation():
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 (fake) devices")
    rt4 = build_mesh(ParallelConfig(context_parallel=4),
                     devices=jax.devices()[:4])
    with pytest.raises(ValueError, match="geometry must be one of"):
        make_cp_comm(rt4.mesh, "dense", cfg=CFG, geometry="3d")
    with pytest.raises(ValueError, match="subgroup .cp_head. >= 2"):
        make_cp_comm(rt4.mesh, "dense", cfg=CFG, geometry="2d", subgroup=0)
    with pytest.raises(ValueError, match="does not divide"):
        make_cp_comm(rt4.mesh, "dense", cfg=CFG, geometry="2d", subgroup=3)
    # the head all-to-all hands each member heads/subgroup heads — a
    # head count the subgroup doesn't divide fails at build
    cfg_h2 = presets.tiny(vocab_size=64, seq_length=64,
                          num_attention_heads=2, num_kv_heads=2)
    with pytest.raises(ValueError, match="head count"):
        make_cp_comm(rt4.mesh, "dense", cfg=cfg_h2, geometry="2d",
                     subgroup=4)
    with pytest.raises(ValueError, match="takes no subgroup"):
        make_cp_comm(rt4.mesh, "dense", cfg=CFG, subgroup=2)
    two_d = make_cp_comm(rt4.mesh, "dense", cfg=CFG, geometry="2d",
                         subgroup=2)
    assert two_d.seq_groups() == 2 and two_d.ring_hops() == 1
    flat = make_cp_comm(rt4.mesh, "dense", cfg=CFG)
    assert flat.subgroup == 1 and flat.ring_hops() == 3


def test_cp_2d_byte_model_a2a_rows():
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 (fake) devices")
    rt4 = build_mesh(ParallelConfig(context_parallel=4),
                     devices=jax.devices()[:4])
    flat = make_cp_comm(rt4.mesh, "dense", cfg=CFG)
    two_d = make_cp_comm(rt4.mesh, "dense", cfg=CFG, geometry="2d",
                         subgroup=2)
    b_flat = cp_ring_comm_bytes(CFG, flat, 2, 1)
    b_2d = cp_ring_comm_bytes(CFG, two_d, 2, 1)
    # the flat ring never runs a2a legs
    assert b_flat["a2a_dense"] == b_flat["a2a_compressed"] == 0
    # 2d: 1 cross-subgroup hop at half the head payload vs 3 full-
    # payload flat hops => ring wire drops by 6x; the a2a legs appear
    assert b_2d["dense"] * 6 == b_flat["dense"]
    assert b_2d["a2a_dense"] > 0
    # int8 a2a compresses the o payload; the cp_a2a policy pins it dense
    i8 = make_cp_comm(rt4.mesh, "int8", cfg=CFG, geometry="2d",
                      subgroup=2)
    b_i8 = cp_ring_comm_bytes(CFG, i8, 2, 1)
    assert 0 < b_i8["a2a_compressed"] < b_i8["a2a_dense"]
    gated = make_cp_comm(rt4.mesh, "int8", cfg=CFG, geometry="2d",
                         subgroup=2, policy={"cp_a2a": False})
    assert gated.a2a_wire_mode() == "dense" and gated.compresses()
    b_gated = cp_ring_comm_bytes(CFG, gated, 2, 1)
    assert b_gated["a2a_compressed"] == b_gated["a2a_dense"]
    assert b_gated["compressed"] < b_gated["dense"]  # ring still int8


def test_cp_loc_tables_striping_and_invariant(cp_setup):
    _, _, cpe = cp_setup
    npl, mpl = cpe._npl, cpe._mpl
    row = np.zeros((1, cpe.max_pages), np.int32)
    # logical 0 -> rank 0 local 1; logical 1 -> rank 1 local 2
    row[0, 0], row[0, 1] = 1, npl + 2
    loc = cpe._loc_tables(row)
    assert loc.shape == (2, 1, mpl)
    assert loc[0, 0, 0] == 1 and loc[1, 0, 0] == 2
    # unallocated tail: local scratch on rank 0, sentinel elsewhere
    assert loc[0, 0, 1] == 0 and loc[1, 0, 1] == npl
    # a page on the wrong rank is a loud invariant violation
    bad = np.zeros((1, cpe.max_pages), np.int32)
    bad[0, 1] = 1  # logical 1 must live on rank 1, page 1 is rank 0's
    with pytest.raises(AssertionError, match="striping invariant"):
        cpe._loc_tables(bad)


# ---------------------------------------------------------------------------
# geometry x transport parity matrix (ISSUE 20): every cell must stay
# token-identical to the one-shot reference through fresh ragged
# traffic, radix prefix hits, and mid-prefill preempt/resume, with zero
# decode recompiles. Dense transports also hold logprobs to 1e-5; int8
# cells carry the ring/a2a quantization noise in the logprobs (bounded,
# measured <= 1.5e-3 at this geometry) while the argmax stays exact.


MATRIX = {
    "ring_serial_dense": dict(cp=2, cp_overlap=False),
    "ring_overlap_dense": dict(cp=2, cp_overlap=True),
    "ring_overlap_int8": dict(cp=2, cp_overlap=True,
                              cp_collectives="int8"),
    "2d_dense": dict(cp=4, cp_geometry="2d", cp_subgroup=2),
    "2d_int8": dict(cp=4, cp_geometry="2d", cp_subgroup=2,
                    cp_collectives="int8"),
}


def _logprob_atol(cell: str) -> float:
    return 5e-3 if "int8" in cell else 1e-5


# tier-1 keeps the dense transport of every schedule and geometry: the
# serial ring, the overlapped ring (the default) and the 2D geometry;
# the int8 transports ride the slow suite and keep their tier-1
# roundtrip/jaxpr units above.
_TIER1_CELLS = ("2d_dense", "ring_overlap_dense", "ring_serial_dense")


def _matrix_cells():
    return [c if c in _TIER1_CELLS
            else pytest.param(c, marks=pytest.mark.slow)
            for c in sorted(MATRIX)]


@pytest.fixture(scope="module")
def matrix_cache():
    """Lazily built engines, one per matrix cell, shared across the
    scenario tests so each cell compiles its steps exactly once."""
    return {}


def _matrix_engine(cache, cell):
    if cell not in cache:
        spec = dict(MATRIX[cell])
        cp = spec.pop("cp")
        if len(jax.devices()) < cp:
            pytest.skip(f"needs >= {cp} (fake) devices")
        rt = build_mesh(ParallelConfig(context_parallel=cp),
                        devices=jax.devices()[:cp])
        sp = shard_tree(rt, PARAMS, param_specs(CFG))
        cache[cell] = ContextParallelEngine(
            CFG, sp, num_slots=2, max_seq_len=64, page_size=8,
            prefill_chunk=8, mesh=rt.mesh, **spec)
    return cache[cell]


@pytest.mark.parametrize("cell", _matrix_cells())
def test_cp_matrix_fresh_ragged_parity(cp_setup, matrix_cache, cell):
    _, dense, _ = cp_setup
    eng = _matrix_engine(matrix_cache, cell)
    prompts = np.asarray([[3, 7, 11, 2, 9, 4, 1, 8, 5, 6, 2, 3, 7]],
                         np.int32)
    lengths = np.asarray([13], np.int32)
    a = dense.generate(prompts, lengths, max_new_tokens=8, temperature=0.0)
    b = eng.generate(prompts, lengths, max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(a.tokens, b.tokens)
    np.testing.assert_allclose(a.logprobs, b.logprobs,
                               atol=_logprob_atol(cell), rtol=0)
    assert eng.stats["cp_ring_steps"] > 0


@pytest.mark.parametrize("cell", _matrix_cells())
def test_cp_matrix_radix_hit_parity(cp_setup, matrix_cache, cell):
    """The second request aliases 3 cached prefix pages whose stripe
    spans every rank; exactness must survive the alias in each
    geometry/transport combination."""
    _, dense, _ = cp_setup
    eng = _matrix_engine(matrix_cache, cell)
    prefix = list(range(5, 29))  # 24 tokens = 3 full pages
    _run(eng, prefix + [30, 31])
    hits0 = eng.stats["prefix_hits"]
    got = _run(eng, prefix + [40, 41, 42])
    assert eng.stats["prefix_hits"] > hits0
    want = dense.run(prefix + [40, 41, 42])
    assert got.generated == want.generated
    np.testing.assert_allclose(got.logprobs, want.logprobs,
                               atol=_logprob_atol(cell), rtol=0)


@pytest.mark.parametrize("cell", _matrix_cells())
def test_cp_matrix_preempt_resume_parity(cp_setup, matrix_cache, cell):
    """Preempt mid-prefill, resume, and still land the exact tokens the
    uninterrupted run produces — per geometry and transport."""
    _, dense, _ = cp_setup
    eng = _matrix_engine(matrix_cache, cell)
    prompt = [int(t) for t in
              np.random.default_rng(11).integers(1, 64, 40)]
    req = eng.submit(_req(prompt, 6))
    eng.step()  # admit + first chunk
    eng.step()  # second chunk (prompt is 5 chunks of 8)
    assert eng.prefill_queue.peek() is not None  # mid-prefill
    assert eng._preempt_one()
    eng.run_until_idle()
    assert req.error is None, req.error
    want = dense.run(prompt, 6)
    assert req.generated == want.generated
    np.testing.assert_allclose(req.logprobs, want.logprobs,
                               atol=_logprob_atol(cell), rtol=0)


def test_cp_overlap_moves_the_serial_rings_hops_and_bytes(matrix_cache):
    """Overlap moves exposed time, never traffic. Statically: the
    committed decode_cp2_overlap golden carries EXACTLY the serial
    ring's (decode_tp2_cp2) ppermute rows. At run time (order-dependent
    on the matrix scenarios above, which gave both engines the same
    requests): equal ring steps and equal bytes on the wire."""
    from megatron_tpu.analysis import contracts

    def ppermute_rows(name):
        rows = contracts.load_manifest(name)["jaxpr"]["collectives"]
        return {k: (v["count"], v["total_wire_bytes"])
                for k, v in rows.items() if k.startswith("ppermute")}

    rows = ppermute_rows("decode_cp2_overlap")
    assert rows and rows == ppermute_rows("decode_tp2_cp2")
    serial = matrix_cache["ring_serial_dense"].stats
    over = matrix_cache["ring_overlap_dense"].stats
    assert serial["cp_ring_steps"] == over["cp_ring_steps"] > 0
    assert serial["cp_comm_dense_bytes"] == over["cp_comm_dense_bytes"] > 0


def test_cp_matrix_zero_decode_recompiles(matrix_cache):
    """Order-dependent on the matrix scenarios above: every cell's
    decode step must have compiled exactly once across fresh + radix +
    preempt traffic."""
    assert matrix_cache, "matrix scenarios did not run"
    for cell, eng in sorted(matrix_cache.items()):
        assert eng.stats["decode_recompiles"] == 0, cell


# ---------------------------------------------------------------------------
# satellite 1 (ISSUE 20): striped-pool exhaustion is a first-class
# admission signal — the dry shard is named in the 503 detail, counted
# per shard, and journaled once per episode.


def test_cp_pool_exhaustion_names_dry_shards(tmp_path):
    import json as _json

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 (fake) devices")
    from megatron_tpu.telemetry.journal import (
        EventJournal, set_global_journal,
    )

    rt = build_mesh(ParallelConfig(context_parallel=2),
                    devices=jax.devices()[:2])
    sp = shard_tree(rt, PARAMS, param_specs(CFG))
    eng = ContextParallelEngine(CFG, sp, num_slots=2, max_seq_len=64,
                                page_size=8, prefill_chunk=8, mesh=rt.mesh)
    set_global_journal(EventJournal(str(tmp_path)))
    try:
        # drain the pool: striped pairs, then each rank's uneven tail
        # (rank 0's shard is one short — the scratch page lives there)
        f = eng.pool.free_pages_by_rank()
        grabbed = eng._alloc_pages(2 * min(f))
        assert grabbed is not None
        for r, extra in enumerate(f):
            for _ in range(extra - min(f)):
                tail = eng._alloc_pages(1, logical_start=r)
                assert tail is not None
                grabbed += tail
        assert eng.pool.free_pages == 0
        assert eng._overload_detail() == ""
        # both shards dry: the striped pair cannot fit anywhere
        assert eng._alloc_pages(2) is None
        assert eng.stats["cp_admission_blocked"] == 1
        blocked = eng.metrics.counter("engine_cp_admission_blocked_total",
                                      label_names=("shard",))
        assert blocked.value(shard="0") == 1.0
        assert blocked.value(shard="1") == 1.0
        assert "cp shard(s) 0,1 exhausted" in eng._overload_detail()
        # a retried tick re-counts but does NOT re-journal (per episode)
        assert eng._alloc_pages(2) is None
        assert eng.stats["cp_admission_blocked"] == 2
        # the 503 rejection carries the shard detail, distinct from
        # plain queue depth
        eng.max_queue = 0
        rej = eng.submit(_req([1, 2, 3], 2))
        assert rej.overloaded
        assert "cp shard(s) 0,1 exhausted" in rej.error
        # free one rank-1 page: only shard 0 now blocks a striped pair —
        # a NEW episode (different dry set) journals again
        page1 = next(p for p in grabbed if eng.pool.owner(p) == 1)
        eng.pool.release([page1])
        assert eng._alloc_pages(2) is None
        assert "cp shard(s) 0 exhausted" in eng._overload_detail()
        # a successful grab (the freed rank-1 page) clears the episode
        got = eng._alloc_pages(1, logical_start=1)
        assert got is not None
        assert eng._overload_detail() == ""
    finally:
        set_global_journal(None)
    events = [_json.loads(line)
              for line in open(tmp_path / "events.jsonl")]
    dry = [e for e in events if e["kind"] == "cp_admission_blocked"]
    assert [e["shards"] for e in dry] == [[0, 1], [0]]
    assert dry[0]["free_by_rank"] == [0, 0] and dry[0]["need"] == [1, 1]


# ---------------------------------------------------------------------------
# CP x DP fleet geometry (ISSUE 20 tentpole part 3): one host, multiple
# independent CP engine lanes behind one GenerationService.


def test_cp_lanes_service_dispatch_and_metrics():
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 (fake) devices")
    from megatron_tpu.inference.fleet.scrape import (
        parse_prom_text, replica_load, sample_sum,
    )
    from megatron_tpu.inference.server import GenerationService
    from megatron_tpu.telemetry.metrics import MetricsRegistry
    from megatron_tpu.tokenizer.tokenizer import NullTokenizer

    rt = build_mesh(ParallelConfig(context_parallel=2),
                    devices=jax.devices()[:2])
    sp = shard_tree(rt, PARAMS, param_specs(CFG))
    svc = GenerationService(CFG, sp, NullTokenizer(CFG.vocab_size - 1),
                            mesh=rt.mesh, engine_slots=2,
                            engine_max_seq_len=64,
                            page_size=8, prefill_chunk=8,
                            cp_serving=True, cp_lanes=2,
                            metrics=MetricsRegistry())
    try:
        # two live lanes over disjoint cp-sized device groups
        assert len(svc.engines) == 2
        d0 = {d.id for d in svc.engines[0].mesh.devices.flat}
        d1 = {d.id for d in svc.engines[1].mesh.devices.flat}
        assert len(d0) == 2 and len(d1) == 2 and not (d0 & d1)
        # a real request through the dispatch path completes on a lane
        out = svc.handle({"prompts": ["5 9 13 2 7"],
                          "tokens_to_generate": 4, "temperature": 0.0})
        assert out["text"] and out["text"][0]
        # least-loaded pick: busy slots + queue depth, min wins
        class _Lane:
            def __init__(self, busy, queued):
                self.num_active = busy
                self._queue = [None] * queued

        real = svc.engines
        svc.engines = [_Lane(2, 1), _Lane(1, 1)]
        assert svc._pick_lane() is svc.engines[1]
        svc.engines = real
        # per-lane series share one exposition; the fleet load scrape
        # SUMS lanes into the replica's dispatch score
        svc.engines[1]._m_active.set(2.0)
        text = svc.metrics.render()
        assert 'lane="0"' in text and 'lane="1"' in text
        samples = parse_prom_text(text)
        assert sample_sum(samples, "engine_slots_total") == 4.0
        assert replica_load(samples) == sample_sum(
            samples, "engine_slots_active") + sample_sum(
                samples, "engine_queue_depth", default=0.0)
        assert replica_load(samples) >= 2.0
    finally:
        svc.shutdown()


def test_cp_lanes_validation():
    from megatron_tpu.inference.server import (
        GenerationService, _lane_meshes,
    )
    from megatron_tpu.tokenizer.tokenizer import NullTokenizer

    tok = NullTokenizer(CFG.vocab_size - 1)
    with pytest.raises(ValueError, match="serve_context_parallel"):
        GenerationService(CFG, PARAMS, tok, cp_lanes=2)
    with pytest.raises(ValueError, match="migration"):
        GenerationService(CFG, PARAMS, tok, cp_serving=True, cp_lanes=2,
                          peers=["http://sibling:9000"])
    if len(jax.devices()) >= 4:
        # a tensor-sharded mesh cannot carve replicated lanes
        rt = build_mesh(ParallelConfig(tensor_parallel=2,
                                       context_parallel=2),
                        devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="context-only mesh"):
            _lane_meshes(rt.mesh, 2)
    if len(jax.devices()) == 8:
        rt4 = build_mesh(ParallelConfig(context_parallel=4),
                         devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="only 8 visible"):
            _lane_meshes(rt4.mesh, 3)  # 12 devices needed
