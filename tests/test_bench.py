"""bench.py contract: every stdout line is parseable JSON with
metric/value/unit/vs_baseline, and the headline llama_train_step_mfu line
comes LAST (the driver parses the final line; full runs emit the
serve_decode_throughput_toks_per_s line before it). Run the full candidate
search at a tiny geometry (headline geometry monkeypatched) so the
selection logic, OOM handling shape, and output schema are exercised
hermetically."""

import io
import json
from contextlib import redirect_stdout

import numpy as np

import pytest


@pytest.fixture
def on_cpu(monkeypatch):
    """bench.main() refuses to run without a TPU (test_bench_needs_a_tpu).
    The plumbing tests below steer it from here — the program has no
    option for it: the device gate answers with what JAX found, the peak
    table with the v5e figure, and the kernels run interpreted. (The
    compile cache is off for the whole suite: tests/conftest.py.)"""
    import bench
    from megatron_tpu.platform import device_summary

    monkeypatch.setattr(bench, "require_tpu", device_summary)
    monkeypatch.setattr(bench, "peak_bf16_flops", lambda dev: 197e12)
    monkeypatch.setenv("MEGATRON_TPU_FLASH_INTERPRET", "1")


def test_bench_needs_a_tpu(capsys):
    """No TPU, no number: main() raises before it measures or prints
    anything (as a script: a traceback and a non-zero exit code)."""
    import bench

    with pytest.raises(RuntimeError, match="needs a TPU"):
        bench.main()
    assert capsys.readouterr().out == ""


def test_bench_main_emits_one_json_line(monkeypatch, on_cpu):
    import bench
    from megatron_tpu.models import presets

    for var in ("MEGATRON_TPU_BENCH_QUICK", "MEGATRON_TPU_BENCH_BUDGET_S",
                "MEGATRON_TPU_PROFILE_DIR"):
        monkeypatch.delenv(var, raising=False)

    def tiny_headline(seq_length=2048):
        return presets.tiny(vocab_size=128, seq_length=64, hidden_size=32,
                            num_layers=2, num_attention_heads=4,
                            num_kv_heads=2, ffn_hidden_size=64,
                            params_dtype="float32")

    monkeypatch.setattr(bench, "headline_config", tiny_headline)
    # keep runtime sane on CPU: two candidates, 1 timed iter, and a
    # shrunk speculative leg (2 slots, 16 tokens, 1 drain — the full
    # default geometry runs in the slow speedup-gate test below)
    monkeypatch.setattr(bench, "CANDIDATES", (
        dict(micro_bs=2, granularity="selective", ce_chunk=0),
        dict(micro_bs=2, granularity="selective", ce_chunk=16),
    ))
    import functools

    monkeypatch.setattr(
        bench, "serve_speculative_bench",
        functools.partial(bench.serve_speculative_bench, num_slots=2,
                          new_tokens=16, reps=1))
    monkeypatch.setattr(
        bench, "serving_engine_bench",
        functools.partial(bench.serving_engine_bench, num_slots=2,
                          new_tokens=12))
    monkeypatch.setattr(
        bench, "serve_prefix_cache_bench",
        functools.partial(bench.serve_prefix_cache_bench, num_requests=4,
                          new_tokens=2))
    monkeypatch.setattr(
        bench, "serve_slo_bench",
        functools.partial(bench.serve_slo_bench, num_requests=8,
                          new_tokens=4))
    monkeypatch.setattr(
        bench, "serve_compressed_comm_bench",
        functools.partial(bench.serve_compressed_comm_bench,
                          num_slots=2, new_tokens=8, reps=1))
    monkeypatch.setattr(
        bench, "serve_longctx_prefill_bench",
        functools.partial(bench.serve_longctx_prefill_bench,
                          prompt_len=48, prefill_chunk=16, new_tokens=2,
                          reps=1, cfg=tiny_headline()))
    monkeypatch.setattr(
        bench, "serve_cp_overlap_bench",
        functools.partial(bench.serve_cp_overlap_bench,
                          prompt_len=24, prefill_chunk=16, new_tokens=2,
                          cfg=tiny_headline(), trace=False))
    monkeypatch.setattr(
        bench, "train_attention_bwd_bench",
        functools.partial(bench.train_attention_bwd_bench, s=128, d=32,
                          iters=1))
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench.main()
    lines = [l for l in buf.getvalue().splitlines() if l.strip()]
    # full (non-quick) runs: the serving metric lines + the preemption
    # notice-budget line + the flash-bwd gate line, then the headline
    # LAST (the only positional contract the driver relies on)
    assert len(lines) == 10
    serve = json.loads(lines[0])
    assert serve["metric"] == "serve_decode_throughput_toks_per_s"
    assert set(serve) >= {"metric", "value", "unit", "vs_baseline"}
    assert "error" not in serve and serve["value"] > 0
    assert serve["detail"]["decode_recompiles_after_warmup"] == 0
    prefix = json.loads(lines[1])
    assert prefix["metric"] == "serve_prefix_cache_speedup"
    assert "error" not in prefix, prefix
    # the acceptance floor: >= 1.5x prefill-token savings on
    # shared-system-prompt traffic via the radix prefix cache
    assert prefix["value"] >= 1.5, prefix
    assert prefix["detail"]["decode_recompiles_after_warmup"] == 0
    spec = json.loads(lines[2])
    assert spec["metric"] == "serve_speculative_speedup"
    assert "error" not in spec, spec
    # tier-1 gates only the DETERMINISTIC facts (accept rate off the
    # engine counters, zero recompiles, greedy parity is asserted
    # inside the bench itself); the >= 2x wall-clock gate is the slow
    # test below — a timing ratio in tier-1 flakes under suite load
    assert spec["detail"]["accept_rate"] >= 0.9, spec
    assert spec["detail"]["decode_recompiles_after_warmup"] == 0
    assert spec["vs_baseline"] > 0, spec
    comm = json.loads(lines[3])
    assert comm["metric"] == "serve_compressed_comm"
    assert "error" not in comm, comm
    # the deterministic gate: the committed manifest pair must show the
    # >= 3x wire-byte reduction (wall delta is informational on CPU)
    assert comm["value"] >= 3.0, comm
    assert comm["detail"]["decode_recompiles_after_warmup"] == 0
    assert comm["detail"]["counter_compressed_bytes"] > 0
    lctx = json.loads(lines[4])
    assert lctx["metric"] == "serve_longctx_prefill"
    assert "error" not in lctx, lctx
    # the deterministic gates: CP chunked prefill + ring decode stay
    # token-identical to the single-host paged engine, zero recompiles
    # (throughput vs_baseline is informational on CPU fake devices)
    assert lctx["value"] > 0, lctx
    assert lctx["detail"]["greedy_tokens_match_single_host"], lctx
    assert lctx["detail"]["decode_recompiles_after_warmup"] == 0
    assert lctx["detail"]["cp_ring_steps"] > 0
    ovl = json.loads(lines[5])
    assert ovl["metric"] == "serve_cp_overlap"
    assert "error" not in ovl, ovl
    # the deterministic gates: the overlapped schedule's committed
    # golden carries EXACTLY the serial ring's ppermute rows (same
    # hops, same bytes — only exposed time moves), greedy stays token-
    # identical both ways, and the runtime ring counters agree
    assert ovl["detail"]["golden_hops_bytes_match_serial_ring"], ovl
    assert all(ovl["detail"]["greedy_tokens_match_single_host"].values())
    assert ovl["detail"]["ring_steps_equal"], ovl
    assert ovl["detail"]["ring_bytes_equal"], ovl
    assert ovl["detail"]["decode_recompiles_after_warmup"] == 0
    slo = json.loads(lines[6])
    assert slo["metric"] == "serve_slo_offered_load"
    assert "error" not in slo, slo
    # every request must complete (a lost request zeroes the line) and
    # the percentile block must be populated
    assert slo["value"] > 0 and slo["detail"]["failed"] == 0, slo
    assert set(slo["detail"]["ttft_s"]) == {"p50", "p95", "p99"}
    pre = json.loads(lines[7])
    assert pre["metric"] == "preempt_save_latency_ms"
    assert "error" not in pre, pre
    assert pre["value"] > 0
    fb = json.loads(lines[8])
    assert fb["metric"] == "train_attention_bwd_speedup"
    assert "error" not in fb, fb
    # the deterministic gate: the gradient jaxpr contains the template's
    # kernels and the --no_flash_bwd escape hatch's doesn't (wall
    # speedup is informational — CPU runs the pallas interpreter)
    assert fb["detail"]["bwd_jaxpr_has_kernel"], fb
    assert fb["detail"]["dense_jaxpr_kernel_free"], fb
    assert fb["detail"]["kernel_calls_in_grad"] >= 3, fb
    out = json.loads(lines[-1])
    assert out["metric"] == "llama_train_step_mfu"
    assert set(out) >= {"metric", "value", "unit", "vs_baseline", "detail"}
    # tiny-on-CPU MFU rounds to ~0; the contract is shape, not magnitude
    assert out["value"] >= 0 and np.isfinite(out["value"])
    d = out["detail"]
    assert d["micro_bs"] == 2 and d["recompute"] == "selective"
    assert len(d["sweep"]) == 2
    assert all(("mfu" in s) or s.get("oom") for s in d["sweep"])


def test_bench_extras_ride_in_detail(monkeypatch, on_cpu):
    """Forced extras at tiny geometry: largest_trainable reports a fitting
    config, serving bench reports decode throughput on int8 weights."""
    import bench
    from megatron_tpu.models import presets

    tiny = presets.tiny(vocab_size=128, seq_length=64, hidden_size=32,
                        num_layers=2, num_attention_heads=4, num_kv_heads=2,
                        ffn_hidden_size=64, params_dtype="float32")
    monkeypatch.delenv("MEGATRON_TPU_PROFILE_DIR", raising=False)
    monkeypatch.setenv("MEGATRON_TPU_BENCH_QUICK", "1")
    monkeypatch.setenv("MEGATRON_TPU_BENCH_EXTRAS", "1")
    monkeypatch.setenv("MEGATRON_TPU_BENCH_BUDGET_S", "600")
    monkeypatch.setattr(bench, "headline_config", lambda seq_length=2048: tiny)
    monkeypatch.setattr(bench, "CANDIDATES", (
        dict(micro_bs=2, granularity="selective", ce_chunk=0),))
    monkeypatch.setattr(bench, "largest_candidates", lambda: [tiny])
    orig = bench.serving_int8_7b_bench
    monkeypatch.setattr(
        bench, "serving_int8_7b_bench",
        lambda deadline, **kw: orig(deadline, cfg=tiny, B=2, prompt_len=8,
                                    new_tokens=4, **kw))
    # stub the async-loop micro-bench: it runs three TrainLoops (~25s);
    # the real function is acceptance-tested in its own subprocess
    # (test_prefetch.py::test_async_loop_recovers_injected_data_stall) —
    # here only the extras WIRING is under test
    monkeypatch.setattr(bench, "async_loop_bench",
                        lambda deadline, **kw: {"stubbed": True})
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench.main()
    out = json.loads(buf.getvalue().strip())
    assert out["detail"]["async_loop"] == {"stubbed": True}
    lt = out["detail"]["largest_trainable"]
    assert lt["hidden"] == 32 and lt["mfu"] >= 0
    sv = out["detail"]["serving_int8_7b"]
    assert sv["decode_tokens_per_sec"] > 0
    assert sv["weights"].startswith("int8")
    fp8 = out["detail"]["serving_fp8_7b"]
    assert fp8["decode_tokens_per_sec"] > 0
    assert fp8["weights"].startswith("fp8")


@pytest.mark.slow  # ~35s: two recipe-geometry engines, median-of-3
# drains each way; the acceptance gate for the >= 2x speculative
# speedup claim (timed, so it must run solo — the tier-1 smoke above
# gates only the deterministic accept-rate/recompile facts)
def test_serve_speculative_bench_speedup_gate(monkeypatch):
    import time

    import bench

    line = bench.serve_speculative_bench(time.perf_counter() + 280)
    assert "error" not in line, line
    assert line["detail"]["accept_rate"] >= 0.95, line
    assert line["detail"]["decode_recompiles_after_warmup"] == 0
    # >= 2x tokens/s vs the same engine without speculation on the
    # high-acceptance CPU micro-bench (ISSUE 9 acceptance criterion;
    # measured 2.3-3.0x across quiet runs)
    assert line["vs_baseline"] >= 2.0, line


@pytest.mark.slow  # ~12s: one tiny in-process TrainLoop preempted by a
# real self-delivered SIGTERM; gates the pre-headline
# preempt_save_latency_ms line (ISSUE 11 satellite) — the notice budget
# tracked across PRs
def test_preempt_save_bench_line(monkeypatch):
    import time

    import bench

    line = bench.preempt_save_bench(time.perf_counter() + 280)
    assert "error" not in line, line
    assert line["metric"] == "preempt_save_latency_ms"
    # SIGTERM -> committed checkpoint: a real positive wall time, and
    # sane on this host (the tiny model commits in well under a minute)
    assert 0 < line["value"] < 60_000, line
    assert line["detail"]["save_latency_ms"] <= line["value"]


def test_bench_quick_mode(monkeypatch, on_cpu):
    import bench
    from megatron_tpu.models import presets

    monkeypatch.delenv("MEGATRON_TPU_PROFILE_DIR", raising=False)
    monkeypatch.setenv("MEGATRON_TPU_BENCH_QUICK", "1")
    monkeypatch.setattr(bench, "headline_config",
                        lambda seq_length=2048: presets.tiny(
                            vocab_size=128, seq_length=64, hidden_size=32,
                            num_layers=2, num_attention_heads=4,
                            num_kv_heads=2, ffn_hidden_size=64,
                            params_dtype="float32"))
    monkeypatch.setattr(bench, "CANDIDATES", (
        dict(micro_bs=2, granularity="selective", ce_chunk=0),
        dict(micro_bs=999, granularity="none", ce_chunk=0),  # must NOT run
    ))
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench.main()
    out = json.loads(buf.getvalue().strip())
    assert len(out["detail"]["sweep"]) == 1


def test_bench_profile_dir_attaches_trace_split(monkeypatch, tmp_path,
                                                on_cpu):
    """ISSUE 13: with MEGATRON_TPU_PROFILE_DIR set, the headline detail
    carries the comm/compute/exposed split decoded from the re-run's
    xplane trace."""
    import bench
    from megatron_tpu.models import presets

    monkeypatch.setenv("MEGATRON_TPU_BENCH_QUICK", "1")
    monkeypatch.setenv("MEGATRON_TPU_PROFILE_DIR",
                       str(tmp_path / "prof"))
    monkeypatch.setattr(bench, "headline_config",
                        lambda seq_length=2048: presets.tiny(
                            vocab_size=128, seq_length=64, hidden_size=32,
                            num_layers=2, num_attention_heads=4,
                            num_kv_heads=2, ffn_hidden_size=64,
                            params_dtype="float32"))
    monkeypatch.setattr(bench, "CANDIDATES", (
        dict(micro_bs=2, granularity="selective", ce_chunk=0),))
    buf = io.StringIO()
    with redirect_stdout(buf):
        bench.main()
    out = json.loads(buf.getvalue().strip())
    split = out["detail"]["trace_split"]
    assert split["busy_s"]["compute"] > 0
    assert split["module"]  # the jitted step dominated the trace
    assert "collectives" in split and "exposed_collective_s" in split
