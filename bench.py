"""Benchmark: llama-architecture training-step MFU on one TPU chip.

Prints one JSON line per metric, the headline last:
  {"metric": "serve_decode_throughput_toks_per_s", ...}   (full runs)
  {"metric": "llama_train_step_mfu", "value": N, ...}     (always, LAST)

Method: jitted full training step (fwd + bwd + Adam with fp32 masters,
selective recompute, bf16 compute) on a llama-family model sized to fit one
chip's HBM alongside optimizer state. MFU = achieved model FLOP/s over the
chip's peak bf16 FLOP/s, with model FLOPs = 3x forward (fwd + 2x bwd), the
convention the reference's FLOP formula supports
(ref: megatron/model/language_model.py:370-384).

Baseline (BASELINE.md): the reference's Llama-2-7B finetune does ~0.9k
tokens/s per A100-80GB => MFU = 900 * 6 * 6.74e9 / 312e12 = 0.1166.
vs_baseline is our MFU / that.

A run that finds no TPU fails (non-zero exit, no metric line): nothing here
continues on the CPU or prints a CPU number under a device metric's name.
The metrics themselves are the next benchmark PR's to redesign (ROADMAP S1).

Beyond the 637M headline point, two honest 7B-class numbers ride along in
"detail" when time remains (BASELINE.md's north star is Llama-2-7B, which
cannot *train* on one 16 GB chip):
  - largest_trainable: the biggest llama-geometry model whose full train
    step fits on-chip (descending search), with its own MFU;
  - serving_int8_7b: Llama-2-7B-geometry int8-weight decode throughput
    (random weights; weights alone are 14 GB bf16, so int8 is what makes
    7B serving on this chip possible at all).

tools/bench_sweep.py imports headline_config/build_step/time_step so sweep
points are measured with exactly the headline methodology.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

from megatron_tpu.platform import (
    device_summary, enable_compile_cache, peak_bf16_flops, require_tpu,
)

BASELINE_MFU = 900 * 6 * 6.74e9 / 312e12  # reference A100 finetune

# Goodput ledger for the whole bench process (set by main() once jax is
# up): timed step iterations are attributed productive, XLA compiles from
# the recompile tracker, the remainder (host param fills,
# serving drains) lands in `other`. Rides the headline JSON line as
# detail["goodput"] so the driver's record of a round says not just the
# MFU but where the bench's wall-clock went (tools/telemetry_report.py
# prints the same split for training journals).
GOODPUT = None


def headline_config(seq_length: int = 2048):
    """The headline bench geometry: llama-family, ~640M params — fits one
    chip's HBM with fp32 master + Adam moments."""
    from megatron_tpu.models import presets

    return presets.tiny(
        vocab_size=32000, seq_length=seq_length, hidden_size=2048,
        num_layers=10, num_attention_heads=16, num_kv_heads=16,
        ffn_hidden_size=5504, params_dtype="bfloat16",
        attention_impl="pallas",
    )


def build_step(cfg, micro_bs: int, granularity: str):
    """(state, jitted_step, batch) for one config; fresh state every call."""
    import jax
    import jax.numpy as jnp

    from megatron_tpu.config import OptimizerConfig, TrainingConfig
    from megatron_tpu.models.params import init_params
    from megatron_tpu.training.optimizer import init_train_state
    from megatron_tpu.training.train_step import make_train_step

    opt_cfg = OptimizerConfig(lr=1e-4, lr_decay_style="constant")
    tcfg = TrainingConfig(micro_batch_size=micro_bs,
                          global_batch_size=micro_bs,
                          recompute_granularity=granularity, seed=0)
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (micro_bs, cfg.seq_length)),
            jnp.int32),
        "labels": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (micro_bs, cfg.seq_length)),
            jnp.int32),
        "loss_mask": jnp.ones((micro_bs, cfg.seq_length), jnp.float32),
    }
    params = init_params(cfg, jax.random.PRNGKey(0))
    state = init_train_state(opt_cfg, params)
    step = jax.jit(
        make_train_step(cfg, opt_cfg, tcfg, num_microbatches=1,
                        train_iters=1000),
        donate_argnums=(0,),
    )
    return state, step, batch


def time_step(state, step, batch, iters: int = 5):
    """(seconds_per_step, loss, state) after a 2-step warmup; the timed
    window ends in block_until_ready (it waits for the device —
    chip_smoke.py's device phase checks that on every run)."""
    import jax

    for _ in range(2):
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, batch)
    jax.block_until_ready(metrics["loss"])
    dt = (time.perf_counter() - t0) / iters
    return dt, float(metrics["loss"]), state


def is_oom(e: Exception) -> bool:
    return "RESOURCE_EXHAUSTED" in str(e) or "memory" in str(e).lower()


# operating points searched by main(), best MFU wins. First entry is the
# round-2 verified point (mbs 4, selective, 0.5303 MFU) so even a
# quick/degraded run reports a sane number; the chunked-CE variants free
# the ~2 GB [B,S,V] logits residency and may unlock recompute=none or
# mbs 8 (sweep showed both OOM unchunked).
CANDIDATES = (
    dict(micro_bs=4, granularity="selective", ce_chunk=0),
    dict(micro_bs=4, granularity="none", ce_chunk=512),
    dict(micro_bs=8, granularity="selective", ce_chunk=512),
    dict(micro_bs=4, granularity="selective", ce_chunk=512),
    dict(micro_bs=8, granularity="selective", ce_chunk=0),
)


def _cfg_for(cfg, ce_chunk):
    """Apply a candidate's config variant (single source for measuring AND
    profiling — they must never diverge)."""
    import dataclasses

    if ce_chunk:
        return dataclasses.replace(cfg, ce_chunk_size=ce_chunk).validate()
    return cfg


def _measure(cfg, micro_bs, granularity, ce_chunk, iters=5):
    """(dt, loss) or raises; applies the chunked-CE variant."""
    import gc

    cfg = _cfg_for(cfg, ce_chunk)
    state, step, batch = build_step(cfg, micro_bs, granularity)
    try:
        dt, loss, state = time_step(state, step, batch, iters=iters)
        if GOODPUT is not None:
            GOODPUT.attribute("productive", dt * iters)
        return dt, loss
    finally:
        del state, step, batch
        gc.collect()


# ---------------------------------------------------------------------------
# extra 7B-class points (VERDICT r2 next-round #3)

def largest_candidates():
    """Llama-geometry configs, descending by params; the search reports the
    first whose full train step fits on-chip."""
    from megatron_tpu.models import presets

    geoms = (  # (hidden, layers, heads)
        (2816, 18, 22),
        (2560, 18, 20),
        (2560, 14, 20),
        (2304, 14, 18),
    )
    out = []
    for h, L, nh in geoms:
        ffn = int(round(8 * h / 3 / 256)) * 256
        out.append(presets.tiny(
            vocab_size=32000, seq_length=2048, hidden_size=h, num_layers=L,
            num_attention_heads=nh, num_kv_heads=nh, ffn_hidden_size=ffn,
            params_dtype="bfloat16", attention_impl="pallas"))
    return out


def largest_trainable_bench(deadline, peak):
    """Largest on-chip-trainable llama geometry + its MFU, or an error
    record. Descending search; per-geometry (mbs, recompute) tiers from
    fastest to most memory-frugal — chunked CE throughout, selective
    first, then full and sqrt-remat (uniform:N) which trade step time for
    fitting a bigger model (the metric here is SIZE, not MFU)."""
    from megatron_tpu.models.params import num_params

    for cfg in largest_candidates():
        ce_chunk = 512 if cfg.seq_length % 512 == 0 else 0
        # sqrt-remat chunk must DIVIDE the layer count (scan_with_remat
        # raises otherwise — and that ValueError is not an OOM, it would
        # abort the whole search): nearest divisor of L to sqrt(L), >1
        L = cfg.num_layers
        divs = [d for d in range(2, L + 1) if L % d == 0]
        chunk = min(divs, key=lambda d: abs(d - L ** 0.5)) if divs else 1
        tiers = [(2, "selective"), (1, "selective"), (1, "full")]
        if chunk > 1:
            tiers.append((1, f"uniform:{chunk}"))
        for mbs, gran in tiers:
            if deadline - time.perf_counter() < 45:
                return {"error": "budget_exhausted"}
            try:
                dt, loss = _measure(cfg, mbs, gran, ce_chunk, iters=3)
            except Exception as e:
                if not is_oom(e):
                    return {"error": str(e)[:300]}
                print(f"# largest: h={cfg.hidden_size} L={cfg.num_layers} "
                      f"mbs={mbs} {gran} OOM", file=sys.stderr)
                continue
            n = num_params(cfg)
            tps = mbs * cfg.seq_length / dt
            mfu = tps * 3.0 * cfg.flops_per_token_fwd() / peak
            return {
                "n_params": n,
                "hidden": cfg.hidden_size, "layers": cfg.num_layers,
                "micro_bs": mbs, "seq": cfg.seq_length,
                "recompute": gran,
                "mfu": round(mfu, 4),
                "tokens_per_sec_per_chip": round(tps),
                "step_ms": round(dt * 1e3, 2), "loss": loss,
            }
    return {"error": "all_geometries_oom"}


def _host_random_params(cfg, seed=0, std=0.02):
    """Random param tree built on HOST (numpy) from eval_shape — a 7B bf16
    tree must never materialize on a 16 GB device."""
    import jax

    from megatron_tpu.models.params import init_params

    shapes = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    rng = np.random.default_rng(seed)

    def mk(s):
        return (rng.standard_normal(s.shape, np.float32) * std).astype(s.dtype)

    return jax.tree.map(mk, shapes)


_SERVING_HOST_CACHE = {}


def serving_int8_7b_bench(deadline, cfg=None, B=4, prompt_len=64,
                          new_tokens=128, mode="int8"):
    """Llama-2-7B geometry, int8 or fp8(e4m3) weights, decode tokens/s
    (random weights — throughput is weight-value-independent). Ref north
    star: BASELINE.md; the fp8 point answers VERDICT r4 #7's fp8 half.
    The host random tree is cached per geometry so the int8 and fp8
    points pay the 7B host fill once."""
    from megatron_tpu.inference.generation import generate_tokens
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import num_params
    from megatron_tpu.ops.weight_quant import quantize_params_for_serving

    cfg = cfg or presets.llama("7B", version=2, seq_length=2048)
    if deadline - time.perf_counter() < 60:
        return {"error": "budget_exhausted"}
    try:
        import jax

        # quantize on host, then place the int8 tree on-device ONCE —
        # _generate_jit traces params, so numpy leaves would re-transfer
        # ~7 GB inside every (timed) call
        key = (cfg.hidden_size, cfg.num_layers, cfg.vocab_size,
               cfg.seq_length)
        if key not in _SERVING_HOST_CACHE:
            _SERVING_HOST_CACHE[key] = _host_random_params(cfg)
        params = jax.device_put(
            quantize_params_for_serving(_SERVING_HOST_CACHE[key], mode=mode))
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size, (B, prompt_len)).astype(np.int32)
        lengths = np.full((B,), prompt_len, np.int32)

        def run():
            return generate_tokens(cfg, params, prompts, lengths,
                                   max_new_tokens=new_tokens, temperature=1.0,
                                   top_k=1, eod=None, want_logprobs=False)

        run()  # compile + transfer
        t0 = time.perf_counter()
        run()
        dt = time.perf_counter() - t0
        tps = B * new_tokens / dt
        return {
            "n_params": num_params(cfg),
            "batch": B, "prompt_len": prompt_len, "new_tokens": new_tokens,
            "decode_tokens_per_sec": round(tps, 1),
            "weights": ("int8 (per-channel symmetric)" if mode == "int8"
                        else "fp8 e4m3 (per-channel amax)"),
        }
    except Exception as e:
        return {"error": str(e)[:300]}


def serving_engine_bench(deadline, num_slots=4, prompt_len=8, new_tokens=24):
    """Offered-load continuous-batching throughput: submit num_slots
    concurrent requests to an InferenceEngine (inference/engine.py) and
    time the drain against handling the same requests sequentially
    through generate_tokens — one shared jitted batched decode step vs a
    per-request loop. Returns the full metric line; vs_baseline is the
    speedup over sequential handling (> 1 = continuous batching wins, and
    it grows with concurrency until the chip saturates). Geometry rides
    on headline_config so hermetic tests stay tiny."""
    line = {"metric": "serve_decode_throughput_toks_per_s", "value": 0.0,
            "unit": "tokens_per_sec", "vs_baseline": 0.0}
    if deadline - time.perf_counter() < 30:
        line["error"] = "budget_exhausted"
        return line
    try:
        import jax

        from megatron_tpu.inference.engine import InferenceEngine
        from megatron_tpu.inference.generation import generate_tokens
        from megatron_tpu.models.params import init_params

        cfg = headline_config()
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = rng.integers(
            0, cfg.vocab_size, (num_slots, prompt_len)).astype(np.int32)
        lengths = np.full((num_slots,), prompt_len, np.int32)
        eng = InferenceEngine(cfg, params, num_slots=num_slots,
                              max_seq_len=min(cfg.seq_length, 128))

        # warmup compiles both paths: the engine's prefill bucket + the
        # one batched decode step, and the baseline's generate loop
        eng.generate(prompts[:1], lengths[:1], max_new_tokens=new_tokens)
        generate_tokens(cfg, params, prompts[:1], lengths[:1],
                        max_new_tokens=new_tokens, temperature=0.0,
                        want_logprobs=False)

        t0 = time.perf_counter()
        for i in range(num_slots):
            generate_tokens(cfg, params, prompts[i:i + 1], lengths[i:i + 1],
                            max_new_tokens=new_tokens, temperature=0.0,
                            want_logprobs=False)
        t_seq = max(time.perf_counter() - t0, 1e-9)

        def compiles():
            try:  # jitted-fn cache size = number of distinct compiles
                return int(eng._decode_step._cache_size())
            except Exception:  # noqa: BLE001 - diagnostics only
                return -1

        warm = compiles()
        t0 = time.perf_counter()
        eng.generate(prompts, lengths, max_new_tokens=new_tokens)
        t_eng = max(time.perf_counter() - t0, 1e-9)

        tps = num_slots * new_tokens / t_eng
        line.update(
            value=round(tps, 1),
            vs_baseline=round(t_seq / t_eng, 3),
            detail={
                "num_slots": num_slots, "prompt_len": prompt_len,
                "new_tokens": new_tokens,
                "engine_drain_s": round(t_eng, 4),
                "sequential_s": round(t_seq, 4),
                "decode_recompiles_after_warmup": (
                    compiles() - warm if warm >= 0 else -1),
                "hidden": cfg.hidden_size, "layers": cfg.num_layers,
            })
    except Exception as e:  # noqa: BLE001 - the metric line must emit
        line["error"] = str(e)[:300]
    return line


def serve_prefix_cache_bench(deadline, num_requests=8, shared_len=64,
                             unique_len=8, new_tokens=4):
    """Shared-system-prompt traffic through the paged engine
    (inference/paging/): every request is a shared `shared_len`-token
    system prefix plus a distinct `unique_len`-token user suffix — the
    "millions of users, one prompt template" shape. The first request
    populates the radix prefix cache; the rest alias its pages and skip
    prefill for the shared span. value = total prompt tokens / prefill
    tokens actually computed (deterministic — read off the engine's
    counters, not wall clocks); vs_baseline is the wall-time speedup of
    the same traffic vs the slot engine, which recomputes every prefix."""
    line = {"metric": "serve_prefix_cache_speedup", "value": 0.0,
            "unit": "x_prefill_tokens", "vs_baseline": 0.0}
    if deadline - time.perf_counter() < 30:
        line["error"] = "budget_exhausted"
        return line
    try:
        import jax

        from megatron_tpu.inference.engine import InferenceEngine, Request
        from megatron_tpu.inference.paging import PagedInferenceEngine
        from megatron_tpu.models.params import init_params

        cfg = headline_config()
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        shared = rng.integers(1, cfg.vocab_size, shared_len)
        prompts = [np.concatenate([
            shared, rng.integers(1, cfg.vocab_size, unique_len),
        ]).astype(np.int32) for _ in range(num_requests)]

        def drive(eng):
            # first request alone (populates the prefix cache), then the
            # rest concurrently — the arrival pattern a warm template sees
            t0 = time.perf_counter()
            r0 = eng.submit(Request(prompt=prompts[0],
                                    max_new_tokens=new_tokens))
            eng.run_until_idle()
            rest = [eng.submit(Request(prompt=p, max_new_tokens=new_tokens))
                    for p in prompts[1:]]
            eng.run_until_idle()
            for r in [r0] + rest:
                if r.error:
                    raise RuntimeError(r.error)
            return time.perf_counter() - t0

        # page-aligned so neither engine warns about seq-len rounding
        max_len = -(-(shared_len + unique_len + new_tokens + 16) // 16) * 16
        paged = PagedInferenceEngine(cfg, params, num_slots=4,
                                     max_seq_len=max_len,
                                     page_size=16, prefill_chunk=32,
                                     want_logprobs=False)
        drive(paged)  # warmup: compiles chunk + decode steps
        # drop the warmup's radix entries so the measured drive IS the
        # documented cold-template scenario (r0 populates, the rest
        # alias) — without this every request including r0 hits the
        # warm cache and `value` overstates the cold-traffic savings
        paged.prefix_cache.clear()
        warm_computed = paged.stats["prefill_tokens"]
        warm_hits = paged.stats["prefix_hits"]
        t_paged = drive(paged)
        computed = paged.stats["prefill_tokens"] - warm_computed

        slot = InferenceEngine(cfg, params, num_slots=4,
                               max_seq_len=max_len,
                               want_logprobs=False)
        drive(slot)  # warmup
        t_slot = drive(slot)

        total_prompt = num_requests * (shared_len + unique_len)
        line.update(
            value=round(total_prompt / max(computed, 1), 3),
            vs_baseline=round(t_slot / max(t_paged, 1e-9), 3),
            detail={
                "num_requests": num_requests, "shared_len": shared_len,
                "unique_len": unique_len,
                "prefill_tokens_computed": int(computed),
                "prefill_tokens_total": int(total_prompt),
                "prefix_hits": int(paged.stats["prefix_hits"] - warm_hits),
                "paged_wall_s": round(t_paged, 4),
                "slot_wall_s": round(t_slot, 4),
                "decode_recompiles_after_warmup": int(
                    paged.stats["decode_recompiles"]),
                "hidden": cfg.hidden_size, "layers": cfg.num_layers,
            })
    except Exception as e:  # noqa: BLE001 - the metric line must emit
        line["error"] = str(e)[:300]
    return line


def serve_speculative_bench(deadline, num_slots=4, prompt_len=16,
                            new_tokens=64, spec_k=8, reps=3):
    """Speculative-decoding throughput on high-acceptance greedy
    traffic (inference/speculative.py): the same requests drained
    through a plain engine and through one running the zero-weight
    n-gram drafter at k=spec_k. The model's weights are ZEROED so its
    greedy continuation is constant — after a couple of warm-up tokens
    the drafter's prompt-lookup proposals match the target argmax
    every tick, i.e. the documented high-acceptance (repetitive /
    copy-heavy) traffic shape as an upper bound. What the ratio then
    measures is the ENGINE mechanics claim: k+1 tokens emitted per
    single [N, k+1] verify forward, with the accept rate reported
    alongside so the number can be derated for real traffic. Greedy
    parity is asserted inside the bench (spec tokens must equal the
    plain engine's), and "speculation off" IS the baseline engine —
    the non-speculative code path is untouched by the feature."""
    line = {"metric": "serve_speculative_speedup", "value": 0.0,
            "unit": "tokens_per_sec", "vs_baseline": 0.0}
    if deadline - time.perf_counter() < 30:
        line["error"] = "budget_exhausted"
        return line
    try:
        import jax
        import jax.numpy as jnp

        from megatron_tpu.inference.engine import InferenceEngine
        from megatron_tpu.inference.speculative import SpecConfig
        from megatron_tpu.models.params import init_params

        cfg = headline_config()
        params = jax.tree.map(lambda a: jnp.zeros_like(a),
                              init_params(cfg, jax.random.PRNGKey(0)))
        rng = np.random.default_rng(0)
        prompts = rng.integers(
            1, cfg.vocab_size, (num_slots, prompt_len)).astype(np.int32)
        lengths = np.full((num_slots,), prompt_len, np.int32)

        base = InferenceEngine(cfg, params, num_slots=num_slots,
                               max_seq_len=128, want_logprobs=False)
        spec = InferenceEngine(cfg, params, num_slots=num_slots,
                               max_seq_len=128, want_logprobs=False,
                               speculative=SpecConfig(k=spec_k,
                                                      drafter="ngram"))
        # warmup compiles both decode steps + the shared prefill bucket
        base.generate(prompts[:1], lengths[:1], max_new_tokens=new_tokens)
        spec.generate(prompts[:1], lengths[:1], max_new_tokens=new_tokens)

        # median of `reps` interleaved drains: the 2-core host's wall
        # clocks are noisy, and interleaving keeps background load from
        # biasing one engine's measurements
        t_bases, t_specs = [], []
        prop0, acc0 = spec.stats["spec_proposed"], spec.stats["spec_accepted"]
        for _ in range(reps):
            t0 = time.perf_counter()
            want = base.generate(prompts, lengths,
                                 max_new_tokens=new_tokens)
            t_bases.append(max(time.perf_counter() - t0, 1e-9))
            t0 = time.perf_counter()
            got = spec.generate(prompts, lengths,
                                max_new_tokens=new_tokens)
            t_specs.append(max(time.perf_counter() - t0, 1e-9))
            if not np.array_equal(want.tokens, got.tokens):
                raise RuntimeError("speculative greedy output diverged "
                                   "from the plain engine")
        t_base = sorted(t_bases)[reps // 2]
        t_spec = sorted(t_specs)[reps // 2]

        proposed = spec.stats["spec_proposed"] - prop0
        accepted = spec.stats["spec_accepted"] - acc0
        tps = num_slots * new_tokens / t_spec
        line.update(
            value=round(tps, 1),
            vs_baseline=round(t_base / t_spec, 3),
            detail={
                "num_slots": num_slots, "prompt_len": prompt_len,
                "new_tokens": new_tokens, "spec_k": spec_k,
                "drafter": "ngram",
                "baseline_toks_per_s": round(
                    num_slots * new_tokens / t_base, 1),
                "accept_rate": round(accepted / max(proposed, 1), 3),
                "spec_wall_s": round(t_spec, 4),
                "baseline_wall_s": round(t_base, 4),
                "decode_recompiles_after_warmup": int(
                    spec.stats["decode_recompiles"]),
                "model": "zero-weights (constant greedy continuation — "
                         "high-acceptance upper bound; derate by the "
                         "accept rate for real traffic)",
                "hidden": cfg.hidden_size, "layers": cfg.num_layers,
            })
    except Exception as e:  # noqa: BLE001 - the metric line must emit
        line["error"] = str(e)[:300]
    return line


def serve_slo_bench(deadline, num_replicas=2, engine_slots=2,
                    num_requests=18, offered_rps=3.0, new_tokens=8):
    """Offered-load SLO replay through the fleet router
    (inference/fleet/): an in-process fleet of `num_replicas` replica
    servers behind a RouterServer receives a deterministic open-loop
    trace at `offered_rps` (tools/slo_harness.py inlined), and the line
    reports TTFT/TPOT p50/p95/p99 scraped off the engines' Prometheus
    histograms (diffed around the window, so warmup compiles fall out).
    value = achieved completed-requests/s; vs_baseline = achieved/offered
    (1.0 = the fleet keeps up with the offered load; every request must
    complete — a lost request zeroes the line). Tiny deterministic
    geometry on every backend: this measures the control plane's latency
    distribution under load, not model throughput (the throughput story
    is serve_decode_throughput_toks_per_s)."""
    line = {"metric": "serve_slo_offered_load", "value": 0.0,
            "unit": "requests_per_sec", "vs_baseline": 0.0}
    if deadline - time.perf_counter() < 60:
        line["error"] = "budget_exhausted"
        return line
    services, servers, threads = [], [], []
    router = None
    try:
        import threading
        from http.server import ThreadingHTTPServer

        import jax

        from megatron_tpu.inference.fleet import slo
        from megatron_tpu.inference.fleet.router import RouterServer
        from megatron_tpu.inference.server import (
            GenerationService, make_handler,
        )
        from megatron_tpu.models import presets
        from megatron_tpu.models.params import init_params
        from megatron_tpu.telemetry.metrics import MetricsRegistry
        from megatron_tpu.tokenizer.tokenizer import NullTokenizer

        cfg = presets.tiny(vocab_size=64, seq_length=64)
        tok = NullTokenizer(cfg.vocab_size - 1)
        params = init_params(cfg, jax.random.PRNGKey(0))
        urls = []
        for _ in range(num_replicas):
            # per-replica registries: shared default_registry would merge
            # both engines' histograms before the scrape even runs
            # warmup=True defers the warmed flag so svc.warmup() below
            # actually compiles (with the default it's a no-op and the
            # jit compile would land INSIDE the measured SLO window)
            svc = GenerationService(cfg, params, tok,
                                    engine_slots=engine_slots,
                                    engine_max_seq_len=64,
                                    metrics=MetricsRegistry(),
                                    warmup=True)
            srv = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(svc))
            th = threading.Thread(target=srv.serve_forever, daemon=True)
            th.start()
            svc.warmup()
            services.append(svc)
            servers.append(srv)
            threads.append(th)
            urls.append(f"http://127.0.0.1:{srv.server_address[1]}")
        router = RouterServer(urls).start()
        trace = slo.make_trace(num_requests, offered_rps,
                               vocab=cfg.vocab_size, new_tokens=new_tokens)
        report = slo.run_slo(router.url + "/api",
                             [u + "/metrics" for u in urls], trace,
                             offered_rps, timeout=60.0)
        value = report["achieved_rps"] if report["failed"] == 0 else 0.0
        line.update(
            value=value,
            vs_baseline=round(value / offered_rps, 3),
            detail={
                "num_replicas": num_replicas,
                "engine_slots": engine_slots,
                "requests": report["requests"],
                "completed": report["completed"],
                "failed": report["failed"],
                "ttft_s": report["ttft_s"],
                "tpot_s": report["tpot_s"],
                "client_wall_s": report["client_wall_s"],
                "new_tokens": new_tokens,
                "hidden": cfg.hidden_size, "layers": cfg.num_layers,
            })
    except Exception as e:  # noqa: BLE001 - the metric line must emit
        line["error"] = str(e)[:300]
    finally:
        if router is not None:
            router.close()
        for srv in servers:
            srv.shutdown()
            srv.server_close()
        for svc in services:
            svc.shutdown()
    return line


def serve_compressed_comm_bench(deadline, num_slots=4, prompt_len=8,
                                new_tokens=24, reps=3):
    """Compressed TP collectives for serving (megatron_tpu/quant/,
    Flash Communication 2412.04964): value = the contract-verified
    wire-byte reduction between the committed decode_tp2_dense and
    decode_tp2_int8 golden comm manifests — DETERMINISTIC (read off the
    repo, asserted >= 3x by tools/comm_report.py --check and the tier-1
    tests, so a silent revert to dense transport zeroes this line too).
    vs_baseline = the dense/int8 wall ratio of the same greedy traffic
    through two real engines on a tp=2 mesh — informational on CPU
    (2 fake devices on 2 cores pay quantize/dequantize compute without
    real interconnect to save; the byte counters are the gate, the chip
    window turns the wall number real). Needs >= 2 devices for the wall
    leg; the byte ratio emits regardless."""
    line = {"metric": "serve_compressed_comm", "value": 0.0,
            "unit": "x_wire_bytes", "vs_baseline": 0.0}
    try:
        from megatron_tpu.analysis import contracts

        dense_m = contracts.load_manifest("decode_tp2_dense")
        int8_m = contracts.load_manifest("decode_tp2_int8")
        ratio = contracts.compression_ratio(int8_m, dense_m)
        detail = {
            "dense_wire_bytes": dense_m["jaxpr"]["total_wire_bytes"],
            "int8_wire_bytes": int8_m["jaxpr"]["total_wire_bytes"],
            "manifests": ["decode_tp2_dense", "decode_tp2_int8"],
        }
        line.update(value=round(ratio, 3), detail=detail)
    except Exception as e:  # noqa: BLE001 - the metric line must emit
        line["error"] = str(e)[:300]
        return line
    if deadline - time.perf_counter() < 30:
        detail["wall"] = "budget_exhausted"
        return line
    try:
        import jax

        if len(jax.devices()) < 2:
            detail["wall"] = "needs >= 2 devices for the tp=2 wall leg"
            return line

        from megatron_tpu.config import ModelConfig, ParallelConfig
        from megatron_tpu.inference.engine import InferenceEngine
        from megatron_tpu.models.params import init_params, param_specs
        from megatron_tpu.parallel.mesh import build_mesh
        from megatron_tpu.parallel.sharding import shard_tree

        cfg = ModelConfig(
            num_layers=4, hidden_size=128, num_attention_heads=8,
            num_kv_heads=4, ffn_hidden_size=256, vocab_size=1024,
            seq_length=64, params_dtype="float32").validate()
        params = init_params(cfg, jax.random.PRNGKey(0))
        rt = build_mesh(ParallelConfig(tensor_parallel=2),
                        devices=jax.devices()[:2])
        sparams = shard_tree(rt, params, param_specs(cfg))
        dense = InferenceEngine(cfg, sparams, num_slots=num_slots,
                                max_seq_len=64, mesh=rt.mesh,
                                want_logprobs=False)
        comp = InferenceEngine(cfg, sparams, num_slots=num_slots,
                               max_seq_len=64, mesh=rt.mesh,
                               want_logprobs=False,
                               compress_collectives="int8")
        rng = np.random.default_rng(0)
        prompts = rng.integers(
            1, cfg.vocab_size, (num_slots, prompt_len)).astype(np.int32)
        lengths = np.full((num_slots,), prompt_len, np.int32)
        # warmup compiles both decode steps + the shared prefill bucket
        dense.generate(prompts[:1], lengths[:1], max_new_tokens=new_tokens)
        comp.generate(prompts[:1], lengths[:1], max_new_tokens=new_tokens)
        t_d, t_c = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            dense.generate(prompts, lengths, max_new_tokens=new_tokens)
            t_d.append(max(time.perf_counter() - t0, 1e-9))
            t0 = time.perf_counter()
            comp.generate(prompts, lengths, max_new_tokens=new_tokens)
            t_c.append(max(time.perf_counter() - t0, 1e-9))
        wall_d = sorted(t_d)[reps // 2]
        wall_c = sorted(t_c)[reps // 2]
        line["vs_baseline"] = round(wall_d / wall_c, 3)
        detail.update({
            "dense_wall_s": round(wall_d, 4),
            "int8_wall_s": round(wall_c, 4),
            "counter_dense_bytes": comp.stats["comm_dense_bytes"],
            "counter_compressed_bytes": comp.stats["comm_compressed_bytes"],
            "decode_recompiles_after_warmup": int(
                comp.stats["decode_recompiles"]),
            "num_slots": num_slots, "new_tokens": new_tokens,
            "hidden": cfg.hidden_size, "layers": cfg.num_layers,
            "wall_note": ("CPU wall is informational: fake devices share "
                          "the host cores, so the quantize math costs "
                          "show and the saved interconnect bytes don't"),
        })
    except Exception as e:  # noqa: BLE001 - pre-headline lines must never
        # cost the run its headline
        detail["wall_error"] = str(e)[:300]
    return line


def serve_longctx_prefill_bench(deadline, prompt_len=192, page_size=8,
                                prefill_chunk=32, new_tokens=4, reps=3,
                                cfg=None):
    """Context-parallel long-context serving
    (megatron_tpu/inference/context_parallel/): one long prompt chunk-
    prefilled through the CP engine — the prompt's paged KV sequence-
    striped over a cp=2 mesh, every chunk ring-attended across the
    shards. value = CP prefill throughput (prompt tokens/s, median of
    reps); vs_baseline = single-host-paged / CP wall ratio of the same
    traffic — informational on CPU (fake devices share host cores and
    the ring hops become memcpy; on a chip the win is CAPACITY: per-
    device KV bytes drop by 1/cp, which is what lets the million-token
    prompt fit at all). The gates riding in detail are real everywhere:
    greedy tokens must match the single-host paged engine exactly and
    decode must not recompile after warmup."""
    line = {"metric": "serve_longctx_prefill", "value": 0.0,
            "unit": "prompt_toks_per_s", "vs_baseline": 0.0}
    if deadline - time.perf_counter() < 30:
        line["error"] = "budget_exhausted"
        return line
    try:
        import jax

        if len(jax.devices()) < 2:
            line["error"] = "needs >= 2 devices for the cp=2 mesh"
            return line

        from megatron_tpu.config import ModelConfig, ParallelConfig
        from megatron_tpu.inference.context_parallel import (
            ContextParallelEngine,
        )
        from megatron_tpu.inference.paging import PagedInferenceEngine
        from megatron_tpu.models.params import init_params, param_specs
        from megatron_tpu.parallel.mesh import build_mesh
        from megatron_tpu.parallel.sharding import shard_tree

        if cfg is None:
            cfg = ModelConfig(
                num_layers=4, hidden_size=128, num_attention_heads=8,
                num_kv_heads=4, ffn_hidden_size=256, vocab_size=1024,
                seq_length=256, params_dtype="float32").validate()
        params = init_params(cfg, jax.random.PRNGKey(0))
        rt = build_mesh(ParallelConfig(context_parallel=2),
                        devices=jax.devices()[:2])
        sparams = shard_tree(rt, params, param_specs(cfg))
        kw = dict(num_slots=2, max_seq_len=cfg.seq_length,
                  page_size=page_size, prefill_chunk=prefill_chunk,
                  want_logprobs=False)
        base = PagedInferenceEngine(cfg, params, **kw)
        cpe = ContextParallelEngine(cfg, sparams, mesh=rt.mesh, **kw)
        rng = np.random.default_rng(0)
        prompts = rng.integers(
            1, cfg.vocab_size, (1, prompt_len)).astype(np.int32)
        lengths = np.full((1,), prompt_len, np.int32)
        # warmup compiles chunk + decode steps on both engines, and the
        # greedy-parity gate rides on the warmup outputs
        a = base.generate(prompts, lengths, max_new_tokens=new_tokens)
        b = cpe.generate(prompts, lengths, max_new_tokens=new_tokens)
        tokens_match = bool((a.tokens == b.tokens).all())
        t_b, t_c = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            base.generate(prompts, lengths, max_new_tokens=new_tokens)
            t_b.append(max(time.perf_counter() - t0, 1e-9))
            t0 = time.perf_counter()
            cpe.generate(prompts, lengths, max_new_tokens=new_tokens)
            t_c.append(max(time.perf_counter() - t0, 1e-9))
        wall_b = sorted(t_b)[reps // 2]
        wall_c = sorted(t_c)[reps // 2]
        line["value"] = round(prompt_len / wall_c, 2)
        line["vs_baseline"] = round(wall_b / wall_c, 3)
        line["detail"] = {
            "prompt_len": prompt_len, "cp": cpe.cp,
            "prefill_chunk": prefill_chunk, "page_size": page_size,
            "greedy_tokens_match_single_host": tokens_match,
            "decode_recompiles_after_warmup": int(
                cpe.stats["decode_recompiles"]),
            "cp_ring_steps": int(cpe.stats["cp_ring_steps"]),
            "cp_ring_dense_bytes": int(cpe.stats["cp_comm_dense_bytes"]),
            "per_device_kv_fraction": round(1.0 / cpe.cp, 3),
            "single_host_wall_s": round(wall_b, 4),
            "cp_wall_s": round(wall_c, 4),
            "wall_note": ("CPU wall is informational: fake devices share "
                          "host cores; the chip-real win is 1/cp KV "
                          "bytes per device (capacity), byte-priced in "
                          "the decode_tp2_cp2/prefill_cp2 manifests"),
        }
        if not tokens_match:
            line["error"] = "greedy tokens diverged from single-host paged"
    except Exception as e:  # noqa: BLE001 - the metric line must emit
        line["error"] = str(e)[:300]
    return line


def serve_cp_overlap_bench(deadline, prompt_len=96, page_size=8,
                           prefill_chunk=32, new_tokens=6, cfg=None,
                           trace=True):
    """Comm-compute overlapped CP ring (ISSUE 20 tentpole): the same
    cp=2 engine with the serial hop schedule (permute -> merge -> permute)
    vs the overlapped one (hop l+1's collective-permute issued before hop
    l's merge, double-buffered carry). The deterministic gates are what
    CPU can prove: the committed decode_cp2_overlap golden's ppermute
    rows EQUAL the serial ring ledger's (decode_tp2_cp2) — the overlap
    moves zero extra hops/bytes — plus greedy parity vs the single-host
    paged engine for BOTH schedules, identical ring-step/byte counters,
    and zero decode recompiles. value/vs_baseline = serial/overlapped
    wall ratio (informational on CPU: fake devices share host cores);
    with trace=True both runs are captured under jax.profiler and the
    collective-permute EXPOSED fractions (telemetry/tracing/analyze.py)
    ride in detail — on a chip that delta IS the win."""
    line = {"metric": "serve_cp_overlap", "value": 0.0,
            "unit": "serial_over_overlapped_wall", "vs_baseline": 0.0}
    if deadline - time.perf_counter() < 30:
        line["error"] = "budget_exhausted"
        return line
    try:
        import shutil
        import tempfile

        import jax

        if len(jax.devices()) < 2:
            line["error"] = "needs >= 2 devices for the cp=2 mesh"
            return line

        from megatron_tpu.analysis import contracts
        from megatron_tpu.config import ModelConfig, ParallelConfig
        from megatron_tpu.inference.context_parallel import (
            ContextParallelEngine,
        )
        from megatron_tpu.inference.paging import PagedInferenceEngine
        from megatron_tpu.models.params import init_params, param_specs
        from megatron_tpu.parallel.mesh import build_mesh
        from megatron_tpu.parallel.sharding import shard_tree

        # gate 1 — the committed manifests: overlap must move EXACTLY
        # the serial ring's hops and bytes (the ledger keys op counts,
        # not order, so any extra/missing permute would show here)
        def _ppermute_rows(name):
            man = json.loads(contracts.manifest_path(name).read_text())
            return {k: (v["count"], v["total_wire_bytes"])
                    for k, v in man["jaxpr"]["collectives"].items()
                    if k.startswith("ppermute")}

        hops_match = (_ppermute_rows("decode_cp2_overlap")
                      == _ppermute_rows("decode_tp2_cp2"))

        if cfg is None:
            cfg = ModelConfig(
                num_layers=4, hidden_size=128, num_attention_heads=8,
                num_kv_heads=4, ffn_hidden_size=256, vocab_size=1024,
                seq_length=256, params_dtype="float32").validate()
        params = init_params(cfg, jax.random.PRNGKey(0))
        rt = build_mesh(ParallelConfig(context_parallel=2),
                        devices=jax.devices()[:2])
        sparams = shard_tree(rt, params, param_specs(cfg))
        kw = dict(num_slots=2, max_seq_len=cfg.seq_length,
                  page_size=page_size, prefill_chunk=prefill_chunk,
                  want_logprobs=False)
        base = PagedInferenceEngine(cfg, params, **kw)
        serial = ContextParallelEngine(cfg, sparams, mesh=rt.mesh,
                                       cp_overlap=False, **kw)
        over = ContextParallelEngine(cfg, sparams, mesh=rt.mesh,
                                     cp_overlap=True, **kw)
        rng = np.random.default_rng(0)
        prompts = rng.integers(
            1, cfg.vocab_size, (1, prompt_len)).astype(np.int32)
        lengths = np.full((1,), prompt_len, np.int32)
        # warmup compiles everything; gate 2 (greedy parity) rides on it
        ref = base.generate(prompts, lengths, max_new_tokens=new_tokens)
        t0 = time.perf_counter()
        out_s = serial.generate(prompts, lengths, max_new_tokens=new_tokens)
        warm_walls = {"serial": max(time.perf_counter() - t0, 1e-9)}
        t0 = time.perf_counter()
        out_o = over.generate(prompts, lengths, max_new_tokens=new_tokens)
        warm_walls["overlapped"] = max(time.perf_counter() - t0, 1e-9)
        parity = {
            "serial": bool((ref.tokens == out_s.tokens).all()),
            "overlapped": bool((ref.tokens == out_o.tokens).all()),
        }

        def _timed(eng, trace_dir=None):
            if trace_dir is not None:
                jax.profiler.start_trace(trace_dir)
            t0 = time.perf_counter()
            try:
                eng.generate(prompts, lengths, max_new_tokens=new_tokens)
            finally:
                wall = max(time.perf_counter() - t0, 1e-9)
                if trace_dir is not None:
                    jax.profiler.stop_trace()
            return wall

        def _exposed_frac(trace_dir):
            from megatron_tpu.telemetry.tracing import (
                analyze_events, classify_xspace, find_xplane_files,
                load_xspace,
            )

            events = []
            for f in find_xplane_files(trace_dir):
                events.extend(classify_xspace(load_xspace(f)))
            for c in analyze_events(events).collectives:
                if c.op == "collective-permute":
                    return round(c.exposed_frac, 4)
            return None

        exposed = {}
        walls = {}
        trace_error = None
        if trace:
            tmp = tempfile.mkdtemp(prefix="cp_overlap_trace_")
            try:
                for tag, eng in (("serial", serial), ("overlapped", over)):
                    d = os.path.join(tmp, tag)
                    try:
                        walls[tag] = _timed(eng, trace_dir=d)
                        exposed[tag] = _exposed_frac(d)
                    except Exception as e:  # noqa: BLE001 - the trace
                        # delta is informational; the gates must emit
                        walls.setdefault(tag, _timed(eng))
                        exposed[tag] = None
                        trace_error = str(e)[:200]
            finally:
                shutil.rmtree(tmp, ignore_errors=True)
        else:
            # gates-only mode (tier-1 rides here): the warmup walls stand
            # in for the A/B — compile-inclusive, so the ratio is even
            # more informational than the traced CPU one; the
            # deterministic gates below are the point
            walls = warm_walls

        ratio = walls["serial"] / walls["overlapped"]
        line["value"] = round(ratio, 3)
        line["vs_baseline"] = round(ratio, 3)
        steps_eq = (int(serial.stats["cp_ring_steps"])
                    == int(over.stats["cp_ring_steps"]))
        bytes_eq = (int(serial.stats["cp_comm_dense_bytes"])
                    == int(over.stats["cp_comm_dense_bytes"]))
        recompiles = (int(serial.stats["decode_recompiles"])
                      + int(over.stats["decode_recompiles"]))
        delta = None
        if exposed.get("serial") is not None \
                and exposed.get("overlapped") is not None:
            delta = round(exposed["serial"] - exposed["overlapped"], 4)
        line["detail"] = {
            "cp": over.cp, "prompt_len": prompt_len,
            "golden_hops_bytes_match_serial_ring": hops_match,
            "greedy_tokens_match_single_host": parity,
            "ring_steps_equal": steps_eq,
            "ring_bytes_equal": bytes_eq,
            "decode_recompiles_after_warmup": recompiles,
            "serial_wall_s": round(walls["serial"], 4),
            "overlapped_wall_s": round(walls["overlapped"], 4),
            "exposed_frac_serial": exposed.get("serial"),
            "exposed_frac_overlapped": exposed.get("overlapped"),
            "exposed_frac_delta": delta,
            "wall_note": ("CPU wall/exposure deltas are informational "
                          "(fake devices share host cores); the "
                          "deterministic gates — golden hop/byte match, "
                          "greedy parity, equal ring counters, zero "
                          "recompiles — hold everywhere"),
        }
        if trace_error:
            line["detail"]["trace_error"] = trace_error
        if not hops_match:
            line["error"] = ("overlapped ring ledger diverged from the "
                             "serial ring's ppermute rows")
        elif not (parity["serial"] and parity["overlapped"]):
            line["error"] = "greedy tokens diverged from single-host paged"
        elif not (steps_eq and bytes_eq):
            line["error"] = "ring step/byte counters diverged"
    except Exception as e:  # noqa: BLE001 - the metric line must emit
        line["error"] = str(e)[:300]
    return line


def async_loop_bench(deadline, stall_ms=20.0, iters=14, skip_gaps=2):
    """Async-goodput-loop micro-bench (ISSUE 5 acceptance; CPU-able): a
    tiny TrainLoop is fed an iterator with an injected stall_ms host stall
    per batch, synchronous loop vs async loop (prefetch + lagged metrics).
    Steady-state per-step wall comes from journal step-event timestamp
    gaps (the first `skip_gaps` gaps carry compile/pipeline-fill and are
    dropped). recovered_stall_frac = (sync - async) / injected stall; the
    two runs' final goodput splits ride along so the data_wait share drop
    is visible in the headline detail, and the measured data waits are
    attributed into the bench's own goodput ledger."""
    import shutil
    import tempfile

    from megatron_tpu.config import (
        ModelConfig, OptimizerConfig, RunConfig, TrainingConfig,
    )
    from megatron_tpu.telemetry.journal import read_events
    from megatron_tpu.training.pretrain import TrainLoop

    if deadline - time.perf_counter() < 60:
        return {"error": "budget_exhausted"}
    import jax

    # one row per data shard; on a multi-device mesh (the 8-fake-device
    # test conftest) shrink the geometry so the aggregate step stays in
    # the stall-dominated-if-unoverlapped regime instead of 8x the work
    n_dev = jax.device_count()
    gbs = n_dev
    h, seq, vocab = (256, 128, 512) if n_dev == 1 else (128, 64, 256)
    model = ModelConfig(
        num_layers=2, hidden_size=h, num_attention_heads=4, num_kv_heads=4,
        ffn_hidden_size=2 * h, vocab_size=vocab, seq_length=seq,
        params_dtype="float32").validate()
    rng = np.random.default_rng(0)
    proto = {
        "tokens": rng.integers(0, vocab, (gbs, seq)).astype(np.int64),
        "labels": rng.integers(0, vocab, (gbs, seq)).astype(np.int64),
        "loss_mask": np.ones((gbs, seq), np.float32),
    }

    def factory(consumed, gbs):
        def gen():
            while True:
                time.sleep(stall_ms / 1000.0)  # the injected host stall
                yield proto
        return gen()

    tmp = tempfile.mkdtemp(prefix="mtpu_async_bench_")  # journals only

    def run(tag, async_on, train_iters):
        tele = os.path.join(tmp, tag)
        cfg = RunConfig(
            model=model,
            optimizer=OptimizerConfig(lr=1e-3, lr_decay_style="constant"),
            training=TrainingConfig(
                micro_batch_size=1, global_batch_size=gbs,
                train_iters=train_iters, log_interval=1 << 30,
                seed=0, async_loop=async_on, telemetry_dir=tele))
        loop = TrainLoop(cfg, log=lambda m: None)
        loop.train(factory)
        evs, _ = read_events(os.path.join(tele, "events.jsonl"))
        steps = [e for e in evs if e["kind"] == "step"]
        final = [e for e in evs if e["kind"] == "goodput"][-1]
        gaps = [b["ts"] - a["ts"] for a, b in zip(steps, steps[1:])]
        gaps = gaps[skip_gaps:]
        waits = [e["data_wait_ms"] for e in steps[1 + skip_gaps:]]
        return {
            "steady_step_ms_mean": round(1e3 * sum(gaps) / max(len(gaps), 1),
                                         2),
            "steady_data_wait_ms_mean": round(
                sum(waits) / max(len(waits), 1), 3),
            "goodput": {k: final[k] for k in
                        ("goodput", "productive_s", "data_wait_s",
                         "compile_s", "wall_s")},
        }

    try:
        # throwaway warm-up run: both timed runs then meet the same state
        # of the process (and of the compile cache, wherever it is placed)
        run("warm", True, 2)
        sync = run("sync", False, iters)
        asyn = run("async", True, iters)
        n_gaps = iters - 1 - skip_gaps
        # wall-gap recovery: noisy on a busy host (step-time variance rides
        # the numerator) but the end-to-end truth
        recovered = ((sync["steady_step_ms_mean"]
                      - asyn["steady_step_ms_mean"]) / stall_ms)
        # critical-path recovery: the stall still felt by the loop is
        # exactly the steady-state queue-pop wait — sleep-based, low-noise.
        # If the async loop were stall-bound (step < stall) pops would
        # block on the sleeping worker and this correctly reports < 1.
        recovered_wait = 1.0 - asyn["steady_data_wait_ms_mean"] / stall_ms
        if GOODPUT is not None:
            GOODPUT.attribute(
                "data_wait", sync["goodput"]["data_wait_s"]
                + asyn["goodput"]["data_wait_s"])
            GOODPUT.attribute(
                "productive", sync["goodput"]["productive_s"]
                + asyn["goodput"]["productive_s"])
        return {
            "stall_ms": stall_ms, "iters": iters, "steady_gaps": n_gaps,
            "recovered_stall_frac": round(recovered, 3),
            "recovered_wait_frac": round(recovered_wait, 3),
            "sync": sync, "async": asyn,
        }
    except Exception as e:  # noqa: BLE001 - extras must never kill the run
        return {"error": str(e)[:300]}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def preempt_save_bench(deadline, preempt_iter=4, train_iters=64):
    """SIGTERM -> committed-checkpoint wall time (CPU-able, pre-headline):
    a tiny TrainLoop is preempted at an exact step via the `preempt_at`
    fault (which self-delivers a real SIGTERM), takes the expedited
    synchronous-save path, and the journal's `preemption` event reports
    notice->commit latency — the preemption notice budget, tracked across
    PRs so checkpoint growth or save-path regressions show up as a number
    rather than as lost work on the next real preemption."""
    import shutil
    import tempfile

    from megatron_tpu.config import (
        ModelConfig, OptimizerConfig, RunConfig, TrainingConfig,
    )
    from megatron_tpu.telemetry.journal import read_events
    from megatron_tpu.training import checkpointing, resilience
    from megatron_tpu.training.pretrain import TrainLoop

    line = {"metric": "preempt_save_latency_ms", "value": 0.0,
            "unit": "ms_sigterm_to_committed_checkpoint",
            "vs_baseline": 0.0, "detail": {}}
    if deadline - time.perf_counter() < 45:
        line["error"] = "budget_exhausted"
        return line
    import jax

    n_dev = jax.device_count()
    gbs = n_dev
    h, seq, vocab = (256, 128, 512) if n_dev == 1 else (128, 64, 256)
    model = ModelConfig(
        num_layers=2, hidden_size=h, num_attention_heads=4, num_kv_heads=4,
        ffn_hidden_size=2 * h, vocab_size=vocab, seq_length=seq,
        params_dtype="float32").validate()
    rng = np.random.default_rng(0)
    proto = {
        "tokens": rng.integers(0, vocab, (gbs, seq)).astype(np.int64),
        "labels": rng.integers(0, vocab, (gbs, seq)).astype(np.int64),
        "loss_mask": np.ones((gbs, seq), np.float32),
    }

    def factory(consumed, gbs_):
        def gen():
            while True:
                yield proto
        return gen()

    tmp = tempfile.mkdtemp(prefix="mtpu_preempt_bench_")
    prev_fault = os.environ.get(resilience.FAULT_ENV)
    try:
        os.environ[resilience.FAULT_ENV] = f"preempt_at:{preempt_iter}"
        tele = os.path.join(tmp, "tele")
        save = os.path.join(tmp, "ckpt")
        cfg = RunConfig(
            model=model,
            optimizer=OptimizerConfig(lr=1e-3, lr_decay_style="constant"),
            training=TrainingConfig(
                micro_batch_size=1, global_batch_size=gbs,
                train_iters=train_iters, log_interval=1 << 30,
                seed=0, save=save, telemetry_dir=tele,
                preempt_save_timeout=120.0))
        loop = TrainLoop(cfg, log=lambda m: None)
        loop.train(factory)
        evs, _ = read_events(os.path.join(tele, "events.jsonl"))
        pre = [e for e in evs if e["kind"] == "preemption"]
        if not pre:
            line["error"] = "no preemption event journaled"
            return line
        if checkpointing.read_tracker(save) != preempt_iter:
            line["error"] = (f"tracker {checkpointing.read_tracker(save)} "
                             f"!= preempt iteration {preempt_iter}")
            return line
        line["value"] = float(pre[-1]["notice_to_commit_ms"])
        line["detail"] = {
            "save_latency_ms": pre[-1]["save_latency_ms"],
            "iteration": pre[-1]["iteration"],
            "n_params": sum(int(np.prod(x.shape))
                            for x in jax.tree.leaves(loop.state.params)),
            "async_save": True,
        }
    except Exception as e:  # noqa: BLE001 - pre-headline lines must never
        # kill the run (the headline MFU contract)
        line["error"] = str(e)[:300]
    finally:
        if prev_fault is None:
            os.environ.pop(resilience.FAULT_ENV, None)
        else:
            os.environ[resilience.FAULT_ENV] = prev_fault
        shutil.rmtree(tmp, ignore_errors=True)
    return line


def train_attention_bwd_bench(deadline, b=2, s=512, hq=4, hkv=2, d=64,
                              iters=3):
    """Custom-vjp flash gradient step vs the XLA-grad step (pre-headline,
    ISSUE 16). The deterministic gate — and the thing tracked across
    PRs — is that the GRADIENT jaxpr of attention(impl='pallas')
    contains the template's pallas kernels (the fused recompute
    backward) and that --no_flash_bwd's doesn't: `value` is the wall
    speedup of the flash grad step over the dense one and is
    informational only (on a CPU host the kernels run under the pallas
    interpreter, so wall there measures the interpreter, not the
    kernels — the gate is what must hold; a test that runs this on a
    CPU host sets MEGATRON_TPU_FLASH_INTERPRET=1 itself)."""
    import warnings

    line = {"metric": "train_attention_bwd_speedup", "value": 0.0,
            "unit": "x_wall_vs_xla_grad", "vs_baseline": 0.0, "detail": {}}
    if deadline - time.perf_counter() < 30:
        line["error"] = "budget_exhausted"
        return line
    import jax
    import jax.numpy as jnp

    from megatron_tpu.ops.attention import attention
    from megatron_tpu.ops.pallas.flash_template import interpret_forced

    try:
        rng = np.random.default_rng(3)
        q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)),
                               jnp.float32)
                   for h in (hq, hkv, hkv))

        def loss_flash(q, k, v):
            return jnp.sum(jnp.square(attention(q, k, v, impl="pallas")))

        def loss_dense(q, k, v):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")  # the deliberate loud path
                return jnp.sum(jnp.square(
                    attention(q, k, v, impl="pallas", flash_bwd=False)))

        def wall(f):
            g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
            jax.block_until_ready(g(q, k, v))  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                out = g(q, k, v)
            jax.block_until_ready(out)
            return (time.perf_counter() - t0) / iters

        jx_flash = str(jax.make_jaxpr(
            jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v))
        jx_dense = str(jax.make_jaxpr(
            jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v))
        gate = ("pallas_call" in jx_flash
                and "pallas_call" not in jx_dense)
        t_flash = wall(loss_flash)
        t_dense = wall(loss_dense)

        line["value"] = round(t_dense / max(t_flash, 1e-9), 3)
        line["detail"] = {
            "bwd_jaxpr_has_kernel": "pallas_call" in jx_flash,
            "dense_jaxpr_kernel_free": "pallas_call" not in jx_dense,
            "kernel_calls_in_grad": jx_flash.count("pallas_call"),
            "flash_grad_ms": round(t_flash * 1e3, 2),
            "xla_grad_ms": round(t_dense * 1e3, 2),
            "interpret_mode": interpret_forced(),
            "geometry": {"b": b, "s": s, "hq": hq, "hkv": hkv, "d": d},
        }
        if not gate:
            line["error"] = ("flash bwd gate failed: gradient jaxpr "
                             "missing the pallas kernels (or the dense "
                             "escape hatch still contains them)")
    except Exception as e:  # noqa: BLE001 - pre-headline lines must never
        # kill the run (the headline MFU contract)
        line["error"] = str(e)[:300]
    return line


def moe_dispatch_bench(deadline, peak):
    """Iso-parameter 4-expert/top-2 MoE at the headline geometry, capacity
    vs dropless dispatch MFU (useful-FLOP accounting like
    tools/bench_sweep.py --experts). Round-3 capacity dispatch measured
    0.239 MFU single-chip (builder-measured); the dropless ragged_dot path
    is the designed fix — this records both so the gain is driver-capturable."""
    import dataclasses

    cfg = headline_config()
    moe = dataclasses.replace(
        cfg, num_experts=4, moe_top_k=2,
        ffn_hidden_size=cfg.ffn_size // 4).validate()
    out = {}
    for mode in ("capacity", "dropless"):
        if deadline - time.perf_counter() < 60:
            out[mode] = {"error": "budget_exhausted"}
            continue
        mcfg = dataclasses.replace(moe, moe_dispatch=mode).validate()
        try:
            dt, loss = _measure(mcfg, 4, "selective", 0, iters=3)
        except Exception as e:  # noqa: BLE001
            out[mode] = {"error": str(e)[:200]}
            continue
        tps = 4 * mcfg.seq_length / dt
        # useful FLOPs: top_k of E experts active per token
        mfu = tps * 3.0 * mcfg.flops_per_token_fwd() / peak
        out[mode] = {"mfu": round(mfu, 4),
                     "tokens_per_sec_per_chip": round(tps),
                     "step_ms": round(dt * 1e3, 2)}
    return out


def run_extras(deadline, peak, extras):
    """Fill `extras` in place."""
    extras["largest_trainable"] = largest_trainable_bench(deadline, peak)
    # the async-loop point early: it is cheap (tiny model, warm cache) and
    # is the round's record of the data-stall recovery the loop buys
    extras["async_loop"] = async_loop_bench(deadline)
    # MoE before the serving pair: on a tight window the two 7B serving
    # runs must not starve the capacity-vs-dropless comparison
    extras["moe_dispatch"] = moe_dispatch_bench(deadline, peak)
    extras["serving_int8_7b"] = serving_int8_7b_bench(deadline)
    extras["serving_fp8_7b"] = serving_int8_7b_bench(deadline, mode="fp8")


def main():
    budget_s = float(os.environ.get("MEGATRON_TPU_BENCH_BUDGET_S", "420"))
    t_start = time.perf_counter()
    deadline = t_start + budget_s

    import jax

    # a run that finds no TPU fails here: no CPU number is ever printed
    # under a device metric's name
    require_tpu()
    # before the first jit: JAX_COMPILATION_CACHE_DIR where set, else
    # .jax_cache/ in the checkout (megatron_tpu/platform.py)
    enable_compile_cache()

    global GOODPUT
    from megatron_tpu.telemetry import GoodputTracker, recompile_tracker

    GOODPUT = GoodputTracker()
    _compiles0 = recompile_tracker().snapshot()

    if os.environ.get("MEGATRON_TPU_BENCH_SERVING_ONLY"):
        # local recipe (docs/serving.md): just the serving metrics, skip
        # the multi-minute training-step search. Never set by the driver.
        print(json.dumps(serving_engine_bench(deadline)), flush=True)
        print(json.dumps(serve_prefix_cache_bench(deadline)), flush=True)
        print(json.dumps(serve_speculative_bench(deadline)), flush=True)
        print(json.dumps(serve_compressed_comm_bench(deadline)), flush=True)
        print(json.dumps(serve_longctx_prefill_bench(deadline)), flush=True)
        print(json.dumps(serve_cp_overlap_bench(deadline)), flush=True)
        print(json.dumps(serve_slo_bench(deadline)), flush=True)
        return

    from megatron_tpu.models.params import num_params

    cfg = headline_config()
    n_params = num_params(cfg)
    dev = jax.devices()[0]
    peak = peak_bf16_flops(dev)
    flops_per_token = 3.0 * cfg.flops_per_token_fwd()  # fwd + bwd(2x)

    quick = bool(os.environ.get("MEGATRON_TPU_BENCH_QUICK"))
    candidates = CANDIDATES[:1] if quick else CANDIDATES
    extras_mode = os.environ.get("MEGATRON_TPU_BENCH_EXTRAS", "auto")
    want_extras = (extras_mode == "1"
                   or (extras_mode == "auto" and dev.platform == "tpu"))
    # the candidate search stops opening new points past this, leaving the
    # rest of the budget for the 7B-class extras
    now = time.perf_counter()
    search_deadline = (now + 0.55 * (deadline - now)
                       if want_extras else deadline)

    best = None        # (mfu, cand, dt, loss)
    sweep = []
    extras = {}

    def emit_best():
        """Print the one-line JSON for the best point."""
        mfu, cand, dt, loss_val = best
        tokens_per_sec = cand["micro_bs"] * cfg.seq_length / dt
        detail = {
            "tokens_per_sec_per_chip": round(tokens_per_sec),
            "step_ms": round(dt * 1e3, 2),
            "n_params": n_params,
            "loss": loss_val,
            "device": device_summary(),
            "peak_bf16_flops": peak,
            "micro_bs": cand["micro_bs"],
            "recompute": cand["granularity"],
            "ce_chunk": cand["ce_chunk"],
            "attention": "pallas(flash_template)",
            "sweep": sweep,
        }
        detail.update(extras)
        cdelta = recompile_tracker().delta(_compiles0)
        GOODPUT.attribute("compile", cdelta["compile_seconds"]
                          + cdelta["trace_seconds"])
        detail["goodput"] = dict(GOODPUT.report(),
                                 compiles=int(cdelta["compiles"]))
        line = {
            "metric": "llama_train_step_mfu",
            "value": round(mfu, 4),
            "unit": "fraction_of_peak_bf16",
            "vs_baseline": round(mfu / BASELINE_MFU, 3),
            "detail": detail,
        }
        print(json.dumps(line), flush=True)

    for cand in candidates:
        if best is not None and time.perf_counter() > search_deadline:
            print("# bench search budget reached, stopping", file=sys.stderr)
            break
        try:
            dt, loss = _measure(cfg, **cand)
        except Exception as e:  # noqa: BLE001 - only an OOM is a result
            # ("this point does not fit"); anything else fails the run
            if not is_oom(e):
                raise
            sweep.append({**cand, "oom": True})
            print(f"# {cand} OOM", file=sys.stderr)
            continue
        tps = cand["micro_bs"] * cfg.seq_length / dt
        mfu = tps * flops_per_token / peak
        sweep.append({**cand, "mfu": round(mfu, 4),
                      "step_ms": round(dt * 1e3, 2)})
        print(f"# {cand} mfu={mfu:.4f}", file=sys.stderr)
        if best is None or mfu > best[0]:
            best = (mfu, cand, dt, loss)
    if best is None:
        raise RuntimeError("every bench operating point OOMed")

    if not quick:
        # serving metrics ride as their own JSON lines BEFORE the
        # headline (and before any extras lines — the only positional
        # contract is that the headline MFU line comes LAST for the
        # driver; consumers of serving metrics must match on "metric")
        print(json.dumps(serving_engine_bench(deadline)), flush=True)
        print(json.dumps(serve_prefix_cache_bench(deadline)),
              flush=True)
        print(json.dumps(serve_speculative_bench(deadline)),
              flush=True)
        print(json.dumps(serve_compressed_comm_bench(deadline)),
              flush=True)
        print(json.dumps(serve_longctx_prefill_bench(deadline)),
              flush=True)
        # overlapped-ring CP gate: golden hop/byte match + greedy
        # parity (exposed-fraction trace delta rides in detail)
        print(json.dumps(serve_cp_overlap_bench(deadline)),
              flush=True)
        print(json.dumps(serve_slo_bench(deadline)), flush=True)
        # preemption notice budget: SIGTERM -> committed checkpoint
        print(json.dumps(preempt_save_bench(deadline)), flush=True)
        # flash bwd gate: the gradient jaxpr must contain the
        # template's kernels (wall speedup informational)
        print(json.dumps(train_attention_bwd_bench(deadline)),
              flush=True)
    if want_extras:
        run_extras(deadline, peak, extras)

    mfu, cand, dt, loss_val = best
    profile_dir = os.environ.get("MEGATRON_TPU_PROFILE_DIR")
    if profile_dir:
        # re-run the winner under the profiler (trace excludes compile)
        state, step, batch = build_step(_cfg_for(cfg, cand["ce_chunk"]),
                                        cand["micro_bs"],
                                        cand["granularity"])
        _, _, state = time_step(state, step, batch, iters=1)
        jax.profiler.start_trace(profile_dir)
        try:
            time_step(state, step, batch, iters=3)
        finally:
            jax.profiler.stop_trace()
        # attach the comm/compute/exposed split to the headline detail
        # (megatron_tpu/telemetry/tracing/)
        from megatron_tpu.telemetry.tracing import (
            analyze_events, classify_xspace, find_xplane_files,
            load_xspace,
        )

        trace_events = []
        for f in find_xplane_files(profile_dir):
            trace_events.extend(
                classify_xspace(load_xspace(f)))
        rep = analyze_events(trace_events).to_dict(top=0)
        extras["trace_split"] = {
            k: rep[k] for k in ("module", "busy_s",
                                "exposed_collective_s",
                                "collectives")}

    emit_best()


if __name__ == "__main__":
    main()
