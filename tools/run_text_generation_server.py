#!/usr/bin/env python
"""Start the REST generation server on a trained checkpoint.

Equivalent of the reference's tools/run_text_generation_server.py (84 LoC) —
without the rank>0 worker loop (single-controller JAX needs none).

  python tools/run_text_generation_server.py --load ckpts --model_name tiny \
      --tokenizer_type null --vocab_size 128 --port 5000
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def extra_args(parser):
    g = parser.add_argument_group("server")
    g.add_argument("--host", default="0.0.0.0")
    g.add_argument("--port", type=int, default=5000)
    g.add_argument("--serve_num_slots", type=int, default=8,
                   help="KV-cache slots for the continuous-batching engine "
                        "(concurrent requests share every decode step; "
                        "docs/serving.md). 0 restores the one-request-at-"
                        "a-time server")
    g.add_argument("--serve_max_seq_len", type=int, default=None,
                   help="per-slot KV-cache length for the engine (default "
                        "min(seq_length, 2048) — the persistent cache "
                        "costs slots x this x layers x kv_heads x "
                        "head_dim, so an uncapped long-context model "
                        "would OOM at startup where the old per-request "
                        "server booted). Raise it to serve longer "
                        "prompt+generation budgets")
    g.add_argument("--serve_kv_paging", action="store_true",
                   help="accepted and without effect: the engine's KV "
                        "cache is always the shared page pool")
    g.add_argument("--serve_page_size", type=int, default=16,
                   help="tokens per KV page; multiples of 8 keep the TPU "
                        "paged flash-decode kernel usable")
    g.add_argument("--serve_prefill_chunk", type=int, default=32,
                   help="prompt tokens prefilled per engine tick: chunked "
                        "prefill interleaves with decode so one long "
                        "prompt never stalls the batch")
    g.add_argument("--serve_num_pages", type=int, default=None,
                   help="KV pool size in pages (default = slots x "
                        "pages-per-sequence: every slot can grow to "
                        "--serve_max_seq_len). Smaller oversubscribes: the "
                        "engine evicts cached prefixes and preempts the "
                        "youngest request under pressure")
    g.add_argument("--serve_speculative", choices=("ngram", "model"),
                   default=None,
                   help="speculative decoding in the engine "
                        "(docs/serving.md): per-slot draft proposal + one "
                        "batched multi-token verify forward per tick, "
                        "exact accept/reject — greedy output is token-"
                        "identical to plain decode, throughput scales "
                        "with the acceptance rate. 'ngram' is the zero-"
                        "weight prompt-lookup drafter; 'model' runs a "
                        "small draft model (see --serve_draft_*)")
    g.add_argument("--serve_spec_k", type=int, default=4,
                   help="drafted tokens per slot per tick (the verify "
                        "forward takes k+1 query rows; the engine "
                        "reserves k positions of sequence headroom)")
    g.add_argument("--serve_draft_layers", type=int, default=None,
                   help="draft model depth (--serve_speculative model): "
                        "the draft is the target architecture truncated "
                        "to this many layers (default: same depth — only "
                        "useful for testing). Loading a DEEPER checkpoint "
                        "into the truncated tree restores its FIRST N "
                        "layers (the stacked-layer leading dim slices); a "
                        "properly distilled draft checkpoint is still the "
                        "real producer (ROADMAP item 3). The draft keeps "
                        "its own KV cache tree threaded through the same "
                        "slot/page machinery")
    g.add_argument("--serve_draft_checkpoint", default=None,
                   help="committed checkpoint dir for the draft model's "
                        "weights (manifest-verified like /admin/reload; "
                        "the tree must match the draft config). Without "
                        "it the draft serves randomly initialized "
                        "weights — acceptance will be near zero")
    g.add_argument("--serve_max_queue", type=int, default=None,
                   help="bound the engine admission queue: requests "
                        "beyond this many waiters get HTTP 503 + "
                        "Retry-After instead of unbounded queue latency "
                        "(default: unbounded)")
    g.add_argument("--serve_request_timeout", type=float, default=None,
                   help="per-request deadline in seconds (engine path): a "
                        "queued or mid-decode request past it fails with "
                        "HTTP 504 instead of waiting forever — bounds the "
                        "fleet router's retry worst case (default: no "
                        "deadline; a request's own deadline_s field may "
                        "shorten this but never extend past it)")
    g.add_argument("--serve_drain_timeout", type=float, default=30.0,
                   help="graceful-drain budget on SIGTERM/SIGINT: stop "
                        "admitting (503 + Retry-After), wait up to this "
                        "many seconds for in-flight requests, then exit; "
                        "a second signal force-exits immediately")
    g.add_argument("--serve_warmup", action="store_true",
                   help="compile the decode step before /readyz goes "
                        "green, so a fleet router or k8s-style prober "
                        "never routes a request into the warmup compile")
    g.add_argument("--serve_compress_collectives",
                   choices=("none", "int8", "fp8"), default="none",
                   help="low-bit tensor-parallel collectives in the "
                        "serving engine (quant/, docs/serving.md): the "
                        "per-layer TP output reductions and the vocab-"
                        "parallel logits gather move int8/fp8 payloads "
                        "with per-chunk scales riding alongside (Flash "
                        "Communication) — >= 3x fewer collective wire "
                        "bytes than dense (the decode_tp2_* golden comm "
                        "manifests). No-op unless --tensor_parallel > 1; "
                        "greedy output is gated at >= 99%% token match "
                        "vs the dense engine (int8)")
    g.add_argument("--serve_comm_policy", default=None,
                   help="path to a per-collective compression policy "
                        "JSON (tools/trace_report.py --emit-comm-policy "
                        "derives one from a runtime trace's measured "
                        "exposed fractions): sites whose collective time "
                        "hides under compute stay dense. Default: "
                        "compress every site")
    g.add_argument("--serve_context_parallel", action="store_true",
                   help="context-parallel serving (docs/serving.md): "
                        "shard each sequence's paged KV over the mesh's "
                        "context axis and ring-attend across the shards "
                        "— long-context prompts whose KV exceeds one "
                        "device. Needs --context_parallel >= 2; greedy "
                        "output stays token-identical to single-host "
                        "serving")
    g.add_argument("--serve_cp_collectives",
                   choices=("dense", "int8", "fp8"), default="dense",
                   help="transport for the CP ring-attention hops "
                        "(quant/collectives.py ring_permute): int8/fp8 "
                        "compress the rotating attention partials; the "
                        "per-position log-sum-exp row stays fp32")
    g.add_argument("--serve_cp_comm_policy", default=None,
                   help="site-policy JSON gating the cp_ring and cp_a2a "
                        "sites (tools/trace_report.py --emit-comm-policy)")
    g.add_argument("--serve_cp_geometry", choices=("ring", "2d"),
                   default="ring",
                   help="context-axis attention geometry (docs/serving.md "
                        "'CP geometry and overlap'): 'ring' rotates KV "
                        "partials around all cp ranks; '2d' factors cp = "
                        "cp_seq x cp_head — a head all-to-all inside each "
                        "--serve_cp_subgroup-sized subgroup (intra-node "
                        "bandwidth), ring hops only ACROSS subgroups at "
                        "1/subgroup payload (topology-aware placement)")
    g.add_argument("--serve_cp_subgroup", type=int, default=0,
                   help="subgroup size (cp_head) for --serve_cp_geometry "
                        "2d: must divide both cp and the model's query-"
                        "head count. 0/1 for ring geometry")
    g.add_argument("--serve_cp_overlap", choices=("on", "off"),
                   default="on",
                   help="ring-hop schedule: 'on' issues hop l+1's "
                        "collective-permute before merging hop l's stripe "
                        "(double-buffered carry, comm hides under merge "
                        "compute); 'off' keeps the serial permute->merge "
                        "chain. Identical numerics, hop count and wire "
                        "bytes either way — only exposed comm time moves")
    g.add_argument("--serve_cp_lanes", type=int, default=1,
                   help="run this many independent CP engine lanes on one "
                        "host (CP x DP): lane i gets its own cp-sized "
                        "device group and engine; the in-process "
                        "dispatcher routes each request to the least-"
                        "loaded lane and /metrics carries a lane=\"i\" "
                        "label per series. Needs cp * lanes <= local "
                        "device count and a context-only mesh")
    g.add_argument("--serve_profile_dir", default=None,
                   help="output dir for POST /admin/profile on-demand "
                        "captures (default runs/serve_profile); read the "
                        "result with tools/trace_report.py")
    g.add_argument("--kv_cache_int8", action="store_true",
                   help="serve with an int8-quantized KV cache (half the "
                        "cache HBM -> 2x context/batch per chip)")
    g.add_argument("--weight_int8", action="store_true",
                   help="int8 weight-only quantization at load: half the "
                        "param HBM (7B fits one 16GB chip); single-chip "
                        "serving only")
    g.add_argument("--weight_fp8", action="store_true",
                   help="fp8(e4m3) weight-only quantization at load: same "
                        "1 byte/weight as int8 with a log-wise grid "
                        "(better for heavy-tailed weights); single-chip "
                        "serving only")
    return parser


def main(argv=None):
    import jax

    from megatron_tpu.arguments import args_to_run_config, parse_args
    from megatron_tpu.inference.server import run_server
    from megatron_tpu.models.params import init_params
    from megatron_tpu.platform import device_summary, enable_compile_cache
    from megatron_tpu.tokenizer import build_tokenizer
    from megatron_tpu.training import checkpointing

    args = parse_args(argv, extra_args_provider=extra_args)
    cfg = args_to_run_config(args)
    cache_dir = enable_compile_cache(cfg.training.compilation_cache_dir or "")
    # what this replica runs on, said once where a log reader finds it
    print(f"devices: {json.dumps(device_summary())} | "
          f"compile cache: {cache_dir}", flush=True)
    tokenizer = build_tokenizer(
        args.tokenizer_type, vocab_file=args.vocab_file,
        merges_file=args.merges_file, tokenizer_model=args.tokenizer_model,
        vocab_size=args.vocab_size,
        vocab_extra_ids=args.vocab_extra_ids or 0,
        new_tokens=args.new_tokens)

    params = init_params(cfg.model, jax.random.PRNGKey(cfg.training.seed))
    weights_version = None
    if cfg.training.load:
        params = checkpointing.load_params_only(cfg.training.load, params)
        weights_version = checkpointing.read_tracker(cfg.training.load)
        print(f"loaded checkpoint at iteration {weights_version}")
    else:
        print("WARNING: serving randomly initialized weights (no --load)")

    # sharded serving: build the mesh, shard params, and (for pp>1) use the
    # pipelined forward (ref run_text_generation_server's multi-rank loop)
    mesh = forward_fn = None
    par = cfg.parallel
    sharded = (par.tensor_parallel * par.pipeline_parallel
               * par.context_parallel > 1)
    if args.weight_int8 and args.weight_fp8:
        raise SystemExit("--weight_int8 and --weight_fp8 are exclusive")
    if args.weight_int8 or args.weight_fp8:
        mode = "int8" if args.weight_int8 else "fp8"
        if sharded:
            raise SystemExit(
                f"--weight_{mode} is single-chip serving only in v1 (the "
                "quantized leaves change the tree that the sharding "
                "specs mirror); drop one of the two flags")
        if cfg.model.num_experts is not None:
            raise SystemExit(
                f"--weight_{mode} does not cover MoE expert weights in v1 — "
                "the bulk of a MoE model's params would stay bf16 while "
                "the flag promises halved HBM; serve MoE without it")
        from megatron_tpu.ops.weight_quant import quantize_params_for_serving

        params = quantize_params_for_serving(params, mode=mode)
        print(f"serving {mode}-quantized weights (matmul + embedding "
              "tables)")
    if sharded:
        from megatron_tpu.inference.pipelined import make_pipelined_lm_forward
        from megatron_tpu.models.params import param_specs
        from megatron_tpu.parallel.mesh import build_mesh
        from megatron_tpu.parallel.sharding import shard_tree

        rt = build_mesh(par)
        params = shard_tree(rt, params, param_specs(cfg.model))
        mesh = rt.mesh
        if rt.pp > 1:
            if args.kv_cache_int8:
                raise SystemExit(
                    "--kv_cache_int8 is not supported with pipeline-parallel "
                    "serving (the pp>1 forward threads bf16 cache pairs); "
                    "drop one of the two flags")
            forward_fn = make_pipelined_lm_forward(cfg.model, rt.mesh, rt.pp)
        print(f"serving sharded: mesh={dict(rt.mesh.shape)}"
              + (" (pipelined forward)" if forward_fn else ""))

    engine_slots = args.serve_num_slots
    if forward_fn is not None and engine_slots:
        print("pipelined (pp>1) serving runs one-shot; ignoring "
              f"--serve_num_slots {engine_slots}")
        engine_slots = 0
    engine_max_seq_len = args.serve_max_seq_len
    if engine_slots and engine_max_seq_len is None:
        engine_max_seq_len = min(cfg.model.seq_length, 2048)

    # speculative decoding: build the draft model (model drafter) and
    # load its verified weights (PR 7's loader — torn/bitrotted saves
    # never reach a serving replica)
    draft_cfg = draft_params = None
    if args.serve_speculative == "model":
        import dataclasses

        draft_cfg = cfg.model
        if args.serve_draft_layers:
            draft_cfg = dataclasses.replace(
                cfg.model, num_layers=args.serve_draft_layers).validate()
        draft_params = init_params(draft_cfg,
                                   jax.random.PRNGKey(cfg.training.seed + 1))
        if args.serve_draft_checkpoint:
            from megatron_tpu.inference.fleet.reload import (
                load_verified_params,
            )

            draft_params, dit = load_verified_params(
                args.serve_draft_checkpoint, draft_params)
            print(f"loaded draft checkpoint at iteration {dit}")
        else:
            print("WARNING: draft model serving randomly initialized "
                  "weights (no --serve_draft_checkpoint) — expect near-"
                  "zero acceptance")
    if args.serve_speculative and sharded:
        raise SystemExit(
            "--serve_speculative is single-chip serving only in v1 "
            "(the spec step is not threaded through the sharded forward)")
    if engine_slots:
        m = cfg.model
        bpe = 1 if args.kv_cache_int8 else 2
        ps = args.serve_page_size
        pages = (args.serve_num_pages
                 or engine_slots * (-(-engine_max_seq_len // ps)) + 1)
        gib = (2 * m.num_layers * pages * ps * m.n_kv_heads
               * m.head_dim * bpe) / 2**30
        print(f"paged KV pool: {pages} pages x {ps} tokens = "
              f"{gib:.2f} GiB"
              + (" (int8)" if args.kv_cache_int8 else " (bf16)"))
    run_server(cfg.model, params, tokenizer, host=args.host, port=args.port,
               mesh=mesh, forward_fn=forward_fn,
               kv_cache_int8=args.kv_cache_int8,
               engine_slots=engine_slots,
               engine_max_seq_len=engine_max_seq_len,
               engine_max_queue=args.serve_max_queue,
               page_size=args.serve_page_size,
               prefill_chunk=args.serve_prefill_chunk,
               num_pages=args.serve_num_pages,
               request_timeout=args.serve_request_timeout,
               drain_timeout=args.serve_drain_timeout,
               warmup=args.serve_warmup,
               reload_dir=cfg.training.load or None,
               weights_version=weights_version,
               speculative=args.serve_speculative,
               spec_k=args.serve_spec_k,
               draft_cfg=draft_cfg, draft_params=draft_params,
               profile_dir=args.serve_profile_dir,
               compress_collectives=args.serve_compress_collectives,
               comm_policy=args.serve_comm_policy,
               cp_serving=args.serve_context_parallel,
               cp_collectives=args.serve_cp_collectives,
               cp_comm_policy=args.serve_cp_comm_policy,
               cp_geometry=args.serve_cp_geometry,
               cp_subgroup=args.serve_cp_subgroup,
               cp_overlap=args.serve_cp_overlap == "on",
               cp_lanes=args.serve_cp_lanes)


if __name__ == "__main__":
    main()
