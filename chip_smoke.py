#!/usr/bin/env python
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user would call —
trainer takes steps and commits a checkpoint, server loads it and answers
requests — at the full width of Mistral-7B (models/presets.py mistral:
hidden 4096, FFN 14336, 32 query / 8 KV heads, head dim 128, vocab 32000,
RMSNorm, SwiGLU, window 4096, bf16) with depth cut to the 2 layers one
16 GB chip trains with Adam. Weights and data are random, made from --seed.

    python chip_smoke.py              one chip (what the driver runs)
    python chip_smoke.py --chips 4    the sharded trainer and what it is
                                      compared with, and no other phase
    python chip_smoke.py --rehearse   the same control flow at toy size on
                                      whatever backend JAX finds (tests;
                                      JAX_PLATFORMS=cpu); the device named
                                      in every line is the one it ran on

One process uses the chip at a time: this parent never imports JAX and
runs each phase as a child that exits before the next starts. Every phase
prints one JSON line ({"phase", "ok", "device", "seconds", ..."setup"});
numbers under "setup" (compile seconds, peak bytes, step and request
times) are observations for planning, not results. The last line of
stdout is exactly {"ok": true, "device": {"platform", "kind", "count"}};
any failing phase makes the exit code non-zero and leaves that line out.
Without a TPU (and without --rehearse) the first child fails and nothing
is printed on stdout.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
TRAINER = os.path.join(REPO, "pretrain_gpt.py")
PREPROCESS = os.path.join(REPO, "tools", "preprocess_data.py")
SERVER = os.path.join(REPO, "tools", "run_text_generation_server.py")
CKPT_VERIFY = os.path.join(REPO, "tools", "checkpoint_util.py")

# Widths are Mistral-7B's, untouched; "toy" is the rehearsal size.
SIZES = {
    "real": dict(layers=2, hidden=4096, heads=32, kv_heads=8, ffn=14336,
                 vocab=32000, seq=4096, window=4096, ce_chunk=512, slots=8,
                 serve_len=2048, train_iters=16, lr="1e-4", new_tokens=16,
                 prompt_lens=(24, 40, 40, 72), grad_seq=2048,
                 grad_window=1024, sync_dim=4096, sync_iters=256),
    "toy": dict(layers=2, hidden=64, heads=4, kv_heads=2, ffn=128,
                vocab=512, seq=256, window=256, ce_chunk=64, slots=8,
                serve_len=128, train_iters=8, lr="3e-3", new_tokens=6,
                prompt_lens=(5, 9, 9, 17), grad_seq=256,
                grad_window=128, sync_dim=256, sync_iters=8),
}


class PhaseFailed(Exception):
    pass


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise PhaseFailed(what)


# ---------------------------------------------------------------------------
# children that hold the chip (each is `chip_smoke.py --phase ...`)
# ---------------------------------------------------------------------------


def _emit(phase: str, t0: float, dev: dict, **fields) -> None:
    print(json.dumps({"phase": phase, "ok": True, "device": dev,
                      "seconds": round(time.time() - t0, 2), **fields}),
          flush=True)


def _device(rehearse: bool) -> dict:
    """What JAX found: a TPU, or (only in a rehearsal) whatever it is."""
    from megatron_tpu.platform import device_summary, require_tpu

    return device_summary() if rehearse else require_tpu()


def child_device(size: dict, rehearse: bool) -> dict:
    """Which device JAX found (a TPU, or the run stops here) and whether
    block_until_ready waits for it."""
    t0 = time.time()
    import jax
    import jax.numpy as jnp
    import jaxlib

    from megatron_tpu.platform import enable_compile_cache, peak_bf16_flops

    dev = _device(rehearse)
    cache_dir = enable_compile_cache()
    if dev["platform"] == "tpu":
        peak_bf16_flops(jax.devices()[0])  # unknown device_kind raises
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - version string only
        libtpu = None

    n, iters = size["sync_dim"], size["sync_iters"]
    x = jnp.full((n, n), 1.0, jnp.bfloat16)

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(
            0, iters, lambda i, y: (y @ x) * (1.0 / n), x)

    for _ in range(2):  # the first round compiles the chain and the fetch
        t = time.perf_counter()
        y = chain(x)
        t_dispatch = time.perf_counter() - t
        y.block_until_ready()
        t_block = time.perf_counter() - t - t_dispatch
        t = time.perf_counter()
        val = float(y[0, 0])
        t_fetch = time.perf_counter() - t
    # if block_until_ready returned without waiting, the fetch right after
    # it would pay for the whole chain
    waited = t_fetch <= max(0.2 * (t_dispatch + t_block), 0.005)
    _check(waited, f"block_until_ready did not wait: dispatch "
           f"{t_dispatch:.4f}s block {t_block:.4f}s then fetch "
           f"{t_fetch:.4f}s")
    _check(abs(val - 1.0) < 0.05, f"matmul chain returned {val}")
    _emit("device", t0, dev,
          versions={"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                    "libtpu": libtpu},
          env={k: os.environ.get(k) for k in (
              "TPU_WORKER_HOSTNAMES", "JAX_COMPILATION_CACHE_DIR",
              "JAX_PLATFORMS")},
          setup={"compile_cache": cache_dir,
                 "block_until_ready_waits": waited,
                 "sync_dispatch_s": round(t_dispatch, 5),
                 "sync_block_s": round(t_block, 5),
                 "sync_fetch_after_block_s": round(t_fetch, 5)})
    return dev


def child_kernels(size: dict, dev: dict, seed: int) -> None:
    """Every kernel of the path, compiled for real where the backend is a
    TPU, against the plain jnp attention (impl="xla") on the device."""
    t0 = time.time()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatron_tpu.ops.attention import attention

    hq, hkv = size["heads"], size["kv_heads"]
    d = size["hidden"] // hq
    rng = np.random.default_rng(seed)
    bf16 = jnp.bfloat16

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32), bf16)

    cases, compile_s = {}, {}

    def compare(name, fn_of_impl, args):
        """max |kernel - reference| over max |reference|, per output."""
        outs = {}
        for impl in ("pallas", "xla"):
            fn = fn_of_impl(impl)
            if impl == "pallas":
                # a dispatcher that quietly picked the dense path would
                # compare the reference with itself
                jaxpr = str(jax.make_jaxpr(fn)(*args))
                _check("pallas_call" in jaxpr,
                       f"{name}: no pallas_call in the jaxpr")
            t = time.perf_counter()
            out = jax.block_until_ready(jax.jit(fn)(*args))
            if impl == "pallas":
                compile_s[name] = round(time.perf_counter() - t, 2)
            outs[impl] = [np.asarray(o, np.float32)
                          for o in jax.tree.leaves(out)]
        errs = []
        for got, want in zip(outs["pallas"], outs["xla"]):
            _check(np.isfinite(got).all(), f"{name}: non-finite output")
            _check(got.shape == want.shape, f"{name}: shape {got.shape}")
            errs.append(float(np.abs(got - want).max()
                              / max(np.abs(want).max(), 1e-6)))
        cases[name] = round(max(errs), 5)
        # bf16 in, fp32 accumulation on both sides: agreement to a few
        # bf16 ulps of the largest value
        _check(max(errs) < 3e-2, f"{name}: kernel differs from the jnp "
               f"reference by {max(errs):.4f} of its range")

    # forward at the training shape
    S, W = size["seq"], size["window"]
    q, k, v = rand(1, S, hq, d), rand(1, S, hkv, d), rand(1, S, hkv, d)
    compare("forward", lambda impl: lambda q, k, v: attention(
        q, k, v, sliding_window=W, impl=impl), (q, k, v))

    # gradient, with a window that bites (the dense reference's O(S^2)
    # gradient at the full sequence would crowd a 16 GB chip)
    S, W = size["grad_seq"], size["grad_window"]
    q, k, v = rand(1, S, hq, d), rand(1, S, hkv, d), rand(1, S, hkv, d)
    cot = rand(1, S, hq, d)
    compare("gradient", lambda impl: jax.grad(
        lambda q, k, v: jnp.sum(
            attention(q, k, v, sliding_window=W, impl=impl
                      ).astype(jnp.float32) * cot.astype(jnp.float32)),
        argnums=(0, 1, 2)), (q, k, v))

    # decode against the server's cache shape: slots of different ages
    B, C, W = size["slots"], size["serve_len"], size["window"]
    kc, vc = rand(B, C, hkv, d), rand(B, C, hkv, d)
    lens = jnp.asarray(
        [1, 2, C // 16 + 1, C // 4, C // 2 - 1, C // 2, C - 5, C - 4][:B],
        jnp.int32)
    for name, rows in (("decode", 1), ("decode_mq5", 5)):
        q = rand(B, rows, hq, d)
        compare(name, lambda impl: lambda q, k, v, n: attention(
            q, k, v, kv_lengths=n, sliding_window=W, impl=impl),
            (q, kc, vc, lens))

    # paged decode: the same cache cut into pages, scattered over a pool
    q = rand(B, 1, hq, d)
    want_fn = lambda q, k, v, n: attention(  # noqa: E731
        q, k, v, kv_lengths=n, sliding_window=W, impl="xla")
    for ps in (16, 128):
        per_seq = C // ps
        order = rng.permutation(B * per_seq) + 1  # page 0 = scratch
        table = jnp.asarray(order.reshape(B, per_seq), jnp.int32)

        def pool(dense):
            pages = dense.reshape(B * per_seq, ps, hkv, d)
            out = jnp.zeros((B * per_seq + 1, ps, hkv, d), dense.dtype)
            return out.at[table.reshape(-1)].set(pages)

        kp, vp = pool(kc), pool(vc)
        name = f"paged_decode_page{ps}"

        def paged_of_impl(impl, kp=kp, vp=vp, table=table):
            if impl == "xla":
                return lambda q, n: want_fn(q, kc, vc, n)
            return lambda q, n: attention(
                q, kp, vp, kv_lengths=n, page_table=table,
                sliding_window=W, impl="pallas")

        compare(name, paged_of_impl, (q, lens))

    _emit("kernels", t0, dev, max_rel_err=cases,
          setup={"first_call_s": compile_s,
                 "peak_bytes_in_use": (
                     jax.local_devices()[0].memory_stats() or {}
                 ).get("peak_bytes_in_use")})


def child_train4(size: dict, rehearse: bool, mode: str, argv: list,
                 out_path: str) -> None:
    """--chips 4: the trainer in-process (pretrain_gpt.main), so that the
    final state's placement and each device's memory can be read. mode
    "sharded" runs the flags as given over all devices; "baseline" is the
    same seed and global batch unsharded on ONE of them — the trainer has
    no option for that (and gets none here), so the child hands its mesh
    builder that one device."""
    import jax

    dev = _device(rehearse)
    import pretrain_gpt
    from megatron_tpu.training import pretrain as pretrain_mod

    if mode == "baseline":
        one = jax.devices()[:1]
        build = pretrain_mod.build_mesh
        pretrain_mod.build_mesh = lambda par, devices=None: build(
            par, devices=one)
    state = pretrain_gpt.main(argv)

    leaves = jax.tree.leaves((state.params, state.master, state.mu,
                              state.nu))
    placed_on = sorted({d.id for leaf in leaves
                        for d in leaf.sharding.device_set})
    # one shard per device that is NOT a full replica: a sharded leaf
    shard_fraction = min(
        leaf.addressable_shards[0].data.size / max(leaf.size, 1)
        for leaf in leaves)
    mem = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        mem[str(d.id)] = {k: stats.get(k) for k in (
            "bytes_in_use", "peak_bytes_in_use")}
    report = {"device": dev, "devices_holding_state": placed_on,
              "smallest_shard_fraction": shard_fraction, "memory": mem}

    if mode == "sharded":
        # the collectives of the compiled step: the trainer's own
        # make_train_step, compiled for these devices (training/aot.py)
        from megatron_tpu.arguments import args_to_run_config, parse_args
        from megatron_tpu.training.aot import aot_compile_train_step

        cfg = args_to_run_config(parse_args(argv))
        t = cfg.training
        dp = cfg.parallel.derive_data_parallel(len(jax.devices()))
        compiled, meta = aot_compile_train_step(
            cfg.model, cfg.parallel, cfg.optimizer,
            micro_batch_size=t.micro_batch_size,
            num_microbatches=t.global_batch_size // (
                t.micro_batch_size * dp),
            recompute=t.recompute_granularity)
        text = compiled.as_text()
        # counted with a plain pattern: analysis/jaxpr_audit.hlo_collectives
        # misses layout-annotated tuple results in TPU HLO text (it found
        # 16 of 20 all-reduces and no collective-permute in this step)
        report["compiled"] = {
            "mesh": meta["mesh_shape"],
            "tpu_custom_calls": text.count("tpu_custom_call"),
            "collectives": {
                kind: len(re.findall(rf"\b{kind}(-start)?\(", text))
                for kind in ("all-reduce", "all-gather", "all-to-all",
                             "reduce-scatter", "collective-permute")},
            "argument_bytes_per_device": int(
                compiled.memory_analysis().argument_size_in_bytes),
        }
    with open(out_path, "w") as f:
        json.dump(report, f)


# ---------------------------------------------------------------------------
# the parent: never imports JAX
# ---------------------------------------------------------------------------


def _child_env(rehearse: bool, **extra) -> dict:
    env = dict(os.environ)
    env["PYTHONUNBUFFERED"] = "1"
    if rehearse:
        # the kernels dispatch (interpreted) on a CPU host only when asked
        env.setdefault("MEGATRON_TPU_FLASH_INTERPRET", "1")
    env.update({k: v for k, v in extra.items() if v is not None})
    return env


def _run(cmd: list, env: dict, log_path: str, timeout: float) -> str:
    """Run a child to its end; stdout+stderr to log_path. Returns the log
    text; PhaseFailed on a non-zero exit or a timeout (child killed)."""
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                                env=env, cwd=REPO)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            raise PhaseFailed(f"{os.path.basename(cmd[1])} exceeded "
                              f"{timeout:.0f}s (log: {log_path})")
    text = _read(log_path)
    if rc != 0:
        raise PhaseFailed(f"{' '.join(cmd[1:3])} exited {rc}; log tail:\n"
                          + text[-3000:])
    return text


def _read(path: str) -> str:
    with open(path) as f:
        return f.read()


def _device_from_log(text: str) -> dict:
    """The `devices: {...}` line trainer and server print at start-up."""
    for line in text.splitlines():
        if line.startswith("devices: "):
            return json.loads(line[len("devices: "):].split(" | ")[0])
    raise PhaseFailed("child printed no `devices:` line")


def _dumped(ir_dir: str, name: str) -> dict:
    """Pallas kernels in the step JAX lowered under JAX_DUMP_IR_TO: the
    pallas_call equations of its jaxpr, and (what they become for a TPU)
    the tpu_custom_call ops of its StableHLO."""
    def count(pattern, needle):
        files = glob.glob(os.path.join(ir_dir, pattern))
        _check(bool(files), f"no dump matching {pattern} in {ir_dir}")
        return max(_read(path).count(needle) for path in files)

    return {"pallas_calls_in_jaxpr": count(f"jax_*_{name}.jaxpr.txt",
                                           "pallas_call"),
            "tpu_custom_calls_lowered": count(
                f"jax_ir*_jit_{name}_compile.mlir", "tpu_custom_call")}


def _check_kernels_in_step(counts: dict, dev: dict, what: str,
                           least: int) -> None:
    _check(counts["pallas_calls_in_jaxpr"] >= least,
           f"{what} holds {counts['pallas_calls_in_jaxpr']} pallas_call "
           f"equations (< {least}): it ran on the XLA attention path")
    if dev["platform"] == "tpu":
        _check(counts["tpu_custom_calls_lowered"] >= least,
               f"{what} lowered without its tpu_custom_call ops")


def model_flags(size: dict) -> list:
    """Mistral's architecture as explicit flags (--model_name mistral-7B
    cannot be cut in depth); trainer and server get the same list."""
    return [
        "--num_layers", str(size["layers"]),
        "--hidden_size", str(size["hidden"]),
        "--num_attention_heads", str(size["heads"]),
        "--num_attention_heads_kv", str(size["kv_heads"]),
        "--ffn_hidden_size", str(size["ffn"]),
        "--vocab_size", str(size["vocab"]),
        "--seq_length", str(size["seq"]),
        "--max_position_embeddings", str(size["seq"]),
        "--position_embedding_type", "rotary",
        "--use_rms_norm", "--layernorm_epsilon", "1e-5",
        "--glu_activation", "swiglu", "--no_tie_embed_logits",
        "--sliding_window_size", str(size["window"]),
        "--bf16", "--attention_impl", "pallas",
        "--ce_chunk_size", str(size["ce_chunk"]),
    ]


def train_flags(size: dict, work: str, seed: int, gbs: int,
                save: bool) -> list:
    flags = model_flags(size) + [
        "--micro_batch_size", "1", "--global_batch_size", str(gbs),
        "--recompute_granularity", "selective",
        "--train_iters", str(size["train_iters"]), "--log_interval", "1",
        "--lr", size["lr"], "--lr_decay_style", "constant",
        "--data_path", os.path.join(work, "corpus"),
        "--split", "100,0,0", "--eval_interval", "100000",
        "--eval_iters", "0", "--seed", str(seed),
    ]
    if save:
        flags += ["--save", os.path.join(work, "ckpt"),
                  "--save_interval", str(size["train_iters"])]
    return flags


CYCLE = 64


def corpus_cycle(size: dict, seed: int) -> list:
    """The token cycle every training document (and serving prompt)
    walks: CYCLE distinct ids below the end-of-document id."""
    import random

    return random.Random(seed).sample(range(size["vocab"] - 1), CYCLE)


def phase_data(size: dict, work: str, seed: int, rehearse: bool,
               gbs: int) -> dict:
    """A corpus from --seed through tools/preprocess_data.py, and the
    native index helpers built from the committed source on this machine.
    CPU work: these children are held to the CPU."""
    import random

    t0 = time.time()
    env = _child_env(rehearse, JAX_PLATFORMS="cpu")
    # start from no binary at all: what loads below was compiled here
    for stale in glob.glob(os.path.join(
            REPO, "megatron_tpu", "data", "_helpers_native*")):
        os.remove(stale)
    origin = _run(
        [sys.executable, "-c",
         "from megatron_tpu.data import helpers; "
         "print('ORIGIN', helpers.native_origin())"],
        env, os.path.join(work, "helpers.log"), 300)
    origin = origin.rsplit("ORIGIN ", 1)[-1].split()[0]
    compiler = shutil.which("g++")
    # the Python index builders are the reference, and the fallback of a
    # host with no compiler; with a compiler present they mean the build
    # of the committed source failed
    _check(origin == "built" or compiler is None,
           f"native dataset helpers: {origin} (g++ at {compiler})")

    # Every document walks one fixed cycle over CYCLE token ids (drawn from
    # --seed), so the next token is a function of the current one: a few
    # optimizer steps learn it, "the loss falls" is a real check, and the
    # served model's greedy choice has a wide margin. The null
    # tokenizer takes id vocab-1 as end-of-document.
    rnd = random.Random(seed)
    vocab = size["vocab"] - 1
    cycle = corpus_cycle(size, seed)
    need = (size["train_iters"] * gbs + 2) * (size["seq"] + 1)
    docs, doc_len = max(need // 512 + 1, 16), 512
    jsonl = os.path.join(work, "corpus.jsonl")
    with open(jsonl, "w") as f:
        for _ in range(docs):
            at = rnd.randrange(CYCLE)
            ids = [cycle[(at + i) % CYCLE] for i in range(doc_len)]
            f.write(json.dumps({"text": " ".join(map(str, ids))}) + "\n")
    _run([sys.executable, PREPROCESS, "--input", jsonl,
          "--output_prefix", os.path.join(work, "corpus"),
          "--tokenizer_type", "null", "--vocab_size", str(vocab),
          "--append_eod"], env, os.path.join(work, "preprocess.log"), 600)
    return {"phase": "data", "ok": True,
            "seconds": round(time.time() - t0, 2),
            "native_helpers": origin, "compiler": compiler,
            "documents": docs, "tokens": docs * (doc_len + 1)}


def _train_events(tele_dir: str) -> list:
    with open(os.path.join(tele_dir, "events.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    return [e for e in events if e.get("kind") == "step"]


def _check_losses(steps: list, size: dict) -> list:
    losses = [s["loss"] for s in steps]
    _check(len(losses) == size["train_iters"],
           f"{len(losses)} step records, want {size['train_iters']}")
    _check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    ln_v = math.log(size["vocab"])
    # random logits of std s add s^2/2 to ln(vocab): 0.8 at hidden 4096
    # and the default init std of 0.02
    _check(abs(losses[0] - ln_v) < 1.0,
           f"first loss {losses[0]:.3f} not near ln(vocab)={ln_v:.3f}")
    _check(min(losses[-2:]) < losses[0] - 0.05,
           f"loss did not fall: {losses}")
    return losses


def phase_train(size: dict, work: str, seed: int, rehearse: bool) -> dict:
    t0 = time.time()
    tele, ir = os.path.join(work, "tele"), os.path.join(work, "ir_train")
    log = _run(
        [sys.executable, TRAINER] + train_flags(size, work, seed, 1, True)
        + ["--telemetry_dir", tele],
        _child_env(rehearse, JAX_DUMP_IR_TO=ir,
                   JAX_DUMP_IR_MODES="stablehlo,jaxpr"),
        os.path.join(work, "train.log"), 900)
    dev = _device_from_log(log)
    _check(rehearse or dev["platform"] == "tpu", f"trainer ran on {dev}")
    steps = _train_events(tele)
    losses = _check_losses(steps, size)
    # the first step compiles; from the second on, nothing may
    recompiles = sum(s.get("compiles", 0) for s in steps[1:])
    _check(recompiles == 0, f"{recompiles} compiles after the first step: "
           + json.dumps([s.get("compiles", 0) for s in steps]))
    kernels = _dumped(ir, "train_step")
    # forward, the backward's row statistics and the fused backward
    # kernel, in the one scanned layer body
    _check_kernels_in_step(kernels, dev, "the train step", 3)
    _check("fused fwd+bwd (custom vjp)" in log,
           "trainer did not announce the flash gradient path")
    # the checkpoint is committed: tracker, manifest, every file's crc
    _run([sys.executable, CKPT_VERIFY, "verify", "--load",
          os.path.join(work, "ckpt"), "--deep"],
         _child_env(rehearse, JAX_PLATFORMS="cpu"),
         os.path.join(work, "ckpt_verify.log"), 600)
    with open(os.path.join(work, "ckpt",
                           "latest_checkpointed_iteration.txt")) as f:
        committed = int(f.read().strip())
    _check(committed == size["train_iters"],
           f"tracker says {committed}, want {size['train_iters']}")
    return {"phase": "train", "ok": True, "device": dev,
            "seconds": round(time.time() - t0, 2),
            "losses": [round(x, 4) for x in losses],
            "recompiles_after_first_step": recompiles,
            "kernels_in_step": kernels,
            "checkpoint_iteration": committed,
            "setup": {
                "first_step_compile_ms": steps[0].get("compile_ms"),
                "step_ms": [s["step_ms"] for s in steps[1:]],
                "tokens_per_step": steps[0]["ntokens"]}}


def _http(method: str, url: str, body: dict | None = None,
          timeout: float = 300.0):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def phase_serve(size: dict, work: str, seed: int, rehearse: bool,
                iteration: int) -> dict:
    """The real server on the trainer's checkpoint: boot, warm up, answer
    concurrent greedy requests, report zero decode recompiles, drain on
    SIGTERM. Returns the phase line."""
    import random

    t0 = time.time()
    ir = os.path.join(work, "ir_serve")
    port = _free_port()
    cmd = [sys.executable, SERVER] + model_flags(size) + [
        "--tokenizer_type", "null", "--load", os.path.join(work, "ckpt"),
        "--host", "127.0.0.1", "--port", str(port),
        "--serve_num_slots", str(size["slots"]),
        "--serve_max_seq_len", str(size["serve_len"]), "--serve_warmup",
        "--serve_drain_timeout", "60", "--seed", str(seed),
    ]
    log_path = os.path.join(work, "serve.log")
    base = f"http://127.0.0.1:{port}"
    rnd = random.Random(seed + 1)
    cycle = corpus_cycle(size, seed)
    prompts = []
    for n in size["prompt_lens"]:  # stretches of the training cycle
        at = rnd.randrange(CYCLE)
        prompts.append(" ".join(str(cycle[(at + i) % CYCLE])
                                for i in range(n)))
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
            env=_child_env(rehearse, JAX_DUMP_IR_TO=ir,
                           JAX_DUMP_IR_MODES="stablehlo,jaxpr"))
    try:
        deadline = time.time() + 600
        ready = False
        while time.time() < deadline and proc.poll() is None:
            try:
                ready = _http("GET", base + "/readyz", timeout=5)[0] == 200
            except OSError:
                ready = False  # not listening yet (model load, compile)
            if ready:
                break
            time.sleep(1.0)
        _check(ready, f"server not ready (rc={proc.poll()}); log tail:\n"
               + _read(log_path)[-3000:])
        t_ready = time.time() - t0

        # one request alone, then the rest at once: concurrent requests
        # share decode ticks in the engine
        replies = [None] * len(prompts)

        def ask(i):
            t = time.perf_counter()
            code, text = _http("PUT", base + "/api", {
                "prompts": [prompts[i]], "top_k": 1,
                "tokens_to_generate": size["new_tokens"]})
            replies[i] = (code, text, time.perf_counter() - t)

        ask(0)
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(1, len(prompts))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)
        generated = []
        for i, reply in enumerate(replies):
            _check(reply is not None, f"request {i} never returned")
            code, text, _ = reply
            _check(code == 200, f"request {i}: HTTP {code}: {text[:300]}")
            out = json.loads(text)
            _check(out.get("weights_version") == iteration,
                   f"weights_version {out.get('weights_version')} != "
                   f"trainer iteration {iteration}")
            toks = out["text"][0].split()
            n_prompt = len(prompts[i].split())
            _check(toks[:n_prompt] == prompts[i].split(),
                   f"request {i}: reply does not start with its prompt")
            _check(len(toks) == n_prompt + size["new_tokens"],
                   f"request {i}: {len(toks) - n_prompt} new tokens, asked "
                   f"{size['new_tokens']}")
            generated.append(toks[n_prompt:])
        # how far the replies go on walking the cycle the model was
        # trained on (reported, not required: the steps were few)
        nxt = {str(a): str(b) for a, b in zip(cycle, cycle[1:] + cycle[:1])}
        walked = sum(
            new == nxt.get(prev) for p, g in zip(prompts, generated)
            for prev, new in zip([p.split()[-1]] + g, g))
        code, metrics = _http("GET", base + "/metrics")
        _check(code == 200, f"/metrics: HTTP {code}")
        recompiles = [float(line.split()[-1])
                      for line in metrics.splitlines()
                      if line.startswith("engine_decode_recompiles_total")]
        _check(recompiles == [0.0],
               f"engine_decode_recompiles_total {recompiles}")
        served = [line for line in metrics.splitlines()
                  if line.startswith("server_requests_total")]
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    log_text = _read(log_path)
    _check(rc == 0, f"server exited {rc} on SIGTERM; log tail:\n"
           + log_text[-2000:])
    _check("drain complete" in log_text, "no clean drain in the server log")
    _check(f"loaded checkpoint at iteration {iteration}" in log_text,
           "server did not load the trainer's checkpoint")
    dev = _device_from_log(log_text)
    _check(rehearse or dev["platform"] == "tpu", f"server ran on {dev}")
    kernels = _dumped(ir, "decode_step")
    _check_kernels_in_step(kernels, dev, "the decode step", 1)
    return {"phase": "serve", "ok": True, "device": dev,
            "seconds": round(time.time() - t0, 2),
            "requests": len(prompts), "concurrent": len(prompts) - 1,
            "new_tokens_each": size["new_tokens"],
            "tokens_continuing_the_cycle":
                f"{walked}/{len(prompts) * size['new_tokens']}",
            "weights_version": iteration, "decode_recompiles": 0,
            "kernels_in_step": kernels, "drained": True,
            "metrics": served,
            "setup": {"boot_to_ready_s": round(t_ready, 2),
                      "request_s": [round(r[2], 3) for r in replies]}}


def phase_train4(size: dict, work: str, seed: int, rehearse: bool,
                 mode: str) -> dict:
    """One of the two --chips 4 trainings, as a child that reports on the
    final state (child_train4)."""
    t0 = time.time()
    sub = os.path.join(work, mode)
    os.makedirs(sub, exist_ok=True)
    tele, ir = os.path.join(sub, "tele"), os.path.join(sub, "ir")
    argv = train_flags(size, work, seed, 2, False) + [
        "--telemetry_dir", tele]
    if mode == "sharded":
        argv += ["--tensor_model_parallel_size", "2",
                 "--sequence_parallel", "--use_distributed_optimizer"]
    out_path = os.path.join(sub, "report.json")
    log = _run(
        [sys.executable, os.path.abspath(__file__), "--phase", "train4",
         "--mode", mode, "--out", out_path]
        + (["--rehearse"] if rehearse else []) + ["--"] + argv,
        _child_env(rehearse, JAX_DUMP_IR_TO=ir,
                   JAX_DUMP_IR_MODES="stablehlo,jaxpr"),
        os.path.join(sub, "train.log"), 1500)
    with open(out_path) as f:
        report = json.load(f)
    steps = _train_events(tele)
    report["losses"] = _check_losses(steps, size)
    report["recompiles_after_first_step"] = sum(
        s.get("compiles", 0) for s in steps[1:])
    report["kernels_in_step"] = _dumped(ir, "train_step")
    _check_kernels_in_step(report["kernels_in_step"], report["device"],
                           f"the {mode} train step", 3)
    report["mesh_line"] = next(
        (l for l in log.splitlines() if l.startswith("mesh: ")), None)
    report["seconds"] = round(time.time() - t0, 2)
    report["setup"] = {
        "first_step_compile_ms": steps[0].get("compile_ms"),
        "step_ms": [s["step_ms"] for s in steps[1:]]}
    return report


def run_four_chips(size: dict, work: str, seed: int, rehearse: bool,
                   emit) -> dict:
    """Sharded training over four devices (TP 2 x DP 2 derived, sequence
    parallel, sharded optimizer, the Pallas kernel per shard) against the
    same seed and global batch on one device. Nothing else runs."""
    emit(phase_data(size, work, seed, rehearse, gbs=2))
    sharded = phase_train4(size, work, seed, rehearse, "sharded")
    base = phase_train4(size, work, seed, rehearse, "baseline")
    dev = sharded["device"]
    _check(dev["count"] == 4, f"--chips 4 found {dev['count']} devices")

    diffs = [abs(a - b) for a, b in zip(sharded["losses"], base["losses"])]
    # bf16 weights and activations, different reduction orders. The first
    # loss is one forward pass of the same weights on the same batch; the
    # next two follow Adam's first (sign-like, large) updates, where a
    # rounding difference moves the loss by a share of its value; later
    # steps drift as updates compound and are reported, not required
    tolerances = [0.02] + [0.05 + 0.15 * max(a, b) for a, b in zip(
        sharded["losses"][1:3], base["losses"][1:3])]
    _check(all(d <= t for d, t in zip(diffs, tolerances)),
           f"sharded and unsharded losses differ: {sharded['losses']} vs "
           f"{base['losses']} (allowed {tolerances})")
    _check(sharded["devices_holding_state"] == sorted(
        int(k) for k in sharded["memory"]) and
        len(sharded["devices_holding_state"]) == 4,
        f"state sits on devices {sharded['devices_holding_state']}")
    _check(len(base["devices_holding_state"]) == 1,
           f"baseline state on {base['devices_holding_state']}")
    _check(sharded["smallest_shard_fraction"] <= 0.25 + 1e-9,
           "no leaf of the sharded state is split four ways (TP 2 x "
           f"ZeRO over DP 2): {sharded['smallest_shard_fraction']}")
    in_use = {k: v["bytes_in_use"] for k, v in sharded["memory"].items()}
    whole = base["memory"][str(base["devices_holding_state"][0])][
        "bytes_in_use"]
    if whole is not None:  # the CPU backend reports no memory_stats
        _check(all(v is not None and v < 0.6 * whole
                   for v in in_use.values()),
               f"per-device bytes_in_use {in_use} not well below the "
               f"unsharded run's {whole}")
    comp = sharded["compiled"]
    # every kind of collective the golden comm contract of this layout
    # lists (analysis/golden/train_tp2_sp.json, compiled for the CPU mesh)
    # is in the step compiled for these devices
    with open(os.path.join(REPO, "megatron_tpu", "analysis", "golden",
                           "train_tp2_sp.json")) as f:
        expected = sorted(json.load(f)["hlo"]["collectives"])
    for kind in expected:
        _check(comp["collectives"].get(kind, 0) > 0,
               f"compiled sharded step has no {kind} (the train_tp2_sp "
               f"contract lists {expected})")
    if dev["platform"] == "tpu":
        _check(comp["tpu_custom_calls"] >= 3,
               "compiled sharded step lost its Pallas custom calls")
    for mode, rep in (("sharded", sharded), ("baseline", base)):
        emit({"phase": f"train_{mode}", "ok": True, "device": rep["device"],
              "seconds": rep["seconds"], "mesh": rep["mesh_line"],
              "losses": [round(x, 4) for x in rep["losses"]],
              "recompiles_after_first_step":
                  rep["recompiles_after_first_step"],
              "kernels_in_step": rep["kernels_in_step"],
              "devices_holding_state": rep["devices_holding_state"],
              "smallest_shard_fraction":
                  round(rep["smallest_shard_fraction"], 4),
              **({"compiled": rep["compiled"]} if "compiled" in rep else {}),
              "setup": dict(rep["setup"], memory=rep["memory"])})
    emit({"phase": "compare", "ok": True, "device": dev,
          "collective_kinds_of_train_tp2_sp_contract": expected,
          "max_loss_diff_first3": round(max(diffs[:3]), 5),
          "loss_diffs": [round(x, 5) for x in diffs],
          "bytes_in_use_sharded": in_use,
          "bytes_in_use_unsharded": whole})
    return dev


def run_one_chip(size: dict, work: str, seed: int, rehearse: bool,
                 emit) -> dict:
    # device + kernels share one child (each process takes ~15 s to reach
    # the chip); its stdout carries their two lines
    log = _run([sys.executable, os.path.abspath(__file__), "--phase",
                "device+kernels", "--seed", str(seed)]
               + (["--rehearse"] if rehearse else []),
               _child_env(rehearse), os.path.join(work, "kernels.log"), 900)
    lines = [json.loads(l) for l in log.splitlines()
             if l.startswith('{"phase"')]
    _check([l["phase"] for l in lines] == ["device", "kernels"],
           "device+kernels child printed " + str([l["phase"] for l in lines]))
    for line in lines:
        emit(line)
    dev = lines[0]["device"]
    emit(phase_data(size, work, seed, rehearse, gbs=1))
    train = phase_train(size, work, seed, rehearse)
    emit(train)
    emit(phase_serve(size, work, seed, rehearse,
                     train["checkpoint_iteration"]))
    _check(train["device"] == dev, "phases ran on different devices: "
           f"{train['device']} vs {dev}")
    return dev


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=1234)
    p.add_argument("--rehearse", action="store_true",
                   help="toy size on whatever backend JAX finds")
    p.add_argument("--workdir", default=os.path.join(
        REPO, "runs", "chip_smoke"))
    p.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    p.add_argument("--mode", default=None, help=argparse.SUPPRESS)
    p.add_argument("--out", default=None, help=argparse.SUPPRESS)
    p.add_argument("rest", nargs=argparse.REMAINDER, help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    size = SIZES["toy" if args.rehearse else "real"]

    missing = [f for f in (TRAINER, PREPROCESS, SERVER,
                           os.path.join(REPO, "megatron_tpu"))
               if not os.path.exists(f)]
    if missing:
        print(f"chip_smoke: not in a checkout of the repo (missing "
              f"{missing}); there is nothing to drive", file=sys.stderr)
        return 2

    if args.phase:  # a child: this process may touch JAX
        sys.path.insert(0, REPO)
        try:
            if args.phase == "device+kernels":
                dev = child_device(size, args.rehearse)
                child_kernels(size, dev, args.seed)
            elif args.phase == "train4":
                child_train4(size, args.rehearse, args.mode,
                             [a for a in args.rest if a != "--"], args.out)
            else:
                raise SystemExit(f"unknown phase {args.phase}")
        except PhaseFailed as e:
            print(f"chip_smoke[{args.phase}]: FAILED: {e}", file=sys.stderr)
            return 1
        return 0

    work = os.path.abspath(args.workdir)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    started = []  # phase lines printed so far

    def emit(line: dict) -> None:
        started.append(line["phase"])
        print(json.dumps(line), flush=True)

    try:
        if args.chips == 4:
            dev = run_four_chips(size, work, args.seed, args.rehearse, emit)
        else:
            dev = run_one_chip(size, work, args.seed, args.rehearse, emit)
        _check(args.chips == dev["count"] or args.rehearse,
               f"--chips {args.chips} but JAX reports {dev['count']} "
               "devices")
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED after {started}: {e}", file=sys.stderr)
        if started:  # with no device there is no result line of any kind
            print(json.dumps({"ok": False, "after": started}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
