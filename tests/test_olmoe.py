"""OLMoE on the program's normal path, held to the plain reference of
benchmark/reference/olmoe.py, and that reference held to `transformers`'
`OlmoeForCausalLM`. Toy widths, whole structure: 8 experts, 4 a token,
raw (un-renormalised) gates, QK-norm over the whole projections, MHA, two
layers, untied head, both router losses. Weights are seeded random draws
at a standard deviation of 0.1 with norm scales drawn around 1, so that
every term carries weight in the loss (at 0.02 and scales of one, QK-norm
and the gates move the loss by less than bf16 rounding does)."""

import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.harness import spec  # noqa: E402
from megatron_tpu.arguments import args_to_run_config, parse_args  # noqa: E402
from megatron_tpu.models.language_model import lm_forward, lm_loss  # noqa: E402
from megatron_tpu.models.params import init_params, param_specs  # noqa: E402
from megatron_tpu.ops import moe  # noqa: E402

reference = spec.load_module(
    os.path.join(REPO, "benchmark", "reference", "olmoe.py"))

SEQ = 32
TOY = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 32,
    "max_position_embeddings": 128, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 4, "num_experts": 8,
    "num_experts_per_tok": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 256,
    "assumed": {"head_dim": {"value": 16},
                "initializer_range": {"value": 0.1},
                "router_aux_loss_coef": {"value": 0.01},
                "router_z_loss_coef": {"value": 0.001}},
}


def program_config(dtype="--fp32", **overrides):
    """The toy model as the trainer builds it from the reference's own
    translation into flags (what the benchmark's child passes)."""
    argv = reference.program_flags(TOY, SEQ) + [
        dtype, "--micro_batch_size", "1", "--global_batch_size", "1"]
    model = args_to_run_config(parse_args(argv)).model
    return dataclasses.replace(model, **overrides).validate()


def seeded_params(cfg, seed=0):
    """init_params, with every norm scale drawn around 1 (ones would hide
    a scale applied in the wrong place)."""
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def draw(path, leaf):
        if path[-1].key == "scale":
            return 1.0 + 0.3 * jax.random.normal(next(keys), leaf.shape)
        # the output projections are drawn 1 / sqrt(2 L) narrower: widen
        # them, so that attention and the experts weigh on the residual
        return 4.0 * leaf if path[-1].key in ("wo", "w_out") else leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def one_sequence(seed=0, rows=1):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, TOY["vocab_size"], (rows, SEQ + 1))
    mask = (rng.random((rows, SEQ)) > 0.1).astype(np.float32)
    return {"tokens": jnp.asarray(tokens[:, :-1], jnp.int32),
            "labels": jnp.asarray(tokens[:, 1:], jnp.int32),
            "loss_mask": jnp.asarray(mask)}


def reference_loss(params, batch):
    return reference.lm_loss(reference.from_program_params(params),
                             batch["tokens"], batch["labels"],
                             batch["loss_mask"], TOY)


def in_dtype(params, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), params)


def test_the_preset_is_the_published_model():
    from megatron_tpu.models import presets
    from megatron_tpu.models.params import num_params

    cfg = args_to_run_config(parse_args(
        ["--model_name", "olmoe-1B-7B", "--micro_batch_size", "1",
         "--global_batch_size", "1"])).model
    assert cfg == dataclasses.replace(
        presets.olmoe("1B-7B"), attention_impl=cfg.attention_impl,
        params_dtype=cfg.params_dtype)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.n_kv_heads, cfg.head_dim, cfg.ffn_size) == (
        16, 2048, 16, 16, 128, 1024)
    assert (cfg.num_experts, cfg.moe_top_k, cfg.moe_renorm_gates,
            cfg.moe_dispatch, cfg.qk_norm, cfg.tie_embed_logits) == (
        64, 8, False, "dropless", True, False)
    assert (cfg.vocab_size, cfg.seq_length) == (50304, 4096)
    assert num_params(cfg) == 6_919_161_856  # the model card's 6.9 B
    # the flags the benchmark's configuration turns into build this model
    with open(os.path.join(REPO, "benchmark", "configs",
                           "olmoe-1b-7b-d1.json")) as f:
        published = dict(json.load(f), num_hidden_layers=16)
    from_flags = args_to_run_config(parse_args(
        reference.program_flags(published, 4096)
        + ["--bf16", "--micro_batch_size", "1",
           "--global_batch_size", "1"])).model
    for field in ("num_layers", "hidden_size", "num_attention_heads",
                  "n_kv_heads", "head_dim", "ffn_size", "vocab_size",
                  "num_experts", "moe_top_k", "moe_renorm_gates",
                  "moe_dispatch", "moe_aux_loss_coeff", "moe_z_loss_coeff",
                  "qk_norm", "tie_embed_logits", "normalization",
                  "activation", "rope_theta", "layernorm_epsilon"):
        assert getattr(from_flags, field) == getattr(cfg, field), field


# --- (a) the program against the reference ----------------------------------

def test_float32_loss_and_every_gradient_leaf_match_the_reference():
    """Same mathematics in float32 by two mechanisms (sort, gather,
    grouped matmul and scatter-add against a masked loop over all
    experts): they differ by the order of float32 sums only, which at
    these sizes is a few 1e-7 of the largest entry; 1e-5 of each leaf's
    largest entry passes that and fails any wrong term."""
    cfg = program_config()
    params = seeded_params(cfg)
    batch = one_sequence()
    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(cfg, p, batch)[0])(params)
    want, want_grads = jax.value_and_grad(
        lambda p: reference_loss(p, batch))(params)
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    got = jax.tree_util.tree_leaves_with_path(grads)
    ref = jax.tree.leaves(want_grads)
    assert len(got) == len(ref) == 14
    for (path, g), w in zip(got, ref):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0, path
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-5 * scale, path


# bf16 weights and activations against the float32 reference differ by
# 2e-3 or less over four sequences of this size (measured over seeds:
# 0.9e-3 to 1.9e-3, 0.9e-3 on the batch used); on that batch each dropped
# term below moves the loss by 0.011 or more (QK-norm off 0.017, gates
# renormalised 0.030, z-loss off 0.012, top-1 balance 0.06)
BF16_TOLERANCE = 3e-3
BATCH_SEED = 2


def _bf16_loss(batch, **overrides):
    """(the bf16 program's loss, with `overrides` on its configuration;
    the float32 reference's) under one set of weights."""
    params = seeded_params(program_config())
    cfg = program_config("--bf16", **overrides)
    low = in_dtype(params, jnp.bfloat16)
    rows = [jax.tree.map(lambda a: a[i:i + 1], batch)
            for i in range(batch["tokens"].shape[0])]
    # one sequence a micro-batch, as the cell accumulates them
    loss = np.mean([float(lm_loss(cfg, low, row)[0]) for row in rows])
    return float(loss), float(reference_loss(params, batch))


def test_bf16_program_is_within_tolerance_of_the_float32_reference():
    got, want = _bf16_loss(one_sequence(BATCH_SEED, rows=4))
    assert abs(got - want) <= BF16_TOLERANCE


def _top1_balance(monkeypatch):
    """The load-balance fraction as it was before this model: the top-1
    assignment only."""
    real = moe._aux_losses

    def top1(cfg, logits, gates, frac):
        g = gates.reshape(-1, cfg.num_experts)
        first = jax.nn.one_hot(jnp.argmax(g, -1), cfg.num_experts)
        return real(cfg, logits, gates, jnp.mean(first, 0))

    monkeypatch.setattr(moe, "_aux_losses", top1)
    return {}


@pytest.mark.parametrize("dropped", [
    lambda mp: {"qk_norm": False},
    lambda mp: {"moe_renorm_gates": True},
    _top1_balance,
    lambda mp: {"moe_z_loss_coeff": 0.0},
], ids=["qk_norm_off", "gates_renormalised", "top1_balance_loss",
        "z_loss_off"])
def test_a_dropped_term_fails_the_bf16_tolerance(monkeypatch, dropped):
    got, want = _bf16_loss(one_sequence(BATCH_SEED, rows=4),
                           **dropped(monkeypatch))
    assert abs(got - want) > 2 * BF16_TOLERANCE


# --- (b), (g) the cell's path: pretrain_gpt.main under the harness ----------

@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    """The real BENCHMARK.json's metrics over a toy OLMoE configuration
    and a mix that accumulates 4 micro-batches of one sequence, run
    traced through benchmark/run.py on the CPU: the trainer's own entry
    point, data pipeline, accumulation and journal."""
    root = tmp_path_factory.mktemp("toy_olmoe")
    cell = "toy_olmoe_accum"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["paths"] = ["."]
    bench["configs"] = [{"name": "toy-olmoe", "source": "none",
                         "file": "toy-olmoe.json", "reduced": [],
                         "why": "CPU rehearsal"}]
    bench["workloads"] = [{"name": cell, "config": "toy-olmoe",
                           "traffic": cell, "chips": 1,
                           "why": "CPU rehearsal of train_olmoe1b7b_seq4k"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ([cell] if "train_olmoe1b7b_seq4k"
                              in m["workloads"] else [])
    config = dict(TOY, source="none", reference="olmoe",
                  program={"flags": ["--fp32", "--attention_impl", "pallas"]})
    mix = {"driver": "train", "seq_length": 128, "micro_batch_size": 1,
           "global_batch_size": 4,
           "flags": ["--recompute_granularity", "selective",
                     "--ce_chunk_size", "64", "--lr", "3e-3",
                     "--lr_decay_style", "constant"],
           "warmup_steps": 2, "max_steps_per_s": 60,
           "trace_after_steps": 1, "trace_steps": 2,
           "corpus": {"tokens": 60000, "cycle": 64,
                      "doc_tokens_median": 100, "doc_tokens_sigma": 1.0,
                      "doc_tokens_min": 8, "doc_tokens_max": 1024},
           "first_loss_tolerance": 1e-4, "loss_must_fall_by": 0.0}
    os.makedirs(root / "traffic")
    for path, value in ((root / "spec.json", bench),
                        (root / "toy-olmoe.json", config),
                        (root / "traffic" / (cell + ".json"), mix)):
        with open(path, "w") as f:
            json.dump(value, f)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--spec", str(root / "spec.json"), "--workload", cell, "--seed",
         "2147484001", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    run_dir = os.path.join(REPO, "runs", "benchmark", cell)
    with open(os.path.join(run_dir, "result.json")) as f:
        result = json.load(f)
    with open(os.path.join(run_dir, "tele", "events.jsonl")) as f:
        journal = [json.loads(line) for line in f if line.strip()]
    return json.loads(proc.stdout.strip().splitlines()[-1]), result, journal


def test_accumulated_step_through_the_trainer_matches_the_reference(
        rehearsed):
    """One optimizer step of 4 accumulated micro-batches through
    pretrain_gpt.main: the journal's `loss` is the mean of the
    micro-batches' totals (CE + router losses, each per sequence), which
    is what the reference computes; float32, so to 1e-5."""
    line, result, _ = rehearsed
    assert line["correct"] is True, line.get("problems")
    first = result["steps"][0]
    assert first["iteration"] == 1 and first["ntokens"] == 4 * 128
    assert abs(first["loss"] - result["reference_first_loss"]) <= 1e-5 * abs(
        result["reference_first_loss"])


def test_the_step_record_carries_the_load_counter(rehearsed):
    line, _, journal = rehearsed
    steps = [r for r in journal if r.get("kind") == "step"]
    assert steps and all(
        1.0 <= r["moe_load_max_over_mean"] <= TOY["num_experts"]
        for r in steps)
    assert line["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0


def test_a_dense_models_step_has_no_load_counter():
    from megatron_tpu.config import OptimizerConfig, TrainingConfig
    from megatron_tpu.models import presets
    from megatron_tpu.training.optimizer import init_train_state
    from megatron_tpu.training.train_step import make_train_step

    cfg = presets.tiny(seq_length=SEQ)
    opt = OptimizerConfig(lr=1e-3)
    state = init_train_state(opt, init_params(cfg, jax.random.PRNGKey(0)))
    batch = {k: jnp.tile(v, (2, 1)) for k, v in one_sequence().items()}
    step = make_train_step(cfg, opt, TrainingConfig(), num_microbatches=2)
    _, metrics = jax.jit(step)(state, batch)
    assert moe.LOAD_METRIC not in metrics


def test_the_lowered_step_names_the_five_scopes():
    """Every scope a reader of benchmark/layer_metrics/ asks for is in
    the name stacks of the compiled step: the four stages of the MoE block
    inside `mlp`, forward and backward, and the accumulator's add."""
    from megatron_tpu.config import OptimizerConfig, ParallelConfig
    from megatron_tpu.telemetry.tracing.events import scope_tokens
    from megatron_tpu.training.aot import aot_compile_train_step

    compiled, _ = aot_compile_train_step(
        program_config(), ParallelConfig(), OptimizerConfig(lr=1e-4),
        micro_batch_size=1, num_microbatches=2, recompute="selective",
        devices=jax.devices()[:1])
    stacks = [(n, scope_tokens(n)) for n in set(
        re.findall(r'op_name="([^"]+)"', compiled.as_text()))]
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine"):
        under = [(n, toks) for n, toks in stacks if scope in toks]
        assert under, scope
        assert all("mlp" in toks[:toks.index(scope)] for _n, toks in under)
        assert any("transpose(" in n for n, _toks in under), scope
    accumulate = [toks for _n, toks in stacks if "grad_accumulate" in toks]
    assert accumulate and not any(
        {"mlp", "attention", "optimizer"} & set(toks) for toks in accumulate)
    # no scatter under `mlp`: rows cross the sort by expert through
    # gathers in both directions, the counts and the chosen gates are
    # dense sums (tests/test_chip_compile.py asserts the same of the
    # cell's own step compiled for the chip). The embedding's backward is
    # the scatter that shows the search finds one
    scatters = [toks for n, toks in stacks if "scatter" in n.lower()]
    assert scatters and not [t for t in scatters if "mlp" in t], scatters


# --- (c) QK-norm under tensor parallelism ------------------------------------

def test_qk_norm_at_tp2_on_two_devices_equals_tp1():
    """The mean square of the QK-norm spans all heads, so at TP 2 it
    crosses the shards of the projection (and the scale is sharded with
    it): loss and the gradients of both scales equal the unsharded
    run's."""
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.parallel.sharding import shard_tree

    cfg = program_config()
    params = seeded_params(cfg)
    batch = one_sequence()

    def loss_and_scale_grads(p, b):
        loss, grads = jax.value_and_grad(
            lambda q: lm_loss(cfg, q, b)[0])(p)
        attn = grads["layers"]["attn"]
        return loss, attn["q_norm"]["scale"], attn["k_norm"]["scale"]

    want = loss_and_scale_grads(params, batch)
    rt = build_mesh(ParallelConfig(tensor_parallel=2),
                    devices=jax.devices()[:2])
    specs = param_specs(cfg)
    assert "tensor" in specs["layers"]["attn"]["q_norm"]["scale"]
    with jax.sharding.set_mesh(rt.mesh):
        sharded = shard_tree(rt, params, specs)
        got = jax.jit(loss_and_scale_grads)(sharded, batch)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-5, atol=1e-7)


# --- (d) the two dispatches --------------------------------------------------

def test_dropless_equals_capacity_dispatch_with_ample_capacity():
    """k = 4 without renormalisation: with room for every choice the
    capacity einsums and the sort + grouped matmuls are the same layer,
    outputs, router losses and load counter."""
    cfg = program_config()
    ample = dataclasses.replace(cfg, moe_dispatch="capacity",
                                moe_capacity_factor=float(cfg.num_experts)
                                ).validate()
    params = seeded_params(cfg)
    layer = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, cfg.hidden_size))
    y_drop, aux_drop, load_drop = moe.moe_block(cfg, layer, x)
    y_cap, aux_cap, load_cap = moe.moe_block(ample, layer, x)
    np.testing.assert_allclose(np.asarray(y_drop), np.asarray(y_cap),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(float(aux_drop), float(aux_cap), rtol=1e-6)
    np.testing.assert_allclose(float(load_drop), float(load_cap), rtol=1e-6)
    assert float(load_drop) >= 1.0


# --- (e) serving: the cache holds normed, rotated keys -----------------------

def test_prefill_then_decode_through_the_slot_cache_equals_the_forward():
    from megatron_tpu.ops.kv_store import create as _init_caches

    cfg = program_config()
    params = seeded_params(cfg)
    tokens = one_sequence(seed=5)["tokens"]                  # [1, SEQ]
    full = lm_forward(cfg, params, tokens)
    prompt = 20
    caches = _init_caches(cfg, 1, SEQ)
    logits, caches = lm_forward(cfg, params, tokens[:, :prompt],
                                kv_caches=caches, cache_index=0)
    steps = [logits]
    for t in range(prompt, SEQ):
        # a vector cache_index is the continuous-batching slot cache
        logits, caches = lm_forward(
            cfg, params, tokens[:, t:t + 1], kv_caches=caches,
            cache_index=jnp.asarray([t], jnp.int32))
        steps.append(logits)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(steps, 1)),
                               np.asarray(full), rtol=1e-4, atol=1e-5)


# --- (f) the reference against transformers ----------------------------------

def test_the_reference_matches_transformers_olmoe():
    """Logits of the plain reference against `OlmoeForCausalLM` under the
    same weights, and the load-balance value against transformers'
    `load_balancing_loss_func` (called a layer at a time: given several
    layers it pools their tokens before the product, where the paper,
    Megatron and this program sum the layers' own losses)."""
    torch = pytest.importorskip("torch")
    olmoe = pytest.importorskip("transformers.models.olmoe.modeling_olmoe")
    from transformers import OlmoeConfig

    cfg = program_config()
    weights = reference.from_program_params(seeded_params(cfg))
    hf_config = OlmoeConfig(
        **{k: v for k, v in TOY.items() if k != "assumed"},
        output_router_logits=True, attn_implementation="eager")
    model = olmoe.OlmoeForCausalLM(hf_config).eval()

    def t(a):  # [in, out] here, [out, in] there
        return torch.from_numpy(np.array(np.asarray(a).T))

    state = {"model.embed_tokens.weight": t(weights["embed"].T),
             "model.norm.weight": t(weights["final_norm"]),
             "lm_head.weight": t(weights["lm_head"])}
    names = {"attn_norm": "input_layernorm", "wq": "self_attn.q_proj",
             "mlp_norm": "post_attention_layernorm",
             "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
             "wo": "self_attn.o_proj", "q_norm": "self_attn.q_norm",
             "k_norm": "self_attn.k_norm", "router": "mlp.gate"}
    f = TOY["intermediate_size"]
    for i in range(TOY["num_hidden_layers"]):
        w = jax.tree.map(lambda a: a[i], weights["layers"])
        for ours, theirs in names.items():
            state[f"model.layers.{i}.{theirs}.weight"] = t(w[ours])
        for e in range(TOY["num_experts"]):
            prefix = f"model.layers.{i}.mlp.experts.{e}."
            state[prefix + "gate_proj.weight"] = t(w["w_gate_up"][e][:, :f])
            state[prefix + "up_proj.weight"] = t(w["w_gate_up"][e][:, f:])
            state[prefix + "down_proj.weight"] = t(w["w_down"][e])
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("rotary" in k for k in missing), (
        missing, unexpected)

    tokens = one_sequence(seed=7)["tokens"][0]
    with torch.no_grad():
        out = model(torch.from_numpy(np.asarray(tokens))[None].long())
    got, balance, _z = reference.logits_and_router_losses(weights, tokens,
                                                          TOY)
    np.testing.assert_allclose(np.asarray(got), out.logits[0].numpy(),
                               rtol=2e-4, atol=2e-5)
    theirs = sum(float(olmoe.load_balancing_loss_func(
        (layer,), TOY["num_experts"], TOY["num_experts_per_tok"]))
        for layer in out.router_logits)
    assert abs(float(balance) - theirs) <= 1e-5 * theirs
    # all k choices: near k a layer, where the top-1 fraction gives near 1
    assert theirs > 0.8 * TOY["num_experts_per_tok"] * TOY[
        "num_hidden_layers"]
