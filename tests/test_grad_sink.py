"""The step that sums the experts' weight gradient where it is made
(training/train_step.py, models/language_model.py `grad_sink`,
ops/pallas/grouped_matmul.py `sink`), on the CPU with the kernels in
interpret mode: a toy MoE model of two layers, four micro-batches a step.
The step with the sink against the same step with the add left to XLA and
against a float32 model; a dense model's step is the same jaxpr whether
the kernels would serve or not; and the trainer's journal says how many
leaves the kernels sum.
"""

import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import (
    OptimizerConfig, ParallelConfig, RunConfig, TrainingConfig,
)
from megatron_tpu.models import presets
from megatron_tpu.models.params import init_params
from megatron_tpu.ops.pallas import grouped_matmul as gm
from megatron_tpu.training import train_step as ts
from megatron_tpu.training.optimizer import init_train_state

SEQ, MICRO = 64, 4
# no clipping and no decay: Adam's first moment after one step is a tenth
# of the accumulated gradient
OPT = OptimizerConfig(lr=1e-3, lr_decay_style="constant", clip_grad=0.0,
                      weight_decay=0.0)
TRAIN = TrainingConfig(micro_batch_size=1, global_batch_size=MICRO,
                       train_iters=2, recompute_granularity="selective")


def toy_moe(dtype="bfloat16", **overrides):
    """OLMoE's structure at widths the kernels' tiles divide: 64 tokens x
    2 choices = 128 rows in 4 groups, k and n of 128 and 256."""
    return dataclasses.replace(
        presets.olmoe(seq_length=SEQ), num_layers=2, hidden_size=128,
        num_attention_heads=4, num_kv_heads=4, ffn_hidden_size=128,
        vocab_size=256, num_experts=4, moe_top_k=2, params_dtype=dtype,
        attention_impl="xla", ce_chunk_size=0, **overrides).validate()


def toy_dense():
    return dataclasses.replace(toy_moe(), num_experts=None).validate()


def batch_of(rows=MICRO):
    tokens = np.random.default_rng(0).integers(0, 256, (rows, SEQ + 1))
    return {"tokens": jnp.asarray(tokens[:, :-1], jnp.int32),
            "labels": jnp.asarray(tokens[:, 1:], jnp.int32),
            "loss_mask": jnp.ones((rows, SEQ), jnp.float32)}


def state_of(cfg, dtype):
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    # wider than the init, so that the experts weigh on the loss; the
    # values bf16 holds, in whichever dtype: one model for every step
    params = jax.tree.map(
        lambda p: (4.0 * p).astype(jnp.bfloat16).astype(dtype), params)
    return init_train_state(OPT, params)


@pytest.fixture
def as_on_one_tpu(monkeypatch):
    monkeypatch.setattr(gm, "_one_tpu", lambda: True)


@pytest.fixture(scope="module")
def stepped():
    """One step of the toy model three ways: the sink; the same kernels
    with the add left to XLA (`takes_sink` false); float32 throughout."""
    def one_step(cfg, dtype):
        step = ts.make_train_step(cfg, OPT, TRAIN, num_microbatches=MICRO)
        text = str(jax.make_jaxpr(step)(state_of(cfg, dtype), batch_of()))
        return jax.jit(step)(state_of(cfg, dtype), batch_of()), text

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gm, "_one_tpu", lambda: True)
        out = {"sink": one_step(toy_moe(), jnp.bfloat16)}
        mp.setattr(gm, "takes_sink", lambda *a: False)
        out["plain"] = one_step(toy_moe(), jnp.bfloat16)
        out["float32"] = one_step(toy_moe("float32"), jnp.float32)
    return out


def _experts(tree):
    moe = tree["layers"]["moe"]
    return {name: np.asarray(moe[name], np.float32)
            for name in ("w_in", "w_out")}


def test_the_sink_moves_the_add_into_the_kernel(stepped):
    """With the sink the step's two `moe_tgmm` calls (one a matrix; the
    layers are a scan) give float32 stacks aliased to an operand and the
    step holds no expert-shaped gradient in bf16; without it they give the
    layer's bf16 gradient and XLA adds."""
    sink, plain = stepped["sink"][1], stepped["plain"][1]
    for text in (sink, plain):
        assert text.count("name=moe_tgmm") == 2
        # selective recomputation saves the two forward products
        assert text.count("name=moe_gmm") == 4
    assert "out_avals=(ShapedArray(float32[2,4,128,256]),)" in sink
    assert "out_avals=(ShapedArray(float32[2,4,128,128]),)" in sink
    assert "out_avals=(ShapedArray(bfloat16[4,128,256]),)" in plain
    assert "out_avals=(ShapedArray(bfloat16[4,128,128]),)" in plain


def test_step_with_the_sink_agrees_with_the_plain_step(stepped):
    """Same loss to the last bit (the forward is untouched); every leaf
    the kernels do not sum has the same accumulated gradient (Adam's first
    moment) and the same new value, bit for bit; the expert matrices agree
    within the bf16 rounding of a micro-batch's gradient."""
    (sink, m_sink), _ = stepped["sink"]
    (plain, m_plain), _ = stepped["plain"]
    assert float(m_sink["loss"]) == float(m_plain["loss"])
    for which in ("mu", "master"):
        theirs = dict(jax.tree_util.tree_leaves_with_path(
            getattr(plain, which)))
        for path, ours in jax.tree_util.tree_leaves_with_path(
                getattr(sink, which)):
            if path[-1].key not in ("w_in", "w_out"):
                np.testing.assert_array_equal(
                    ours, theirs[path],
                    err_msg=which + jax.tree_util.keystr(path))
    for name, got in _experts(sink.mu).items():
        want = _experts(plain.mu)[name]
        assert np.abs(want).max() > 0
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=2.0 ** -8 * np.abs(want).max())
    for name, got in _experts(sink.master).items():
        np.testing.assert_allclose(got, _experts(plain.master)[name],
                                   rtol=0, atol=2.5 * OPT.lr)


def test_the_sink_is_no_further_from_float32_than_the_plain_step(stepped):
    """Against the same model in float32 (whose activations, and with them
    some tokens' experts, differ from the bf16 model's: that distance is
    common to both) the unrounded products leave the accumulated expert
    gradients no further off than the rounded ones, root mean square over
    each matrix."""
    ref = _experts(stepped["float32"][0][0].mu)
    sink = _experts(stepped["sink"][0][0].mu)
    plain = _experts(stepped["plain"][0][0].mu)
    for name in ref:
        def rms(a):
            return float(np.sqrt(np.mean(np.square(a))))

        assert rms(sink[name] - ref[name]) <= 1.01 * rms(
            plain[name] - ref[name]), name
        assert rms(sink[name] - ref[name]) < rms(ref[name]), name


def test_a_dense_step_is_the_same_jaxpr_either_way(monkeypatch):
    """No leaf qualifies in a model without experts: its step traces to
    the same jaxpr where the kernels would serve and where they would
    not, and `kernel_summed` names nothing."""
    cfg = toy_dense()
    state, batch = state_of(cfg, jnp.bfloat16), batch_of()
    texts = []
    for serve in (False, True):
        monkeypatch.setattr(gm, "_one_tpu", lambda serve=serve: serve)
        step = ts.make_train_step(cfg, OPT, TRAIN, num_microbatches=MICRO)
        # (a function's repr in the text holds its address)
        texts.append(re.sub(r" at 0x[0-9a-f]+", "", str(
            jax.make_jaxpr(step)(state, batch))))
        assert not any(jax.tree.leaves(
            ts.kernel_summed(cfg, state.params, batch, MICRO)))
    assert texts[0] == texts[1]


def test_who_gets_a_sink(as_on_one_tpu):
    """The two stacked expert matrices, where the step accumulates over
    micro-batches through its own loss and the products are the kernels;
    nobody in a single micro-batch, under a task's loss, at rows the
    tiles do not divide, or off the TPU."""
    cfg = toy_moe()
    params, batch = state_of(cfg, jnp.bfloat16).params, batch_of()

    def named(tree):
        return [jax.tree_util.keystr(p) for p, s in
                jax.tree_util.tree_leaves_with_path(tree) if s]

    assert named(ts.kernel_summed(cfg, params, batch, MICRO)) == [
        "['layers']['moe']['w_in']", "['layers']['moe']['w_out']"]
    assert not named(ts.kernel_summed(cfg, params, batch, 1))
    assert not named(ts.kernel_summed(cfg, params, batch, MICRO,
                                      own_loss=False))
    odd = {k: v[:, :SEQ - 1] for k, v in batch.items()}
    assert not named(ts.kernel_summed(cfg, params, odd, MICRO))
    capacity = dataclasses.replace(cfg, moe_dispatch="capacity")
    assert not named(ts.kernel_summed(capacity, params, batch, MICRO))


def test_off_the_tpu_nobody_gets_a_sink():
    cfg = toy_moe()
    params = state_of(cfg, jnp.bfloat16).params
    assert not any(jax.tree.leaves(
        ts.kernel_summed(cfg, params, batch_of(), MICRO)))


@pytest.mark.parametrize("model,leaves,share", [
    ("moe", 2, None), ("dense", 0, 0.0)])
def test_the_journal_counts_the_leaves_the_kernels_sum(
        tmp_path, monkeypatch, as_on_one_tpu, model, leaves, share):
    """A run that traced journals, in its `step_program` record, how many
    parameter leaves and what share of the parameters' elements have
    their gradient summed in the kernel: the toy MoE step's two expert
    matrices, none of a dense one."""
    from megatron_tpu.parallel.mesh import single_device_mesh
    from megatron_tpu.training import pretrain

    monkeypatch.setattr(pretrain, "build_mesh",
                        lambda parallel: single_device_mesh())
    cfg = toy_moe() if model == "moe" else toy_dense()
    run = RunConfig(
        model=cfg, parallel=ParallelConfig(), optimizer=OPT,
        training=dataclasses.replace(
            TRAIN, global_batch_size=2, train_iters=3, log_interval=1,
            profile=True, profile_step_start=2, profile_step_end=3,
            profile_dir=str(tmp_path / "trace"),
            telemetry_dir=str(tmp_path / "tele")))
    rows = np.random.default_rng(0).integers(0, 256, (2, SEQ + 1))

    def factory(consumed, gbs):
        while True:
            yield {"tokens": rows[:, :-1].astype(np.int64),
                   "labels": rows[:, 1:].astype(np.int64),
                   "loss_mask": np.ones((gbs, SEQ), np.float32)}

    loop = pretrain.TrainLoop(run, log=lambda s: None)
    loop.train(factory)
    with open(tmp_path / "tele" / "events.jsonl") as f:
        records = [json.loads(line) for line in f]
    [program] = [r for r in records if r["kind"] == "step_program"]
    assert program["num_microbatches"] == 2
    assert program["kernel_summed_leaves"] == leaves
    if share is None:
        sizes = {jax.tree_util.keystr(p): x.size for p, x in
                 jax.tree_util.tree_leaves_with_path(loop.state.params)}
        share = sum(n for k, n in sizes.items()
                    if "w_in" in k or "w_out" in k) / sum(sizes.values())
        assert 0.1 < share < 1.0
    assert program["kernel_summed_share"] == pytest.approx(share)


# ---------------------------------------------------------------------------
# the dropless block with the activation inside its kernels
# (ops/pallas/grouped_matmul.py `grouped_mlp`) against its `lax.ragged_dot`
# form, whole and as a share of the experts, sinks included
# ---------------------------------------------------------------------------

BLOCK_CASES = {
    "whole": {},
    # experts 2 and 3 of the router's four: the rows of experts 0 and 1
    # stand behind the two groups
    "share": dict(moe_experts_held=2, moe_expert_share=1),
}


def _block_parts(cfg, sunk, kernels):
    """(loss, d x, d router, d w_in, d w_out or what its stack got) of a
    scalar of `moe_block_dropless`'s result, float32, the products the
    kernels (interpreted) or `lax.ragged_dot`."""
    from megatron_tpu.ops import moe

    p = jax.tree.map(
        lambda a: a[0], init_params(cfg, jax.random.PRNGKey(1),
                                    dtype=jnp.float32)["layers"]["moe"])
    # wider than the init, so that the experts weigh on the scalar
    p = {name: 4.0 * a for name, a in p.items()}
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    x = jax.random.normal(keys[0], (1, SEQ, cfg.hidden_size))
    weight = jax.random.normal(keys[1], x.shape)
    stacks = {name: jax.random.normal(key, (2,) + p[name].shape)
              for name, key in zip(moe.EXPERT_MATRICES, keys[2:])}

    def scalar(x, p, stacks):
        if not sunk:
            y, aux, _ = moe.moe_block_dropless(cfg, p, x)
            return jnp.sum(y * weight) + aux, {}
        y, aux, _, through = moe.moe_block_dropless(
            cfg, p, x, grad_sink=(stacks, jnp.int32(1)))
        return jnp.sum(y * weight) + aux, through

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(gm, "_one_tpu", lambda: kernels)
        text = str(jax.make_jaxpr(scalar)(x, p, stacks))
        (loss, through), vjp = jax.vjp(scalar, x, p, stacks)
        dx, dp, dstacks = vjp((jnp.ones(()), through))
    return text, {"loss": loss, "d x": dx, "d router": dp["router"],
                  **{f"d {name}": (dstacks[name][1] - stacks[name][1]
                                   if sunk else dp[name])
                     for name in moe.EXPERT_MATRICES}}, (dp, dstacks, stacks)


@pytest.mark.parametrize("sunk", [False, True], ids=["plain", "sinks"])
@pytest.mark.parametrize("case", list(BLOCK_CASES))
def test_the_block_with_the_activation_in_its_kernels_is_the_ragged_dot_form(
        case, sunk):
    """`moe_block_dropless`, whole and as a share (the rows of the experts
    held elsewhere behind the last group, where no kernel writes the first
    product): loss and every gradient with the kernels, which hold the
    activation, are those of the `lax.ragged_dot` form, which zeroes those
    rows and applies the activation between its products. With sinks each
    matrix's gradient is in its stack's layer, the other layer untouched,
    and the matrix's own cotangent is zero."""
    cfg = toy_moe("float32", **BLOCK_CASES[case])
    text, got, (dp, dstacks, stacks) = _block_parts(cfg, sunk, kernels=True)
    # (off the kernels nobody gets a sink: the matrices' own gradients)
    ragged_text, want, _ = _block_parts(cfg, False, kernels=False)
    assert text.count("name=moe_gmm") == 2 and "ragged_dot" not in text
    assert "ragged_dot" in ragged_text and "pallas_call" not in ragged_text
    for what in want:
        assert np.isfinite(np.asarray(got[what])).all(), what
        np.testing.assert_allclose(got[what], want[what], rtol=2e-5,
                                   atol=2e-4, err_msg=what)
    if sunk:
        for name, stack in stacks.items():
            assert not np.any(np.asarray(dp[name])), name
            np.testing.assert_array_equal(dstacks[name][0], stack[0])
            assert np.abs(np.asarray(got[f"d {name}"])).max() > 0
