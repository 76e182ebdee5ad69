"""Both configurations of the benchmark compiled at their real sizes for
a described TPU v5e, no chip attached (on-chip-measurement guide, section
2): the four-chip cell's train step at its micro-batch, and the served
model's decode and prefill-chunk programs over its page pool. Sizes are
read from the benchmark's own files, so a cell that no longer fits 16 GB
fails here and not on chip time. Nothing runs: a compile that passes is
not a chip run. (tests/test_chip_compile.py holds the kernels and the
one-chip step.)"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import dataclasses
import importlib
import json

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
GIB = 1 << 30
HBM = 15.75 * GIB   # what the chip's compiler allows a program


def _load(*parts):
    with open(os.path.join(REPO, "benchmark", *parts)) as f:
        return json.load(f)


def _flag(flags, name):
    return flags[flags.index(name) + 1]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or it is held by
        # another process: nothing can be compiled for the chip here.
        # tests/test_chip_compile.py describes a topology too, and under
        # xdist the two files can land on different workers: without
        # ALLOW_MULTIPLE_LIBTPU_LOAD one of them loses libtpu. The driver's
        # run sets it, so there a skip would be the guard on "the cell
        # still fits 16 GB" vanishing without a failure: fail instead.
        if os.environ.get("ALLOW_MULTIPLE_LIBTPU_LOAD"):
            raise
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(scope="module")
def as_on_the_chip():
    """The backend switches read jax.default_backend(), which is the CPU
    under such a compile: steer them to their chip side from the test."""
    from megatron_tpu.ops.pallas import flash_template as ft

    attention_mod = importlib.import_module("megatron_tpu.ops.attention")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ft, "_interpret", lambda: False)
        mp.setattr(attention_mod, "_kernels_dispatchable", lambda: True)
        yield


def _model(config, seq_length, **kw):
    from megatron_tpu.models import presets

    base = presets.mistral(seq_length=seq_length)
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("num_attention_heads", "num_attention_heads"),
                         ("vocab_size", "vocab_size")):
        assert getattr(base, ours) == config[theirs]
    return dataclasses.replace(
        base, num_layers=config["num_hidden_layers"],
        params_dtype="bfloat16", attention_impl="pallas", **kw).validate()


def _bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def test_four_chip_cells_step_fits_at_its_micro_batch(topo, as_on_the_chip):
    from megatron_tpu.config import OptimizerConfig, ParallelConfig
    from megatron_tpu.training.aot import aot_compile_train_step

    mix = _load("traffic", "tp2dp2.json")
    cfg = _model(_load("configs", "mistral-7b-d2.json"), mix["seq_length"],
                 ce_chunk_size=int(_flag(mix["flags"], "--ce_chunk_size")))
    tp = int(_flag(mix["flags"], "--tensor_model_parallel_size"))
    dp = len(topo.devices) // tp
    assert mix["global_batch_size"] == mix["micro_batch_size"] * dp
    compiled, meta = aot_compile_train_step(
        cfg, ParallelConfig(tensor_parallel=tp, sequence_parallel=True),
        OptimizerConfig(lr=1e-4, use_distributed_optimizer=True),
        micro_batch_size=mix["micro_batch_size"], num_microbatches=1,
        recompute=_flag(mix["flags"], "--recompute_granularity"),
        devices=topo.devices)
    assert meta["mesh_shape"]["tensor"] == tp
    assert meta["mesh_shape"]["data"] == dp
    assert compiled.as_text().count("tpu_custom_call") >= 3
    # a real job fills the chip, and leaves the allocator some room
    assert 0.5 * HBM < _bytes(compiled) < 0.9 * HBM, _bytes(compiled) / GIB


def test_served_models_steps_fit_beside_their_page_pool(topo, as_on_the_chip):
    from megatron_tpu.models.language_model import lm_forward
    from megatron_tpu.models.params import param_shapes

    config = _load("configs", "mistral-7b-d8-serve.json")
    flags = config["program"]["serve"]["flags"]
    pages = int(_flag(flags, "--serve_num_pages"))
    page = int(_flag(flags, "--serve_page_size"))
    slots = int(_flag(flags, "--serve_num_slots"))
    max_len = int(_flag(flags, "--serve_max_seq_len"))
    chunk = int(_flag(flags, "--serve_prefill_chunk"))
    cfg = _model(config, max_len)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(lambda s: abstract(s.shape, s.dtype),
                          param_shapes(cfg))
    pool = (abstract((cfg.num_layers, pages, page, cfg.n_kv_heads,
                      cfg.head_dim), jnp.bfloat16),) * 2
    i32 = jnp.int32

    # the model calls of PagedInferenceEngine's decode and chunk steps
    def decode(params, caches, table, tok, lengths):
        return lm_forward(cfg, params, tok[:, None], kv_caches=caches,
                          cache_index=lengths, page_table=table)

    def prefill_chunk(params, caches, row, toks, off, start, end):
        return lm_forward(cfg, params, toks, kv_caches=caches,
                          cache_index=off, page_table=row,
                          page_write_start=start, page_write_end=end)

    per_seq = max_len // page
    decoded = jax.jit(decode, donate_argnums=(1,)).lower(
        params, pool, abstract((slots, per_seq), i32),
        abstract((slots,), i32), abstract((slots,), i32)).compile()
    chunked = jax.jit(prefill_chunk, donate_argnums=(1,)).lower(
        params, pool, abstract((1, per_seq), i32), abstract((1, chunk), i32),
        abstract((), i32), abstract((), i32), abstract((), i32)).compile()
    assert decoded.as_text().count("tpu_custom_call") >= 1
    weights_and_pool = decoded.memory_analysis().argument_size_in_bytes
    assert weights_and_pool > 0.25 * 16e9          # the driver's floor
    for compiled in (decoded, chunked):
        assert _bytes(compiled) < 0.9 * HBM, _bytes(compiled) / GIB
