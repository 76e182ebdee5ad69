"""The main path compiled for a described TPU v5e — no chip attached.

The chip's compiler is installed with jaxlib/libtpu and compiles for a
topology that is described, not present (on-chip-measurement guide,
section 2). Interpret mode lowers a Pallas kernel to plain XLA ops, which
always compile and always partition; these cases compile the kernels FOR
REAL at Mistral-7B widths, so a slice that is not tile-aligned, a kernel
that needs too much VMEM, a step that does not fit 16 GB or a kernel that
cannot be partitioned over a mesh fails here and not on chip time.

Nothing runs: these say the compiler accepts the program, never that its
results or times are right (chip_smoke.py checks results on the chip).
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp

import collections
import dataclasses
import functools
import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from megatron_tpu.config import OptimizerConfig, ParallelConfig
from megatron_tpu.models import presets
from megatron_tpu.ops.pallas import flash_template as ft
from megatron_tpu.ops.pallas import grouped_matmul as gm
from megatron_tpu.ops.pallas import ssm_scan as ssm_scan_mod

# megatron_tpu.ops re-exports the attention FUNCTION under the module's name
attention_mod = importlib.import_module("megatron_tpu.ops.attention")

GIB = 1 << 30
# Mistral-7B attention widths (models/presets.py mistral)
HQ, HKV, D, WINDOW = 32, 8, 128, 4096
SEQ = 4096          # training sequence
SLOTS, CACHE = 8, 2048   # the smoke's server: --serve_num_slots 8, 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu / no such topology:
        # the host cannot describe the chip, so there is nothing to compile
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(scope="module", autouse=True)
def _as_on_the_chip():
    """The backend switches read jax.default_backend(), which is the CPU
    under such a compile: steer them to their chip side from the test
    (module scope, so the compiled-step fixture below sees it too)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ft, "_interpret", lambda: False)
        mp.setattr(ssm_scan_mod, "_interpret", lambda: False)
        mp.setattr(attention_mod, "_kernels_dispatchable", lambda: True)
        mp.setattr(gm, "_one_tpu", lambda: True)
        yield


def _abstract(shape, dtype, device):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(device))


def _kernel_cases():
    bf16, i32 = jnp.bfloat16, jnp.int32
    q_train = ((1, SEQ, HQ, D), bf16)
    kv_train = ((1, SEQ, HKV, D), bf16)
    kv_cache = ((SLOTS, CACHE, HKV, D), bf16)
    lens = ((SLOTS,), i32)

    def fwd(q, k, v):
        return ft.flash_mha(q, k, v, sliding_window=WINDOW)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    def decode(q, k, v, n):
        return ft.flash_decode(q, k, v, n, sliding_window=WINDOW)

    def decode_mq(q, k, v, n):
        return ft.flash_decode_mq(q, k, v, n, sliding_window=WINDOW)

    def decode_int8(q, kq, vq, ks, vs, n):
        # --kv_cache_int8: the cache dequantizes to bf16 in front of the
        # same kernel (models/transformer.py attention_block)
        from megatron_tpu.ops.kv_quant import dequantize_kv

        return ft.flash_decode(q, dequantize_kv(kq, ks, bf16),
                               dequantize_kv(vq, vs, bf16), n,
                               sliding_window=WINDOW)

    def paged(q, kp, vp, table, n):
        return ft.paged_flash_decode(q, kp, vp, table, n,
                                     sliding_window=WINDOW)

    # (case, function, arguments, the kernels it runs, by the names a
    # device trace finds them under)
    cases = [
        ("forward", fwd, [q_train, kv_train, kv_train], ["flash_fwd"]),
        ("forward_backward", fwd_bwd, [q_train, kv_train, kv_train],
         ["flash_bwd", "flash_bwd_stats", "flash_fwd"]),
    ]
    # the tiles `pick_blocks` gives other lengths (window 4096 clips the
    # longer ones): a pick that does not fit VMEM or is not tile-aligned
    # fails here. The backward is the one fused kernel while its sums over
    # a whole sequence fit in VMEM beside the tiles (`ft.fused_bwd_fits`):
    # dq of the query head's and, where several query heads share a KV
    # head, dk and dv of the KV head's. Under GQA 16384 rows are the
    # longest that do and 32768 run the split pair; with a KV head a query
    # head 65536 still fit and 131072 do not (fewer heads there, so that
    # the tensors of the case fit the chip)
    fused = ["flash_bwd", "flash_bwd_stats", "flash_fwd"]
    split = ["flash_bwd_dkv", "flash_bwd_dq", "flash_bwd_stats", "flash_fwd"]
    cases += [
        (f"forward_backward_s{s}", fwd_bwd,
         [((1, s, hq, D), bf16), ((1, s, hkv, D), bf16),
          ((1, s, hkv, D), bf16)],
         kernels)
        for s, hq, hkv, kernels in (
            (1024, HQ, HKV, fused), (16384, HQ, HKV, fused),
            (32768, 8, 2, split), (65536, 8, 8, fused),
            (131072, 4, 1, split))
    ]
    # the training cells' calls (benchmark/configs/: Mellum's window-1024
    # and full layers at 8192 x micro-batch 2, the TP 2 x DP 2 cell's
    # shard, OLMoE's causal layer; "forward_backward" above is the one-chip
    # Mistral cell's): each kernel holds a body a class of tile
    # (`ft._tile_classes`), whose half-tile slices the chip's compiler
    # has to take
    cases += [
        (f"forward_backward_{cell}",
         functools.partial(_fwd_bwd_cell, window),
         [((b, s, hq, D), bf16), ((b, s, hkv, D), bf16),
          ((b, s, hkv, D), bf16)],
         ["flash_bwd", "flash_bwd_stats", "flash_fwd"])
        for cell, (b, s, hq, hkv, window) in _TRAIN_CELLS.items()
    ]
    cases += [
        ("decode", decode,
         [((SLOTS, 1, HQ, D), bf16), kv_cache, kv_cache, lens],
         ["flash_decode"]),
        ("decode_mq5", decode_mq,
         [((SLOTS, 5, HQ, D), bf16), kv_cache, kv_cache, lens],
         ["flash_decode"]),
        ("decode_int8_kv", decode_int8,
         [((SLOTS, 1, HQ, D), bf16),
          ((SLOTS, CACHE, HKV, D), jnp.int8),
          ((SLOTS, CACHE, HKV, D), jnp.int8),
          ((SLOTS, CACHE, HKV, 1), jnp.float32),
          ((SLOTS, CACHE, HKV, 1), jnp.float32), lens], ["flash_decode"]),
    ]
    for ps in (8, 16, 128):
        per_seq = CACHE // ps
        pool = ((SLOTS * per_seq + 1, ps, HKV, D), bf16)
        cases.append((f"paged_decode_page{ps}", paged,
                      [((SLOTS, 1, HQ, D), bf16), pool, pool,
                       ((SLOTS, per_seq), i32), lens],
                      ["paged_flash_decode"]))
    # the paged decode at the two served cells' shapes (benchmark/configs/
    # mistral-7b-d8-serve.json, jamba2-3b-serve.json: slots x table
    # entries, query heads over kv heads, the window, the layers' pools
    # seen flat as kv_store.read hands them over), single-token and as
    # the speculative verify's five query rows
    for cell, (entries, hq, hkv, window, pages) in _SERVED_DECODE.items():
        pool = ((pages, 16, hkv, D), bf16)
        for sq, entry in ((1, ft.paged_flash_decode),
                          (5, ft.paged_flash_decode_mq)):
            cases.append((
                f"paged_decode_{cell}" + ("" if sq == 1 else f"_mq{sq}"),
                functools.partial(_paged_cell, entry, window),
                [((_DECODE_SLOTS, sq, hq, D), bf16), pool, pool,
                 ((_DECODE_SLOTS, entries), i32), ((_DECODE_SLOTS,), i32)],
                ["paged_flash_decode"]))
    # the prefill chunk at both served cells' shapes (one chunk of 512 of
    # one prompt, the same tables and pools), and a slot cache's whole
    # prompt (pages that are whole rows: the kernel's `parts` form)
    for cell, (entries, hq, hkv, window, pages) in _SERVED_DECODE.items():
        pool = ((pages, 16, hkv, D), bf16)
        cases.append((
            f"paged_chunk_{cell}", functools.partial(_chunk_cell, window),
            [((1, _CHUNK, hq, D), bf16), pool, pool, ((1, entries), i32),
             ((1,), i32), ((1,), i32)], ["paged_flash_chunk"]))
    # the Mamba-2 decode step at the Nemotron share's shapes (benchmark/
    # configs/nemotron3-super-120b-a12b-s4-d11-serve.json: 5 layers' rows
    # of 64 slots, a state of 128 over 8192 channels in 8 groups), the
    # store in place
    f32 = jnp.float32
    cases.append((
        "ssd_step_nemotron", _ssd_step_cell,
        [((5, 64, 128, 8192), f32), ((), i32), ((64, 8192), f32),
         ((64, 8192), f32), ((64, 8, 128), f32), ((64, 8, 128), f32)],
        ["ssd_step"]))
    cases.append((
        "paged_chunk_slot_rows", functools.partial(_chunk_cell, WINDOW),
        [((SLOTS, _CHUNK, HQ, D), bf16), kv_cache, kv_cache,
         ((SLOTS, 1), i32), ((SLOTS,), i32), ((SLOTS,), i32)],
        ["paged_flash_chunk"]))
    return cases


def _ssd_step_cell(store, layer, decay, dtx, b, c):
    from megatron_tpu.ops.pallas.ssd_step import ssd_step

    return ssd_step(store, layer, decay, dtx, b, c)


def _chunk_cell(window, q, kp, vp, table, offs, ends):
    return ft.paged_flash_chunk(q, kp, vp, table, offs, ends,
                                sliding_window=window)


def _fwd_bwd_cell(window, q, k, v):
    return jax.grad(
        lambda *a: ft.flash_mha(*a, sliding_window=window)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)


# cell's layer: (micro-batch, sequence, query heads, kv heads, window) of
# one chip's flash calls
_TRAIN_CELLS = {"mellum_sliding": (2, 8192, 32, 4, 1024),
                "mellum_full": (2, 8192, 32, 4, None),
                "mistral_tp2dp2": (8, 4096, 16, 4, 4096),
                "olmoe": (1, 4096, 16, 16, None)}


def _paged_cell(entry, window, q, kp, vp, table, n):
    return entry(q, kp, vp, table, n, sliding_window=window)


# cell: (table entries, query heads, kv heads, window, pages of all layers)
_SERVED_DECODE = {"instruct": (528, HQ, HKV, WINDOW, 8 * 17000),
                  "reasoning": (256, 20, 1, None, 2 * 16640)}
_DECODE_SLOTS = 64
_CHUNK = 512      # --serve_prefill_chunk of both served cells
_CASES = _kernel_cases()
_KERNEL_TEXTS = {}


def _kernel_text(topo, name):
    """The case's program compiled for one described chip, once."""
    if name not in _KERNEL_TEXTS:
        _, fn, args, _ = next(c for c in _CASES if c[0] == name)
        dev = topo.devices[0]
        _KERNEL_TEXTS[name] = jax.jit(fn).lower(
            *[_abstract(s, d, dev) for s, d in args]).compile().as_text()
    return _KERNEL_TEXTS[name]


def _kernel_name_stacks(text):
    """The name stack (`op_name`) of every Pallas custom call of a
    compiled program, as the tokens a trace reader splits it into."""
    from megatron_tpu.telemetry.tracing.events import scope_tokens

    stacks = []
    for line in text.splitlines():
        if "tpu_custom_call" in line and " custom-call(" in line:
            m = re.search(r'op_name="([^"]+)"', line)
            stacks.append(scope_tokens(m.group(1) if m else ""))
    return stacks


def _kernels_named(text):
    """Each Pallas custom call by the rule a trace reader finds it by:
    the part in front of its name stack's closing `pallas_call`."""
    from megatron_tpu.telemetry.tracing.events import kernel_of

    return sorted(kernel_of(toks) or "<unnamed>"
                  for toks in _kernel_name_stacks(text))


@pytest.mark.parametrize("name", [c[0] for c in _CASES])
def test_kernel_compiles_for_v5e(topo, name):
    kernels = next(c for c in _CASES if c[0] == name)[3]
    assert _kernel_text(topo, name).count("tpu_custom_call") >= len(kernels)


@pytest.mark.parametrize("name", [c[0] for c in _CASES])
def test_kernel_carries_its_name_for_v5e(topo, name):
    """Each custom call the case counts sits under its kernel's
    `jax.named_scope` in the chip compiler's own metadata (what a device
    trace hands out as `tf_op`), and the HLO instruction is named after
    the kernel (`pl.pallas_call(name=...)`): none unnamed, none extra."""
    kernels = next(c for c in _CASES if c[0] == name)[3]
    text = _kernel_text(topo, name)
    assert _kernels_named(text) == kernels
    for kernel in kernels:
        assert re.search(rf"%{kernel}(\.\d+)? = ", text), kernel
    if "flash_bwd" in kernels:
        # the fused backward's FIRST result is dq, of the query's shape:
        # benchmark/kernel_costs/flash_bwd.py counts over it
        args = next(c for c in _CASES if c[0] == name)[2]
        b, s, h, d = args[0][0]
        first = re.search(r"%flash_bwd(?:\.\d+)? = \((\w+\[[\d,]+\])", text)
        assert first.group(1) == f"bf16[{b},{h},{s},{d}]"


@pytest.mark.parametrize("name", ["forward_backward"] + [
    f"forward_backward_{cell}" for cell in _TRAIN_CELLS])
def test_training_kernels_fit_the_vmem_their_formulas_ask_for(topo, name):
    """Each training kernel is compiled under the scoped-VMEM limit its
    own formula gives (`_fwd_vmem_bytes`, `_fused_bwd_vmem_bytes` at the
    call's query heads a KV head; the default where that is more), and Mosaic refuses a kernel that needs
    more than its limit: the compile above, with a body a class of tile
    in each kernel, is the proof that the formulas still bound them. The
    classes engage at every cell's shape: a kernel that fell back to the
    one masked body would compile too."""
    _, _, args, _ = next(c for c in _CASES if c[0] == name)
    (b, s, hq, d), item = args[0][0], 2
    groups = hq // args[1][0][2]
    window = WINDOW if name == "forward_backward" else _TRAIN_CELLS[
        name[len("forward_backward_"):]][4]
    block_q, block_k = ft.pick_blocks(s, d, jnp.bfloat16)
    asked = {}
    for line in _kernel_text(topo, name).splitlines():
        m = re.search(r"%(flash_\w+?)(?:\.\d+)? = .*tpu_custom_call.*"
                      r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                      line)
        if m:
            asked[m.group(1)] = int(m.group(2))
    want = {"flash_fwd": ft._fwd_vmem_bytes(block_q, block_k, d, item),
            "flash_bwd": ft._fused_bwd_vmem_bytes(s, block_q, block_k, d,
                                                  item, groups)}
    assert ft._bwd_vmem_bytes(block_q, block_k, d, item) < want["flash_bwd"]
    for kernel, formula in want.items():
        assert asked[kernel] == max(formula, ft._DEFAULT_SCOPED_VMEM)
        assert asked[kernel] <= ft._MAX_SCOPED_VMEM
    n = s // block_q
    classes = ft._tile_classes(n, n, block_q, block_k, True, window, None)
    assert sorted(classes) == ([0] if window is None or window >= s
                               else [0, window // block_q])
    assert all(len(pieces) == 2 for pieces in classes.values())


@pytest.mark.parametrize("name", [c[0] for c in _CASES if "decode" in c[0]])
def test_decode_block_fits_the_default_scoped_vmem(name):
    """What a decode call keeps in VMEM (two buffers each of K and V, the
    block's float32 copies, the scores: `_decode_vmem_bytes`) stays
    inside Mosaic's default scoped limit at every compiled shape, so the
    launch asks for none of its own; the compile above is the compiler's
    own word on it. A block is as many pages as reach _DECODE_TILE_ROWS
    rows: 16 of Mistral's (8 kv heads), 128 of Jamba's (one)."""
    _, _, args, _ = next(c for c in _CASES if c[0] == name)
    (_, sq, hq, d), (_, ps, hkv, _) = args[0][0], args[1][0]
    # the dense entries (a cache row is one page; an int8 cache reaches
    # them dequantized to bf16) take flash_decode's block_k of 256
    dense = name.startswith("decode")
    unit, units = ft._decode_block(ps, hkv, 256 if dense else None)
    rows = unit * units * hkv
    assert rows <= ft._DECODE_TILE_ROWS
    if not dense:
        assert units == ft._DECODE_TILE_ROWS // (ps * hkv)
    assert (ft._decode_vmem_bytes(rows, sq * hq, d, 2)
            <= ft._DEFAULT_SCOPED_VMEM)


@pytest.mark.parametrize("step", ["decode", "chunk"])
def test_paged_decode_partitions_over_tp2_dp2(topo, step):
    """The paged kernels under a mesh, as the TP-sharded engines run
    them: `ops/attention.py` wraps the kernel in one `shard_map` over
    every axis (slots over `data`, kv heads over `tensor`, the pools'
    pages whole on every chip), so each chip's kernel loops over its own
    rows' tables with 4 of the 8 kv heads (the decode step's 64 slots,
    32 pages a block; a prefill chunk's one row at its scalar offset and
    end, on a TP 2 replica); the pools stay where they lie (no
    all-gather, no copy of a pool's shape)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from megatron_tpu.parallel.mesh import build_mesh

    # a chunk is ONE row: it takes the kernel where the batch axes hold
    # one device (a TP-only replica), and the dense path, said out loud,
    # where they hold more (`_shard_plan`: the one mesh condition)
    devices = topo.devices if step == "decode" else topo.devices[:2]
    rt = build_mesh(ParallelConfig(tensor_parallel=2), devices=devices)
    entries, hq, hkv, window, _ = _SERVED_DECODE["instruct"]
    pages = 17000
    heads = P(None, None, "tensor", None)
    pool = ((pages, 16, hkv, D), jnp.bfloat16, heads)
    if step == "decode":
        rows = P(("data", "expert"))
        shapes = [((_DECODE_SLOTS, 1, hq, D), jnp.bfloat16,
                   P(("data", "expert"), None, "tensor", None)), pool, pool,
                  ((_DECODE_SLOTS, entries), jnp.int32, rows),
                  ((_DECODE_SLOTS,), jnp.int32, rows)]

        def call(q, kp, vp, table, n):
            return attention_mod.attention(q, kp, vp, sliding_window=window,
                                           impl="pallas", kv_lengths=n,
                                           page_table=table)
    else:
        shapes = [((1, _CHUNK, hq, D), jnp.bfloat16, heads), pool, pool,
                  ((1, entries), jnp.int32, P()), ((), jnp.int32, P()),
                  ((), jnp.int32, P())]

        def call(q, kp, vp, table, off, end):
            return attention_mod.attention(q, kp, vp, sliding_window=window,
                                           impl="pallas", q_offset=off,
                                           page_table=table, kv_end=end)

    with jax.sharding.set_mesh(rt.mesh):
        text = jax.jit(call).lower(*[
            jax.ShapeDtypeStruct(shape, dtype,
                                 sharding=NamedSharding(rt.mesh, spec))
            for shape, dtype, spec in shapes]).compile().as_text()
    assert _kernels_named(text) == [f"paged_flash_{step}"]
    assert not re.search(r"\ball-gather(-start)?\(", text)
    assert f"bf16[{pages},16,{hkv // 2},{D}]" in text
    assert not re.search(rf"= bf16\[{pages},16,\d+,{D}\]\S* (copy|fusion)\(",
                         text)


@pytest.mark.parametrize("s", [128, 384, 640, 1024, 1536, 2048, 4096,
                               16384, 32768])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_picked_blocks_tile_the_sequence(s, dtype):
    """The tile `pick_blocks` hands the kernels divides the sequence, is
    lane-aligned and is the swept one wherever that divides; the serving
    buckets that 128 divides and 256 does not run at 128."""
    bq, bk = ft.pick_blocks(s, D, dtype)
    assert s % bq == 0 and s % bk == 0, (bq, bk)
    assert bq % 128 == 0 and bk % 128 == 0, (bq, bk)
    want = {128: 128, 384: 128, 640: 128, 1536: 512}.get(s, 1024)
    assert (bq, bk) == (want, want)


def test_picked_blocks_shrink_for_rows_that_do_not_fit_vmem():
    """A row wide enough that the backward tiles would outgrow what a
    kernel may ask of VMEM gets a smaller tile, never an uncompilable
    one."""
    wide = ft.pick_blocks(4096, 8192, jnp.float32)
    assert wide[0] < ft.pick_blocks(4096, D, jnp.bfloat16)[0]
    assert ft._bwd_vmem_bytes(*wide, 8192, 4) <= ft._MAX_SCOPED_VMEM


def test_explicit_blocks_win_over_the_pick(monkeypatch):
    """block_q= / block_k= given to flash_mha (tests, ring stripes) reach
    the kernels; one left out keeps its picked value."""
    seen = []
    monkeypatch.setattr(
        ft, "_flash_bhsd",
        lambda q, k, v, scale, causal, window, block_q, block_k:
        seen.append((block_q, block_k)) or q)
    x = jnp.zeros((1, 2048, 2, D), jnp.bfloat16)
    ft.flash_mha(x, x, x)
    ft.flash_mha(x, x, x, block_q=128, block_k=256)
    ft.flash_mha(x, x, x, block_k=128)
    picked = ft.pick_blocks(2048, D, jnp.bfloat16)
    assert seen == [picked, (128, 256), (picked[0], 128)]


def _mistral_2l():
    """presets.mistral at full width, depth cut to what one 16 GB chip
    trains with Adam (chip_smoke.py's model)."""
    return dataclasses.replace(
        presets.mistral(seq_length=SEQ), num_layers=2,
        params_dtype="bfloat16", ce_chunk_size=512,
        attention_impl="pallas").validate()


def _per_device_bytes(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


@pytest.fixture(scope="module")
def one_chip_step(topo):
    from megatron_tpu.training.aot import aot_compile_train_step

    compiled, _ = aot_compile_train_step(
        _mistral_2l(), ParallelConfig(),
        OptimizerConfig(lr=1e-4), micro_batch_size=1, num_microbatches=1,
        recompute="selective", devices=topo.devices[:1])
    return compiled


@pytest.mark.slow  # ~10 s of compile; tier-1 runs against its time limit
# (ROADMAP D10) and keeps the four-chip case below, which guards the repair
def test_train_step_fits_one_v5e(one_chip_step):
    """The trainer's own step (training/train_step.make_train_step) at
    the smoke's size: Pallas forward + the fused backward kernel per layer
    scan, and XLA's buffer assignment under the chip's 16 GB."""
    text = one_chip_step.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert _per_device_bytes(one_chip_step) < 16e9
    _assert_step_kernels_named(text)
    cfg = _mistral_2l()
    _assert_head_loss_forms_its_logits_once(
        text, f"{cfg.ce_chunk_size},{cfg.vocab_size}",
        SEQ // cfg.ce_chunk_size)


@pytest.fixture(scope="module")
def olmoe_step(topo):
    """(the compiled step of the benchmark's `train_olmoe1b7b_seq4k` cell
    at one layer, its configuration)."""
    from megatron_tpu.training.aot import aot_compile_train_step

    cfg = dataclasses.replace(
        presets.olmoe(seq_length=SEQ), num_layers=1,
        params_dtype="bfloat16", ce_chunk_size=512,
        attention_impl="pallas").validate()
    compiled, _ = aot_compile_train_step(
        cfg, ParallelConfig(), OptimizerConfig(lr=1e-4),
        micro_batch_size=1, num_microbatches=16, recompute="selective",
        devices=topo.devices[:1])
    return compiled, cfg


def test_olmoe_cell_step_fits_one_v5e(olmoe_step):
    """The step of the benchmark's `train_olmoe1b7b_seq4k` cell: OLMoE-1B-7B
    widths, one layer with all 64 experts, 16 micro-batches of one
    4096-token sequence accumulated in float32. It fits the chip; the six
    grouped products of the experts (forward and the two backward products
    of both expert matrices) are the program's own kernels, by name and
    under the scopes `mlp` and `moe_experts`, and none is left to XLA's
    `ragged-dot-none`; the flash kernels keep their names and the MoE
    block's scopes arrive, on forward and transposed operations alike;
    and nothing under `mlp` is a scatter: rows cross the sort by expert
    through gathers in both directions (ops/moe.py rows_to_expert_order,
    rows_to_token_order), and the counts and the chosen gates are dense
    sums; and the head and loss form each chunk's logits once. The expert
    matrices' gradient is summed where it is made: `moe_tgmm`'s results
    are the step's float32 accumulators themselves, each aliased to the
    operand it came in as, nothing else in the micro-batch loop has a
    result of their shape, and `grad_accumulate` names the add of the
    other leaves."""
    from megatron_tpu.telemetry.tracing.events import scope_tokens

    compiled, cfg = olmoe_step
    assert _per_device_bytes(compiled) < 15e9
    text = compiled.as_text()
    assert "ragged-dot" not in text
    # every expert held: the row movements are one pass each, no loop
    assert not [name for name in re.findall(r'op_name="([^"]+/while)"', text)
                if "mlp" in scope_tokens(name)]
    # the program's own kernels by name, nothing unnamed. Selective
    # recomputation saves the grouped products like the dots they are, so
    # `moe_gmm` runs four times (two forward products, two gradients of
    # the rows) and not six; at one layer the compiler merges the
    # recomputed flash forward with the forward itself
    assert _kernels_named(text) == [
        "flash_bwd", "flash_bwd_stats", "flash_fwd",
        "moe_gmm", "moe_gmm", "moe_gmm", "moe_gmm", "moe_tgmm", "moe_tgmm"]
    results = re.findall(
        r"%moe_t?gmm[.\d]* = (\w+\[[\d,]+\])", text)
    # (the rows' gradient through w_out leaves its kernel as the first
    # product's cotangent, 2 x 1024 wide: no [32768, 1024] array is written)
    assert set(results) == {"bf16[32768,2048]",
                            "f32[1,64,2048,2048]", "f32[1,64,1024,2048]"}
    _assert_only_the_kernel_touches_the_accumulators(
        text, r"f32\[1,64,(2048|1024),2048\]")
    # what the rest of the loop adds is the other leaves': the parent's
    # two `grad_accumulate/add` fusions of the experts' shapes are gone
    assert not re.findall(
        r"= (?:bf16|f32)\[(?:1,)?64,(?:2048|1024),2048\][^\n]*"
        r"grad_accumulate", text)
    for toks in _kernel_name_stacks(text):
        kernel = toks[-2]
        if kernel.startswith("moe_"):
            assert "mlp" in toks and "moe_experts" in toks, toks
            assert toks.index("mlp") < toks.index("moe_experts"), toks
    names = set(re.findall(r'op_name="([^"]+)"', text))
    stacks = [scope_tokens(n) for n in names]
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "grad_accumulate"):
        assert any(scope in toks for toks in stacks), scope
    for scope in ("moe_router", "moe_dispatch", "moe_combine"):
        sides = {"transpose(" in n for n in names if scope in scope_tokens(n)}
        assert sides == {False, True}, (scope, sides)
    # the step's scatters (the embedding's backward is one): none under
    # `mlp`, and none without a name, which could hide from the scopes
    scatters = _scatter_op_names(text)
    assert scatters and all(scatters), scatters
    assert not [op for op in scatters if "mlp" in scope_tokens(op)]
    # the head and loss: one chunk loop a micro-batch, the logits once
    _assert_head_loss_forms_its_logits_once(
        text, f"{cfg.ce_chunk_size},{cfg.vocab_size}",
        16 * SEQ // cfg.ce_chunk_size)


def _benchmark_cell_step(topo, name):
    """The compiled step of a one-chip training cell of BENCHMARK.json,
    built from the cell's own files as the harness builds it."""
    from benchmark.harness import spec
    from megatron_tpu.arguments import args_to_run_config, parse_args
    from megatron_tpu.training.aot import aot_compile_train_step

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = spec.Cell(os.path.join(repo, "BENCHMARK.json"), name)
    mix = cell.traffic
    flags = spec.load_module(cell.reference_path()).program_flags(
        cell.config, mix["seq_length"])
    run = args_to_run_config(parse_args(
        flags + cell.config["program"]["flags"] + mix["flags"]
        + ["--micro_batch_size", str(mix["micro_batch_size"]),
           "--global_batch_size", str(mix["global_batch_size"])]))
    assert (mix["micro_batch_size"], mix["global_batch_size"]) == (2, 2)
    compiled, _ = aot_compile_train_step(
        run.model, ParallelConfig(), OptimizerConfig(lr=1e-4),
        micro_batch_size=2, num_microbatches=1,
        recompute=run.training.recompute_granularity,
        devices=topo.devices[:1])
    return compiled


@pytest.fixture(scope="module")
def mellum_step(topo):
    """The compiled step of the benchmark's `train_mellum2_share4_seq8k`
    cell."""
    return _benchmark_cell_step(topo, "train_mellum2_share4_seq8k")


@pytest.fixture(scope="module")
def zaya_step(topo):
    """The compiled step of the benchmark's `train_zaya1_share8_seq8k`
    cell."""
    return _benchmark_cell_step(topo, "train_zaya1_share8_seq8k")


def test_zaya_cell_step_fits_one_v5e(zaya_step):
    """The step of the benchmark's `train_zaya1_share8_seq8k` cell, built
    from the cell's own files: ZAYA1-8B widths, five layers of compressed
    convolutional attention and a top-1 expert layer that holds 8 of its
    router's 16 experts, sequence 8192 at the micro-batch the mix states
    (ISSUE 71's fallback, micro-batch 1, is not taken). It fits the 15.75
    GiB a program gets with little to spare (15.36 GB when it was added:
    a change that costs the layers' loop another kept activation shows
    here before it shows on the chip); attention runs the flash kernels
    at 8 query over 2 KV heads in the latent (never the dense scores: at
    8192 positions those are 4 GB a layer); the held experts' products are
    the program's own kernels; and the parts this model adds carry their
    scopes, forward and backward."""
    from megatron_tpu.telemetry.tracing.events import scope_tokens

    assert 0.25 * 16e9 < _per_device_bytes(zaya_step) < 15.75 * GIB
    text = zaya_step.as_text()
    assert "ragged-dot" not in text
    kernels = _kernels_named(text)
    assert {"flash_fwd", "flash_bwd", "moe_gmm", "moe_tgmm"} <= set(kernels)
    assert re.search(r"flash_fwd[^\n]*bf16\[2,8,8192,128\]", text)
    assert not re.search(r"f32\[2,2,4,8192,8192\]", text)
    names = re.findall(r'op_name="([^"]+)"', text)
    for scope in ("cca_mix", "residual_scale", "moe_router", "attn_rope"):
        sides = {"transpose(" in name for name in names
                 if scope in scope_tokens(name)}
        assert sides == {False, True}, (scope, sides)


def test_mellum_cell_step_fits_one_v5e(mellum_step):
    """The step of the benchmark's `train_mellum2_share4_seq8k` cell, built
    from the cell's own files as the harness builds it: Mellum 2 widths,
    one period of four layers (three window-1024, one full under YaRN), 16
    of the router's 64 experts held, sequence 8192 at the micro-batch the
    mix states. It fits the 15.75 GiB a program gets at that micro-batch
    (ISSUE 42's fallback, micro-batch 1 with 2 accumulated, is not taken);
    every layer's window is static at its kernel calls: four `flash_fwd`,
    four fused `flash_bwd` (at 8192 rows dq of a head's sequence fits in
    VMEM) and their statistics, each under its kind's scope in region
    `attention`, three sliding for one full; the held experts' six
    products a layer are the program's own kernels over 16 groups of a
    buffer that takes every one of the call's 131,072 (token, choice)
    rows (the share path has no shorter one: no routing leaves a row
    out), and a seventh is the second product computed again for the gate's gradient
    (a share does not keep it: ops/moe.py rows_to_token_order); none is
    left to XLA's `ragged-dot`. The row movements around the kernels walk
    the live rows (PR 69): loops under `moe_dispatch` and `moe_combine`,
    forward and transposed, no copy of the buffer around them, and the
    step at 14.52 GB where one pass over every row had 14.18 (which
    holds only while the buffer the dispatch fills names the rows it will
    take as an operand, `grouped_matmul.unwritten_rows`: produced out of
    nothing it stood nowhere in the step's order, and the scheduler left
    the flash forward's lane-padded log-sum-exp lying until the backward
    pass, 0.6 GB more)."""
    from megatron_tpu.telemetry.tracing.events import scope_tokens

    compiled = mellum_step
    assert 0.25 * 16e9 < _per_device_bytes(compiled) < 14.6e9
    text = compiled.as_text()
    assert "ragged-dot" not in text
    loops = {(scope, "transpose(" in name)
             for name in re.findall(r'op_name="([^"]+/while)"', text)
             for scope in ("moe_dispatch", "moe_combine")
             if scope in scope_tokens(name)}
    assert loops == {(scope, side) for scope in ("moe_dispatch", "moe_combine")
                     for side in (False, True)}, loops
    assert not re.findall(r"= bf16\[131072,2304\][^ ]* copy\(", text)
    assert collections.Counter(_kernels_named(text)) == {
        "flash_fwd": 4, "flash_bwd": 4, "flash_bwd_stats": 4,
        "moe_gmm": 20, "moe_tgmm": 8,
        # the buffers the walks fill block by block (the dispatch's,
        # forward and made again; the token side's sums, of the combine
        # and of the dispatch's backward): a kernel that writes nothing,
        # for XLA's fill of zeros
        "moe_unwritten_rows": 16}
    kinds = collections.Counter()
    for toks in _kernel_name_stacks(text):
        kernel = toks[-2]
        if kernel.startswith("flash_"):
            at = toks.index("attention")
            assert toks[at + 1] in ("attn_sliding", "attn_full"), toks
            kinds[kernel, toks[at + 1]] += 1
        else:
            scopes = (("moe_dispatch", "moe_combine")
                      if kernel == "moe_unwritten_rows" else ("moe_experts",))
            assert any(toks.index("mlp") < toks.index(scope)
                       for scope in scopes if scope in toks), toks
    assert kinds == {(k, "attn_sliding"): 3 for k in (
        "flash_fwd", "flash_bwd", "flash_bwd_stats")} | {
        (k, "attn_full"): 1 for k in (
            "flash_fwd", "flash_bwd", "flash_bwd_stats")}
    # the grouped products run over the buffer's rows and the 16 held
    # experts' matrices
    results = set(re.findall(r"%moe_t?gmm[.\d]* = (\w+\[[\d,]+\])", text))
    assert results == {"bf16[131072,1792]", "bf16[131072,2304]",
                       "bf16[16,2304,1792]", "bf16[16,896,2304]"}, results
    names = set(re.findall(r'op_name="([^"]+)"', text))
    stacks = [scope_tokens(n) for n in names]
    for scope in ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine", "attn_sliding", "attn_full"):
        assert any(scope in toks for toks in stacks), scope
    assert not [op for op in _scatter_op_names(text)
                if "mlp" in scope_tokens(op)]


# fixture, the (token, choice) rows a micro-batch sorts by expert
_EXPERT_STEPS = {"mellum": ("mellum_step", 131072),
                 "olmoe": ("olmoe_step", 32768)}


@pytest.mark.parametrize("case", list(_EXPERT_STEPS))
def test_nothing_but_the_kernels_touches_the_experts_rows(request, case):
    """In the compiled steps of both MoE cells no instruction under the
    scope `moe_experts` outside the Pallas calls has a result with the
    buffer's rows: the activation, its backward and (Mellum, a share) the
    zeroing of the first product's rows behind the last group are made of
    the tiles inside `moe_gmm` / `moe_tgmm` (ops/pallas/grouped_matmul.py
    `grouped_mlp`), and `jax.checkpoint`'s rounding of the saved first
    product is no pass of its own. What is left there is the visit table's
    integers."""
    from megatron_tpu.analysis.step_program import _NO_WORK, Program
    from megatron_tpu.telemetry.tracing.events import scope_tokens

    fixture, rows = _EXPERT_STEPS[case]
    step = request.getfixturevalue(fixture)
    compiled = step[0] if isinstance(step, tuple) else step
    program = Program(compiled.as_text())
    kernels, others = 0, []
    for comp, line, _name, results, opcode in program.instructions():
        if (program.fused[comp] or opcode in _NO_WORK
                or "moe_experts" not in scope_tokens(
                    program.op_name(comp, line))):
            continue
        if "tpu_custom_call" in line:
            kernels += 1
            continue
        for dtype, dims in _RESULT.findall(results):
            if str(rows) in dims.split(","):
                others.append((opcode, dtype, dims))
    assert kernels and not others, others


def _rotary_results(text, seq, heads, d):
    """(dtype, dims, times a step, bytes) of every array that an
    instruction under the scope `attn_rope` writes, outside fusions'
    bodies. Bytes where it is q-, k- or table-shaped: a last dimension of
    d or d / 2 (lane-padded to 128) behind the sequence, the dims in front
    taken as the device's rows (of a loop's stacked buffer one layer's
    slice is written and counts), or (seq, heads * d) flat. What a
    collective fusion carries beside the pass (the next layer's gathered
    weights) is nobody's q and counts 0."""
    from megatron_tpu.analysis.step_program import _NO_WORK, Program
    from megatron_tpu.telemetry.tracing.events import scope_tokens

    program = Program(text)
    found = []
    for comp, line, _name, results, opcode in program.instructions():
        if (program.fused[comp] or opcode in _NO_WORK
                or "attn_rope" not in scope_tokens(
                    program.op_name(comp, line))):
            continue
        for dtype, dims in _RESULT.findall(results):
            dims = tuple(int(i) for i in dims.split(",") if i)
            nbytes = 0
            if dims[-1:] in ((d,), (d // 2,)) and seq in dims[-3:-1]:
                nbytes = (_ITEMSIZE[dtype] * math.prod(dims[-4:-1])
                          * max(dims[-1], 128))
            elif dims[-2:] in {(seq, h * d) for h in heads}:
                nbytes = _ITEMSIZE[dtype] * math.prod(dims[-3:])
            found.append((dtype, dims, program.times[comp], nbytes))
    return found


# fixture, rows of the batch a device holds, sequence, (q heads, kv heads)
# a device holds, layers
_ROTARY_STEPS = {
    "one_chip": ("one_chip_step", 1, SEQ, (HQ, HKV), 2),
    "tp2_dp2": ("tp2_dp2_step", 1, SEQ, (HQ // 2, HKV // 2), 2),
    "mellum": ("mellum_step", 2, 8192, (32, 4), 4),
}


@pytest.mark.parametrize("case", list(_ROTARY_STEPS))
def test_rotary_is_one_pass_in_the_compiled_step(request, case):
    """In the compiled steps nothing under `attn_rope` has a result half a
    head wide (ops/rotary.py: the half turn is a product, and the chip's
    compiler does not fuse a slice and concatenate of the lane axis: it
    wrote both halves of q out in float32, lane-padded, three times a
    layer), and what is written under `attn_rope` is at most three times
    what the applications write of q and k: two a layer, forward and
    backward, since `selective` keeps the rotated q and k (1.5 to 2.0
    times as this was written; the slices read 10.8)."""
    fixture, rows, seq, heads, layers = _ROTARY_STEPS[case]
    step = request.getfixturevalue(fixture)
    compiled = step[0] if isinstance(step, tuple) else step  # (step, meta)
    found = _rotary_results(compiled.as_text(), seq, heads, D)
    assert not [f for f in found if f[1][-1:] == (D // 2,)], found
    written = sum(times * nbytes for _, _, times, nbytes in found)
    needed = layers * 2 * rows * seq * sum(heads) * D * 2
    assert needed <= written <= 3 * needed, written / needed


def _computations(text):
    """{name: [instruction lines]} of a compiled program's text, and the
    names of the computations that are some fusion's body."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and " = " in line:
            cur.append(line.strip())
    return comps, set(re.findall(r"calls=%?([\w.\-]+)", text))


def _assert_only_the_kernel_touches_the_accumulators(text, shape):
    """In the step program `text`, inside the micro-batch loop (every
    computation but the entry, which zeroes the accumulators and runs the
    optimizer over them), the only instructions with an array result of
    an expert accumulator's `shape` (a regex) are `moe_tgmm` calls whose
    result aliases its last operand: no fusion, no copy, no (dynamic-)slice or
    update of that size; loops and tuples only pass them on."""
    comps, fused = _computations(text)
    entry = re.search(r"^ENTRY %?([\w.\-]+)", text, re.M).group(1)
    kernels = 0
    for name, lines in comps.items():
        if name == entry or name in fused:
            continue
        for line in lines:
            m = re.match(r"(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\(", line)
            if (not m or not re.fullmatch(shape + r"(\{[^ ]*)?", m.group(2))
                    or m.group(3) in ("get-tuple-element", "parameter",
                                      "bitcast")):
                continue
            assert m.group(1).startswith("moe_tgmm"), (name, line[:200])
            # the accumulator is the call's last operand: behind the visit
            # table, the layer, the rows' operand (a GLU's first product
            # twice, as its gate and its up blocks) and the cotangent's
            alias = re.search(
                r"output_to_operand_aliasing=\{\{\}: \((\d+), \{\}\)\}", line)
            assert alias and alias.group(1) in ("7", "8"), line[:200]
            kernels += 1
    assert kernels == 2, kernels


def test_olmoe_depth_2_carries_the_accumulators_through_the_layer_loop(topo):
    """Where the layer scan is a loop (two layers; 16 experts at the
    published widths, a size the described chip compiles), the stacked
    float32 accumulators of the expert matrices ride the BACKWARD loop's
    carry and `moe_tgmm` updates the layer's blocks in place: no
    `dynamic-slice`, `dynamic-update-slice`, `copy` or fusion of an
    accumulator's size anywhere inside the micro-batch loop, as a scanned
    slice of the stack would need. The forward loop does not carry them
    at all: the hand-through folds away."""
    from megatron_tpu.training.aot import aot_compile_train_step

    cfg = dataclasses.replace(
        presets.olmoe(seq_length=SEQ), num_layers=2, num_experts=16,
        params_dtype="bfloat16", ce_chunk_size=512,
        attention_impl="pallas").validate()
    compiled, _ = aot_compile_train_step(
        cfg, ParallelConfig(), OptimizerConfig(lr=1e-4),
        micro_batch_size=1, num_microbatches=4, recompute="selective",
        devices=topo.devices[:1])
    text = compiled.as_text()
    stack = r"f32\[2,16,(2048|1024),2048\]"
    _assert_only_the_kernel_touches_the_accumulators(text, stack)
    # the loops that hold a stack in their state: the micro-batch loop and
    # in it the backward layer loop, and no third (the forward one)
    comps, _ = _computations(text)
    loops = [line for lines in comps.values() for line in lines
             if " while(" in line
             and re.search(stack, line.split(" while(")[0])]
    assert len(loops) == 2, [line[:120] for line in loops]


def _scatter_op_names(text):
    """The `op_name` of every scatter instruction of a compiled program
    ("" for one that carries none)."""
    lines = re.findall(r"= \S+ scatter\([^\n]*", text)
    return [(re.findall(r'op_name="([^"]+)"', line) or [""])[0]
            for line in lines]


def _tp2_dp2_step(topo, recompute="selective"):
    from megatron_tpu.training.aot import aot_compile_train_step

    return aot_compile_train_step(
        _mistral_2l(),
        ParallelConfig(tensor_parallel=2, sequence_parallel=True),
        OptimizerConfig(lr=1e-4, use_distributed_optimizer=True),
        micro_batch_size=1, num_microbatches=1, recompute=recompute,
        devices=topo.devices)


@pytest.fixture(scope="module")
def tp2_dp2_step(topo):
    return _tp2_dp2_step(topo)


def _assert_step_kernels_named(text, recompute="selective"):
    """The train step's Pallas calls by name: per layer scan the
    forward, the backward's row statistics and the one backward kernel,
    all nested under the `attention` scope. Under `selective` the layer's checkpoint keeps the forward's
    output and log-sum-exp, so no forward stands under
    `rematted_computation`; under `full` it keeps nothing and the
    backward pass runs the forward a second time. The journal's
    `step_program.kernel_calls` (analysis/step_program.py) says the same
    of the same text."""
    from megatron_tpu.analysis import step_program

    again = ["flash_fwd"] if recompute == "full" else []
    assert _kernels_named(text) == ["flash_bwd", "flash_bwd_stats",
                                    "flash_fwd"] + again
    for toks in _kernel_name_stacks(text):
        kernel = next(t for t in reversed(toks) if t.startswith("flash_"))
        assert "attention" in toks[:toks.index(kernel)], toks
    rematted = [toks for toks in _kernel_name_stacks(text)
                if "rematted_computation" in toks]
    assert len(rematted) == len(again)
    assert all("flash_fwd" in toks for toks in rematted)
    layers = _mistral_2l().num_layers
    assert step_program.kernel_calls(text) == {
        "flash_bwd": {"calls": 1, "rematted": 0, "times": layers},
        "flash_bwd_stats": {"calls": 1, "rematted": 0, "times": layers},
        "flash_fwd": {"calls": 1 + len(again), "rematted": len(again),
                      "times": layers * (1 + len(again))}}


def test_train_step_tp2_dp2_partitions_the_kernel(tp2_dp2_step):
    """The same step over the four chips of a v5e 2x2 host at TP 2 x DP 2
    with sequence parallelism and the sharded optimizer. GSPMD cannot
    partition a Mosaic kernel ("wrap the call in a shard_map"): the
    dispatcher in ops/attention.py runs it per shard, so this compiles,
    keeps its custom calls, gains the collectives, and each device holds
    well under the unsharded state (bf16 params + fp32 master and Adam
    moments: 14 bytes a parameter)."""
    compiled, meta = tp2_dp2_step
    assert meta["mesh_shape"]["tensor"] == 2
    assert meta["mesh_shape"]["data"] == 2
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    for collective in ("all-reduce", "all-gather"):
        assert re.search(rf"\b{collective}(-start)?\(", text), collective
    sharded = compiled.memory_analysis().argument_size_in_bytes
    whole = 14 * meta["n_params"]
    assert sharded < 0.5 * whole, (sharded / GIB, whole / GIB)


def test_train_step_tp2_dp2_names_its_kernels_and_regions(tp2_dp2_step):
    """Under the mesh the kernels run inside `shard_map`: the names still
    arrive, on exactly the custom calls the step holds, and every region
    of the step is in the chip compiler's metadata."""
    from megatron_tpu.telemetry.tracing.events import (
        REGION_SCOPES, scope_tokens,
    )

    text = tp2_dp2_step[0].as_text()
    _assert_step_kernels_named(text)
    assert all("shard_map" in toks for toks in _kernel_name_stacks(text))
    stacks = [scope_tokens(n)
              for n in set(re.findall(r'op_name="([^"]+)"', text))]
    for scope in REGION_SCOPES:
        assert any(scope in toks for toks in stacks), scope


# fixture, K as one chip's flash calls take it: [micro-batch a chip, KV
# heads a chip, sequence, head]
_K_OPERANDS = {
    "tp2_dp2": ("tp2_dp2_step", f"bf16[1,{HKV // 2},{SEQ},{D}]"),
    "mellum": ("mellum_step", "bf16[2,4,8192,128]"),
    "olmoe": ("olmoe_step", f"bf16[1,16,{SEQ},{D}]"),
}


@pytest.mark.parametrize("case", list(_K_OPERANDS))
def test_the_step_hands_the_flash_kernels_k_by_kv_head(request, case):
    """The compiled steps' flash calls take K (and V) at the KV heads'
    shape: nothing is broadcast to the query heads in front of the
    kernels, in the forward or in the backward, whose dk and dv leave it
    at that shape too while dq, of the query heads', stays its first
    result. Under TP 2 each shard holds 16 query heads over 4 KV heads:
    the plan shards heads, so the group's size is the same on every
    shard. What a traced run journals as `step_program.flash_k_operands`
    (analysis/step_program.py), read off the same text."""
    from megatron_tpu.analysis import step_program

    fixture, k = _K_OPERANDS[case]
    step = request.getfixturevalue(fixture)
    text = (step[0] if isinstance(step, tuple) else step).as_text()
    assert step_program.flash_k_operands(text) == {
        "flash_bwd": [k], "flash_fwd": [k]}
    results = [re.findall(r"\w+\[[\d,]+\]", line[:line.index(" custom-call(")])
               for line in text.splitlines()
               if re.match(r"\s*%flash_bwd(\.\d+)? = ", line)]
    assert results and all((dk, dv) == (k, k) for _, dk, dv in results)


@pytest.mark.parametrize("recompute", ["selective", "full"])
def test_the_policy_decides_how_often_the_flash_forward_runs(
        topo, tp2_dp2_step, recompute):
    """`selective` keeps what the flash forward hands its backward and
    runs it once a layer; `full` keeps nothing and runs it twice. The
    price of keeping: `selective` holds more at the step's peak than
    `full`, and both fit the chip."""
    compiled = (tp2_dp2_step[0] if recompute == "selective"
                else _tp2_dp2_step(topo, recompute)[0])
    _assert_step_kernels_named(compiled.as_text(), recompute)
    assert _per_device_bytes(compiled) < 15.75 * GIB
    if recompute == "full":
        assert (compiled.memory_analysis().temp_size_in_bytes
                < tp2_dp2_step[0].memory_analysis().temp_size_in_bytes)


def test_the_scopes_change_no_byte_of_the_step(topo, tp2_dp2_step):
    """`jax.named_scope` writes metadata and nothing else: the same step
    built with every scope a no-op needs the same bytes on a chip, to the
    byte, and holds the same instructions. (The kernels keep their
    `name=`: that names the HLO instruction and the Mosaic module, and is
    part of the program with scopes and without.)"""
    import contextlib

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare, _ = _tp2_dp2_step(topo)
    assert "attention" not in " ".join(
        re.findall(r'op_name="([^"]+)"', bare.as_text()))
    named, _ = tp2_dp2_step
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "alias_size_in_bytes", "temp_size_in_bytes",
                  "generated_code_size_in_bytes"):
        assert (getattr(named.memory_analysis(), field)
                == getattr(bare.memory_analysis(), field)), field
    opcodes = lambda c: sorted(re.findall(  # noqa: E731
        r"^\s*(?:ROOT )?%[\w.\-]+ = \S+ ([a-z\-]+)\(", c.as_text(), re.M))
    assert opcodes(named) == opcodes(bare)


# ---------------------------------------------------------------------------
# Where the step's collectives stand: in which loop, how often, over which axis
# ---------------------------------------------------------------------------

_COLLECTIVE = re.compile(r" (all-gather|all-reduce|reduce-scatter|all-to-all|"
                         r"collective-permute)(?:-start)?\(")
_RESULT = re.compile(r"\b(pred|[subf]\d+|bf16)\[([\d,]*)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4}


def _elements(dims):
    return math.prod(int(d) for d in dims.split(",") if d)


def _replica_groups(text):
    """`{{0,2},{1,3}}` or the iota form `[2,2]<=[2,2]T(1,0)` as a set of
    frozensets of device ids."""
    if text.startswith("{"):
        return {frozenset(int(i) for i in g.split(","))
                for g in re.findall(r"\{([\d,]+)\}", text)}
    import numpy as np

    m = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", text)
    shape, reshape, perm = (tuple(int(i) for i in g.split(",")) if g else None
                            for g in m.groups())
    ids = np.arange(math.prod(reshape)).reshape(reshape)
    if perm:
        ids = ids.transpose(perm)
    return {frozenset(int(i) for i in row) for row in ids.reshape(shape)}


# a product as the step program holds it: a fusion around a convolution
_PRODUCT = re.compile(r" (fusion|convolution)\(")


def _collectives(text):
    return _instructions(text, _COLLECTIVE)


def _instructions(text, opcode):
    """Every instruction of a compiled program that matches `opcode` (a
    pattern with the kind as its one group) and that the step reaches:
    kind, dtype, elements of its largest result, how often a step runs it
    (the product of the trip counts of the loops around it), whether a
    loop is around it, whether it stands inside a fusion, its replica
    groups, and its `op_name` (its own, else that of the instruction
    calling the computation it stands in: the chip compiler fuses an
    all-reduce with the slice that follows it and leaves the name on the
    fusion). The walk over the computations is the program's own
    (analysis/step_program.py: what the trainer journals of a traced
    step)."""
    from megatron_tpu.analysis.step_program import Program

    program = Program(text)
    found = []
    for comp, line, _name, _results, _opcode in program.instructions():
        m = opcode.search(line)
        if not m:
            continue
        dtype, dims = max(_RESULT.findall(line[:m.start()]),
                          key=lambda r: _elements(r[1]))
        groups = re.search(r"replica_groups=(\S+?),? ", line)
        found.append(dict(
            kind=m.group(1), dtype=dtype, dims=dims,
            elements=_elements(dims), times=program.times[comp],
            in_loop=program.looped[comp], fused=program.fused[comp],
            groups=_replica_groups(groups.group(1)) if groups else set(),
            op_name=program.op_name(comp, line)))
    return found


def _assert_head_loss_forms_its_logits_once(text, chunk_dims, times):
    """Under `head_loss` nothing is computed again, and the product that
    gives a chunk's logits, bf16[.., C, V / tp], stands in the step once,
    inside the region's one chunk loop, `times` times a step
    (ops/cross_entropy.py _chunk_loop forms the chunk's gradient in the
    iteration that holds its logits). The parent of PR 31 (94cc775) held
    it twice, in two loops, the second under `rematted_computation`."""
    from megatron_tpu.telemetry.tracing.events import scope_tokens

    again = [n for n in set(re.findall(r'op_name="([^"]+)"', text))
             if {"head_loss", "rematted_computation"} <= set(scope_tokens(n))]
    assert not again, again[:3]
    logits = [i for i in _instructions(text, _PRODUCT)
              if not i["fused"] and i["dtype"] == "bf16"
              and i["dims"].endswith(chunk_dims)
              and "head_loss" in scope_tokens(i["op_name"])
              and "dot_general" in i["op_name"]]
    assert [(i["times"], i["in_loop"]) for i in logits] == [(times, True)], \
        logits


def test_head_and_loss_state_their_collectives_tp2_dp2(tp2_dp2_step):
    """Under TP x SP the head and the chunked cross-entropy run as one
    `shard_map` that writes its collectives where it wants them
    (ops/cross_entropy.py chunked_head_loss). In the described v5e 2x2
    step program, under `head_loss`:

    * no collective inside a loop has a result larger than one chunk of
      the hidden state, B x C x H;
    * none inside a loop crosses `data`;
    * the head's weight gradient, `[H, V / tp]`, crosses `data` once a
      step;
    * what the all-gathers over `tensor` deliver a step is the hidden
      state once (the one pass over the head that PR 31 left) and at most
      one float32 a token;
    * nothing is computed again, and the chunk's logits are formed once.

    Fails on the parent of PR 30 (78a1ffa), where every sharding in the
    region was the partitioner's and a pass of the chip compiler sank the
    gather of the whole hidden state into both chunk loops: there the
    program holds `all-gather bf16[8,B,512,4096]` 16 times a step (sixteen
    times the hidden state), `all-reduce bf16[4096,16000]` over `data` 8
    times and `all-reduce bf16[1,B,512,4096]` over `tensor` 8 times, all
    inside the loops. A later change of compiler or code cannot bring
    that back unseen."""
    from megatron_tpu.telemetry.tracing.events import scope_tokens

    text = tp2_dp2_step[0].as_text()
    cfg = _mistral_2l()
    b, chunk, h = 1, cfg.ce_chunk_size, cfg.hidden_size
    mine = [c for c in _collectives(text)
            if "head_loss" in scope_tokens(c["op_name"])]
    # device ids are laid out data-major, tensor-minor (parallel/mesh.py)
    tensor = {frozenset({0, 1}), frozenset({2, 3})}
    data = {frozenset({0, 2}), frozenset({1, 3})}
    in_loops = [c for c in mine if c["in_loop"]]
    assert in_loops and {c["times"] for c in in_loops} == {SEQ // chunk}
    for c in in_loops:
        assert c["elements"] <= b * chunk * h, c
        assert c["groups"] == tensor, c
    head_grads = [c for c in mine
                  if c["dims"] == f"{h},{cfg.vocab_size // 2}"]
    assert [(c["times"], c["groups"]) for c in head_grads] == [(1, data)]
    gathered = sum(c["elements"] * _ITEMSIZE[c["dtype"]] * c["times"]
                   for c in mine
                   if c["kind"] == "all-gather" and c["groups"] == tensor)
    hidden = b * SEQ * h * 2
    assert hidden <= gathered <= hidden + b * SEQ * 4, gathered / hidden
    _assert_head_loss_forms_its_logits_once(
        text, f"{chunk},{cfg.vocab_size // 2}", SEQ // chunk)


def test_one_chip_step_holds_no_collective(one_chip_step):
    """On a mesh of one device the head and loss name no collective (a
    reduction over an axis of one device is not written), and the whole
    step communicates with nobody; the journal's record of it
    (analysis/step_program.py) is empty too."""
    from megatron_tpu.analysis import step_program

    assert _collectives(one_chip_step.as_text()) == []
    assert step_program.collectives(one_chip_step.as_text()) == []


def test_step_program_record_says_where_the_collectives_stand(tp2_dp2_step):
    """What the trainer journals of a traced step (`step_program`'s
    `collectives` and `unnamed_instructions`, analysis/step_program.py),
    on the described v5e 2x2 step at TP 2 x DP 2 with SP and ZeRO-1, one
    sequence a replica. The record names every collective by region and
    by the scope one level down, and says which the chip compiler fused
    into an operation a trace shows as a `fusion`:

    * every collective of a layer carries a part of its region in its
      name stack, and none stands under `attn_core` (the kernels'
      `shard_map` and the layout changes around it) or `attn_rope`;
    * behind the output projection stands an all-reduce of the whole
      [B, S, h] over `tensor`, under `attention/attn_out` (with the slice
      behind it the sequence-parallel reduce-scatter; at the benchmark
      cell's eight sequences a replica the chip compiler fuses the two,
      "all-reduce-scatter fusion", and the record says `fused`: PERF.md
      section 5), and the weight gradients' reductions are fused;
    * the gathers the backward pass needs ride through chains of fusions
      beside the matmuls (`async`, `fused`, `links` > 1);
    * at this size GSPMD also moves the FFN's activation between its two
      shardings around the activation function (all-to-all under
      `mlp_act`): the record is where that is seen;
    * it agrees with this file's own walk on how many collective
      instructions the step holds, fused and not, the copies of one
      chained collective counted as its links;
    * the instructions without a name stack are mostly slices, copies
      and the buffers they fill: no matmul and no reduction or gather
      across devices among them."""
    from megatron_tpu.analysis import step_program
    from megatron_tpu.telemetry.tracing.events import scope_tokens

    text = tp2_dp2_step[0].as_text()
    found = step_program.collectives(text)
    mine = _collectives(text)
    parts = {"attention": {"attn_norm", "attn_qkv", "attn_out"},
             "mlp": {"mlp_norm", "mlp_in", "mlp_act", "mlp_out"}}
    for c in mine:
        toks = scope_tokens(c["op_name"])
        region = next((t for t in toks if t in parts), None)
        if region:
            assert parts[region] & set(toks), c["op_name"]
    by_scope = collections.defaultdict(list)
    for c in found:
        by_scope[(c["region"], c["scope"])].append(c)
    assert {"attn_qkv", "attn_out", "mlp_in", "mlp_out"} <= {
        scope for _region, scope in by_scope}
    cfg = _mistral_2l()
    whole = f"bf16[1,{SEQ},{cfg.hidden_size}]"
    behind_out = [c for c in by_scope[("attention", "attn_out")]
                  if c["kind"] == "all-reduce" and c["result"] == whole]
    assert [(c["group_size"], c["times"]) for c in behind_out] == [
        (2, cfg.num_layers)], by_scope[("attention", "attn_out")]
    weight_grads = [c for c in found if c["region"] in parts
                    and c["kind"] == "all-reduce" and c["fused"]]
    assert len(weight_grads) >= 4 and not any(c["async"]
                                              for c in weight_grads)
    chained = [c for c in found if c["links"] > 1]
    assert chained and all(c["async"] and c["fused"]
                           and c["kind"] == "all-gather" for c in chained)
    assert any(c["kind"] == "all-to-all"
               for c in by_scope[("mlp", "mlp_act")] + by_scope[
                   ("mlp", "silu")])
    assert len(mine) == sum(c["links"] for c in found)
    assert (sum(c["fused"] for c in mine)
            == sum(c["links"] for c in found if c["fused"]))
    for c in found:
        assert c["result_bytes"] > 0 and c["times"] >= 1, c
        assert c["group_size"] in (0, 2, 4), c
    unnamed = step_program.unnamed_instructions(text)
    assert unnamed and all(set(u) == {"opcode", "result", "count", "times"}
                           for u in unnamed)
    opcodes = {u["opcode"] for u in unnamed}
    assert {"slice-start", "copy-start"} <= opcodes
    assert not {"convolution", "dot", "all-reduce", "all-gather"} & opcodes


# ---------------------------------------------------------------------------
# Serving: the KV cache is carried through the layer scan and written in place
# ---------------------------------------------------------------------------

# the candidate serving configuration (benchmark/configs/
# mistral-7b-d8-serve.json): Mistral-7B widths, 8 layers, the paged engine
# at page 16 and chunk 32, 64 slots of up to 8448 tokens
SERVE_LAYERS, PAGE, CHUNK, SERVE_SLOTS, SERVE_LEN = 8, 16, 32, 64, 8448
HBM = 15.75 * GIB   # what the chip's compiler allows a program
# how many rows the store has in each case: pages of the pool (8000 is the
# candidate's; 17000 is what ISSUE 22 asked for and the parent's compiler
# refused), or the slots of a slot cache (16 of 8448 positions: 4.4 GB)
_SERVE_CASES = {"decode-8000": 8000, "chunk-8000": 8000,
                "decode-17000": 17000, "chunk-17000": 17000}


@pytest.fixture(scope="module")
def serve_cfg():
    return dataclasses.replace(
        presets.mistral(seq_length=SERVE_LEN), num_layers=SERVE_LAYERS,
        params_dtype="bfloat16", attention_impl="pallas").validate()


def _paged_step(topo, cfg, step, pages, page, slots, max_len, chunk):
    """The model call of InferenceEngine's decode step or prefill
    chunk (inference/paging/engine.py `make_forward`; the state store beside
    the pool where the model has state-space layers) compiled for one
    described chip with pool and state donated."""
    from megatron_tpu.models.language_model import lm_forward
    from megatron_tpu.models.params import param_shapes
    from megatron_tpu.ops import kv_store, ssm

    dev, i32 = topo.devices[0], jnp.int32

    def abstract(tree):
        return jax.tree.map(lambda s: _abstract(s.shape, s.dtype, dev), tree)

    params = abstract(param_shapes(cfg))
    pool = abstract(jax.eval_shape(lambda: kv_store.create(cfg, pages, page)))
    state = abstract(jax.eval_shape(
        lambda: ssm.create_state(cfg, slots))) if cfg.has_ssm else None

    # as the engine's steps: every model's decode step is told which rows
    # decode (the attention layers hand the kernel the idle length for the
    # others), a chunk of a model with a state store or expert layers which
    # positions are real
    told = state is not None or cfg.num_experts is not None

    def decode(params, caches, state, table, tok, lengths):
        decoding = jnp.any(table != 0, axis=1).astype(i32)
        return lm_forward(cfg, params, tok[:, None], kv_caches=caches,
                          ssm_state=state, cache_index=lengths,
                          page_table=table, state_valid=decoding)

    def prefill_chunk(params, caches, state, row, toks, off, start, end,
                      slot):
        real = jnp.clip(end - off, 0, chunk)[None] if told else None
        return lm_forward(cfg, params, toks, kv_caches=caches,
                          ssm_state=state, cache_index=off, page_table=row,
                          page_write_start=start, page_write_end=end,
                          state_row=None if state is None else slot,
                          state_valid=real)

    per_seq = max_len // page
    fn, args = {
        "decode": (decode, [((slots, per_seq), i32), ((slots,), i32),
                            ((slots,), i32)]),
        "chunk": (prefill_chunk, [((1, per_seq), i32), ((1, chunk), i32)]
                  + [((), i32)] * 4)}[step]
    return jax.jit(fn, donate_argnums=(1, 2)).lower(
        params, pool, state,
        *[_abstract(shape, dtype, dev) for shape, dtype in args]).compile()


def _serve_program(topo, cfg, case):
    """The model call of the engine's decode or chunk step compiled for
    one described chip with the store donated, and the store's shape."""
    rows = _SERVE_CASES[case]
    compiled = _paged_step(topo, cfg, case.split("-")[0], rows, PAGE,
                           SERVE_SLOTS, SERVE_LEN, CHUNK)
    return compiled, (cfg.num_layers, rows, PAGE, cfg.n_kv_heads,
                      cfg.head_dim)


_RESULT_LINE = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\S* ([a-z\-]+)\(", re.M)


@pytest.mark.parametrize("case", list(_SERVE_CASES))
def test_serving_step_writes_the_cache_in_place(topo, serve_cfg, case):
    """`lm_forward` carries the KV store through its layer scan and each
    layer scatters its new rows into it (ops/kv_store.py), so with the
    store donated the described-chip step program

    (a) hands the store back in the buffers it came in (all of its bytes
        aliased, input to output);
    (b) holds temporaries of under a quarter of the store (it holds none
        of a layer's share: kv_store.read hands attention a view);
    (c) has no instruction with the whole store's shape but its
        parameter, the loop's tuple elements and the in-place scatters,
        and none at all with one layer's share of it: nothing slices a
        layer out, transposes it (the decode kernel tiles the cache as it
        lies: flash_template._decode_call) or stacks a new store;
    (d) fits the chip at 17000 pages (weights 4.0 + pool 8.9 GB).

    On the parent of PR 32 (3c93fba: the store went through the scan as
    `xs` and came back as `ys`) the 8000-page programs hold 5.9 / 4.9 GiB
    of temporaries (the new stacked pool, and a transposed copy of a
    layer for the kernel), 24 / 18 instructions of a layer's shape, and at
    17000 pages the compiler refuses both (22.4 / 21.4 of 15.75 GiB): (b),
    (c) and (d) fail there; (a) held, by a copy into the donor at the end."""
    compiled, shape = _serve_program(topo, serve_cfg, case)
    ma = compiled.memory_analysis()
    store_bytes = 2 * math.prod(shape) * 2          # k and v, bf16
    assert ma.alias_size_in_bytes == store_bytes                     # (a)
    assert ma.temp_size_in_bytes < store_bytes / 4, \
        ma.temp_size_in_bytes / GIB                                  # (b)
    assert _per_device_bytes(compiled) < HBM, \
        _per_device_bytes(compiled) / GIB                            # (d)
    text = compiled.as_text()
    whole, layer = math.prod(shape), math.prod(shape[1:])
    in_place = {"parameter", "get-tuple-element", "bitcast", "scatter"}
    for dtype, dims, opcode in _RESULT_LINE.findall(text):           # (c)
        n = _elements(dims)
        if not dims.endswith(f",{shape[-1]}"):
            continue   # rows of no head: the embedding is 32000 x 4096
        if opcode == "fusion" and n == whole:
            continue   # checked below: the scatter, fused with its indices
        assert n != layer or opcode == "bitcast", (opcode, dtype, dims)
        assert n != whole or opcode in in_place, (opcode, dtype, dims)
    fused = [line for line in text.splitlines()
             if " fusion(" in line and f"[{','.join(map(str, shape))}]"
             in line.split(" fusion(")[0]]
    assert all(re.search(r'op_name="[^"]*/scatter"', line)
               for line in fused), fused[:1]
    kernels = {"decode": ["paged_flash_decode"],
               "chunk": ["paged_flash_chunk"]}[case.split("-")[0]]
    assert _kernels_named(text) == kernels


# ---------------------------------------------------------------------------
# Serving: a layer's weights are read where they lie (PR 55)
# ---------------------------------------------------------------------------

_SERVED_STEPS = [(cell, step)
                 for cell in ("serve_mistral7b_instruct",
                              "serve_jamba2_3b_reasoning")
                 for step in ("decode", "chunk")]


def _served_step_text(topo, cell_name, step):
    """A served cell's decode step or prefill chunk (`_paged_step`) at the
    sizes of the cell's own configuration file: the compiled text, and the
    model configuration."""
    from benchmark.harness import spec
    from megatron_tpu.arguments import args_to_run_config, parse_args

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = spec.Cell(os.path.join(repo, "BENCHMARK.json"), cell_name)
    serve = cell.config["program"]["serve"]
    cfg = args_to_run_config(parse_args(
        spec.load_module(cell.reference_path()).program_flags(
            cell.config, serve["seq_length"])
        + cell.config["program"]["flags"]
        + ["--micro_batch_size", "1", "--global_batch_size", "1"])).model
    sizes = [int(serve["flags"][serve["flags"].index(f"--serve_{name}") + 1])
             for name in ("num_pages", "page_size", "num_slots",
                          "max_seq_len", "prefill_chunk")]
    return _paged_step(topo, cfg, step, *sizes).as_text(), cfg


def _dims(result):
    """(1, 4096, 4096) of `bf16[1,4096,4096]{2,1,0:...}`."""
    return tuple(int(d) for d in
                 re.findall(r"\d+", result.split("[")[1].split("]")[0]))


@pytest.mark.parametrize("cell, step", _SERVED_STEPS,
                         ids=[f"{c}-{s}" for c, s in _SERVED_STEPS])
def test_served_step_reads_a_layers_weights_where_they_lie(topo, cell, step):
    """A decode tick has 64 rows and a prefill chunk 512, the weights 2560
    or 4096: in such a call `attention_block` keeps the q/k/v products
    apart from the split into heads (models/transformer.py), so the chip's
    compiler

    (a) neither copies nor slices out an array of the shape of one of an
        attention layer's matrices (`step_program.relaid_arrays` over the
        smallest of them: no record of such a shape);
    (b) gives every product under `attn_qkv` the STACK of its weight as an
        operand, the layer's slice fused into the product, as `attn_out`
        and the FFN have always had theirs.

    On the parent of PR 55 (cada2b2) the compiler folds the split into
    the product, which then yields q head-major, and pays with the
    weight: in each Mistral program `wq`, `wk` and `wv` are sliced out of
    their stacks and copied into the layout `{1,2,0}`, 50.3 MB a layer and
    0.40 GB a call (3 copies and 3 slices found), and the three products
    read the copies; in each Jamba program `wq` is (13.1 MB a layer)."""
    from megatron_tpu.analysis import step_program
    from megatron_tpu.models.params import param_shapes

    text, cfg = _served_step_text(topo, cell, step)
    layers = param_shapes(cfg)["layers"]
    stacks = {k: v.shape for part in ("attn", "mlp")
              for k, v in layers[part].items() if len(v.shape) == 3}
    assert {"wq", "wk", "wv", "wo"} <= set(stacks)
    matrices = {shape[1:] for shape in stacks.values()}
    smallest = min(math.prod(m) for m in matrices) * 2       # bf16
    moved = [r for r in step_program.relaid_arrays(text, smallest)
             if _dims(r["result"])[-2:] in matrices
             and math.prod(_dims(r["result"])[:-2]) == 1]
    assert moved == []                                               # (a)

    program = step_program.Program(text)
    instructions = list(program.instructions())
    dims_of = {(comp, name): _dims(results)
               for comp, _, name, results, _ in instructions}
    products = []
    for comp, line, name, results, opcode in instructions:
        called = re.search(r"calls=%([\w.\-]+)", line)
        if (program.fused[comp] or opcode != "fusion" or not called
                or "/attn_qkv/" not in program.op_name(comp, line)
                or not any(" convolution(" in ln
                           for ln in program.lines[called.group(1)])):
            continue
        operands = re.findall(
            r"%([\w.\-]+)", line[line.index(" fusion(") + 8:].split(")")[0])
        products.append((name, {dims_of.get((comp, o)) for o in operands}))
    assert len(products) == 3, products
    weights = {stacks[k] for k in ("wq", "wk", "wv")}
    for name, shapes in products:                                    # (b)
        assert shapes & weights, (name, shapes)


@pytest.mark.parametrize("cell", ["serve_mistral7b_instruct",
                                  "serve_jamba2_3b_reasoning"])
def test_served_chunk_attends_the_pages_where_they_lie(topo, cell):
    """A prefill chunk's attention is the chunk kernel over the pool
    (`flash_template.paged_flash_chunk`, under `attention/attn_core`), so
    the compiled chunk step of each served cell

    (a) neither copies nor slices out the row's context
        (`step_program.relaid_arrays` finds nothing of the size of the
        table's pages of one layer's keys: the gather is gone);
    (b) holds no instruction whose result runs the sequence limit's
        length (the dense scores and their mask were [.., 512, limit]
        float32, the gathered keys [limit, kv heads, 128]);
    (c) asks for the scoped VMEM the kernel's formula gives (none of its
        own inside Mosaic's default, where both cells' are).

    On the parent of PR 58 (6f6f86b) the Mistral program gathers
    `bf16[528,16,8,128]` twice a layer (17.3 MB each) and forms
    `f32[1,8,4,512,8448]` scores; the Jamba program `bf16[256,16,1,128]`
    and `f32[1,1,20,512,4096]`."""
    from megatron_tpu.analysis import step_program

    text, cfg = _served_step_text(topo, cell, "chunk")
    from megatron_tpu.telemetry.tracing.events import kernel_of

    stacks = [toks for toks in _kernel_name_stacks(text)
              if kernel_of(toks) == "paged_flash_chunk"]
    assert stacks and all("attention" in toks and "attn_core" in toks
                          for toks in stacks)
    entries, page = {"serve_mistral7b_instruct": (528, 16),
                     "serve_jamba2_3b_reasoning": (256, 16)}[cell]
    limit = entries * page
    gathered = entries * page * cfg.n_kv_heads * cfg.head_dim * 2
    moved = [r for r in step_program.relaid_arrays(text, gathered)
             if cfg.head_dim in _dims(r["result"])[-1:]
             and math.prod(_dims(r["result"])) == gathered // 2]
    assert moved == []                                               # (a)
    for dtype, dims, opcode in _RESULT_LINE.findall(text):           # (b)
        if opcode in ("parameter", "get-tuple-element"):
            continue    # the table's row (s32[1, entries]) and the rotary
        shape = [int(d) for d in dims.split(",") if d]
        assert not (limit in shape and math.prod(shape) >= limit * _CHUNK
                    ), (opcode, dtype, dims)
    asked = re.search(r"%paged_flash_chunk(?:\.\d+)? = .*tpu_custom_call.*"
                      r'"scoped_memory_configs":\[\{[^}]*"size":"(\d+)"',
                      text)
    groups = cfg.num_attention_heads // cfg.n_kv_heads
    tq, unit, units, _, _ = ft._chunk_geometry(_CHUNK, groups, entries, page,
                                               cfg.n_kv_heads)
    want = ft._chunk_vmem_bytes(unit * units * cfg.n_kv_heads,
                                cfg.num_attention_heads, groups * tq,
                                unit * units, tq, cfg.head_dim, 2)
    # a launch inside Mosaic's default asks for no limit of its own, and
    # the compile is the compiler's word that it fits
    assert want <= ft._MAX_SCOPED_VMEM
    assert (int(asked.group(1)) if asked else ft._DEFAULT_SCOPED_VMEM
            ) == max(want, ft._DEFAULT_SCOPED_VMEM)                  # (c)


# ---------------------------------------------------------------------------
# The accepted cells' programs are what they were before layer TYPES (PR 47)
# ---------------------------------------------------------------------------

def _olmoe_1l():
    return dataclasses.replace(
        presets.olmoe(seq_length=SEQ), num_layers=1,
        params_dtype="bfloat16", ce_chunk_size=512,
        attention_impl="pallas").validate()


def _mellum_cell_cfg():
    from benchmark.harness import spec
    from megatron_tpu.arguments import args_to_run_config, parse_args

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = spec.Cell(os.path.join(repo, "BENCHMARK.json"),
                     "train_mellum2_share4_seq8k")
    flags = spec.load_module(cell.reference_path()).program_flags(
        cell.config, cell.traffic["seq_length"])
    return args_to_run_config(parse_args(
        flags + cell.config["program"]["flags"] + cell.traffic["flags"]
        + ["--micro_batch_size", "2", "--global_batch_size", "2"])).model


# (configuration, leaves by path, loops of the layer stack in the forward
# pass: a stack of one trip is a call)
_ACCEPTED = {
    "mistral": (_mistral_2l, {
        "embed/tokens": (32000, 4096), "final_ln/scale": (4096,),
        "lm_head/w": (4096, 32000),
        "layers/ln1/scale": (2, 4096), "layers/ln2/scale": (2, 4096),
        "layers/attn/wq": (2, 4096, 4096), "layers/attn/wk": (2, 4096, 1024),
        "layers/attn/wv": (2, 4096, 1024), "layers/attn/wo": (2, 4096, 4096),
        "layers/mlp/w_in": (2, 4096, 28672),
        "layers/mlp/w_out": (2, 14336, 4096)}, 1),
    "olmoe": (_olmoe_1l, {
        "embed/tokens": (50304, 2048), "final_ln/scale": (2048,),
        "lm_head/w": (2048, 50304),
        "layers/ln1/scale": (1, 2048), "layers/ln2/scale": (1, 2048),
        "layers/attn/wq": (1, 2048, 2048), "layers/attn/wk": (1, 2048, 2048),
        "layers/attn/wv": (1, 2048, 2048), "layers/attn/wo": (1, 2048, 2048),
        "layers/attn/q_norm/scale": (1, 2048),
        "layers/attn/k_norm/scale": (1, 2048),
        "layers/moe/router": (1, 2048, 64),
        "layers/moe/w_in": (1, 64, 2048, 2048),
        "layers/moe/w_out": (1, 64, 1024, 2048)}, 0),
    "mellum": (_mellum_cell_cfg, {
        "embed/tokens": (24576, 2304), "final_ln/scale": (2304,),
        "lm_head/w": (2304, 24576),
        "layers/ln1/scale": (4, 2304), "layers/ln2/scale": (4, 2304),
        "layers/attn/wq": (4, 2304, 4096), "layers/attn/wk": (4, 2304, 512),
        "layers/attn/wv": (4, 2304, 512), "layers/attn/wo": (4, 4096, 2304),
        "layers/moe/router": (4, 2304, 64),
        "layers/moe/w_in": (4, 16, 2304, 1792),
        "layers/moe/w_out": (4, 16, 896, 2304)}, 0),
}


@pytest.mark.parametrize("name", list(_ACCEPTED))
def test_the_accepted_cells_trees_and_loops_are_what_they_were(name):
    """Layer types (a stack with state-space layers stacks each type's
    leaves over that type's layers and indexes them inside its loop)
    moved nothing of a stack of one type: the same leaves under the same
    names with the same shapes, every one stacked over all the layers,
    and the layer stack one `scan` over them (none where the stack is one
    trip: one layer, or one period of kinds)."""
    from megatron_tpu.models.language_model import lm_forward
    from megatron_tpu.models.params import param_shapes

    make, leaves, loops = _ACCEPTED[name]
    cfg = make()
    assert cfg.layer_pattern is None and not cfg.has_ssm
    shapes = param_shapes(cfg)
    flat = {"/".join(k.key for k in path): leaf.shape for path, leaf
            in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert flat == leaves
    tokens = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t: lm_forward(cfg, p, t, return_hidden=True))(shapes,
                                                                tokens)

    def scans(jaxpr):
        found = []
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += scans(sub)
        return found

    stack = [e for e in scans(jaxpr.jaxpr)
             if e.params["length"] == cfg.num_layers // len(
                 cfg.attention_period)]
    assert len(stack) == loops
    for eqn in stack:
        # the stacked leaves are the loop's scanned inputs, none closed over
        n_xs = len(eqn.invars) - eqn.params["num_consts"] \
            - eqn.params["num_carry"]
        assert n_xs >= len([k for k in leaves if k.startswith("layers/")])
