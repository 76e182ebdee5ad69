"""Collective-op taxonomy shared by the static and runtime analyzers.

One stdlib-only module holding the vocabulary both measurement seams
key off (ROADMAP item 2):

  * ``jaxpr_audit`` counts the jaxpr/HLO *static* view against it when
    building the golden comm contracts (``analysis/golden/*.json``);
  * ``telemetry/tracing`` classifies profiler *runtime* events against
    it (an xplane op event named ``all-reduce.12`` is communication, a
    ``fusion.3`` is compute) and joins measured counts back to the
    contracts — ``measured vs. expected`` per config.

No jax import: ``tools/trace_report.py`` reads traces on machines with
no accelerator stack at all (the same contract jaxlint has with
``ast_lint``).
"""

from __future__ import annotations

import re
from typing import Optional

#: explicit collective primitives at jaxpr level (pre-GSPMD view)
COLLECTIVE_PRIMITIVES = {
    "psum", "pmax", "pmin", "ppermute", "pbroadcast", "all_gather",
    "all_to_all", "reduce_scatter", "psum_scatter", "pgather",
    "ragged_all_to_all",
}

#: host-callback primitives (the train/decode steps must have ZERO).
#: jax.debug.print is its own primitive (debug_print) since jax 0.9 —
#: before, it traced to debug_callback.
CALLBACK_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "outside_call",
}

#: HLO collective op mnemonics (post-SPMD-partitioning view). These are
#: also the names XLA's runtime thunks carry into profiler traces, so
#: the SAME tuple classifies both compiled text and xplane op events.
HLO_COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "all-to-all", "collective-permute",
    "reduce-scatter", "collective-broadcast", "ragged-all-to-all",
)

#: HLO ops that move data between host and device rather than computing:
#: infeed/outfeed queues and host transfers (TPU input pipelines).
HLO_TRANSFER_OPS = ("infeed", "outfeed", "copy-start", "copy-done",
                    "send", "recv", "send-done", "recv-done")

#: bits per element for HLO shape strings (``f32[8,128]``)
HLO_DTYPE_BITS = {
    "pred": 8, "s8": 8, "u8": 8, "f8e4m3": 8, "f8e5m2": 8,
    "s16": 16, "u16": 16, "f16": 16, "bf16": 16,
    "s32": 32, "u32": 32, "f32": 32,
    "s64": 64, "u64": 64, "f64": 64, "c64": 64, "c128": 128,
}

#: low-bit transport dtypes (jaxpr dtype strings): a collective moving
#: one of these is the COMPRESSED pattern (megatron_tpu/quant/) — the
#: auditor flags it so the golden manifests show int8 bytes, not bf16
LOW_BIT_DTYPES = {
    "int8", "uint8", "float8_e4m3fn", "float8_e4m3", "float8_e5m2",
    "float8_e4m3fnuz", "float8_e5m2fnuz",
}


def is_low_bit_dtype(dtype_str: str) -> bool:
    """True for <=8-bit collective payloads (quantized transport)."""
    return str(dtype_str) in LOW_BIT_DTYPES


def wire_bytes_per_call(primitive: str, payload_bytes: int,
                        axis_size: int) -> int:
    """Estimated per-device bytes one collective call moves over the
    interconnect, from its (result) payload size and the participating
    axis size n — the standard ring/bidirectional cost model:

      * all-reduce (psum/pmax/pmin): 2 * payload * (n-1)/n
        (reduce-scatter phase + all-gather phase)
      * all-gather / all-to-all: payload * (n-1)/n received (a device
        already holds its own shard of the result)
      * reduce/psum_scatter: payload is the SCATTERED result, so each
        device received (n-1) result-sized contributions
      * ppermute / pbroadcast: the payload once

    axis_size <= 1 moves nothing (including positional-axes psums, whose
    named-axis tuple is empty). axis_size 0 = unknown (no mesh on the
    enclosing shard_map): fall back to the payload itself rather than
    claiming zero traffic. The SAME model prices the telemetry counters
    (quant/collectives.forward_comm_bytes), so manifests and live
    counters agree."""
    if axis_size == 0:
        return payload_bytes
    n = int(axis_size)
    if n <= 1:
        return 0
    if primitive in ("psum", "pmax", "pmin"):
        return 2 * payload_bytes * (n - 1) // n
    if primitive in ("all_gather", "pgather", "all_to_all",
                     "ragged_all_to_all"):
        return payload_bytes * (n - 1) // n
    if primitive in ("reduce_scatter", "psum_scatter"):
        return payload_bytes * (n - 1)
    if primitive in ("ppermute", "pbroadcast"):
        # ring-permute / broadcast: each device sends and receives the
        # payload exactly once per hop (the CP ring-attention transport,
        # inference/context_parallel/ring_kv.py)
        return payload_bytes
    return payload_bytes

# An HLO instruction name is the op mnemonic plus an optional
# ``.<number>`` (or ``-start``/``-done`` async halves): the trace event
# for GSPMD's 12th all-gather is named ``all-gather.12``.
_COLLECTIVE_RE = re.compile(
    r"^(" + "|".join(HLO_COLLECTIVE_OPS) + r")(-start|-done)?(\.\d+)?$")
_TRANSFER_RE = re.compile(
    r"^(" + "|".join(HLO_TRANSFER_OPS) + r")(\.\d+)?$")


def collective_base(op_name: str) -> Optional[str]:
    """The collective mnemonic an HLO instruction name belongs to, or
    None for non-collectives. ``all-gather-start.3`` -> ``all-gather``
    (async-pair halves fold into their base; see
    ``is_collective_done_half`` for keeping pair COUNTS aligned with the
    contract manifests, which count each pair once)."""
    m = _COLLECTIVE_RE.match(op_name)
    return m.group(1) if m else None


def is_collective_done_half(op_name: str) -> bool:
    """True for the ``-done`` half of an async collective pair. Its time
    is still communication (the wait), but it must not COUNT as a second
    collective or measured-vs-expected on async-collective backends
    (TPU) would read ~2x the static contract."""
    m = _COLLECTIVE_RE.match(op_name)
    return bool(m) and m.group(2) == "-done"


def is_transfer(op_name: str) -> bool:
    """True for infeed/outfeed/host-transfer instruction names."""
    return _TRANSFER_RE.match(op_name) is not None
