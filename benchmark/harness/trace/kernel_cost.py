"""A kernel's share of its roofline: the least time the chip could take
for what the kernel NEEDS to do over the time the trace shows for it.
Nothing here comes from the program: the shapes are read from the HLO
text of the kernel's event in the device trace, the peaks from
benchmark/peaks.json, and the needed work from a file of the kernel's own,

    <path>/kernel_costs/<kernel>.py:  needed(dims, itemsize, config)
                                      -> (FLOP, bytes) of one call, or
                                      None for a shape it does not know

found by the name the program gives the kernel (`pallas_call(name=)`) like
a reader is found by its metric's; `config` is the cell's whole
configuration. Never the `flops` or `bytes_accessed` a trace event
carries: those are the compiler's count of what the program does,
recomputation included. A kernel without such a file has no roofline.

`causal_attention` is the arithmetic the flash-attention kernels' files
share. Causal attention over S positions with a window W scores, for each
query i, the keys j <= i with i - j < W: S(S+1)/2 pairs, less the area
the window clips. One matmul over those pairs is 2*B*H*D FLOP a pair. The
forward needs two matmuls (QK^T, PV); the backward five (QK^T again, since
the probabilities are not kept, dO V^T, P^T dO, dS^T Q, dS K): 2.5 times
the forward. The split backward RUNS seven (`flash_bwd_dq` and
`flash_bwd_dkv` each recompute QK^T and dO V^T); the second copies are not
needed work, so the five are booked three on dq and two on dkv, and only
the two kernels' sum means anything. Bytes: each of q, k, v, o read or
written once by the forward (4 tensors of the q shape: the kernels take K
and V already broadcast to the query heads), and q, k, v, o, do, dq, dk,
dv once by the backward (6 booked on dq, 2 on dkv). One fused `flash_bwd`
call is booked the pair's sum: five matmuls, eight tensors.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Optional, Sequence, Tuple

ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "f8e4m3fn": 1,
            "f8e5m2": 1}
Needed = Callable[[Sequence[int], int, dict],
                  Optional[Tuple[float, float]]]

_RESULT = re.compile(r"=\s*\(?([a-z0-9]+)\[([0-9,]+)\]")


def result_shape(hlo_text: str) -> Optional[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of an instruction's (first) result, from its text:
    "%flash_fwd.1 = (bf16[1,32,4096,128]{...}, f32[...]) custom-call(..."
    -> ("bf16", (1, 32, 4096, 128))."""
    m = _RESULT.search(hlo_text)
    if not m:
        return None
    return m.group(1), tuple(int(d) for d in m.group(2).split(","))


def causal_pairs(s: int, window: Optional[int]) -> int:
    """Query-key pairs of causal attention over s positions, each query
    seeing at most `window` keys (itself included)."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    return window * (window + 1) // 2 + (s - window) * window


def causal_attention(dims: Sequence[int], itemsize: int,
                     window: Optional[int], matmuls: int,
                     tensors: int) -> Optional[Tuple[float, float]]:
    """(FLOP, bytes) of `matmuls` matmuls over the causal windowed pairs
    of [B, H, S, D] and `tensors` tensors of that shape moved once; None
    for a result of another rank."""
    if len(dims) != 4:
        return None
    b, h, s, d = dims
    flops = matmuls * 2.0 * b * h * d * causal_pairs(s, window)
    return flops, float(tensors * b * h * s * d * itemsize)


def roofline(kernels: Dict[str, dict], names: Sequence[str],
             needed_of: Callable[[str], Optional[Needed]], config: dict,
             peaks: dict) -> Optional[dict]:
    """The share of the roofline the calls of `names` reached in one run
    of the step: the least time the chip could take for what they need
    (the larger of FLOP over peak FLOP/s and bytes over peak bytes/s)
    over the time they took. `kernels` is named.per_run()["kernels"],
    `needed_of(name)` the `needed` of that kernel's cost file (spec.Cell.
    kernel_cost). None where none of them ran, one that ran has no cost
    file, or a call's shape cannot be read or is not one its file knows."""
    flops = nbytes = seconds = 0.0
    for name in names:
        k = kernels.get(name)
        if k is None:
            continue
        needed = needed_of(name)
        if needed is None:
            return None
        seconds += k["s"]
        for text, calls in k["calls"].items():
            shape = result_shape(text)
            if shape is None or shape[0] not in ITEMSIZE:
                return None
            work = needed(shape[1], ITEMSIZE[shape[0]], config)
            if work is None:
                return None
            flops += calls * work[0]
            nbytes += calls * work[1]
    if not seconds:
        return None
    compute_s = flops / peaks["bf16_flops_per_s"]
    memory_s = nbytes / peaks["hbm_bytes_per_s"]
    return {"pct": 100.0 * max(compute_s, memory_s) / seconds,
            "bound": "compute" if compute_s >= memory_s else "memory",
            "needed_flop": flops, "needed_bytes": nbytes,
            "needed_ms": 1e3 * max(compute_s, memory_s),
            "measured_ms": 1e3 * seconds}
