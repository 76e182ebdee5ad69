"""What the flash-attention kernels NEED to do, as functions of their
shapes: the yardstick a kernel's measured time is held against for its
share of the roofline. Nothing here comes from the program: the shapes are
read from the HLO text of the kernel's event in the device trace, the
window from the cell's configuration, the peaks from benchmark/peaks.json
(never the `flops` or `bytes_accessed` a trace event carries: those are the
compiler's count of what the program does, recomputation included).

Causal attention over S positions with a window W scores, for each query
i, the keys j <= i with i - j < W: S(S+1)/2 pairs, less the area the
window clips. One matmul over those pairs is 2*B*H*D FLOP a pair. The
forward needs two matmuls (QK^T, PV); the backward five (QK^T again, since
the probabilities are not kept, dO V^T, P^T dO, dS^T Q, dS K): 2.5 times
the forward. The split backward RUNS seven (`flash_bwd_dq` and
`flash_bwd_dkv` each recompute QK^T and dO V^T); the second copies are not
needed work, so the five are booked three on dq and two on dkv, and only
the two kernels' sum means anything. Bytes: each of q, k, v, o read or
written once by the forward (4 tensors of the q shape: the kernels take K
and V already broadcast to the query heads), and q, k, v, o, do, dq, dk,
dv once by the backward (6 booked on dq, 2 on dkv).
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Sequence, Tuple

ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "f8e4m3fn": 1,
            "f8e5m2": 1}
NEEDED_MATMULS = {"flash_fwd": 2, "flash_bwd_dq": 3, "flash_bwd_dkv": 2}
NEEDED_TENSORS = {"flash_fwd": 4, "flash_bwd_dq": 6, "flash_bwd_dkv": 2}

_RESULT = re.compile(r"=\s*\(?([a-z0-9]+)\[([0-9,]+)\]")


def result_shape(hlo_text: str) -> Optional[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of an instruction's (first) result, from its text:
    "%flash_fwd.1 = (bf16[1,32,4096,128]{...}, f32[...]) custom-call(..."
    -> ("bf16", (1, 32, 4096, 128)). For all three kernels that is a
    tensor of the [B, H, S, D] shape the attention runs over."""
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


def needed(kernel: str, dims: Sequence[int], itemsize: int,
           window: Optional[int]) -> Tuple[float, float]:
    """(FLOP, bytes) one call of `kernel` over [B, H, S, D] needs."""
    b, h, s, d = dims
    flops = NEEDED_MATMULS[kernel] * 2.0 * b * h * d * causal_pairs(s, window)
    return flops, float(NEEDED_TENSORS[kernel] * b * h * s * d * itemsize)


def roofline(kernels: Dict[str, dict], names: Sequence[str],
             window: Optional[int], peaks: dict) -> Optional[dict]:
    """The share of the roofline the calls of `names` reached in one run
    of the step: the least time the chip could take for what they need
    (the larger of FLOP over peak FLOP/s and bytes over peak bytes/s)
    over the time they took. `kernels` is named.per_run()["kernels"].
    None where none of them ran or a call's shape cannot be read."""
    flops = nbytes = seconds = 0.0
    for name in names:
        k = kernels.get(name)
        if k is None:
            continue
        seconds += k["s"]
        for text, calls in k["calls"].items():
            shape = result_shape(text)
            if (shape is None or len(shape[1]) != 4
                    or shape[0] not in ITEMSIZE):
                return None
            f, n = needed(name, shape[1], ITEMSIZE[shape[0]], window)
            flops += calls * f
            nbytes += calls * n
    if not seconds:
        return None
    compute_s = flops / peaks["bf16_flops_per_s"]
    memory_s = nbytes / peaks["hbm_bytes_per_s"]
    return {"pct": 100.0 * max(compute_s, memory_s) / seconds,
            "bound": "compute" if compute_s >= memory_s else "memory",
            "needed_flop": flops, "needed_bytes": nbytes,
            "needed_ms": 1e3 * max(compute_s, memory_s),
            "measured_ms": 1e3 * seconds}
