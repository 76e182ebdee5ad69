"""What one call of the forward flash kernel needs in a stack whose layers
are of several attention kinds: the calls of a step all have one shape,
[B, H, S, D], and differ in their static window, which a call's HLO text
does not show. So each call is booked the MEAN over the configuration's
`layer_types` of what that kind needs: two matmuls (QK^T, PV) over the
causal pairs inside `sliding_window` for a "sliding_attention" layer and
over the whole causal triangle for any other; q, k, v and o moved once.
Over a step's calls (one a layer) the sum is what the step's layers need.
None for a configuration without `layer_types` (kernel_costs/flash_fwd.py
is its file). The mean over the kinds is flash_bwd_by_kind.py's."""

import os

from benchmark.harness import spec

_by_kind = spec.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "flash_bwd_by_kind.py")).needed


def needed(dims, itemsize, config):
    return _by_kind(dims, itemsize, config, matmuls=2, tensors=4)
