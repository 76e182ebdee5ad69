"""Activation functions, including the GLU family.

Behavioral equivalent of megatron/model/glu_activations.py (liglu / geglu /
reglu / swiglu halving the last dim) and the jit-scripted bias-gelu fusion
(megatron/model/fused_bias_gelu.py) — on TPU the bias+act fusion is XLA's
default behaviour, so only the math lives here.

GLU convention: the MLP in-projection packs [gate; up] along the last dim,
and glu(x) = act(gate) * up. The HF Llama mapping (gate_proj, up_proj)
concatenates directly into this layout (see megatron_tpu/interop/hf.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _split_glu(x: jnp.ndarray):
    gate, up = jnp.split(x, 2, axis=-1)
    return gate, up


def glu_activation(name: str, gate: jnp.ndarray,
                   up: jnp.ndarray) -> jnp.ndarray:
    """act(gate) * up of the GLU `name`, the halves already apart (a
    kernel that holds them as two tiles: ops/pallas/grouped_matmul.py)."""
    if name == "swiglu":
        return jax.nn.silu(gate) * up
    if name == "geglu":
        return jax.nn.gelu(gate, approximate=False) * up
    if name == "reglu":
        return jax.nn.relu(gate) * up
    if name == "liglu":
        return gate * up
    raise ValueError(f"unknown GLU activation {name!r}")


def apply_activation(name: str, x: jnp.ndarray) -> jnp.ndarray:
    from megatron_tpu.config import GLU_ACTIVATIONS

    if name == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if name == "gelu_tanh":  # HF "gelu_new" (tanh approximation)
        return jax.nn.gelu(x, approximate=True)
    if name == "relu":
        return jax.nn.relu(x)
    if name == "squared_relu":
        r = jax.nn.relu(x)
        return r * r
    if name in GLU_ACTIVATIONS:
        return glu_activation(name, *_split_glu(x))
    raise ValueError(f"unknown activation {name!r}")


def mlp_input_width_factor(name: str) -> int:
    """GLU activations need a 2x-wide in-projection
    (ref: transformer.py:92-102 doubles the ColumnParallelLinear width)."""
    from megatron_tpu.config import GLU_ACTIVATIONS

    return 2 if name in GLU_ACTIVATIONS else 1
