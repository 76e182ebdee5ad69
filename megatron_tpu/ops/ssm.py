"""The Mamba-1 selective state-space mixer (arXiv:2312.00752), as Jamba's
layers have it (HF modeling_jamba.py JambaMambaMixer.slow_forward).

With u [T, h] the layer's normed input, d_i = expand x h the inner width,
N the state a channel, R the step size's rank, K the convolution's width:

    (xs, z)   = split(u W_in)                               [T, d_i] each
    c_t       = silu(b_conv + sum_{j<K} w_conv[j] * xs[t-K+1+j])
    (d, B, C) = split(c W_x, [R, N, N])
    d, B, C   = RMSNorm(d), RMSNorm(B), RMSNorm(C)          (ssm_inner_norms)
    delta     = softplus(d W_dt + b_dt)                     [T, d_i]
    A         = -exp(a_log)                                 [N, d_i]
    h_t       = exp(delta_t * A) * h_{t-1} + (delta_t * c_t) * B_t[:, None]
    y_t       = sum_n h_t[n] * C_t[n] + d_skip * c_t
    out       = (y * silu(z)) W_out

What a sequence carries from one call to the next is `h` [N, d_i] and the
last K-1 rows of xs (the convolution's tail). `h` is float32 whatever the
model's type: a step multiplies it by a factor close under 1 and adds a
term a thousandth its size, which bfloat16's eight bits lose. The inner
width is the last axis of the state and of every leaf that has it: it is
the axis a vector lane runs along, and a last axis of N = 16 would be
padded to 128 in the chip's memory.

`valid` [B]: the positions of each row that are real. Those past it (a
prefill chunk's padded tail; the one position of a slot that is not
decoding) change neither state: `h` stays where the last real position
left it, and the tail is the K-1 rows before position `valid`.

One sequential pass over time either way: the plain form is a `lax.scan`
(differentiable; what training and the CPU run), the Pallas kernel
`ssm_scan` (ops/pallas/ssm_scan.py) runs the time loop with `h` in VMEM,
and its gradient rule is the plain form's.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.ops.normalization import rmsnorm
from megatron_tpu.ops.weight_quant import deq

F32 = jnp.float32
State = Tuple[jnp.ndarray, jnp.ndarray]   # (h [.., N, d_i] f32, tail [.., K-1, d_i])


# ---------------------------------------------------------------------------
# the state store: a row a slot, beside the KV pages
# ---------------------------------------------------------------------------


def create_state(cfg: ModelConfig, rows: int) -> State:
    """A zeroed state store for `cfg`'s state-space layers, indexed by a
    layer's ordinal among them: `h` [layers, rows, N, d_i] float32 and the
    convolution's tail [layers, rows, K-1, d_i]."""
    layers, di = cfg.layers_of("mamba"), cfg.ssm_d_inner
    return (jnp.zeros((layers, rows, cfg.ssm_d_state, di), F32),
            jnp.zeros((layers, rows, cfg.ssm_d_conv - 1, di), cfg.dtype))


def state_bytes(state: State) -> int:
    return sum(leaf.size * leaf.dtype.itemsize for leaf in state)


def zero_row(state: State, row) -> State:
    """Row `row` (traced) of every layer zeroed: a sequence starts."""
    return tuple(
        jax.lax.dynamic_update_slice(
            leaf, jnp.zeros((leaf.shape[0], 1) + leaf.shape[2:], leaf.dtype),
            (0, row, 0, 0))
        for leaf in state)


def read_state(state: State, layer, row=None) -> State:
    """Layer `layer`'s state: of every row ([rows, ...]; the batch is the
    store's rows in order), or of the one row `row` ([1, ...])."""
    if row is None:
        return tuple(jax.lax.dynamic_index_in_dim(leaf, layer, 0, False)
                     for leaf in state)
    return tuple(
        jax.lax.dynamic_slice(
            leaf, (layer, row, 0, 0), (1, 1) + leaf.shape[2:])[0]
        for leaf in state)


def write_state(state: State, layer, new: State, row=None) -> State:
    """The store with layer `layer`'s state written, in place (the caller
    donates the store and carries it through its scan)."""
    at = (layer, 0 if row is None else row, 0, 0)
    return tuple(
        jax.lax.dynamic_update_slice(leaf, n[None].astype(leaf.dtype), at)
        for leaf, n in zip(state, new))


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------


def selective_scan(x, delta, a, b, c, d_skip, z, h0, valid):
    """The recurrence, the contraction with C, the D skip and the gate, in
    float32, one `lax.scan` over time (T = 1: one step, no loop).

    x, delta, z [B, T, d_i]; a [N, d_i]; b, c [B, T, N]; d_skip [d_i];
    h0 [B, N, d_i]; valid [B] int32. Returns (y [B, T, d_i] float32,
    h [B, N, d_i]: the state after each row's last valid position)."""
    T = x.shape[1]
    live = jnp.arange(T)[None, :] < valid[:, None]              # [B, T]

    def step(h, at):
        x_t, dt_t, b_t, c_t, live_t = at
        decay = jnp.exp(dt_t[:, None, :] * a[None])             # [B, N, d_i]
        new = decay * h + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        h = jnp.where(live_t[:, None, None], new, h)
        # a sum, not a dot: the matrix unit would round h to bfloat16
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    if T == 1:
        h, y = step(h0, (x[:, 0], delta[:, 0], b[:, 0], c[:, 0], live[:, 0]))
        y = y[:, None]
    else:
        time_major = lambda t: jnp.moveaxis(t, 1, 0)  # noqa: E731
        h, y = jax.lax.scan(
            step, h0, tuple(map(time_major, (x, delta, b, c, live))))
        y = jnp.moveaxis(y, 0, 1)
    y = y + d_skip * x
    return y * jax.nn.silu(z), h


def _use_kernel(cfg: ModelConfig, T: int) -> bool:
    """The Pallas kernel serves a chunk of several positions where the
    flash kernels dispatch; one position a row (decode) is XLA's fusion of
    the plain form, and so is the CPU."""
    from megatron_tpu.ops.attention import _kernels_dispatchable

    return T > 1 and cfg.attention_impl == "pallas" and _kernels_dispatchable()


def ssm_mixer(cfg: ModelConfig, p: Dict[str, Any], u: jnp.ndarray,
              state: Optional[State] = None,
              valid: Optional[jnp.ndarray] = None):
    """u [B, T, h] (already normed) -> (out [B, T, h], (h, tail)).

    p: layers/ssm subtree, unstacked. state: each row's (h [B, N, d_i]
    float32, tail [B, K-1, d_i]) as the last call left it; None: a
    sequence's start (zeros). valid [B]: module docstring; None: every
    position is real."""
    B, T, _ = u.shape
    di, N, R, K = cfg.ssm_d_inner, cfg.ssm_d_state, cfg.ssm_rank, cfg.ssm_d_conv
    eps = cfg.layernorm_epsilon
    if valid is None:
        valid = jnp.full((B,), T, jnp.int32)
    if state is None:
        state = (jnp.zeros((B, N, di), F32), jnp.zeros((B, K - 1, di), u.dtype))
    h0, tail = state

    with jax.named_scope("ssm_mixer"):
        with jax.named_scope("ssm_in"):
            xz = u @ deq(p["w_in"], u.dtype)
            xs, z = xz[..., :di], xz[..., di:]
        with jax.named_scope("ssm_conv"):
            ext = jnp.concatenate([tail.astype(xs.dtype), xs], axis=1)
            w = p["conv_w"].astype(F32)
            acc = p["conv_b"].astype(F32)
            for j in range(K):
                acc = acc + w[j] * ext[:, j:j + T].astype(F32)
            conv = jax.nn.silu(acc)                              # [B, T, d_i]
            # the K-1 inputs before position `valid`: rows valid .. valid +
            # K - 2 of the extended input (valid 0: the old tail)
            new_tail = jax.vmap(
                lambda e, v: jax.lax.dynamic_slice_in_dim(e, v, K - 1, 0)
            )(ext, valid)
        with jax.named_scope("ssm_proj"):
            dbc = conv.astype(u.dtype) @ deq(p["w_x"], u.dtype)
            dt, b, c = dbc[..., :R], dbc[..., R:R + N], dbc[..., R + N:]
            if cfg.ssm_inner_norms:
                dt = rmsnorm(dt, p["dt_norm"]["scale"], eps)
                b = rmsnorm(b, p["b_norm"]["scale"], eps)
                c = rmsnorm(c, p["c_norm"]["scale"], eps)
            delta = jax.nn.softplus(
                (dt @ deq(p["w_dt"], u.dtype)).astype(F32)
                + p["b_dt"].astype(F32))
        with jax.named_scope("ssm_scan"):
            operands = (conv, delta, -jnp.exp(p["a_log"].astype(F32)),
                        b.astype(F32), c.astype(F32),
                        p["d_skip"].astype(F32), z.astype(F32), h0, valid)
            if _use_kernel(cfg, T):
                from megatron_tpu.ops.pallas.ssm_scan import ssm_scan

                y, h = ssm_scan(*operands)
            else:
                y, h = selective_scan(*operands)
        with jax.named_scope("ssm_out"):
            out = y.astype(u.dtype) @ deq(p["w_out"], u.dtype)
    return out, (h, new_tail)
