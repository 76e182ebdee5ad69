"""The state-space mixers: Mamba-1's selective scan and Mamba-2's (SSD).

**Mamba-1** (arXiv:2312.00752), as Jamba's layers have it (HF
modeling_jamba.py JambaMambaMixer.slow_forward). With u [T, h] the layer's
normed input, d_i = expand x h the inner width, N the state a channel, R
the step size's rank, K the convolution's width:

    (xs, z)   = split(u W_in)                               [T, d_i] each
    c_t       = silu(b_conv + sum_{j<K} w_conv[j] * xs[t-K+1+j])
    (d, B, C) = split(c W_x, [R, N, N])
    d, B, C   = RMSNorm(d), RMSNorm(B), RMSNorm(C)          (ssm_inner_norms)
    delta     = softplus(d W_dt + b_dt)                     [T, d_i]
    A         = -exp(a_log)                                 [N, d_i]
    h_t       = exp(delta_t * A) * h_{t-1} + (delta_t * c_t) * B_t[:, None]
    y_t       = sum_n h_t[n] * C_t[n] + d_skip * c_t
    out       = (y * silu(z)) W_out

**Mamba-2 / SSD** (arXiv:2405.21060), as Nemotron-H's layers have it (HF
modeling_nemotron_h.py NemotronHMamba2Mixer.torch_forward). H heads of P
channels (d_i = H P), G groups that share B and C (head h reads group
g(h) = h // (H / G)), one step size and one scalar decay a head:

    (z, xBC, dt) = split(u W_in, [d_i, d_i + 2GN, H])
    xBC_t     = silu(b_conv + sum_{j<K} w_conv[j] * xBC[t-K+1+j])   all d_i + 2GN channels
    (x, B, C) = split(xBC, [d_i, GN, GN]);  x [H, P], B and C [G, N]
    dt_t[h]   = softplus(dt_t[h] + b_dt[h]);  A[h] = -exp(a_log[h])
    S_t[h]    = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] * x_t[h] (outer) B_t[g(h)]     [P, N]
    y_t[h]    = S_t[h] C_t[g(h)] + d_skip[h] x_t[h]
    y         = RMSNorm_groups(y * silu(z)) * scale         each of the G groups of d_i / G channels
    out       = y W_out

One position a row (decode) is that recurrence's one step. Several
positions run the chunked form (`ssd_chunked`): within a chunk of
`ssm_chunk_size` positions every output is a masked product over the
chunk's own positions plus the state the chunk started from, and the state
passes from chunk to chunk; it equals the recurrence (a sum reordered).
Decays, step sizes, the state and every product with them are float32.

What a sequence carries from one call to the next is the state (Mamba-1's
`h` [N, d_i]; Mamba-2's `S`, every head's [P, N] held as [N, d_i]: H P
flattened, the same layout) and the convolution's tail, the last K-1 rows
of its input (d_i channels; d_i + 2GN of Mamba-2). The state is float32
whatever the model's type: a step multiplies it by a factor close under 1
and adds a term a thousandth its size, which bfloat16's eight bits lose.
The inner width is the last axis of the state and of every leaf that has
it: it is the axis a vector lane runs along, and a last axis of N = 16
would be padded to 128 in the chip's memory.

`valid` [B]: the positions of each row that are real. Those past it (a
prefill chunk's padded tail; the one position of a slot that is not
decoding) change neither state: the state stays where the last real
position left it (Mamba-2: their step size is 0, so the decay is 1 and
nothing is added), and the tail is the K-1 rows before position `valid`.

Mamba-1 is one sequential pass over time either way: the plain form is a
`lax.scan` (differentiable; what training and the CPU run), the Pallas
kernel `ssm_scan` (ops/pallas/ssm_scan.py) runs the time loop with `h` in
VMEM, and its gradient rule is the plain form's. Mamba-2 is `jax.numpy`
but for a decode tick on the chip, where one kernel advances the rows'
state inside the store (`mixer_through_store`, ops/pallas/ssd_step.py).
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
    layer's ordinal among them: the state [layers, rows, N, d_i] float32
    and the convolution's tail [layers, rows, K-1, the channels it runs
    over] (module docstring: either form's)."""
    layers = cfg.layers_of(cfg.ssm_type)
    return (jnp.zeros((layers, rows, cfg.ssm_d_state, cfg.ssm_d_inner), F32),
            jnp.zeros((layers, rows, cfg.ssm_d_conv - 1, cfg.ssm_conv_width),
                      cfg.dtype))


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
    position is real. The stack's form (cfg.ssm_type) decides which mixer
    this is."""
    if cfg.ssm_type == "mamba2":
        return mamba2_mixer(cfg, p, u, state, valid)
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


# ---------------------------------------------------------------------------
# Mamba-2 (SSD)
# ---------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST


def ssd_step(x, dt, a, b, c, s0):
    """The recurrence's one step, on the state as the store holds it.
    x [B, d_i], dt [B, H] (0 where the position is not real), a [H], b, c
    [B, G, N], s0 [B, N, d_i], all float32. Returns (y [B, d_i], the state
    after). What a head has one of is spread over its channels of the
    inner width, what a group has one of (B, C) along the group's. The
    state is seen as [B, N, G, d_i / G], whole lane rows last, and never
    as [.., H, P]: P is half a lane row, and the chip's compiler re-laid
    the whole store around such a view."""
    B, di = x.shape
    G, N = b.shape[1:]
    wide = lambda t: _spread(t, di).reshape(B, 1, G, di // G)  # noqa: E731
    groups = lambda t: t.transpose(0, 2, 1)[..., None]       # [B, N, G, 1]
    s = (wide(jnp.exp(dt * a)) * s0.reshape(B, N, G, di // G)
         + wide(dt) * x.reshape(B, 1, G, di // G) * groups(b))
    # a sum, not a dot: the matrix unit would round the state to bfloat16
    y = jnp.sum(s * groups(c), axis=1)
    return y.reshape(B, di), s.reshape(B, N, di)


def ssd_chunked(x, dt, a, b, c, s0, chunk: int):
    """The recurrence over T positions, a chunk at a time. x [B, T, G, Hg,
    P], dt [B, T, G, Hg] (0 where the position is not real), a [G, Hg], b,
    c [B, T, G, N], s0 [B, N, G, Hg P], float32. Returns (y like x, the
    state after the last position, like s0).

    With cum_q the sum of dt a over a chunk's positions up to q, position
    q of a chunk that starts from state S reads

        y_q = exp(cum_q) S C_q + sum_{s <= q} exp(cum_q - cum_s) (C_q . B_s) dt_s x_s

    and the chunk leaves exp(cum_last) S + sum_s exp(cum_last - cum_s)
    dt_s x_s (outer) B_s: the recurrence's sums, reordered. The chunks run
    one after another (`lax.scan`), the state in the carry, a group's
    channels its last axis as in the store (`ssd_step` says why): what a
    head has one of is spread over its P channels."""
    B, T, G, Hg, P = x.shape
    Q = min(chunk, T)
    pad = -T % Q
    if pad:
        # positions that are not real, behind the last (dt 0: no effect)
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    chunks = (T + pad) // Q
    by_chunk = lambda t: jnp.moveaxis(  # noqa: E731
        t.reshape((B, chunks, Q) + t.shape[2:]), 1, 0)
    earlier = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None, None]

    def one_chunk(s, at):                      # s [B, N, G, Hg P]
        x, dt, b, c = at                       # [B, Q, ...]
        cum = jnp.cumsum(dt * a, axis=1)       # [B, Q, G, Hg], <= 0
        last = cum[:, -1]
        # what the state the chunk started from gives. A product a group
        # over that group's channels of the state (the same sums as one
        # product with the groups as its batch, which would have the
        # chip's compiler re-lay the whole store with the groups in front)
        y = jnp.exp(cum)[..., None] * jnp.stack(
            [jnp.einsum("bqn,bnd->bqd", c[:, :, g], s[:, :, g],
                        precision=_HIGHEST) for g in range(G)], axis=2
        ).reshape(x.shape)
        # what the chunk's own positions give, each to those behind it
        cb = jnp.einsum("bqgn,bsgn->bqsg", c, b, precision=_HIGHEST)
        decay = jnp.exp(jnp.where(
            earlier, cum[:, :, None] - cum[:, None, :], -jnp.inf))
        y = y + jnp.einsum("bqsgh,bsghp->bqghp",
                           decay * cb[..., None] * dt[:, None], x,
                           precision=_HIGHEST)
        into = jnp.exp(last[:, None] - cum) * dt              # [B, Q, G, Hg]
        xs = (into[..., None] * x).reshape(B, Q, G, Hg * P)
        s = (jnp.repeat(jnp.exp(last), P, axis=-1)[:, None] * s
             + jnp.stack(
                 [jnp.einsum("bsn,bsd->bnd", b[:, :, g], xs[:, :, g],
                             precision=_HIGHEST) for g in range(G)], axis=2))
        return s, y

    s, y = jax.lax.scan(one_chunk, s0, tuple(map(by_chunk, (x, dt, b, c))))
    y = jnp.moveaxis(y, 0, 1).reshape((B, T + pad) + x.shape[2:])
    return y[:, :T], s


def _spread(t, width: int):
    """What a head has one of, [B, H], over its channels of the inner
    width: [B, d_i]."""
    return jnp.repeat(t, width // t.shape[-1], axis=-1)


def _mamba2_in(cfg: ModelConfig, p: Dict[str, Any], u, tail, valid):
    """The mixer in front of its recurrence: the projection, the
    convolution over the tail and the call's positions, the step sizes.
    Returns (z [B, T, d_i], x [B, T, d_i], b, c [B, T, G, N], dt [B, T, H]
    with 0 at the positions that are not real, the new tail), float32."""
    T = u.shape[1]
    di, N, K, W, G = (cfg.ssm_d_inner, cfg.ssm_d_state, cfg.ssm_d_conv,
                      cfg.ssm_conv_width, cfg.ssm_n_groups)
    with jax.named_scope("ssm_in"):
        # float32 out of the product's own accumulator: the step size is
        # read off it, and the decay is exp of it
        zxd = jnp.matmul(u, deq(p["w_in"], u.dtype),
                         preferred_element_type=F32)
        z, xbc, dt = zxd[..., :di], zxd[..., di:di + W], zxd[..., di + W:]
    with jax.named_scope("ssm_conv"):
        ext = jnp.concatenate([tail.astype(F32), xbc], axis=1)
        w = p["conv_w"].astype(F32)
        acc = p["conv_b"].astype(F32)
        for j in range(K):
            acc = acc + w[j] * ext[:, j:j + T]
        conv = jax.nn.silu(acc)                              # [B, T, W]
        # the K-1 inputs before position `valid` (ssm_mixer's rule)
        new_tail = jax.vmap(
            lambda e, v: jax.lax.dynamic_slice_in_dim(e, v, K - 1, 0)
        )(ext, valid).astype(tail.dtype)
    with jax.named_scope("ssm_scan"):
        b = conv[..., di:di + G * N].reshape(conv.shape[:2] + (G, N))
        c = conv[..., di + G * N:].reshape(conv.shape[:2] + (G, N))
        live = jnp.arange(T)[None, :] < valid[:, None]           # [B, T]
        dt = jnp.where(live[..., None],
                       jax.nn.softplus(dt + p["b_dt"].astype(F32)), 0.0)
    return z, conv[..., :di], b, c, dt, new_tail


def _mamba2_out(cfg: ModelConfig, p: Dict[str, Any], y, x, z, dtype):
    """The mixer behind its recurrence: the D skip, the gated norm, the
    projection out. y, x, z [B, T, d_i] float32."""
    B, T, di = y.shape
    G = cfg.ssm_n_groups
    with jax.named_scope("ssm_scan"):
        y = y + _spread(p["d_skip"].astype(F32)[None], di) * x
    with jax.named_scope("ssm_norm"):
        # the gate, then an RMSNorm over each group's d_i / G channels
        y = (y * jax.nn.silu(z)).reshape(B, T, G, -1)
        y = y * jax.lax.rsqrt(
            jnp.mean(y * y, axis=-1, keepdims=True) + cfg.layernorm_epsilon)
        y = y.reshape(B, T, di) * p["norm"]["scale"].astype(F32)
    with jax.named_scope("ssm_out"):
        return y.astype(dtype) @ deq(p["w_out"], dtype)


def mamba2_mixer(cfg: ModelConfig, p: Dict[str, Any], u: jnp.ndarray,
                 state: Optional[State] = None,
                 valid: Optional[jnp.ndarray] = None):
    """The Mamba-2 mixer (module docstring): u [B, T, h] (already normed)
    -> (out [B, T, h], (S [B, N, d_i] float32, tail [B, K-1, d_i + 2GN])).
    Arguments as `ssm_mixer` has them."""
    B, T, _ = u.shape
    di, N, K, W = (cfg.ssm_d_inner, cfg.ssm_d_state, cfg.ssm_d_conv,
                   cfg.ssm_conv_width)
    G, P = cfg.ssm_n_groups, cfg.ssm_head_dim
    Hg = cfg.ssm_num_heads // G
    if valid is None:
        valid = jnp.full((B,), T, jnp.int32)
    if state is None:
        state = (jnp.zeros((B, N, di), F32), jnp.zeros((B, K - 1, W), u.dtype))
    s0, tail = state

    with jax.named_scope("ssm_mixer"):
        z, x, b, c, dt, new_tail = _mamba2_in(cfg, p, u, tail, valid)
        with jax.named_scope("ssm_scan"):
            a = -jnp.exp(p["a_log"].astype(F32))
            if T == 1:
                y, s = ssd_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0], s0)
                y = y[:, None]
            else:
                # The rows' state crosses into the chunked form and back
                # as a flat vector, which has one layout. The products
                # there make the state with N along the lanes, and the
                # chip's compiler carries a layout back through every
                # reshape that can keep it: without the flat form between
                # them it re-laid the WHOLE store to match (two copies of
                # 1.3 GB a chunk of the benchmark's Nemotron share), with
                # it the one row's 4 MB.
                apart = lambda t, shape: jax.lax.optimization_barrier(  # noqa: E731
                    t.reshape(B, -1)).reshape(shape)
                y, s = ssd_chunked(
                    x.reshape(B, T, G, Hg, P), dt.reshape(B, T, G, Hg),
                    a.reshape(G, Hg), b, c, apart(s0, (B, N, G, Hg * P)),
                    cfg.ssm_chunk_size)
                y, s = y.reshape(B, T, di), apart(s, (B, N, di))
        out = _mamba2_out(cfg, p, y, x, z, u.dtype)
    return out, (s, new_tail)


def _step_kernel_serves(cfg: ModelConfig) -> bool:
    """Mamba-2's one position for every row of the store is the Pallas
    kernel `ssd_step` (ops/pallas/ssd_step.py) where the flash kernels
    dispatch and its tiles divide the shapes; XLA's fusion of `ssd_step`
    above elsewhere, and on the CPU."""
    from megatron_tpu.ops.attention import _kernels_dispatchable
    from megatron_tpu.ops.pallas import ssd_step as kernel

    return (cfg.ssm_type == "mamba2" and cfg.attention_impl == "pallas"
            and _kernels_dispatchable()
            and kernel.serves(cfg.ssm_d_state,
                              cfg.ssm_d_inner // cfg.ssm_n_groups))


def mixer_through_store(cfg: ModelConfig, p: Dict[str, Any], u: jnp.ndarray,
                        store: State, layer, row=None,
                        valid: Optional[jnp.ndarray] = None):
    """`ssm_mixer` over state that lives in the store: layer `layer`'s, of
    every row ([rows, ...]: the batch is the store's rows in order) or of
    the one row `row` -> (out, the store with the state after written, in
    place: the caller donates it).

    Every row one position of Mamba-2 (a decode tick) where the kernel
    serves: the state is advanced where it lies, by `ssd_step`'s kernel
    over the store itself, and only the convolution's tail is read out
    and written back."""
    if not (row is None and u.shape[1] == 1 and _step_kernel_serves(cfg)):
        out, state = ssm_mixer(cfg, p, u, read_state(store, layer, row), valid)
        return out, write_state(store, layer, state, row)
    from megatron_tpu.ops.pallas.ssd_step import ssd_step as step_in_store

    states, tails = store
    di = cfg.ssm_d_inner
    with jax.named_scope("ssm_mixer"):
        tail = jax.lax.dynamic_index_in_dim(tails, layer, 0, False)
        z, x, b, c, dt, new_tail = _mamba2_in(cfg, p, u, tail, valid)
        with jax.named_scope("ssm_scan"):
            a = -jnp.exp(p["a_log"].astype(F32))
            y, states = step_in_store(
                states, layer, _spread(jnp.exp(dt[:, 0] * a), di),
                _spread(dt[:, 0], di) * x[:, 0], b[:, 0], c[:, 0])
        out = _mamba2_out(cfg, p, y[:, None], x, z, u.dtype)
        tails = jax.lax.dynamic_update_slice(
            tails, new_tail[None], (layer, 0, 0, 0))
    return out, (states, tails)
