"""Pipeline parallelism for the T5 encoder-decoder.

The reference pipelines T5 by splitting the stage ring at
`pipeline_model_parallel_split_rank`: encoder layers on the first stages,
decoder layers on the rest (ref megatron/initialize.py + the
encoder_and_decoder branch of schedules.py's forward_step). That layout
leaves encoder stages idle during decoder ticks and vice versa, and needs
a second shape-handshaking p2p channel for the encoder output.

The TPU-native schedule instead maps the enc->dec dependency onto the
*interleaved* ring that training/pipeline.py already proves out: every
stage holds one chunk of encoder layers AND one chunk of decoder layers
(V=2 virtual chunks), a microbatch traverses the ring twice — encoder
pass, wrap-around, decoder pass — and the lax.ppermute carry is the pair
(hidden, enc_out):

  * chunk 0 (encoder): stage s runs encoder layers [s*L/Pn, (s+1)*L/Pn);
    the last stage finishes with the encoder final layernorm and loads
    the result into the enc_out slot of the carry,
  * chunk 1 (decoder): stage s runs its decoder slice; cross-attention
    reads the enc_out that rides the ring alongside the hidden state, so
    every decoder stage has the encoder output for its microbatch with no
    broadcast or second channel,
  * loss (decoder final LN + tied logits + vocab-parallel CE) runs under
    lax.cond on the last stage only, exactly as the GPT pipeline.

Both passes keep every stage busy (the 1F1B-interleaved bubble of
(Pn-1)/(2M) rather than split-rank's idle halves), and the backward
schedule is again free: jax.grad of ppermute is the reverse rotation.

Static shapes: the hidden slot is padded to max(Se, Sd) so the encoder
and decoder passes share one ring buffer; each stage body slices to the
real length of its phase.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.language_model import (
    is_full_remat_family, scan_with_remat,
)
from megatron_tpu.models.t5 import _attn, _mlp, _norm
from megatron_tpu.ops.cross_entropy import cross_entropy_loss
from megatron_tpu.training.pipeline import _embed_onehot


def _enc_stack(cfg, layers, x, padding_mask, recompute):
    """Bidirectional encoder slice: scan over this stage's layers."""

    def body(h, lp):
        hn = _norm(cfg, lp["ln1"], h)
        h = h + _attn(cfg, lp["attn"], hn, hn, "bidirectional", padding_mask)
        h = h + _mlp(cfg, lp["mlp"], _norm(cfg, lp["ln2"], h))
        return h, None

    return scan_with_remat([body], x, layers, recompute)


def _dec_stack(cfg, layers, y, enc_out, enc_padding_mask, recompute):
    """Causal decoder slice with cross-attention to the carried enc_out."""

    def body(h, lp):
        hn = _norm(cfg, lp["ln1"], h)
        h = h + _attn(cfg, lp["attn"], hn, hn, "causal", None)
        h = h + _attn(cfg, lp["cross"], _norm(cfg, lp["ln_cross"], h),
                      enc_out, "bidirectional", enc_padding_mask)
        h = h + _mlp(cfg, lp["mlp"], _norm(cfg, lp["ln2"], h))
        return h, None

    return scan_with_remat([body], y, layers, recompute)


def make_t5_pipeline_loss_fn(
    model_cfg: ModelConfig,
    mesh: Mesh,
    num_stages: int,
    num_microbatches: int,
    recompute: str = "selective",
):
    """Returns loss_fn(params, batch, dropout_key) -> (mean_loss, aux).

    batch: enc_tokens/enc_padding_mask [GB, Se], dec_tokens/labels/
    loss_mask [GB, Sd]. Requires num_layers % num_stages == 0 (both
    stacks) and num_microbatches % num_stages == 0 (the interleaved-ring
    constraint, as in the GPT VPP schedule)."""
    Pn, M = num_stages, num_microbatches
    from megatron_tpu.models.t5 import t5_stack_depths

    Le, Ld = t5_stack_depths(model_cfg)
    for name, L in (("encoder", Le), ("decoder", Ld)):
        if L % Pn:
            raise ValueError(
                f"{name}_num_layers={L} not divisible by stages {Pn}")
    if M % Pn:
        raise ValueError(
            f"the enc+dec interleaved ring needs num_microbatches % "
            f"num_stages == 0 (got {M} % {Pn})")
    V = 2  # chunk 0 = encoder slice, chunk 1 = decoder slice
    # full recompute is the memory-pressure regime: segment the tick scan
    # (as the GPT pipeline does) so backward live carries stay ~2*Pn pairs
    # instead of one (hidden, enc_out) pair per tick
    seg = Pn if is_full_remat_family(recompute) else None

    def loss_fn(params: Dict[str, Any], batch: Dict[str, jnp.ndarray],
                dropout_key: Optional[jax.Array] = None):
        enc_tokens = batch["enc_tokens"]
        dec_tokens = batch["dec_tokens"]
        labels = batch["labels"]
        enc_mask = batch["enc_padding_mask"]
        loss_mask = batch.get("loss_mask")
        if loss_mask is None:
            loss_mask = jnp.ones(labels.shape, jnp.float32)
        gb, Se = enc_tokens.shape
        Sd = dec_tokens.shape[1]
        Smax = max(Se, Sd)
        mbs = gb // M

        split = lambda x: x.reshape((M, mbs) + x.shape[1:])
        enc_tokens, dec_tokens = split(enc_tokens), split(dec_tokens)
        labels, loss_mask, enc_mask = (split(labels), split(loss_mask),
                                       split(enc_mask))

        # replicate batch leaves before the manual region (pipeline.py's
        # stage-conditional-resharding deadlock note applies identically)
        rep = NamedSharding(mesh, P())
        con = lambda x: jax.lax.with_sharding_constraint(x, rep)
        enc_tokens, dec_tokens = con(enc_tokens), con(dec_tokens)
        labels, loss_mask, enc_mask = con(labels), con(loss_mask), con(enc_mask)

        T = M * V + Pn - 1

        enc_keys = ("ln1", "attn", "ln2", "mlp")
        dec_keys = ("ln1", "attn", "ln_cross", "cross", "ln2", "mlp")
        enc_layers = {k: params["encoder"][k] for k in enc_keys}
        dec_layers = {k: params["decoder"][k] for k in dec_keys}
        other = {
            "embed": params["embed"],
            "enc_final_ln": params["encoder"]["final_ln"],
            "dec_final_ln": params["decoder"]["final_ln"],
        }

        def pad_s(x):
            if x.shape[1] == Smax:
                return x
            return jnp.pad(x, ((0, 0), (0, Smax - x.shape[1]), (0, 0)))

        def pipelined(enc_layers, dec_layers, other,
                      enc_tokens, enc_mask, dec_tokens, labels, loss_mask):
            embed_params = {"embed": other["embed"]}
            stage = jax.lax.axis_index("pipe")
            is_first = stage == 0
            is_last = stage == Pn - 1
            perm = [(i, (i + 1) % Pn) for i in range(Pn)]

            def tick(carry, t):
                x, enc_out, loss_sum, tok_sum = carry
                n = jnp.clip(t - stage, 0, M * V - 1)
                valid = (t >= stage) & (t - stage < M * V)
                g = n // (Pn * V)
                j = n % (Pn * V)
                c = j // Pn                # 0 = encoder pass, 1 = decoder
                m = g * Pn + j % Pn        # microbatch index

                idx = lambda a: jax.lax.dynamic_index_in_dim(
                    a, m, 0, keepdims=False)
                enc_m, dec_m = idx(enc_tokens), idx(dec_tokens)
                mask_m = idx(enc_mask) > 0

                def embed_in(x):
                    toks = jnp.where(c == 0, pad_tok(enc_m), pad_tok(dec_m))
                    e = _embed_onehot(model_cfg, embed_params, toks, None)
                    return e.astype(model_cfg.dtype)

                def pad_tok(tk):
                    if tk.shape[1] == Smax:
                        return tk
                    return jnp.pad(tk, ((0, 0), (0, Smax - tk.shape[1])))

                x = jax.lax.cond(is_first & valid, embed_in, lambda s: s, x)

                def enc_branch(args):
                    x, enc_out = args
                    xe = _enc_stack(model_cfg, enc_layers, x[:, :Se],
                                    mask_m, recompute)
                    done = _norm(model_cfg, other["enc_final_ln"], xe)
                    enc_out = jnp.where(is_last & valid, done, enc_out)
                    return pad_s(xe), enc_out

                def dec_branch(args):
                    x, enc_out = args
                    yd = _dec_stack(model_cfg, dec_layers, x[:, :Sd],
                                    enc_out, mask_m, recompute)
                    return pad_s(yd), enc_out

                x, enc_out = jax.lax.cond(c == 0, enc_branch, dec_branch,
                                          (x, enc_out))

                def with_loss(_):
                    h = _norm(model_cfg, other["dec_final_ln"], x[:, :Sd])
                    logits = jnp.einsum("bsh,vh->bsv", h,
                                        other["embed"]["tokens"])
                    _, per_tok = cross_entropy_loss(logits, idx(labels))
                    lm = idx(loss_mask)
                    # [1]-shaped, not scalar: rank-0 residuals of a
                    # differentiated shard_map body trip jax 0.4.37's
                    # partial-eval spec naming (see pipeline.py pipelined())
                    return (jnp.sum(per_tok * lm).reshape(1),
                            jnp.sum(lm).reshape(1))

                def without_loss(_):
                    z = jnp.zeros((1,), jnp.float32)
                    return z, z

                lsum, lcnt = jax.lax.cond(is_last & (c == 1) & valid,
                                          with_loss, without_loss,
                                          operand=None)

                x = jax.lax.ppermute(x, "pipe", perm)
                enc_out = jax.lax.ppermute(enc_out, "pipe", perm)
                return (x, enc_out, loss_sum + lsum, tok_sum + lcnt), None

            h0 = jnp.zeros((mbs, Smax, model_cfg.hidden_size),
                           model_cfg.dtype)
            e0 = jnp.zeros((mbs, Se, model_cfg.hidden_size), model_cfg.dtype)
            z = jnp.zeros((1,), jnp.float32)
            carry0 = (h0, e0, z, z)
            if seg is None:
                (x, enc_out, loss_sum, tok_sum), _ = jax.lax.scan(
                    tick, carry0, jnp.arange(T))
            else:
                n_seg = -(-T // seg)
                tick_ids = jnp.arange(n_seg * seg).reshape(n_seg, seg)
                ragged = n_seg * seg != T

                def segment(carry, ids):
                    if not ragged:
                        return jax.lax.scan(tick, carry, ids)

                    def masked_tick(carry, t):
                        # padding ticks keep the carry; t < T is uniform
                        # across pipe ranks, so no conditional-collective
                        # hazard
                        return jax.lax.cond(
                            t < T, lambda c: tick(c, t)[0], lambda c: c,
                            carry), None

                    return jax.lax.scan(masked_tick, carry, ids)

                segment = jax.checkpoint(segment, prevent_cse=False)
                (x, enc_out, loss_sum, tok_sum), _ = jax.lax.scan(
                    segment, carry0, tick_ids)
            loss_sum = jax.lax.psum(loss_sum, "pipe")
            tok_sum = jax.lax.psum(tok_sum, "pipe")
            return (loss_sum / jnp.maximum(tok_sum, 1.0))[0], tok_sum[0]

        in_specs = (
            jax.tree.map(lambda _: P("pipe"), enc_layers),
            jax.tree.map(lambda _: P("pipe"), dec_layers),
            jax.tree.map(lambda _: P(), other),
            P(), P(), P(), P(), P(),
        )
        fn = jax.shard_map(
            pipelined,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), P()),
            axis_names={"pipe"},
            check_vma=False,
        )
        mean_loss, ntokens = fn(enc_layers, dec_layers, other,
                                enc_tokens, enc_mask, dec_tokens,
                                labels, loss_mask)
        return mean_loss, {"lm_loss": mean_loss, "ntokens": ntokens}

    return loss_fn
