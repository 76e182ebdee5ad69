"""Mixture-of-Experts layer: top-k routing over E experts, two dispatches.

Beyond the reference (epfLLM/Megatron-LLM has no MoE). One router and one
definition of the auxiliary losses serve both dispatch forms:

  * router, softmax form (`moe_router_score="softmax"`): softmax over E
    experts in fp32, top-k selection per token (k=1 Switch, k=2
    GShard/Mixtral, k=8 OLMoE); optional renormalization of the selected
    gate weights to sum 1 (Mixtral; OLMoE's `norm_topk_prob: false` is
    `moe_renorm_gates=False`).
  * router, sigmoid form (`"sigmoid"`; DeepSeek-V3's, and Nemotron-H's
    `NemotronHTopkRouter`), fp32: s = sigmoid(u W_r), a score an expert on
    its own; the k chosen are the k largest of s + b, b the learned
    selection bias `router_bias` [E], read for the choice alone; the gates
    are w_e = s_e / sum over the chosen of s (`moe_renorm_gates`), times
    `moe_route_scale`. No load-balance loss goes with it (the bias is
    what balances): the auxiliary loss is zero, the load statistic stays.
  * what the logits are made of (`moe_router_form`, either score form):
    "linear", u W_r; "mlp" (ZAYA1's router, arXiv:2511.17127; the
    dropless block alone), fp32: p_l = u W_d down to
    `moe_router_hidden_size`, r_l = p_l + gamma_l * r_{l-1} with r of the
    previous expert layer (zero in front of the first: an activation the
    layer stack carries from layer to layer, `router_carry`), and logits
    gelu(gelu(r_l W_1) W_2) W_3 (`router_mlp`).
  * a selection bias for the softmax form too (`moe_bias_update_rate`):
    the k chosen are the k largest of logits + b, the gates stay the
    chosen experts' probabilities. b is trained by no gradient: the
    trainer moves it by each layer's load of the step, which the layers
    write into the carry beside the router's state
    (training/optimizer.py update_selection_bias).
  * around the routed experts (the dropless block alone): with
    `moe_latent_size` they work in a narrower width, l = u W_dn in front
    of the dispatch and (sum over the chosen of w_e o_e) W_up behind the
    combine, both linear, no bias, no norm (scopes `moe_latent_in`,
    `moe_latent_out`); the router reads the full-width u. With
    `moe_shared_ffn_size` a shared expert, an MLP of that width that every
    token goes through, is added to their result (scope `moe_shared`).
    With `activation="squared_relu"` an expert is relu(l W1)^2 W2: two
    matrices, no gate matrix.
  * auxiliary losses: the load-balance loss E * sum_e f_e * P_e with f_e
    the fraction of (token, choice) assignments over ALL k choices that
    went to expert e (sum_e f_e = k) and P_e the mean router probability
    (Hugging Face's `load_balancing_loss_func`, the OLMoE and Mixtral
    papers'; Switch's eq. 4 at k = 1), plus the router z-loss
    mean(logsumexp(logits)^2) (ST-MoE). Both are global over the tokens of
    the call (one micro-batch), not per group. Beside the loss every form
    returns the load statistic max_e f_e / mean_e f_e (the largest
    expert's row count over the mean; 1.0 is perfect balance), which the
    step's metrics carry as `moe_load_max_over_mean` (LOAD_METRIC).
  * `moe_dispatch="dropless"` (`moe_block_dropless`; what the benchmark's
    OLMoE cell runs, on one chip): the N*k (token, choice) rows are
    argsorted by expert and gathered into expert order, the two expert
    matmuls run as grouped GEMMs over contiguous per-expert row spans
    (`ops/pallas/grouped_matmul.py`: on one TPU the program's own Pallas
    kernels `moe_gmm` / `moe_tgmm`, forward and both gradients, with
    tiles picked from the shapes; `lax.ragged_dot` on every other
    backend, under a mesh of several devices and at row counts the tiles
    do not divide, such as decode), and the outputs are gathered back
    into token order through the inverse of the sort, where each token's k
    choices are weighted by the gates and summed in float32. The sort is a
    permutation, so rows cross it by gathers in both directions, forward
    and backward (`rows_to_expert_order`, `rows_to_token_order`): the
    block holds no scatter. Each of the four is one pass over all N*k
    rows, but for a call that holds a share of the router's experts (or
    says which rows are read): the rows it keeps stand in front of expert
    order, and at the row counts `_walk_blocks` names the four passes
    walk the blocks that hold kept rows, as many trips as those take, so
    their cost follows the kept rows and not the buffer
    (MOVED_METRIC says how far). No token is dropped and no
    [.., E, C] tensor exists. Its four stages carry the scopes a device
    trace is read by: `moe_router`, `moe_dispatch`, `moe_experts`,
    `moe_combine` (docs/observability.md "Runtime traces"). Under a mesh
    whose data or expert axis divides the batch, `moe_block_dropless_ep`
    runs the same per shard with an explicit expert-axis all-to-all (its
    grouped GEMMs are `lax.ragged_dot` still).
  * `moe_dispatch="capacity"` (GShard/Switch, the default): the N = B*S
    tokens are reshaped into G groups of Sg tokens (Sg divides S, so
    groups never cross batch rows and data sharding stays aligned);
    dispatch and combine are dense einsums over [G, Sg, E, Cg] with
    Cg = ceil(capacity_factor * top_k * Sg / E) — static shapes, memory
    and dispatch FLOPs linear in N, expert parallelism by sharding the
    expert axis (GSPMD inserts the all-to-all). Each expert processes at
    most Cg tokens per group; overflow tokens lose that expert (their
    other choices still apply; a token dropped by all choices passes
    through with zero MLP output, the standard Switch behavior).
"""

from __future__ import annotations

import functools
import math
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from megatron_tpu.config import ModelConfig
from megatron_tpu.ops.activations import apply_activation


# the key of the load statistic in the loss's aux, the step's metrics and
# the journal's `step` record
LOAD_METRIC = "moe_load_max_over_mean"
# the key, in the same places, of the share of a call's N * k (token,
# choice) rows that the router sends to experts held here, mean of the
# layers: only of a model that holds a share of a wider router's experts
# (ModelConfig.moe_experts_held), where the even split is held / E
HELD_METRIC = "moe_held_rows_share"
# the key, beside it, of the rows that the four row movements of such a
# layer (rows_to_expert_order, rows_to_token_order, each one's backward)
# move over the 4 * N * k they would move all at once, mean of the layers:
# how far the passes follow the held rows and not the buffer
# (`moved_rows_share`; 1.0 where the one pass stays)
MOVED_METRIC = "moe_moved_rows_share"
# what of a loss's aux the step's metrics carry, each the mean of the
# step's micro-batches
# the key, in the step's metrics and the journal, of the largest |b| of
# any expert of any layer's selection bias after the step's update: only
# of a model that balances by it (ModelConfig.moe_bias_update_rate)
BIAS_METRIC = "moe_bias_abs_max"
# the key, in a loss's aux alone, of the layers' loads of the call, [expert
# layers, E] choices (what the selection bias moves by)
EXPERT_LOAD = "moe_expert_load"
STEP_METRICS = (LOAD_METRIC, HELD_METRIC, MOVED_METRIC, BIAS_METRIC)
# the `checkpoint_name` of the dropless experts' two grouped products (the
# second under rows_to_token_order, which keeps it for the backward):
# selective recomputation saves weight-matmul outputs, and knows a
# `lax.ragged_dot` for one but not a Pallas call
# (models/language_model.py _remat_policy)
SAVED_PRODUCT = "moe_expert_product"


def moe_capacity(cfg: ModelConfig, num_tokens: int) -> int:
    """Static per-expert token capacity for a batch of num_tokens:
    ceil(capacity_factor * top_k * tokens / E), floored at top_k."""
    E = cfg.num_experts
    c = math.ceil(cfg.moe_capacity_factor * cfg.moe_top_k * num_tokens / E)
    return max(cfg.moe_top_k, c)


def _group_for(s: int, target: int) -> int:
    """Largest divisor of s that is <= target — but never a degenerate
    sliver: if the best divisor is < 256 (e.g. prime s), whole rows win
    (tiny groups disable capacity enforcement — with Sg=1 every choice
    always fits — and shred MXU utilization; whole rows keep semantics at
    a memory cost)."""
    if s <= target:
        return s
    d = next(g for g in range(target, 0, -1) if s % g == 0)
    return d if d >= min(256, target) else s


def moe_group_size(cfg: ModelConfig) -> int:
    """Tokens per dispatch group Sg. cfg.moe_group_size, or auto: the
    largest divisor of seq_length <= 2048 (GShard-scale groups)."""
    if cfg.moe_group_size:
        return cfg.moe_group_size
    return _group_for(cfg.seq_length, 2048)


def topk_dispatch(
    gates: jnp.ndarray,      # [N, E] fp32 router probabilities
    top_k: int,
    capacity: int,
    renorm: bool,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns (combine [N,E,C] fp32, dispatch [N,E,C] bool, chosen
    [N,E]: 1.0 where the expert is among the token's k choices).

    Slot assignment is by token order within each expert, k-level by
    k-level (first choices claim slots before second choices), the GShard
    priority rule.
    """
    N, E = gates.shape
    topw, topi = _topk_gates(gates, top_k, renorm)     # [N, k]
    combine = jnp.zeros((N, E, capacity), jnp.float32)
    base = jnp.zeros((E,), jnp.int32)                  # slots already claimed
    chosen = jnp.zeros((N, E), jnp.float32)
    for k in range(top_k):
        m = jax.nn.one_hot(topi[:, k], E, dtype=jnp.int32)       # [N, E]
        chosen = chosen + m.astype(jnp.float32)
        pos_in_e = jnp.cumsum(m, axis=0) - m + base[None, :]
        pos = jnp.sum(pos_in_e * m, axis=1)                       # [N]
        keep = (pos < capacity).astype(jnp.float32)
        slot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)   # [N, C]
        w = topw[:, k] * keep
        combine = combine + (w[:, None, None]
                             * m.astype(jnp.float32)[:, :, None]
                             * slot[:, None, :])
        base = base + jnp.sum(m, axis=0)
    return combine, combine > 0, chosen


def _topk_gates(gates: jnp.ndarray, top_k: int, renorm: bool,
                select: Optional[jnp.ndarray] = None):
    """THE top-k + renorm numerics (one definition for both dispatch
    modes and both forms of router, so they cannot drift apart). select:
    what the choice is made by where that is not the gates themselves
    (the sigmoid form's scores plus their selection bias)."""
    _, topi = jax.lax.top_k(gates if select is None else select, top_k)
    # the chosen gates are read off by a dense compare-and-sum over E (the
    # same values: one term a choice is not zero), so that their gradient
    # is dense too; lax.top_k's own transposes into a scatter-add
    chosen = topi[..., None] == jnp.arange(gates.shape[-1])
    topw = jnp.sum(jnp.where(chosen, gates[..., None, :], 0.0), axis=-1)
    if renorm:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    return topw, topi


def router_mlp(p: Dict[str, Any], x2d: jnp.ndarray, state: jnp.ndarray):
    """The "mlp" form's (logits [N, E], state r_l [N, R]) for [N, H]
    tokens and the previous expert layer's state r_{l-1} [N, R], fp32
    (module docstring)."""
    f32 = jnp.float32
    r = (x2d.astype(f32) @ p["router_down"].astype(f32)
         + p["router_carry_scale"].astype(f32) * state)
    hidden = jax.nn.gelu(r @ p["router_w1"].astype(f32), approximate=False)
    hidden = jax.nn.gelu(hidden @ p["router_w2"].astype(f32),
                         approximate=False)
    return hidden @ p["router_w3"].astype(f32), r


def router_carry(cfg: ModelConfig, x: jnp.ndarray):
    """What a stack of expert layers carries from layer to layer beside
    x [B, S, h], from its zero on: "state", the "mlp" router's r [B, S, R]
    fp32 (an activation: the backward pass goes through it); "load", each
    expert layer's count of the call's choices an expert, [layers, E]
    fp32, where a selection bias moves by it. None for a model with
    neither."""
    carry = {}
    if cfg.carries_router_state:
        carry["state"] = jnp.zeros(
            x.shape[:2] + (cfg.moe_router_hidden_size,), jnp.float32)
    if cfg.balances_by_bias:
        carry["load"] = jnp.zeros((cfg.expert_layers, cfg.num_experts),
                                  jnp.float32)
    return carry or None


def _route(cfg: ModelConfig, p: Dict[str, Any], x2d: jnp.ndarray,
           logits: Optional[jnp.ndarray] = None):
    """Shared router: (logits, gates, topw, topi) for [N, H] tokens, in
    either form (module docstring). logits: given where they are not the
    one matrix's (`router_mlp`)."""
    if logits is None:
        logits = jnp.einsum("nh,he->ne", x2d.astype(jnp.float32),
                            p["router"].astype(jnp.float32))
    if cfg.moe_router_score == "sigmoid":
        gates = jax.nn.sigmoid(logits)
        topw, topi = _topk_gates(
            gates, cfg.moe_top_k, cfg.moe_renorm_gates,
            select=gates + p["router_bias"].astype(jnp.float32))
        return logits, gates, topw * cfg.moe_route_scale, topi
    gates = jax.nn.softmax(logits, axis=-1)
    topw, topi = _topk_gates(
        gates, cfg.moe_top_k, cfg.moe_renorm_gates,
        select=(logits + p["router_bias"].astype(jnp.float32)
                if "router_bias" in p else None))
    return logits, gates, topw, topi


def _aux_from_stats(cfg: ModelConfig, frac, prob, z_sq_mean):
    """(aux loss, load statistic) from already-reduced statistics. frac:
    [E] assignments to each expert over ALL k choices, per token (sums to
    k); prob: [E] mean router probability; z_sq_mean: mean
    logsumexp(logits)^2. One formula for every dispatch mode — the EP
    path pmean's the stats over the expert axis before calling, which
    equals the global mean exactly (equal token counts per shard)."""
    lb_loss = cfg.num_experts * jnp.sum(frac * prob)
    aux = (cfg.moe_aux_loss_coeff * lb_loss
           + cfg.moe_z_loss_coeff * z_sq_mean).astype(jnp.float32)
    return aux, _load_statistic(frac)


def _load_statistic(frac) -> jnp.ndarray:
    """The largest expert's share of the assignments over the mean."""
    return (jnp.max(frac) / jnp.mean(frac)).astype(jnp.float32)


def _aux_losses(cfg: ModelConfig, logits, gates, frac):
    """Load-balance loss over all k choices + ST-MoE router z-loss, and
    the load statistic (shared between dispatch modes). The sigmoid form
    has no such loss (its scores are no distribution over the experts),
    nor has a router whose two coefficients are zero (one balanced by
    its selection bias): zero, and the load statistic."""
    if cfg.moe_router_score == "sigmoid" or not (
            cfg.moe_aux_loss_coeff or cfg.moe_z_loss_coeff):
        return jnp.zeros((), jnp.float32), _load_statistic(frac)
    prob = jnp.mean(gates.reshape(-1, cfg.num_experts), axis=0)
    z_sq = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return _aux_from_stats(cfg, frac, prob, z_sq)


def layer_stats(aux, load) -> jnp.ndarray:
    """One MoE layer's [aux loss, load statistic] as block_forward hands
    it up the layer scan; `load` of a layer that holds a share of its
    router's experts is the [4]-vector moe_block_dropless says (behind
    the load statistic the held rows' share, the experts read, the moved
    rows' share): all go up."""
    if load.ndim:
        return jnp.concatenate([aux[None], load])
    return jnp.stack([aux, load])


def moe_stats_zero(cfg: ModelConfig) -> jnp.ndarray:
    """What merge_layer_stats starts from (a dense stack under a
    shard_map may start from it too, where a scan's carry must not be of
    rank 0: its layers add their zero scalar to it): [aux loss, load
    statistic] and, of a share of the experts, the three numbers
    moe_block_dropless puts behind them. The serving engine reads those
    by their place (inference/paging/engine.py make_forward)."""
    return jnp.zeros((2 + 3 * cfg.holds_expert_share,), jnp.float32)


def merge_layer_stats(acc: jnp.ndarray, new: jnp.ndarray) -> jnp.ndarray:
    """Across layers the aux losses add, the worst layer's load statistic
    stands, and what stands behind them adds: the held and the moved
    rows' shares (language_model.lm_loss takes their means), the experts
    read. A layer without experts hands up its zero."""
    return jnp.stack([acc[0] + new[0], jnp.maximum(acc[1], new[1])]
                     + [acc[i] + new[i] for i in range(2, acc.shape[0])])


def aux_loss_of(moe_aux: jnp.ndarray) -> jnp.ndarray:
    """The aux-loss term of block_forward's third result as a [1]-vector:
    [aux, load] for an MoE layer, a zero scalar for a dense one."""
    return moe_aux.reshape(-1)[:1]


def _expert_counts(flat_e: jnp.ndarray, num_experts: int) -> jnp.ndarray:
    """[E] int32 rows of each expert among flat_e [N*k]: a dense
    compare-and-sum (jnp.bincount is a scatter-add, which a TPU works
    through one update at a time)."""
    ids = jnp.arange(num_experts, dtype=flat_e.dtype)
    return jnp.sum(flat_e[:, None] == ids, axis=0, dtype=jnp.int32)


def sort_by_expert(topi: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(order [N*k], inv [N, k]) for the expert choices topi [N, k], whose
    (token, choice) rows are numbered n*k + j. `order[r]` is the row that
    stands at place r of expert order; the stable sort keeps token order
    within an expert (GShard priority order, though without capacity it
    only decides the order of float sums). `inv[n, j]` is the place row
    n*k + j went to: the argsort of a permutation is its inverse, and a
    sort of N*k keys costs the chip next to nothing where scattering an
    iota through `order` does not."""
    order = jnp.argsort(topi.reshape(-1), stable=True)
    return order, jnp.argsort(order).reshape(topi.shape)


def _permute(values: jnp.ndarray, to: jnp.ndarray) -> jnp.ndarray:
    """result[to[i]] = values[i] for scalars values [N*k] and a
    permutation `to`: a key-value sort by the destination. Moving N*k
    scalars by a gather through the inverse costs the chip ten times what
    the sort does (rows of h values are another matter: `_take_rows`)."""
    return jax.lax.sort((to, values), num_keys=1)[1]


def _take_rows(a: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """a[idx] along axis 0 for indices known to be in range (they come
    from a permutation): no clamp and no fill select around the gather."""
    return a.at[idx].get(mode="promise_in_bounds")


# A call that hands `kept` (a share of the experts, or a serving step's
# `rows_read`) has its live rows in front: the held groups are the prefix
# [0, kept.sum()) of expert order, and nothing reads the rows behind. Its
# four row movements then run block by block over the live rows alone
# (`_walk_blocks` says when, and how many rows a trip), inside the two
# custom_vjps below, so nothing is differentiated through a loop.
#
# Rows a trip moves on the expert-order side, tokens a trip on the token
# side, from the chip (PR 69). The expert side, alone at the Mellum cell's
# call (tools/moe_rows_bench.py: 131,072 rows of 2,304, 0.44 of them
# kept): 1.87-1.97 ms a pass from 1,024 to 8,192 rows a trip (2.8 at 512,
# 2.4 at 16,384: a trip's own cost against the half block behind the last
# kept row; the one pass 4.9-5.1). The token side, in the cell's step on
# the final tree (one traced run a block size, one seed; ms a step,
# `moe_dispatch` + `moe_combine` / `other` + `unnamed` /
# `train_step_ms_p50`; the parent's one pass 87.7 / 12.85 / 378.5): 512
# tokens a trip 37.0 / 12.6 / 313.4, 1,024 44.3 / 12.1 / 322.1, 2,048
# 46.5 / 11.4 / 324.4; on another seed 256 tokens a trip 34.3 / 13.1 /
# 317.5 against 512's 32.3 / 12.6 / 315.2 (presumably a chain's gathered blocks stand in fast
# memory until one fusion adds them, and eight of 512 rows do where eight
# of 2,048 do not: no trace by operation was read for it). Alone, a pass
# had read the same from 512 to 4,096 tokens a trip, which is why the
# step's own numbers decide. (With a
# `lax.switch` a block in place of a loop a chain the same three read
# 36.4 / 23.9 / 321.8, 50.3 / 15.2 / 330.0 and 53.7 / 13.2 / 330.5: the
# conditionals' 20 us each fell under no scope.)
_EXPERT_BLOCK = 2048
_TOKEN_BLOCK = 512
# The (token, choice) rows from which the passes walk; under it the one
# pass over all of them stays (`_walk_blocks` has the sweep's numbers).
_WALK_MIN_ROWS = 65536


def _walk_blocks(num_tokens: int, k: int) -> Optional[Tuple[int, int]]:
    """(rows a trip on the expert-order side, tokens a trip on the token
    side) of a call of num_tokens tokens of k choices that hands `kept`,
    or None: the one pass over all N*k rows stays. No block hangs over
    the buffer's end: a token count that `_TOKEN_BLOCK` does not divide
    (no caller hands one at these row counts) keeps the one pass too. The
    one place that says so: the passes and `moved_rows_share` ask here,
    and what would try other blocks (tests, tools/moe_rows_bench.py)
    replaces this function, not the numbers.

    What decides is the row count alone, from a sweep on the chip (PR 69:
    tools/moe_rows_bench.py, `chiprun_out/pr69*/moe_rows.jsonl`; the four
    passes of a call added up, 0.44 of the rows kept by a router whose
    tokens choose apart; ms, one pass -> walk at these blocks). Rows of
    2,304 (the Mellum cell's width): 131,072 rows 20.6 -> 10.1, 65,536
    rows 9.5 -> 5.4, 32,768 rows 4.9 -> 3.0, 16,384 rows 1.84 -> 1.61,
    8,192 rows 0.80 -> 1.07. Rows of 1,024 (the Nemotron cell's): 180,224
    rows 12.8 -> 7.0, 90,112 rows 6.0 -> 3.9, 45,056 rows 2.06 -> 2.31,
    22,528 rows 1.06 -> 1.46, the cell's chunk of 11,264 rows
    0.69 -> 0.84, its tick of 1,408 rows 0.43 -> 0.52 (the three under
    16,384 rows: of this PR's first form, a `lax.switch` a block). A walk
    pays its sorts and a few microseconds a trip whatever it moves, and a
    gather out of a source of a few megabytes runs at several times the
    large source's rate, so the small calls keep the one pass: from
    65,536 rows on the walk won at both widths, under 16,384 it lost at
    both, and between them the two widths disagree (no cell lies
    there)."""
    if num_tokens * k < _WALK_MIN_ROWS or num_tokens % _TOKEN_BLOCK:
        return None
    return _EXPERT_BLOCK, _TOKEN_BLOCK


def _blocks(rows, block: int):
    """The blocks of `block` rows that hold the first `rows` rows."""
    return (rows + (block - 1)) // block


def _choice_counts(kept: jnp.ndarray) -> jnp.ndarray:
    """[N] int32: how many of a token's k choices are kept."""
    return jnp.sum(kept, axis=1, dtype=jnp.int32)


def _by_falling_count(counts: jnp.ndarray, k: int):
    """(place_of [N], sorted [N]) for counts [N] in 0..k: the place each
    token takes when the tokens stand by falling count, token order kept
    among equals, and the counts in that order. A counting sort in dense
    sums over the k + 1 values (no sort of N keys, and no scatter)."""
    values = jnp.arange(k, -1, -1, dtype=jnp.int32)       # falling
    is_value = counts[:, None] == values                   # [N, k + 1]
    upto = jnp.cumsum(is_value, axis=0, dtype=jnp.int32)   # ... and here
    ends = jnp.cumsum(upto[-1])                            # a value's last
    first = ends - upto[-1]
    place_of = jnp.sum(jnp.where(is_value, first + upto - 1, 0), axis=1)
    ended = jnp.arange(counts.shape[0])[:, None] >= ends[:-1]
    return place_of, k - jnp.sum(ended, axis=1, dtype=jnp.int32)


def moved_rows_share(kept: jnp.ndarray) -> jnp.ndarray:
    """MOVED_METRIC of a call that hands kept [N, k]: the rows its four
    passes move (`rows_to_expert_order`, `rows_to_token_order`, and each
    one's backward) over 4 * N * k. Counted by the functions the passes
    take their trips from. 1.0 where the one pass stays."""
    n, k = kept.shape
    blocks = _walk_blocks(n, k)
    if blocks is None:
        return jnp.ones((), jnp.float32)
    expert, token = blocks
    counts = _choice_counts(kept)
    expert_side = _blocks(jnp.sum(counts), expert) * expert
    # a block of tokens sorted by falling count gathers its first token's
    # count a token (`_sum_of_kept_choices`); one more row a token goes
    # back to token order
    firsts = _by_falling_count(counts, k)[1][::token]
    token_side = jnp.sum(firsts) * token + n
    return ((expert_side + token_side).astype(jnp.float32)
            / jnp.float32(2 * n * k))


def _sum_of_choices(rows: jnp.ndarray, inv: jnp.ndarray, dtype,
                    topw: Optional[jnp.ndarray] = None,
                    kept: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """[N, h] in `dtype`: the sum over a token's k choices j of
    rows[inv[n, j]] (times topw[n, j], where gates are given), in float32
    and rounded once, rows [N*k, h] standing in expert order; of a share
    of the experts, the sum over the choices kept [N, k] alone. One gather
    of N rows a choice, added up as it arrives: the compiler fuses each
    gather with its multiply-add, where one gather of all N*k rows stands
    alone in front of the sum with an [N, k, h] temporary between them
    (on the chip 16.0 against 20.9 ms a step of the OLMoE cell, each
    way). With `kept`, at the row counts `_walk_blocks` names, the
    gathers follow the kept (token, choice) pairs instead
    (`_sum_of_kept_choices`): the same terms added in the same order."""
    if kept is not None:
        blocks = _walk_blocks(*inv.shape)
        if blocks is not None:
            return _sum_of_kept_choices(rows, inv, dtype, topw, kept,
                                        blocks[1])
    total = None
    for j in range(inv.shape[1]):
        term = _take_rows(rows, inv[:, j]).astype(jnp.float32)
        if topw is not None:
            term = term * topw[:, j, None]
        if kept is not None:
            term = jnp.where(kept[:, j, None], term, 0.0)
        total = term if total is None else total + term
    return total.astype(dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "block"))
def _sum_of_kept_choices(rows, inv, dtype, topw, kept,
                         block: int) -> jnp.ndarray:
    """`_sum_of_choices` over the kept choices, moving held_rows + N rows
    (and block slack) where the one pass moves N*k. Each token's choices
    are put kept-first, in their order (stable: the kept terms are added
    in the one pass's order, and its masked terms are zeros, so the
    float32 sum keeps its bits, but for the sign of a zero: a sum whose
    kept terms are all -0.0 reads -0.0 here and, with a masked term
    among them, +0.0 there), and the tokens by falling count of kept
    choices. A block of `block` such tokens then needs as many gathers as
    its first token keeps choices, c: the one pass's chain cut to c
    gathers (the chip's compiler lands each in fast memory and adds them
    up in one fusion, the running sum never in HBM, rounded once and
    written once; a sum carried from slot to slot through a float32
    [N, h] in HBM cost as much as the rows nobody reads), a token of the
    block that keeps fewer masking its last terms. The blocks stand by
    falling c, so those of one chain are one stretch of them: a loop a
    chain, k + 1 loops with traced bounds a pass, every block written by
    exactly one into a buffer nobody had written (a `lax.switch` a block
    is a `conditional` of ~20 us on the chip whatever it moves, under no
    scope's name: 5.3 ms of the Mellum step at 512 tokens a block).
    Nothing here leans
    on tokens choosing alike: a corpus on which they do makes the counts
    one number, and a router whose tokens all choose differently still
    has at most k blocks in which the count changes. The sums go back to
    token order through one gather of N rows of `dtype`. The tokens'
    order is a counting sort (`_by_falling_count`), a token's choices are
    sorted k at a time and move to the token's place by a key-value sort
    down the columns of [N, k]: sorts the chip's compiler is quick with
    (one sort of N*k (count, token, kept) keys with the places and gates
    behind them took it two minutes, most of the step's compile). A
    function of its own (an inner `jit`), so that the layers and passes
    of a step that call it alike trace and lower its k + 1 chains once;
    it reads nothing of this module but what it is handed (`block`
    among it), so what its cache holds cannot go stale."""
    n, k = inv.shape
    place_of, counts = _by_falling_count(_choice_counts(kept), k)
    # a token's kept choices in front, in their order; then each token's
    # places (and gates) to where the token went
    moved = [inv.astype(jnp.int32)] + ([] if topw is None else [topw])
    _, *moved = jax.lax.sort(
        [jnp.logical_not(kept).astype(jnp.int32), *moved],
        dimension=1, is_stable=True, num_keys=1)
    _, *moved = jax.lax.sort(
        [jnp.broadcast_to(place_of[:, None], (n, k)), *moved],
        dimension=0, num_keys=1)
    # slot-major, so that a slot's tokens stand side by side
    places, *gates = (a.T for a in moved)

    def trips(length: int):
        """The loop body of the blocks whose chain is `length` gathers."""
        def trip(i, sums):
            at = i * block
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at, block,
                                                         axis=-1)
            where = cut(places)
            gate = cut(gates[0]) if gates else None
            keeps = cut(counts)
            total = jnp.zeros((block, rows.shape[1]), jnp.float32)
            for j in range(length):
                term = _take_rows(rows, where[j]).astype(jnp.float32)
                if gate is not None:
                    term = term * gate[j][:, None]
                term = jnp.where((keeps > j)[:, None], term, 0.0)
                total = term if j == 0 else total + term
            return jax.lax.dynamic_update_slice(
                sums, total.astype(dtype), (at, 0))
        return trip

    # with the other kernels: imported where it is used (ops/attention.py)
    from megatron_tpu.ops.pallas.grouped_matmul import unwritten_rows

    # the blocks stand by falling count of their first token, so those of
    # one chain are one stretch of them: a loop a chain, each block in
    # exactly one (every row of `sums` is written)
    firsts = counts[::block]
    sums = unwritten_rows((n, rows.shape[1]), dtype, rows)
    for length in range(k + 1):
        sums = jax.lax.fori_loop(
            jnp.sum(firsts > length, dtype=jnp.int32),
            jnp.sum(firsts >= length, dtype=jnp.int32), trips(length), sums)
    return _take_rows(sums, place_of)


def _token_of(order: jnp.ndarray, k: int) -> jnp.ndarray:
    """The token of each row of expert order. lax.div, not `//`: jnp's
    floor division kept XLA from inlining the one-layer scan before its
    first CSE (grouped_matmul.group_visits has the story); the rows are
    not negative, so it floors."""
    return jax.lax.div(order, jnp.asarray(k, order.dtype))


def _walk_live_rows(kept, block: int, body, init):
    """`body(first row, carry)` over the blocks of `block` rows that hold
    the live rows of expert order, the first kept.sum() of them."""
    return jax.lax.fori_loop(
        0, _blocks(jnp.sum(kept, dtype=jnp.int32), block),
        lambda i, carry: body(i * block, carry), init)


@jax.custom_vjp
def rows_to_expert_order(xf, order, inv, kept=None):
    """Token order -> expert order: xs[r] = xf[order[r] / k] for xf [N, h]
    and the permutation (order, inv) of its N*k (token, choice) rows
    (`sort_by_expert`). A gather forward; and since autodiff would
    transpose a gather into a scatter-add whatever its indices, the
    gradient is written out as what it is here: the cotangent's rows
    gathered through `inv`, each token's k summed in float32 and rounded
    once. A share of the experts hands `kept` [N, k], the choices whose
    expert it holds (else None): the others' rows give no gradient, and
    at the row counts `_walk_blocks` names they are not moved either: the
    kept rows are the first kept.sum() of expert order, the gather fills
    the blocks that hold them, and the rows behind are nobody's: what
    the buffer held (`unwritten_rows`; `experts_mlp`, ragged)."""
    tokens = _token_of(order, inv.shape[1])
    blocks = None if kept is None else _walk_blocks(*inv.shape)
    if blocks is None:
        return _take_rows(xf, tokens)
    # with the other kernels: imported where it is used (ops/attention.py)
    from megatron_tpu.ops.pallas.grouped_matmul import unwritten_rows

    block = blocks[0]

    def fill(at, xs):
        rows = _take_rows(xf, jax.lax.dynamic_slice(tokens, (at,), (block,)))
        return jax.lax.dynamic_update_slice(xs, rows, (at, 0))

    return _walk_live_rows(
        kept, block, fill,
        unwritten_rows((tokens.shape[0], xf.shape[1]), xf.dtype, xf))


def _to_expert_fwd(xf, order, inv, kept):
    return rows_to_expert_order(xf, order, inv, kept), (inv, kept)


def _to_expert_bwd(res, dxs):
    inv, kept = res
    return (_sum_of_choices(dxs, inv, dxs.dtype, kept=kept),
            None, None, None)


rows_to_expert_order.defvjp(_to_expert_fwd, _to_expert_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def rows_to_token_order(out, topw, order, inv, dtype, kept=None):
    """Expert order -> token order with the gates: y[n] = sum over j of
    topw[n, j] * out[inv[n, j]] for out [N*k, h] in expert order and the
    gates topw [N, k]; the k terms are weighted and summed in float32 and
    rounded once, to `dtype`. Its gradient holds gathers only:
    d_out[r] = w[r] * dy[order[r] / k] with w the gates in expert order,
    and d_topw[n, j] = <out[inv[n, j]], dy[n]>, taken as the row sums of
    out * dy[order / k] in expert order (the rows d_out reads anyway) and
    moved to token order as N*k scalars. Residuals are out, the gates and
    the permutation: no second copy of the rows. A share of the experts
    hands `kept` [N, k], the choices whose expert it holds (else None):
    the sum is over those, and the others' gates get no gradient; at the
    row counts `_walk_blocks` names both directions move the kept rows
    alone (`_sum_of_kept_choices` forward; backward the blocks of expert
    order that hold them, d_out's rows behind being what out's were:
    nobody's, `experts_mlp` ragged)."""
    return _sum_of_choices(out, inv, dtype, topw, kept)


def _to_token_fwd(out, topw, order, inv, dtype, kept):
    # selective recomputation keeps `out` for the gate gradient, by this
    # name. Named here, on a value that only the backward reads, and not
    # at the product: jax.checkpoint rounds every saved value that the
    # forward reads too (a `reduce_precision`), and behind a Pallas call
    # that is a pass of its own over all N*k rows
    # (not of a share of the experts: its buffer takes every row of the
    # call, several times what the router sends here in the mean, and the
    # backward pass makes the product again: one more `moe_gmm` a layer)
    saved = out if kept is not None else checkpoint_name(out, SAVED_PRODUCT)
    return (rows_to_token_order(out, topw, order, inv, dtype, kept),
            (saved, topw, order, inv, kept))


def _to_token_bwd(dtype, res, dy):
    out, topw, order, inv, kept = res
    if kept is not None:
        # the gates of the choices held elsewhere are zero here, and so is
        # what comes back for them
        topw = jnp.where(kept, topw, 0.0)
    tokens = _token_of(order, inv.shape[1])

    def gates():                                       # in expert order
        return _permute(topw.reshape(-1), inv.reshape(-1))

    def of_rows(tokens, gates, out):
        dy_rows = _take_rows(dy, tokens).astype(jnp.float32)
        return ((dy_rows * gates()[:, None]).astype(out.dtype),
                jnp.sum(out.astype(jnp.float32) * dy_rows, axis=-1))

    blocks = None if kept is None else _walk_blocks(*inv.shape)
    if blocks is None:
        d_out, d_w = of_rows(tokens, gates, out)
    else:
        block, w = blocks[0], gates()

        def fill(at, carry):
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at, block)
            rows, sums = of_rows(cut(tokens), lambda: cut(w), cut(carry[0]))
            return (jax.lax.dynamic_update_slice(carry[0], rows, (at, 0)),
                    jax.lax.dynamic_update_slice(carry[1], sums, (at,)))

        # d_out takes out's place block by block: a block of out is read
        # for the gates' gradient and written over with the rows' (the
        # product is this pass's alone to read, so no second buffer of
        # N*k rows and no fill of one); behind the kept rows d_out keeps
        # what out held there
        d_out, d_w = _walk_live_rows(
            kept, block, fill, (out, jnp.zeros(out.shape[:1], jnp.float32)))
    d_topw = _permute(d_w, order).reshape(topw.shape)  # back to token order
    if kept is not None:
        d_topw = jnp.where(kept, d_topw, 0.0)
    return d_out, d_topw.astype(topw.dtype), None, None, None


rows_to_token_order.defvjp(_to_token_fwd, _to_token_bwd)


# the expert matrices of a layer's moe subtree: the leaves whose gradient a
# grouped product makes, and so the keys a `grad_sink` may hold
EXPERT_MATRICES = ("w_in", "w_out")


def expert_grad_sinks(cfg: ModelConfig, p: Dict[str, Any],
                      num_tokens: int) -> Tuple[str, ...]:
    """The leaves of the moe subtree `p` (one layer's or the stacked
    layers': the last three axes are read) whose gradient `moe_block` can
    sum into a float32 accumulator it is handed as `grad_sink`, in a call
    over num_tokens tokens traced where this is asked: the expert matrices,
    where the dropless block's products are the program's kernels (one
    TPU, rows the tiles divide: `grouped_matmul.takes_sink`, the predicate
    the products themselves go by). Elsewhere none."""
    from megatron_tpu.ops.pallas.grouped_matmul import takes_sink

    if cfg.num_experts is None or cfg.moe_dispatch != "dropless":
        return ()
    rows = num_tokens * cfg.moe_top_k
    if all(takes_sink(rows, *p[name].shape[-2:], cfg.experts_held)
           for name in EXPERT_MATRICES):
        return EXPERT_MATRICES
    return ()


def experts_mlp(cfg: ModelConfig, p: Dict[str, Any], xs: jnp.ndarray,
                group_sizes: jnp.ndarray, expert_of_row, dtype,
                ragged: bool = False, grad_sink=None, of_layer=None):
    """The held experts' MLP over rows xs [R, h] that stand sorted by
    expert: the next group_sizes[g] rows are expert g's, g counting the
    experts whose matrices `p` holds. Returns ([R, h], the sink's stacks).
    The two products are grouped GEMMs over those groups, the activation
    between them in `dtype`. Where they are the program's Pallas kernels
    (one TPU, shapes the tiles divide) the activation and its backward are
    made of the tiles inside the kernels (`grouped_mlp`) and nothing but
    the kernels touches the first product; elsewhere (`lax.ragged_dot`;
    experts with biases, for which `expert_of_row()` gives each row's
    expert; an activation the kernels do not hold) it stands between the
    products as XLA's (`grouped_matmul`, `apply_activation`).

    ragged: the groups may end before the rows do (a share of the experts:
    the rows behind belong to experts held elsewhere, or to none). No
    kernel visits those rows' tiles, so in every product's result (and in
    the gradient of its rows) they hold whatever the buffer held: callers
    read the result's rows through their own `kept` (`rows_to_token_order`,
    `rows_to_expert_order`), and what they hand in behind the groups need
    be no row of anybody's (`rows_to_expert_order` leaves the buffer as
    it found it there where it walks the kept rows alone). What the
    kernels contract over rows
    (`moe_tgmm`, the last group's boundary window) has to stay clear of
    them: the kernels that hold the activation zero both operands' rows
    there; the other form sets the first product's to zero on the way
    into the activation (and so their gradient on the way back), a pass
    over the buffer each.

    grad_sink = (stacks, layer), as moe_block has it: the matrix's
    gradient goes into its stack where it has one.

    of_layer = (w_in's stack, w_out's stack, layer), as moe_block has it:
    where the products are the kernels they read the layer's matrices in
    the stacks (`grouped_mlp_of_layer`), and `p`'s own are not read."""
    # with the other kernels: imported where it is used (ops/attention.py)
    from megatron_tpu.ops.pallas.grouped_matmul import (
        grouped_matmul, grouped_mlp, grouped_mlp_of_layer, visits_for,
    )

    # one visit table for both products and their gradients (None where
    # the products are lax.ragged_dot)
    visits = visits_for(group_sizes, xs.shape[0])
    stacks, layer = ({}, None) if grad_sink is None else grad_sink
    stacks = dict(stacks)

    # where the products are the kernels, the activation (and, of a share,
    # the rows behind the last group) is theirs too: nothing stands between
    # them but the first product. Experts with biases keep the form below.
    if (of_layer is not None and visits is not None
            and "b_in" not in p and "b_out" not in p):
        out = grouped_mlp_of_layer(xs, *of_layer, group_sizes,
                                   cfg.activation, visits=visits)
        if out is not None:
            return out, stacks
    if visits is not None and "b_in" not in p and "b_out" not in p:
        fused = grouped_mlp(
            xs, p["w_in"], p["w_out"], group_sizes, cfg.activation,
            visits=visits, ragged=ragged, save_as=SAVED_PRODUCT,
            sinks=(*(stacks.get(name) for name in EXPERT_MATRICES), layer))
        if fused is not None:
            out, through = fused
            stacks.update((name, stack) for name, stack
                          in zip(EXPERT_MATRICES, through)
                          if stack is not None)
            return out, stacks

    def product(rows, name):
        """rows · p[name] by group; the matrix's gradient goes into
        its stack where it has one."""
        if stacks.get(name) is None:
            return grouped_matmul(rows, p[name], group_sizes,
                                  visits=visits)
        out, stacks[name] = grouped_matmul(
            rows, p[name], group_sizes, visits=visits,
            sink=(stacks[name], layer))
        return out

    hmid = checkpoint_name(product(xs, "w_in"), SAVED_PRODUCT)
    if "b_in" in p:
        # per-row expert bias: gather by the row's expert id
        hmid = hmid + jnp.take(p["b_in"], expert_of_row(), axis=0)
    if ragged:
        grouped = jnp.arange(xs.shape[0]) < jnp.sum(group_sizes)
        hmid = jnp.where(grouped[:, None], hmid, jnp.zeros_like(hmid))
    hmid = apply_activation(cfg.activation, hmid.astype(dtype))
    # (rows_to_token_order keeps this product for the backward)
    out = product(hmid, "w_out")
    if "b_out" in p:
        out = out + jnp.take(p["b_out"], expert_of_row(), axis=0)
    return out, stacks


def moe_block_dropless(
    cfg: ModelConfig,
    p: Dict[str, Any],
    x: jnp.ndarray,      # [B, S, H]
    grad_sink=None,      # ({"w_in", "w_out": f32 [L, E, k, n]}, layer)
    of_layer=None,       # (w_in [L, E, k, n], w_out [L, E, n, k], layer)
    rows_read=None,      # [B] int32: the positions of each row that count
    router=None,         # (router_carry's dict, layer)
):
    """Sort-based dropless dispatch (MegaBlocks-style, TPU form).
    Returns (y [B,S,H], aux loss, load statistic), and with `grad_sink`
    its stacks behind them, handed through (`moe_block` says what for);
    with `router` the carry's dict last, this layer's part written: the
    "mlp" router reads the previous layer's "state" and leaves its own,
    and "load"[layer] is the call's count of choices an expert.

    No token is ever dropped and no [.., E, C] dispatch/combine tensors
    exist: the N*k (token, choice) rows are argsorted by expert, the two
    expert matmuls run as grouped GEMMs over contiguous per-expert row
    spans (grouped_matmul: the program's Pallas kernels on one TPU,
    lax.ragged_dot elsewhere), and the outputs are gathered back through
    the inverse of the sort, weighted by the gates and summed over each
    token's k choices (rows_to_expert_order, rows_to_token_order: gathers
    forward and backward, no scatter). FLOPs are
    exactly N*k MLP rows vs the capacity path's dense O(G*Sg*E*Cg)
    dispatch einsums.

    One chip's share of an expert-parallel layer, run alone
    (cfg.moe_experts_held of a router cfg.num_experts wide; `p` holds that
    many experts' matrices): the router, its k a token, the gates (over
    the token's k choices, as everywhere) and the load-balance statistics
    are over all num_experts; the rows whose expert is held elsewhere sort
    behind the held ones, where no group of the grouped products reaches
    them, and y is the part of the layer's result that the held experts
    give: the parts of all the shares add up to the whole layer's. Nothing
    stands in for the other chips or their rows. The buffer has room for
    all N*k rows still, so no routing leaves a row out; what follows the
    routing is how much of it is walked: the held rows are its first
    sum(group_sizes), the kernels visit those groups alone, and the row
    movements around them fill, read and add block by block over the
    blocks that hold held rows (`_walk_blocks`: from the row count on at
    which a loop's trips cost less than the rows nobody reads).
    The load statistic is then a [4]-vector: behind it the share of the
    N*k rows that went to held experts (HELD_METRIC); the held experts a
    read row reached (`rows_read`, below; zero without the word); and the
    share of the 4 * N * k rows of the four row movements that they moved
    (MOVED_METRIC; 1.0 where they are one pass each).

    rows_read (a serving step's; None: every position counts): row b's
    first rows_read[b] positions are ones whose result somebody reads.
    The others (an idle slot's row of a decode tick, a chunk's padded
    tail) reach no expert: their choices are treated as held elsewhere,
    so they sort behind the groups, no group counts them and their part
    of y is what the dense parts of the layer give (the shared expert).
    The experts' kernels then move the matrices of the experts a counted
    row reached, and no others (grouped_matmul.GroupVisits). The
    positions that count get the bits they get without the word. Of a
    share of the experts the held rows' share is then of the call's N*k
    rows still, those that count and went to held experts over all, and
    the number behind it is how many of the experts held here a counted
    row reached (the serving engine's counters read both, by their
    place).

    This function is the unsharded form: experts replicated, tokens
    unsharded (or sharded in ways the manual path can't host — batch not
    divisible by the batch axes, mesh missing the named axes). Whenever
    the ambient mesh allows, moe_block routes to moe_block_dropless_ep
    instead, whose manual batch axes give the per-shard local sort (no
    batch-axis argsort collectives) and whose expert axis carries the
    explicit dispatch all-to-all.
    """
    b, s, h = x.shape
    N = b * s
    E = cfg.num_experts
    k = cfg.moe_top_k
    xf = x.reshape(N, h)
    share = cfg.holds_expert_share
    held = cfg.experts_held

    carry, at = ({}, None) if router is None else router
    carry = dict(carry)
    if cfg.moe_router_form == "mlp" and "state" not in carry:
        raise NotImplementedError(
            "moe_router_form='mlp' outside the training layer stack "
            "(models/language_model.py run_layers carries the router's "
            "state from layer to layer; a pipeline stage and the serving "
            "steps do not)")
    with jax.named_scope("moe_router"):
        if cfg.moe_router_form == "mlp":
            logits, state = router_mlp(
                p, xf, carry["state"].reshape(N, -1))
            carry["state"] = state.reshape(b, s, -1)
            logits, gates, topw, topi = _route(cfg, p, xf, logits)
        else:
            logits, gates, topw, topi = _route(cfg, p, xf)
        flat_e = topi.reshape(-1)                      # [N*k]
        group_sizes = _expert_counts(flat_e, E)
        if "load" in carry:
            carry["load"] = carry["load"].at[at].set(
                group_sizes.astype(jnp.float32))
        aux, load = _aux_losses(cfg, logits, gates,
                                group_sizes.astype(jnp.float32) / N)
        mine = None
        if share:
            first = cfg.moe_expert_share * held
            mine = (topi >= first) & (topi < first + held)
            # held experts by their place here; the others behind them all
            topi = jnp.where(mine, topi - first, held)
            group_sizes = group_sizes[first:first + held]
        if rows_read is not None:
            read = (jnp.arange(s) < rows_read[:, None]).reshape(N, 1)
            mine = jnp.broadcast_to(read if mine is None else mine & read,
                                    topi.shape)
            topi = jnp.where(mine, topi, held)
            group_sizes = _expert_counts(topi.reshape(-1), held)
        if share:
            load = jnp.stack([
                load, jnp.sum(group_sizes).astype(jnp.float32) / (N * k),
                jnp.zeros((), jnp.float32) if rows_read is None
                else jnp.sum(group_sizes > 0, dtype=jnp.float32),
                moved_rows_share(mine)])

    xe = xf
    if cfg.moe_latent_size is not None:
        with jax.named_scope("moe_latent_in"):
            # the routed experts' width (the router has read the whole u)
            xe = xf @ p["latent_in"]

    with jax.named_scope("moe_dispatch"):
        # the (token, choice) rows sorted by expert, and the way back
        order, inv = sort_by_expert(topi)
        xs = rows_to_expert_order(xe, order, inv, mine)  # [N*k, w] sorted

    with jax.named_scope("moe_experts"):
        out, stacks = experts_mlp(
            cfg, p, xs, group_sizes,
            lambda: jnp.take(jnp.minimum(topi.reshape(-1), held - 1)
                             if mine is not None else flat_e, order),
            x.dtype, ragged=mine is not None, grad_sink=grad_sink,
            of_layer=of_layer)

    with jax.named_scope("moe_combine"):
        # back to token order through the inverse sort; each token's k
        # choices weighted by its gates and summed in float32
        y = rows_to_token_order(out, topw, order, inv, x.dtype, mine)

    if cfg.moe_latent_size is not None:
        with jax.named_scope("moe_latent_out"):
            y = y @ p["latent_out"]
    if cfg.moe_shared_ffn_size is not None:
        with jax.named_scope("moe_shared"):
            # every token's, whole on every chip of a share: a chip
            # computes it for its own tokens
            y = y + apply_activation(
                cfg.activation, xf @ p["shared_in"]) @ p["shared_out"]
    y = y.reshape(b, s, h)
    return ((y, aux, load) + (() if grad_sink is None else (stacks,))
            + (() if router is None else (carry,)))


def _excl_cumsum(x, axis=0):
    return jnp.cumsum(x, axis=axis) - x


def _use_ragged_transport() -> bool:
    """ragged_all_to_all has no XLA:CPU thunk; tests monkeypatch this to
    force the ragged path through an emulated primitive (so its metadata
    and custom VJP are CI-covered before the one-shot TPU window)."""
    return jax.default_backend() == "tpu"


def _ep_metadata(counts, me, ep: int, El: int, R: int):
    """All transfer bookkeeping for the expert all-to-all, derived from the
    all-gathered per-(source shard, global expert) counts matrix.

    counts: [ep, E] rows source i holds for global expert e (every shard
    computes the identical matrix, so offsets agree without negotiation).
    Expert shard j owns the contiguous global-expert block [j*El, (j+1)*El).
    Chunks land on the receiver packed in source order; when the receive
    buffer R is smaller than worst case, the clamp is greedy in source
    order (first-come slots, the same priority rule capacity dispatch
    applies token-order within an expert)."""
    SS = counts.reshape(ep, ep, El).sum(-1)        # [src, dst] row counts
    before = _excl_cumsum(SS, axis=0)              # rows ahead of src i on dst j
    kept = jnp.clip(R - before, 0, SS)             # greedy receive clamp
    off_on_dst = jnp.minimum(before, R)            # chunk start of src i on dst j
    src_in_off = _excl_cumsum(SS, axis=1)          # span starts in src i's sorted rows
    return {
        "SS": SS, "kept": kept,
        "in_off": src_in_off[me],                  # my span starts      [ep]
        "send": kept[me],                          # rows I send dst j   [ep]
        "out_off": off_on_dst[me],                 # where they land     [ep]
        "recv": kept[:, me],                       # rows I get from i   [ep]
        "recv_off": off_on_dst[:, me],             # where I put them    [ep]
        "back_off": src_in_off[:, me],             # src i's own offset of the
                                                   # chunk it sent me (return trip)
    }


def _dense_exchange(rows, out_len, dst_off, src_rows, valid, axis_name):
    """Transport fallback: all_gather over the expert axis + gather
    reconstruction. Works on every backend (XLA:CPU has no
    ragged-all-to-all thunk) and differentiates through standard
    transpose rules; the TPU fast path is _ragged_exchange below.

    rows: [m, h] local payload. For output slot r (< out_len):
    take gathered[dst_off[r] == source shard, src_rows[r]] when valid[r].
    """
    g = jax.lax.all_gather(rows, axis_name)        # [ep, m, h]
    flat = g.reshape(-1, rows.shape[-1])
    picked = jnp.take(flat, dst_off * rows.shape[0] + src_rows, axis=0)
    return jnp.where(valid[:, None], picked, jnp.zeros_like(picked))


def _ragged_exchange(rows, out_len, in_off, send, out_off, recv,
                     bwd_meta, axis_name):
    """jax.lax.ragged_all_to_all with a custom VJP: the gradient of an
    exchange is the mirrored exchange (dispatch <-> return metadata), so
    no transpose rule for the primitive is needed. TPU-only (see
    _dense_exchange); exercised on hardware, not in CPU CI."""
    import numpy as np

    f0 = jax.dtypes.float0

    @jax.custom_vjp
    def ex(r, i_off, s, o_off, rv, bm):
        out = jnp.zeros((out_len, r.shape[-1]), r.dtype)
        # jaxlint: disable=banned-api - TPU-only path gated behind
        # _use_ragged_transport(); CPU/CI takes _dense_exchange
        return jax.lax.ragged_all_to_all(
            r, out, i_off.astype(jnp.int32), s.astype(jnp.int32),
            o_off.astype(jnp.int32), rv.astype(jnp.int32),
            axis_name=axis_name)

    def fwd(r, i_off, s, o_off, rv, bm):
        return ex(r, i_off, s, o_off, rv, bm), (r.shape[0], bm)

    def bwd(res, g):
        n_in, bm = res
        b_in_off, b_send, b_out_off, b_recv = bm
        gout = jnp.zeros((n_in, g.shape[-1]), g.dtype)
        # jaxlint: disable=banned-api - mirrored exchange of the gated
        # TPU-only forward above; CPU/CI never traces this VJP
        gr = jax.lax.ragged_all_to_all(
            g, gout, b_in_off.astype(jnp.int32), b_send.astype(jnp.int32),
            b_out_off.astype(jnp.int32), b_recv.astype(jnp.int32),
            axis_name=axis_name)
        z = lambda a: np.zeros(a.shape, f0)  # int metadata: zero cotangents
        return (gr, z(b_in_off), z(b_send), z(b_out_off), z(b_recv),
                tuple(z(a) for a in bm))

    ex.defvjp(fwd, bwd)
    return ex(rows, in_off, send, out_off, recv, bwd_meta)


def moe_block_dropless_ep(
    cfg: ModelConfig,
    p: Dict[str, Any],
    x: jnp.ndarray,      # [B, S, H] (GSPMD view; B sharded over (data, expert))
    mesh,
    ep: int,
    include_data: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dropless dispatch composed with expert parallelism. Returns (y,
    aux loss, load statistic).

    shard_map over the expert axis only (data/context/tensor stay GSPMD):
    each shard sorts its LOCAL (token, choice) rows by global expert,
    exchanges rows with the shard owning each expert over an explicit
    expert-axis all-to-all, runs the two lax.ragged_dot grouped GEMMs over
    its E/ep local experts, and returns outputs along the mirrored route;
    gates weight the rows back home (so router grads never cross the
    a2a). Aux-loss statistics are pmean'd over the expert axis before the
    loss formula — exactly the global mean.

    Receive buffer: R = ceil(n*k*f) rows with f = cfg.moe_ep_buffer_factor
    (None => f = ep: mathematically dropless for ANY routing, the default;
    memory/FLOPs per shard then match the ep=1 sorted array, with expert
    WEIGHTS sharded E/ep). Smaller f scales FLOPs/memory by f/ep at the
    cost of greedy source-order drops when one shard's experts attract
    more than f x fair-share rows — the same failure semantics as
    capacity dispatch, at shard granularity. The rows of the slack tail
    belong to no group of the grouped GEMMs and come back zero
    (experts_mlp).

    Transport is ragged_all_to_all on TPU; CPU (and therefore CI) uses an
    all_gather reconstruction with identical math — the ragged path is on
    the on-device capture list.

    The grouped GEMMs here stay lax.ragged_dot, not grouped_matmul's
    Pallas kernels: this shard_map names only the expert (and data) axes,
    and a Mosaic kernel lowers only under ONE shard_map naming every mesh
    axis (PR 21; ops/attention.py _shard_plan). This form has never run on
    the chip and no benchmark cell reaches it: ROADMAP S6's four-chip
    follow-up.

    include_data: also make the DATA axis manual (tokens divide data x
    expert). The sort, the counts and the row gathers then run per-shard
    with no batch-axis collectives — the "local-sort form" the ep=1 docstring
    names as the known GSPMD-argsort fix — and the expert exchange stays
    within each data slice. Requires B % (data*ep) == 0 (the caller
    guards); the context/tensor axes stay auto by design (tensor carries
    the in-expert TP GEMM sharding GSPMD already handles).
    """
    from jax.sharding import PartitionSpec as P

    from megatron_tpu.parallel.mesh import AXIS_DATA, AXIS_EXPERT

    E = cfg.num_experts
    k = cfg.moe_top_k
    El = E // ep
    f = cfg.moe_ep_buffer_factor
    f = float(ep) if f is None else min(float(f), float(ep))
    has_b = "b_in" in p

    def local_fn(xb, router, w_in, w_out, b_in, b_out):
        b, s, h = xb.shape
        n = b * s
        nk = n * k
        R = int(math.ceil(nk * f))
        me = jax.lax.axis_index(AXIS_EXPERT)
        xf = xb.reshape(n, h)

        logits, gates, topw, topi = _route(cfg, {"router": router}, xf)

        # local sort by global expert id
        flat_e = topi.reshape(-1)
        order, inv = sort_by_expert(topi)
        xs = rows_to_expert_order(xf, order, inv)     # [nk, h]
        my_counts = _expert_counts(flat_e, E)
        counts = jax.lax.all_gather(my_counts, AXIS_EXPERT)   # [ep, E]
        md = _ep_metadata(counts, me, ep, El, R)

        # ---- dispatch: send each expert's rows to its owner ----------
        use_ragged = _use_ragged_transport()
        if use_ragged:
            recv_buf = _ragged_exchange(
                xs, R, md["in_off"], md["send"], md["out_off"], md["recv"],
                (md["recv_off"], md["recv"], md["back_off"], md["send"]),
                AXIS_EXPERT)
        else:
            idx = jnp.arange(R)
            src = jnp.searchsorted(md["recv_off"], idx, side="right") - 1
            src_row = md["back_off"][src] + (idx - md["recv_off"][src])
            valid = idx < md["recv"].sum()
            recv_buf = _dense_exchange(xs, R, src, src_row, valid,
                                       AXIS_EXPERT)

        # ---- local-expert ids for each received row, from the counts
        # matrix (no id payload travels): span starts/ends per
        # (source, local expert) are clamped to what the source actually
        # got to send; a +/- delta scatter + cumsum paints the ids, with
        # gaps (the slack tail) to the trash id El -------------------
        Cm = jax.lax.dynamic_slice_in_dim(counts, me * El, El, axis=1)
        rel = _excl_cumsum(Cm, axis=1)
        starts = md["recv_off"][:, None] + jnp.minimum(rel, md["recv"][:, None])
        ends = md["recv_off"][:, None] + jnp.minimum(rel + Cm,
                                                     md["recv"][:, None])
        evals = jnp.tile(jnp.arange(El, dtype=jnp.int32), (ep, 1)) + 1
        delta = (jnp.zeros(R + 1, jnp.int32)
                 .at[starts.ravel()].add(evals.ravel())
                 .at[ends.ravel()].add(-evals.ravel()))
        run = jnp.cumsum(delta[:-1])
        ids = jnp.where(run > 0, run - 1, El)

        # ---- the local experts' MLP over the received rows sorted by
        # local expert, the slack tail behind them (experts_mlp: what a
        # share of the experts runs on one chip without the exchange) ---
        order2 = jnp.argsort(ids, stable=True)
        xs2 = jnp.take(recv_buf, order2, axis=0)
        ids2 = jnp.take(ids, order2)
        gsz = jnp.bincount(ids2, length=El + 1).astype(jnp.int32)[:El]
        local = {"w_in": w_in, "w_out": w_out}
        if has_b:
            local.update(b_in=b_in, b_out=b_out)
        out2, _ = experts_mlp(cfg, local, xs2, gsz,
                              lambda: jnp.minimum(ids2, El - 1), xb.dtype,
                              ragged=True)
        out_rows = (jnp.zeros((R, h), out2.dtype).at[order2].set(out2))

        # ---- return trip along the mirrored route --------------------
        if use_ragged:
            back = _ragged_exchange(
                out_rows, nk, md["recv_off"], md["recv"], md["back_off"],
                md["send"],
                (md["in_off"], md["send"], md["out_off"], md["recv"]),
                AXIS_EXPERT)
        else:
            t = jnp.arange(nk)
            dst = jnp.searchsorted(md["in_off"], t, side="right") - 1
            pos = t - md["in_off"][dst]
            sent = pos < md["send"][dst]
            back = _dense_exchange(out_rows, nk, dst,
                                   md["out_off"][dst] + pos, sent,
                                   AXIS_EXPERT)

        # ---- combine at home: gates weight the returned rows ---------
        y = rows_to_token_order(back, topw, order, inv, xb.dtype)

        stat_axes = ((AXIS_DATA, AXIS_EXPERT) if include_data
                     else AXIS_EXPERT)
        frac = jax.lax.pmean(my_counts.astype(jnp.float32) / n, stat_axes)
        prob = jax.lax.pmean(jnp.mean(gates, axis=0), stat_axes)
        z_sq = jax.lax.pmean(
            jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2), stat_axes)
        aux, load = _aux_from_stats(cfg, frac, prob, z_sq)
        return y.reshape(b, s, h), aux, load

    zeros_b = jnp.zeros((E, 0), x.dtype)
    batch_axes = (AXIS_DATA, AXIS_EXPERT) if include_data else AXIS_EXPERT
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, None),
                  P(AXIS_EXPERT, None, None), P(AXIS_EXPERT, None, None),
                  P(AXIS_EXPERT, None), P(AXIS_EXPERT, None)),
        out_specs=(P(batch_axes, None, None), P(), P()),
        axis_names={AXIS_DATA, AXIS_EXPERT} if include_data
        else {AXIS_EXPERT},
        check_vma=False,
    )
    return fn(x, p["router"], p["w_in"], p["w_out"],
              p.get("b_in", zeros_b), p.get("b_out", zeros_b))


def _ambient_batch_axes() -> Tuple[int, int, bool]:
    """(data size, expert size, both-axes-present) for the ambient mesh.
    The presence flag guards out-of-tree meshes missing one of the named
    batch axes — the shard_map path references BOTH axis names, so it
    must not be entered on such a mesh (build_mesh always creates all
    five)."""
    from megatron_tpu.parallel.mesh import (AXIS_DATA, AXIS_EXPERT,
                                            ambient_mesh_shape)

    shape = ambient_mesh_shape()
    both = AXIS_DATA in shape and AXIS_EXPERT in shape
    return shape.get(AXIS_DATA, 1), shape.get(AXIS_EXPERT, 1), both


def _rows_read_dropped(rows_read, form: str) -> None:
    """Said once a trace: this form of the block computes every row."""
    if rows_read is not None:
        warnings.warn(
            f"moe_block ({form}): the serving step's rows that nobody "
            "reads (idle slots, a chunk's padding) are routed like the "
            "others; only the unsharded dropless block leaves them out",
            stacklevel=3)


def moe_block(
    cfg: ModelConfig,
    p: Dict[str, Any],   # one layer's moe subtree: router, w_in, w_out (+biases)
    x: jnp.ndarray,      # [B, S, H]
    grad_sink=None,
    of_layer=None,
    rows_read=None,
    router=None,
):
    """Returns (y [B,S,H], aux loss, load statistic), both fp32 scalars.

    router = (carry, layer): what the expert layers carry from one to the
    next (`router_carry`) and this layer's index among them; it comes
    back last, this layer's part written (moe_block_dropless). Given for
    a model that has such a carry, whose layers are the unsharded
    dropless block's alone.

    rows_read [B] (a serving step's, else None): how many of each row's
    positions somebody reads. The unsharded dropless block routes those
    alone (moe_block_dropless); the capacity form and the form under an
    expert-parallel mesh compute every row, as without the word.

    of_layer = (w_in's stack, w_out's stack, layer): the stacked layers'
    expert matrices this layer's are part of, [L, E, ...], and its index
    in them, from a caller that makes no gradient (a serving step). The
    dropless block's kernels then read the layer's matrices where they
    lie in the stacks, which `p`'s own, sliced out of them in front of a
    kernel's call, would be copied for. The result is the same.

    grad_sink = (stacks, layer): where the gradients of this layer's
    expert matrices are to be summed, for a step that accumulates them
    over micro-batches. stacks holds, for names `expert_grad_sinks` gave,
    the float32 accumulator of every layer's matrix, [L, E, k, n]. They
    come back as a fourth result, as they went in: they are there for
    their cotangents, which answer a running sum with the sum plus this
    call's gradient at [layer], added by the kernel that makes it
    (`grouped_matmul`'s `sink`); the matrices' own cotangents are then
    zero."""
    if grad_sink is not None:
        # expert_grad_sinks names a leaf only where this form runs
        return moe_block_dropless(cfg, p, x, grad_sink, router=router)
    if (cfg.holds_expert_share or cfg.moe_latent_size is not None
            or cfg.moe_shared_ffn_size is not None
            or cfg.moe_router_score != "softmax" or router is not None):
        # one chip's share runs without the exchange, whatever the mesh;
        # the sigmoid router, the latent projections, the shared expert
        # and what the layers carry between them are this form's alone
        return moe_block_dropless(cfg, p, x, of_layer=of_layer,
                                  rows_read=rows_read, router=router)
    if cfg.moe_dispatch == "dropless":
        dsz, ep, named_axes = _ambient_batch_axes()
        # manual data axis (per-shard local sort, no batch-axis argsort
        # collectives) whenever the batch divides it; ep > 1 takes the
        # exchange path whenever the batch divides the expert axis.
        # Batches that divide neither (single-row decode on an ep mesh)
        # fall back to the GSPMD form — correct against expert-sharded
        # weights (the partitioner gathers them), just not manual.
        # mesh=None: shard_map uses the ambient mesh the sizes were just
        # read from.
        include_data = dsz > 1 and x.shape[0] % (dsz * ep) == 0
        ep_ok = ep > 1 and x.shape[0] % ep == 0
        if named_axes and (ep_ok or include_data):
            _rows_read_dropped(rows_read, "under an expert-parallel mesh")
            return moe_block_dropless_ep(cfg, p, x, None, ep,
                                         include_data=include_data)
        return moe_block_dropless(cfg, p, x, of_layer=of_layer,
                                  rows_read=rows_read)
    _rows_read_dropped(rows_read, "moe_dispatch='capacity'")
    b, s, h = x.shape
    N = b * s
    # group tokens GShard-style; Sg must divide the *runtime* S (decode
    # steps and bucketed prefill call with S != cfg.seq_length) — re-pick
    # the largest runtime divisor under the configured group size rather
    # than jumping straight to quadratic whole rows
    Sg = _group_for(s, moe_group_size(cfg))
    G = N // Sg
    xg = x.reshape(G, Sg, h)

    logits = jnp.einsum("gsh,he->gse", xg.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)

    C = moe_capacity(cfg, Sg)
    combine, dispatch, chosen = jax.vmap(
        lambda g: topk_dispatch(g, cfg.moe_top_k, C, cfg.moe_renorm_gates)
    )(gates)                                     # [G, Sg, E, C] / [G, Sg, E]

    # load balance over all k choices + router z-loss, global over N
    aux, load = _aux_losses(cfg, logits, gates,
                            jnp.mean(chosen, axis=(0, 1)))

    # dispatch -> per-(group, expert) batches -> combine, all as einsums
    xe = jnp.einsum("gsec,gsh->gech", dispatch.astype(x.dtype), xg)
    hmid = jnp.einsum("gech,ehf->gecf", xe, p["w_in"])
    if "b_in" in p:
        hmid = hmid + p["b_in"][None, :, None, :]
    hmid = apply_activation(cfg.activation, hmid)
    out = jnp.einsum("gecf,efh->gech", hmid, p["w_out"])
    if "b_out" in p:
        out = out + p["b_out"][None, :, None, :]
    y = jnp.einsum("gsec,gech->gsh", combine.astype(x.dtype), out)
    return y.reshape(b, s, h), aux, load
