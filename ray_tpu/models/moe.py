"""Mixture-of-experts MLP with static capacity-based dispatch, TPU-first.

The reference has no MoE implementation (vLLM-internal only; SURVEY.md §2.3 row
"Expert parallel (EP/MoE): absent — must be built natively"). This is the
GShard/Switch dispatch pattern expressed as einsums over one-hot dispatch masks:
every shape is static (tokens × experts × capacity), so XLA tiles the expert
matmuls onto the MXU and GSPMD turns the "expert" axis sharding ("ep" mesh axis)
into all-to-alls on ICI — no ragged host-side routing.

Capacity semantics: tokens are processed in fixed-size groups (GShard-style, so
dispatch memory stays linear in sequence length); within a group each expert
takes at most C = ceil(capacity_factor · k · g / E) tokens. An overflow slot is
dropped for that expert and its gate weight is simply lost — the token's MLP
output is underweighted by that fraction (no renormalization over survivors).
With top_k=1 the raw router probability gates the output (Switch), keeping the
router differentiable through the task loss; with top_k>1 the top-k gate values
renormalize to sum to 1 (Mixtral convention).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.quant import as_weight as _qw
from ray_tpu.parallel.sharding import with_sharding_constraint as wsc

from .config import ModelConfig

# Tokens per dispatch group: dispatch/combine tensors are [g, E, C] with C ∝ g/E,
# so per-group memory is O(g²) and total is O(T·g) — bounded, unlike one [T, E, C]
# block whose memory grows as O(T²).
def _moe_group_size() -> int:
    from ray_tpu.config import CONFIG

    return CONFIG.moe_group_size


def expert_capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(cfg.moe_capacity_factor * cfg.moe_top_k * n_tokens / cfg.n_experts) + 1
    return max(4, min(c, n_tokens))


def _group_size(t: int) -> int:
    """Largest divisor of t that is <= the group-size flag (static shapes)."""
    cap = _moe_group_size()
    if t <= cap:
        return t
    for g in range(cap, 0, -1):
        if t % g == 0:
            return g
    return t


def _moe_group(x, mask, router_w, w_gate, w_up, w_down, cfg: ModelConfig):
    """Dispatch one token group. x [g, D]; mask [g] 1.0=real token, 0.0=pad/inactive."""
    g, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    c = expert_capacity(cfg, g)
    dt = x.dtype

    logits = jnp.einsum("td,de->te", x, _qw(router_w, dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # [g, E]

    gate_vals, gate_idx = jax.lax.top_k(probs, k)  # [g, k]
    if k > 1 or cfg.moe_top1_renorm:
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    # k == 1 without moe_top1_renorm: raw top-1 prob gates the output (Switch) so
    # the router receives task-loss gradient; renormalizing pins the gate to 1.0
    # (Mixtral inference semantics — set by config_from_hf for HF checkpoints).

    # Position of each (token, slot) within its expert's capacity. Slot-major order
    # (all top-1 picks get priority over top-2 picks, GShard convention). Masked
    # tokens (padding, inactive decode slots) never claim capacity.
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32) * mask[:, None, None]
    slot_major = onehot.transpose(1, 0, 2).reshape(k * g, e)  # [k*g, E]
    pos_flat = jnp.cumsum(slot_major, axis=0) - slot_major  # rank among same-expert picks
    pos = pos_flat.reshape(k, g, e).transpose(1, 0, 2)  # [g, k, E]
    keep = (pos < c) * onehot  # drop overflow beyond capacity

    # dispatch/combine tensors
    pos_idx = jnp.minimum(pos.astype(jnp.int32), c - 1)
    pos_onehot = jax.nn.one_hot(pos_idx, c, dtype=jnp.float32)  # [g, k, E, C]
    dispatch = jnp.einsum("tke,tkec->tec", keep, pos_onehot)  # [g, E, C] 0/1
    combine = jnp.einsum("tk,tke,tkec->tec", gate_vals, keep, pos_onehot)

    # route tokens to expert buffers, run experts, route back
    xin = jnp.einsum("tec,td->ecd", dispatch.astype(dt), x)  # [E, C, D]
    xin = wsc(xin, "act_expert", None, "act_embed")
    gate = jnp.einsum("ecd,edf->ecf", xin, _qw(w_gate, dt))
    up = jnp.einsum("ecd,edf->ecf", xin, _qw(w_up, dt))
    act = wsc(jax.nn.silu(gate) * up, "act_expert", None, "act_mlp")
    out = jnp.einsum("ecf,efd->ecd", act, _qw(w_down, dt))  # [E, C, D]
    y = jnp.einsum("tec,ecd->td", combine.astype(dt), out)  # [g, D]

    # load-balancing loss (Switch eq. 4) over real tokens only: E * sum_e f_e * P_e
    denom = jnp.maximum(mask.sum(), 1.0)
    me = (probs * mask[:, None]).sum(axis=0) / denom
    ce = (keep.sum(axis=1)).sum(axis=0) / denom
    aux = (me * ce).sum() * e * cfg.moe_aux_loss_coef
    return y, aux


def moe_mlp(
    x: jax.Array,  # [T, D] tokens
    router_w: jax.Array,  # [D, E]
    w_gate: jax.Array,  # [E, D, F]
    w_up: jax.Array,  # [E, D, F]
    w_down: jax.Array,  # [E, F, D]
    cfg: ModelConfig,
    mask: Optional[jax.Array] = None,  # [T] 1.0 = real token
) -> Tuple[jax.Array, jax.Array]:
    """Returns ([T, D] output, scalar load-balancing aux loss)."""
    t, d = x.shape
    if mask is None:
        mask = jnp.ones((t,), jnp.float32)
    mask = mask.astype(jnp.float32)
    g = _group_size(t)
    if g == t:
        return _moe_group(x, mask, router_w, w_gate, w_up, w_down, cfg)
    xg = x.reshape(t // g, g, d)
    mg = mask.reshape(t // g, g)
    yg, auxg = jax.vmap(
        lambda xi, mi: _moe_group(xi, mi, router_w, w_gate, w_up, w_down, cfg)
    )(xg, mg)
    return yg.reshape(t, d), auxg.mean()


# ------------------------------------------------------------- the dropless layer
# cfg.moe_dropless (no capacity factor): every assignment is served. The layer is told
# which experts it holds (cfg.experts_held = (index, of)): it routes over all
# cfg.n_experts, sorts the step's tokens x k assignments by expert, keeps those that fall
# on held experts and computes their part of the result with grouped products over the
# ragged groups (jax.lax.ragged_dot: the TPU compiler has a kernel of its own for it,
# forward and both transposes, whose work follows the rows the groups hold). What the
# experts held elsewhere would add is left out: on one chip the layer runs without its
# exchange, and nothing here stands in for it.
#
# Shapes are static and every assignment CAN fall on held experts, but a layer that holds
# a share of them expects that share of the rows. So the routed path works on a buffer of
# `window_rows` rows, twice what the held experts can expect, and stays dropless at any
# load by walking the sorted held assignments in windows of that many rows, as many as
# the step's load needs: one at any load a balanced router produces, tokens x k over the
# window's rows at most. Inside a window the held rows lie first, grouped, and the rows
# behind them belong to no group.


# What the router made in the forward pass, by the names every remat policy keeps it
# under (`llama._maybe_remat`): the experts chosen, every score, the chosen scores
# (`route`), and the count of the choice an expert (`expert_layer`).
CHOSEN_NAME = "experts_chosen"
SCORES_NAME = "router_scores"
PICKED_NAME = "router_picked"
LOAD_NAME = "router_load"
ROUTER_NAMES = (CHOSEN_NAME, SCORES_NAME, PICKED_NAME, LOAD_NAME)
KEPT = {"every": ROUTER_NAMES}  # what llama.py's table of layer kinds reads of a part (its docstring)

# Rows of a tile of the TPU compiler's grouped kernels (`ragged-dot-none`: its metadata
# has a tile for every 512 rows of the buffer and one more a group); a window is whole tiles.
_ROW_TILE = 512


def route(x: jax.Array, router_w: jax.Array, bias: Optional[jax.Array], cfg: ModelConfig):
    """x [T, D] -> (experts chosen [T, k] int32, their gates [T, k] f32), as glm4_moe_lite
    states it. Sigmoid scores in float32 (cfg.moe_scoring "softmax", sdar_moe's: a softmax
    over ALL experts, with no bias), products at the highest precision (2 % of a
    layer's operations); the k largest of score + bias are chosen; the gates are the
    chosen SCORES (the bias selects and never weights), normalised over the k, times
    moe_route_scale. (Against a float32 reference 2 % of tokens choose another expert at
    8,192 positions in bfloat16, all by the activations' rounding: a bfloat16 router
    chose the same experts to the token on the chip, PERF.md section 6, PR 31.)

    Scoring and choosing have a backward rule of their own (`_score_and_pick`), which
    keeps what the forward pass made, under `ROUTER_NAMES`: the choice, the scores
    [T, E] and the chosen scores [T, k]. The choice is a decision, not arithmetic: a
    forward pass recomputed in the backward pass rounds its bfloat16 activations
    otherwise where XLA fuses it otherwise, and 0.5 % of the MTP block's assignments,
    two scores within a rounding, then went to other experts in the gradient than in
    the loss (its experts' gradients were 6-8 % off on the chip, PERF.md section 6,
    PR 31). The scores are kept so that the gradient weighs what the loss weighed and
    the backward pass of a rematerialised layer runs no score product, sigmoid, `top_k`
    or pick again (with the pick's gradient in one pass, 8 of the router's 24.5 ms a step
    at 22 of 512: PERF.md section 6, PR 36).
    A name on the scores alone does not do that under plain differentiation:
    `jax.nn.sigmoid`'s own derivative rule keeps ITS output, the value before the name,
    which no policy can save, and the product is made again for it."""
    if cfg.moe_scoring not in ("sigmoid", "softmax"):
        raise NotImplementedError(f"the dropless layer scores by sigmoid or softmax, not {cfg.moe_scoring!r}")
    if cfg.moe_scoring == "softmax" and bias is not None:
        raise NotImplementedError(
            "softmax scores with a selection bias: sdar_moe, the family that scores by softmax over all "
            "experts, chooses by the scores alone (`bias=None`)")
    if cfg.moe_n_group > 1:
        raise NotImplementedError(
            f"group-limited routing (n_group {cfg.moe_n_group}): the choice is over all experts at once")
    if not cfg.moe_norm_topk:
        raise NotImplementedError(
            "gates that are not normalised over the chosen experts (norm_topk_prob false)")
    idx, gates = _score_and_pick(x, router_w, bias, cfg.moe_top_k, cfg.moe_scoring)
    gates = gates / (gates.sum(-1, keepdims=True) + cfg.moe_gate_eps)
    return idx, gates * cfg.moe_route_scale


# A mask of [T, k, E] elements is one fused pass where it is small (4 of 64 at 8,192
# tokens: 2 M) and not where 22 are chosen of 512 (92 M, 369 MB in float32, a layer):
# beyond this many elements the k slots are taken one at a time, [T, E] each, and no
# operand of the program has all three extents.
_MASK_ELEMENTS = 1 << 22


def _chosen_scores(scores: jax.Array, idx: jax.Array) -> jax.Array:
    """scores [T, E], idx [T, k] -> the chosen scores [T, k], picked by a mask (the
    forward pass only: `_score_and_pick`'s backward rule writes the transpose itself)."""
    lanes = jnp.arange(scores.shape[-1])
    if idx.size * scores.shape[-1] <= _MASK_ELEMENTS:
        return jnp.sum(scores[:, None, :] * (idx[..., None] == lanes), -1)
    _, picked = jax.lax.scan(
        lambda _, slot: (None, jnp.sum(jnp.where(slot[:, None] == lanes, scores, 0), -1)), None, idx.T)
    return picked.T


def _router_product(eq: str, a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.einsum(eq, a.astype(jnp.float32), b.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _score_and_pick_fwd(x, router_w, bias, k: int, scoring: str = "sigmoid"):
    logits = _router_product("td,de->te", x, router_w)
    scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(scores if bias is None else scores + bias[None, :], k)
    idx = checkpoint_name(idx.astype(jnp.int32), CHOSEN_NAME)
    scores = checkpoint_name(scores, SCORES_NAME)
    picked = checkpoint_name(_chosen_scores(scores, idx), PICKED_NAME)
    return (idx, picked), (x, router_w, scores, idx)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _score_and_pick(x, router_w, bias, k: int, scoring: str = "sigmoid"):
    """x [T, D], router_w [D, E], bias [E] or None -> (the k experts of the largest
    score + bias [T, k] int32, their scores [T, k] f32); `scoring`: sigmoid an expert, or
    softmax over all of them."""
    return _score_and_pick_fwd(x, router_w, bias, k, scoring)[0]


def _score_and_pick_bwd(k, scoring, kept, cotangents):
    """The pick's transpose in one pass: a token's k experts are distinct, so a lane of
    its row of d_scores takes its value from at most one slot (a select a slot over
    [T, E], fused; no loop, no accumulator, no scatter); then the sigmoid's derivative
    (the softmax's: s (d_s - sum(d_s s)) a token) from the kept scores and the two
    products. The bias selects: its gradient is zero."""
    x, router_w, scores, idx = kept
    d_picked = cotangents[1]
    lanes = jnp.arange(scores.shape[-1])
    d_scores = jnp.zeros_like(scores)
    for slot in range(k):
        d_scores = jnp.where(idx[:, slot, None] == lanes, d_picked[:, slot, None], d_scores)
    # written once ([T, E] f32, 0.11 ms at 22 of 512): left to itself XLA fuses the k
    # selects into BOTH products as their producer and makes them again for every tile
    # of each product's other extent (+0.39 and +0.20 ms a layer on the chip, PERF.md
    # section 6, PR 36)
    if scoring == "sigmoid":
        d_logits = jax.lax.optimization_barrier(d_scores * scores * (1 - scores))
    else:
        d_logits = jax.lax.optimization_barrier(
            scores * (d_scores - jnp.sum(d_scores * scores, axis=-1, keepdims=True)))
    dx = _router_product("te,de->td", d_logits, router_w).astype(x.dtype)
    dw = _router_product("td,te->de", x, d_logits).astype(router_w.dtype)
    return dx, dw, None


_score_and_pick.defvjp(_score_and_pick_fwd, _score_and_pick_bwd)


def expert_load(idx: jax.Array, n_experts: int) -> jax.Array:
    """idx [T, k] -> assignments an expert [n_experts] f32 (the step's counter)."""
    lanes = jnp.arange(n_experts)
    if idx.size * n_experts <= _MASK_ELEMENTS:
        return (idx[..., None] == lanes).sum((0, 1), dtype=jnp.float32)
    load, _ = jax.lax.scan(
        lambda acc, slot: (acc + (slot[:, None] == lanes).sum(0, dtype=jnp.float32), None),
        jnp.zeros((n_experts,), jnp.float32), idx.T)
    return load


def held_range(cfg: ModelConfig) -> Tuple[int, int]:
    """[lo, hi) of the experts this layer holds."""
    index, of = cfg.experts_held
    n = cfg.n_experts // of
    return index * n, (index + 1) * n


def window_rows(cfg: ModelConfig, tokens: int) -> int:
    """Rows of the routed path's buffer for `tokens` tokens: twice what the held experts
    can expect of the tokens x k assignments (at uniform routing the held rows of the
    benchmark's cell are 4,096 +- 60 of 32,768, so only a router far out of balance walks a
    second window), in whole tiles of the grouped product, tokens x k at most. A layer
    that holds every expert, or half of them, gets tokens x k: one window, statically."""
    n = tokens * cfg.moe_top_k
    return min(n, -(-2 * n // (cfg.experts_held[1] * _ROW_TILE)) * _ROW_TILE)


def windows_walked(held_rows: jax.Array, rows: int) -> jax.Array:
    """Windows of `rows` rows the layer walks for a load of `held_rows` (int32): the
    first always, one more for every `rows` rows begun beyond it."""
    return jnp.maximum(-(-held_rows // rows), 1)


# A window is rows [start, start + rows) of the sorted order. `_take` brings a token's (or
# an assignment's, k = 1) values to the window's rows, `_put` sums a window's rows back
# onto their tokens; each is the other's transpose, and neither direction scatters (a
# scatter-add serialises on the TPU): assignment a belongs to token a // k and lies at
# inverse[a] in the sorted order. `_take` is a gather of the window's rows. `_put` has
# two forms, and `combine_from_rows` says from the static shapes which one a layer runs:
# from the tokens' side a gather a slot, k gathers of `tokens` rows each, all but
# rows / (tokens x k) of them clipped and zeroed; from the window's side one grouped
# product over the window's rows, whose work follows the rows and not tokens x k. Each
# names its own scope, as the kind of pass it is: JAX drops the caller's `named_scope`s
# from what a `custom_vjp` inside a `custom_vjp` (`_walk`) traces, and the trace's share
# of the expert layer is read by these names (the grouped product is a kernel of the TPU
# compiler's, `ragged-dot-none`, and a kernel carries no scope at all).


def combine_from_rows(tokens: int, k: int, rows: int) -> bool:
    """Whether `_put` sums a window of `rows` rows onto `tokens` tokens from the window's
    side (`_sum_from_rows`) or from the tokens' (a gather a slot): from the window's side
    where the window holds a quarter of the tokens x k assignments or less, which is every
    layer that holds an eighth of its experts or less (`window_rows`). The gathers cost
    by the assignment, the grouped product by the window's row. Measured alone on a v5e
    (PERF.md section 6, PR 34), ms a call, slots -> rows (the gates' scalars, k = 1):
    22 x 8,192 assignments 1,024 wide over a window of a 32nd of them 1.393 -> 0.165
    (1.288 -> 0.088), a 16th 0.228 (0.166), an 8th 0.416 (0.324), a quarter 0.799
    (0.651); 4 x 8,192 assignments 2,048 wide over a quarter 0.381 -> 0.348
    (0.235 -> 0.119), an 8th 0.329 -> 0.292, a 16th 0.328 -> 0.244. Over half of them
    the two cross: 4.836 -> 2.830 (1.288 -> 1.520) at 22 a token, 0.432 -> 0.643
    (0.235 -> 0.229) at 4. A layer that holds all, half or a quarter of its experts keeps
    the gathers."""
    return tokens * k >= 4 * rows


_LANES = 128
_NO_ASSIGNMENT = jnp.iinfo(jnp.int32).max  # beyond every tile of every result
# lhs [rows, tile] and rhs [rows, width] contracted over the rows, which the groups
# split: [groups, tile, width], what a grouped product's weight gradient is too
_ROWS_CONTRACTED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())), lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _sum_from_rows(b, at, n: int, k: int):
    """b [rows, width] -> [n // k, width]: the rows of each token summed, row j being one
    of assignment at[j] (at [rows] int32, each assignment once; `_NO_ASSIGNMENT`: of none).
    b [rows] -> [n]: every assignment's value, 0 for those that have no row (k = 1).

    The rows are brought into the assignments' order (one sort of `rows` keys, one gather
    of `rows` rows), which is the tokens' order too, and every tile of 512 tokens is the
    product of a 0/1 matrix [512, the tile's rows] with those rows: one grouped product
    whose ragged extent is the contraction. 0, 1 and a bfloat16 are exact on the MXU and
    the sum is float32, rounded once; float32 values go at the highest precision and
    keep every bit (a token's sum then differs from a sum in slot order by the order of
    its additions alone). A scalar takes one of 128 lanes, a "token" of 128 assignments."""
    rows, scalars = b.shape[0], b.ndim == 1
    w = _LANES if scalars else k
    span = _ROW_TILE * w  # assignments under a tile of the result
    tiles = -(-n // span)
    # rows of no assignment sort behind every tile's and belong to no group
    at, perm = jax.lax.sort((at, jnp.arange(rows, dtype=jnp.int32)), num_keys=1)
    b = b[perm]
    if scalars:
        b = jnp.where((at % w)[:, None] == jnp.arange(w), b[:, None], 0)
    sizes = (at[:, None] // span == jnp.arange(tiles)).sum(0, dtype=jnp.int32)
    onehot = ((at // w % _ROW_TILE)[:, None] == jnp.arange(_ROW_TILE)).astype(b.dtype)
    out = jax.lax.ragged_dot_general(
        onehot, b, sizes, _ROWS_CONTRACTED, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if b.dtype == jnp.float32 else None)
    out = out.astype(b.dtype)
    return out.reshape(-1)[:n] if scalars else out.reshape(tiles * _ROW_TILE, -1)[:n // k]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _take(a, order, inverse, start, rows: int, k: int):
    """a [T, ...] (k = 1: [T * k]) -> [rows, ...]: row j is a's entry for the token
    (the assignment) of order[start + j]."""
    with jax.named_scope("moe_dispatch" if k > 1 else "moe_combine"):  # tokens, or gates
        return a[jax.lax.dynamic_slice(order, (start,), (rows,)) // k]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _put(b, order, inverse, start, rows: int, k: int):
    """b [rows, ...] -> [T, ...] (k = 1: [T * k]): every assignment's row of the window
    (zeros for the assignments that lie outside it), summed over a token's k in float32
    and rounded once. No buffer has tokens x k rows: from the window's side
    (`combine_from_rows`) one grouped product over the window's rows, else a gather a
    slot, tokens rows each."""
    with jax.named_scope("moe_combine"):
        n = inverse.shape[0]
        if combine_from_rows(n // k, k, rows):
            inside = start + jnp.arange(rows, dtype=jnp.int32) < n  # the last window is padded
            at = jnp.where(inside, jax.lax.dynamic_slice(order, (start,), (rows,)), _NO_ASSIGNMENT)
            return _sum_from_rows(b, at, n, k)
        at = (inverse - start).reshape(-1, k)
        out = 0
        for j in range(k):
            row = b[jnp.clip(at[:, j], 0, rows - 1)]
            if rows != inverse.shape[0]:
                inside = (at[:, j] >= 0) & (at[:, j] < rows)
                row = jnp.where(inside.reshape(-1, *[1] * (b.ndim - 1)), row, 0)
            out = row if k == 1 else out + row.astype(jnp.float32)
        return out.astype(b.dtype)


_take.defvjp(lambda a, *w: (_take(a, *w), w[:3]),
             lambda rows, k, w, g: (_put(g, *w, rows, k), None, None, None))
_put.defvjp(lambda b, *w: (_put(b, *w), w[:3]),
            lambda rows, k, w, g: (_take(g, *w, rows, k), None, None, None))


def _mlp(x, weights, product=jnp.matmul, clean=lambda a: a):
    """An MLP over `product`; `clean` goes around each product's input and output.
    weights (w_gate, w_up, w_down): SiLU-gated; (w_up, w_down): relu(x W_up)^2 W_down,
    two products where the gated one runs three."""
    if len(weights) == 3:
        w_gate, w_up, w_down = weights
        act = clean(jax.nn.silu(clean(product(x, w_gate))) * clean(product(x, w_up)))
    else:
        w_up, w_down = weights
        act = clean(jnp.square(jax.nn.relu(clean(product(x, w_up)))))
    return clean(product(act, w_down))


def mlp_leaves(cfg: ModelConfig, prefix: str = "w_"):
    """Names of an MLP's weights in this configuration, in `_mlp`'s order."""
    parts = ("gate", "up", "down") if cfg.mlp_activation == "silu_gated" else ("up", "down")
    return tuple(prefix + part for part in parts)


def _window(x, weights, gates, order, inverse, ends, start, rows: int, k: int):
    """What the held experts add to y [T, D] for the assignments at [start, start + rows)
    of the sorted order. gates [T * k] f32; ends [held] int32: where each held expert's
    rows end in that order."""
    upto = jnp.clip(ends - start, 0, rows)  # ... and in this window
    group_sizes = jnp.diff(upto, prepend=0)
    served = (jnp.arange(rows) < upto[-1])[:, None]
    where = (order, inverse, start, rows)

    def clean(a):
        # The rows behind the groups come back from the grouped product as whatever it
        # left there, forward (its output) and backward (its cotangents): on the chip that
        # is stale memory, NaN included, and 0 x NaN reaches the gates' gradient. A
        # select, both ways, around every product.
        return jnp.where(served, a, 0)

    with jax.named_scope("moe_dispatch"):
        xin = clean(_take(x, *where, k))
    with jax.named_scope("moe_experts"):
        out = _mlp(xin, weights, clean=clean,
                   product=lambda a, w: jax.lax.ragged_dot(a, w, group_sizes))
    with jax.named_scope("moe_combine"):
        by_row = _take(gates, *where, 1)
        # weighted in float32 and rounded once: the gates' gradient is then a float32 sum
        # over the row (a sum of 2,048 bfloat16 products kept in bfloat16 was 12 % off in
        # the router's gradient on the chip, ten times the plain bfloat16 reference's)
        return _put((out.astype(jnp.float32) * by_row[:, None]).astype(x.dtype), *where, k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _walk(x, weights, gates, order, inverse, ends, rows: int, k: int):
    """The sum of `_window` over the windows that hold served rows. The first is always
    computed; the others, which only a load beyond `rows` has, in a loop of as many turns
    as that load needs. Differentiated by hand: JAX cannot transpose a loop of dynamic
    length, and through a `cond` it would keep both branches' residuals at full size.
    Only the inputs are kept; the backward pass walks the same windows and takes each
    one's `jax.vjp` (under rematerialisation the layer is recomputed anyway)."""
    def window(start):
        return _window(x, weights, gates, order, inverse, ends, start, rows, k)

    with jax.named_scope("moe_walk"):  # the loop's own copies and sums: the layer's too
        return jax.lax.fori_loop(1, windows_walked(ends[-1], rows),
                                 lambda w, y: y + window(w * rows), window(0))


def _walk_bwd(rows, k, args, dy):
    inputs, (order, inverse, ends) = args[:3], args[3:]

    def pull(start):
        return jax.vjp(lambda *a: _window(*a, order, inverse, ends, start, rows, k), *inputs)[1](dy)

    def more(w, acc):  # an overflowing step's sums, in float32
        return jax.tree.map(lambda s, g: (s.astype(jnp.float32) + g.astype(jnp.float32)).astype(s.dtype),
                            acc, pull(w * rows))

    with jax.named_scope("moe_walk"):
        grads = jax.lax.fori_loop(1, windows_walked(ends[-1], rows), more, pull(0))
    return (*grads, None, None, None)


_walk.defvjp(lambda *a: (_walk(*a), a[:6]), _walk_bwd)


def expert_layer(x: jax.Array, lp, cfg: ModelConfig):
    """x [T, D] -> (shared experts' + held routed experts' part of the layer [T, D],
    {"load": assignments an expert [n_experts] f32, over ALL experts: the step's counter;
    "chosen": the experts each token chose [T, k]}).
    lp: router [D, E], router_bias [E] (where cfg.moe_select_bias), w_gate / w_up
    [held, D, F], w_down [held, F, D], shared_gate / shared_up [D, S * F], shared_down
    [S * F, D] (where cfg.n_shared_experts); no w_gate / shared_gate where the MLPs are
    relu2 (cfg.mlp_activation). With cfg.moe_latent_dim the routed experts live in a
    latent L wide (their D above is L): latent_down [D, L] before the dispatch,
    latent_up [L, D] after the combine; router and shared experts see x itself."""
    dt = x.dtype
    k, (lo, hi) = cfg.moe_top_k, held_range(cfg)
    rows = window_rows(cfg, x.shape[0])
    with jax.named_scope("moe_router"):
        idx, gates = route(x, lp["router"], lp.get("router_bias"), cfg)
        # kept like the choice it counts: the windows' ends in the backward pass are read
        # from it, and a rematerialised layer would count the k slots again for them
        load = checkpoint_name(expert_load(idx, cfg.n_experts), LOAD_NAME)
    full = x
    if cfg.moe_latent_dim:
        with jax.named_scope("moe_latent"):
            x = jnp.matmul(x, _qw(lp["latent_down"], dt))
    with jax.named_scope("moe_dispatch"):
        # held assignments first, by expert; every other after them, under one key
        key = jnp.where((idx >= lo) & (idx < hi), idx - lo, hi - lo).reshape(-1)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        order = jnp.pad(order, (0, -order.shape[0] % rows))  # the last window, whole
        ends = jnp.cumsum(load[lo:hi].astype(jnp.int32))
    # one window, statically (every expert held): plain differentiation of it is the
    # program this layer always was, with no loop and nothing recomputed
    with jax.named_scope("moe_experts"):
        weights = tuple(_qw(lp[n], dt) for n in mlp_leaves(cfg))
    args = (x, weights, gates.reshape(-1), order, inverse, ends)
    y = _window(*args, 0, rows, k) if rows == inverse.shape[0] else _walk(*args, rows, k)
    if cfg.moe_latent_dim:
        with jax.named_scope("moe_latent"):
            y = jnp.matmul(y, _qw(lp["latent_up"], dt))
    if cfg.n_shared_experts:
        with jax.named_scope("moe_shared"):
            y = y + _mlp(full, tuple(_qw(lp[n], dt) for n in mlp_leaves(cfg, "shared_")))
    return y, {"load": load, "chosen": idx}


def init_expert_weights(key: jax.Array, cfg: ModelConfig):
    """Per-layer MoE parameter block (replaces the dense w_gate/w_up/w_down). The
    dropless layer has the experts it holds, of width d_ff_expert, and beside them the
    selection bias and the shared experts (as one MLP of n_shared_experts times the
    width) where the configuration has them."""
    d, e = cfg.d_model, cfg.n_experts
    f = (cfg.d_ff_expert or cfg.d_ff) if cfg.moe_dropless else cfg.d_ff
    held = cfg.n_experts_held if cfg.moe_dropless else e
    lat = cfg.moe_latent_dim or d  # the width the routed experts work at
    ks = jax.random.split(key, 7)
    s_in = d**-0.5
    s_out = (2 * cfg.n_layers * f) ** -0.5
    out = {
        "router": jax.random.normal(ks[0], (d, e), jnp.float32) * s_in,
        "w_gate": jax.random.normal(ks[1], (held, lat, f), jnp.float32) * lat**-0.5,
        "w_up": jax.random.normal(ks[2], (held, lat, f), jnp.float32) * lat**-0.5,
        "w_down": jax.random.normal(ks[3], (held, f, lat), jnp.float32) * s_out,
    }
    if cfg.moe_dropless and cfg.moe_select_bias:
        out["router_bias"] = jnp.zeros((e,), jnp.float32)
    if cfg.moe_dropless and cfg.n_shared_experts:
        fs = cfg.shared_width
        out.update(shared_gate=jax.random.normal(ks[4], (d, fs), jnp.float32) * s_in,
                   shared_up=jax.random.normal(ks[5], (d, fs), jnp.float32) * s_in,
                   shared_down=jax.random.normal(ks[6], (fs, d), jnp.float32) * s_out)
    if cfg.moe_latent_dim:
        k_down, k_up = jax.random.split(jax.random.fold_in(key, 7))
        out.update(latent_down=jax.random.normal(k_down, (d, lat), jnp.float32) * s_in,
                   latent_up=jax.random.normal(k_up, (lat, d), jnp.float32) * lat**-0.5)
    if cfg.mlp_activation != "silu_gated":  # a non-gated MLP has no gate
        out.pop("w_gate")
        out.pop("shared_gate", None)
    return out


def n_params(cfg: ModelConfig) -> int:
    """What the dropless layer's `init_expert_weights` makes, counted (less the selection bias)."""
    d, mats, latent = cfg.d_model, len(mlp_leaves(cfg)), cfg.moe_latent_dim or cfg.d_model
    return (d * cfg.n_experts + cfg.n_experts_held * mats * latent * (cfg.d_ff_expert or cfg.d_ff)
            + mats * d * cfg.shared_width + (2 * d * latent if cfg.moe_latent_dim else 0))


init = init_expert_weights  # the part's shape, as llama.py's table reads it
AXES = {  # of every leaf `init` can make
    "router": ("embed", "expert"),
    "w_gate": ("expert", "embed", "mlp"),
    "w_up": ("expert", "embed", "mlp"),
    "w_down": ("expert", "mlp", "embed"),
    "router_bias": ("expert",),
    "shared_gate": ("embed", "mlp"),
    "shared_up": ("embed", "mlp"),
    "shared_down": ("mlp", "embed"),
    "latent_down": ("embed", None),
    "latent_up": (None, "embed"),
}


def balance_bias(bias: jax.Array, load: jax.Array, rate: float) -> jax.Array:
    """The selection bias after a step (DeepSeek-V3, arXiv:2412.19437 section 2.1.2): an
    expert that got less than the mean load rises by `rate`, one that got more falls.
    bias and load [..., E]."""
    return bias + rate * jnp.sign(load.mean(-1, keepdims=True) - load)
