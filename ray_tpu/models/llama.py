"""Llama-family decoder-only transformer, TPU-first.

Pure-JAX (pytree params, no Module framework) so every transform — pjit, scan, remat,
shard_map — composes without adapters. Architecture: RMSNorm, RoPE (rotate-half / HF
convention), GQA, SwiGLU. Layers are stacked on a leading axis and iterated with
`lax.scan` (+ optional `jax.checkpoint`) so compile time is O(1) in depth and XLA tiles
every matmul onto the MXU with static shapes.

The reference framework has no model code (models come from torch/vLLM; SURVEY.md §2.7);
this is the flagship model its Train/Serve equivalents here exercise.
"""
from __future__ import annotations

import contextlib
import functools
import types
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.quant import as_weight as _w
from ray_tpu.parallel.sharding import with_sharding_constraint as wsc

from . import attn, hyper, kda, moe, sconv, ssm
from .attn import attn_out, qkv_proj, rms_norm, rope, rope_pairs_to_halves  # noqa: F401  (llm/ calls them here)
from .config import LAYER_KINDS, ModelConfig

Params = Dict[str, Any]


# ------------------------------------------------------------------ a layer's parts
# A layer kind (config.LAYER_KINDS: its character in a pattern, its stack) names a mixer
# and a feed-forward part, either or none. Each part is a module of one shape, read here
# and nowhere by name: `AXES` (every leaf it can have: a layer's are those its `init`
# makes), `init(keys, cfg)`, `n_params(cfg)` (beside the `init` whose leaves it counts) and
# `KEPT`, the residuals of its kernels that a remat policy keeps by name ({policy or
# "every": names}); a mixer also `mixer(x, lp, cfg)`, `LEAF` (the
# leaf that tells `_block` a layer's parameters hold it), `RECURRENT` (None, or the name
# under which packed documents and a KV cache are refused) and `SCOPE` (the scope `_block`
# runs it under, or None). The second of a row is which of the SEVEN keys a layer splits
# its own into the part's `init` draws from: a seed gives the weights it always gave.


def _dense_init(ks: jax.Array, cfg: ModelConfig) -> Params:
    d, width = cfg.d_model, cfg.d_ff
    shapes = {"w_gate": (ks[0], (d, width), d**-0.5), "w_up": (ks[1], (d, width), d**-0.5),
              "w_down": (ks[2], (width, d), (2 * cfg.n_layers * width) ** -0.5)}
    return {n: jax.random.normal(shapes[n][0], shapes[n][1], jnp.float32) * shapes[n][2]
            for n in moe.mlp_leaves(cfg)}


_dense = types.SimpleNamespace(
    AXES={"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}, init=_dense_init,
    n_params=lambda cfg: len(moe.mlp_leaves(cfg)) * cfg.d_model * cfg.d_ff, KEPT={})
MIXERS = {"attn": (attn, slice(0, 4)), "ssm": (ssm, 0), "kda": (kda, 0), "sconv": (sconv, 0)}
FEED_FORWARD = {"experts": (moe, 4), "dense": (_dense, slice(4, 7))}
# the scope around the loop over layers (a `lax.scan` over a stack's layers or a pattern's periods, or one
# period run as it stands). A layer's operations carry their part's name inside it; what carries this name
# and no part's is the loop's own: the residual stacks' stores and reads, index arithmetic, the gradient
# stacks' zeros, the expert layers' counts stacked (benchmarks/metrics/train_layer_stack_pct.json)
LAYER_LOOP = "layer_stack"
# a looped stack (cfg.loop_steps > 1): the scope around ONE recurrence, its whole stack (outside LAYER_LOOP) and
# the final norm behind it (what carries this name and not LAYER_LOOP's is the recurrence's own: that norm, and
# the carry's copies where the compiler names them so; the shared leaves' gradients are summed under LAYER_LOOP's
# name, where the transposed scans leave them); the scope of the exit gates and the distribution they make; the
# scope of the expectation and its entropy (benchmarks/metrics/train_loop_*.json)
LOOP_STEP, EXIT_GATE, EXIT_LOSS = "loop_step", "exit_gate", "exit_loss"


# ---------------------------------------------------------------------------- init

def _layer_kinds(cfg: ModelConfig) -> Dict[str, Tuple[int, Optional[str], Optional[str]]]:
    """The stacks of params: name -> (layers, mixer: attn | ssm | kda | sconv | None, feed-forward
    part: dense | experts | None). Without a pattern every layer is attention followed by
    a feed-forward part and forward walks the stacks in this order: `layers` is every
    layer of a one-kind model; cfg.n_dense_layers leading layers with the dense MLP lie
    in a stack of their own in front of it. With cfg.layer_pattern a stack holds the
    layers of one character, in the pattern's order (`layers` the expert layers: the
    step's counters and the balance rule take a row of them each), and `_pattern_layers`
    walks the pattern."""
    if cfg.layer_pattern:
        kinds = {}
        for c in cfg.layer_pattern:
            kind = LAYER_KINDS[c]
            kinds[kind.stack] = (kinds.get(kind.stack, (0,))[0] + 1, kind.mixer, kind.ff)
        return kinds
    kinds = {}
    if cfg.n_dense_layers:
        kinds["dense_layers"] = (cfg.n_dense_layers, "attn", "dense")
    kinds["layers"] = (cfg.n_layers - cfg.n_dense_layers, "attn",
                       "experts" if cfg.n_experts > 0 else "dense")
    return kinds


def pattern_period(pattern: str) -> Tuple[str, int]:
    """(unit, n): the shortest unit whose n repetitions are the pattern. What repeats is
    scanned: "MEME*EMEME*E" is 2 periods of "MEME*E". Nemotron-3-Super's published 88
    layers are periods of 9 and of 11 layers (MEMEMEM*E x 3, MEMEMEMEM*E x 4, MEMEMEM*E,
    MEMEMEME), so no shorter unit tiles them and they are one period of 88; the
    benchmark's cut is the first 11, one period as well."""
    size = next(size for size in range(1, len(pattern) + 1)
                if len(pattern) % size == 0 and pattern[:size] * (len(pattern) // size) == pattern)
    return pattern[:size], len(pattern) // size


def _layer_init(key: jax.Array, cfg: ModelConfig, mixer: Optional[str], ff: Optional[str]) -> Params:
    ks = jax.random.split(key, 7)
    out = {}
    if mixer:
        part, keys = MIXERS[mixer]
        out.update(part.init(ks[keys], cfg))
    if ff:
        part, keys = FEED_FORWARD[ff]
        out.update(mlp_norm=jnp.ones((cfg.d_model,), jnp.float32), **part.init(ks[keys], cfg))
    if cfg.part_post_norm:  # a norm behind each part as well (`_onto`)
        out.update({leaf: jnp.ones((cfg.d_model,), jnp.float32)
                    for leaf, has in (("attn_post_norm", mixer), ("mlp_post_norm", ff)) if has})
    if cfg.hc_mult > 1:  # each part reads and writes the n streams through a hyper-connection of its own (models/hyper.py)
        for part, has in zip(hyper.PARTS, (mixer, ff)):
            out.update(hyper.init(key, cfg, part) if has else {})
    return out


@functools.lru_cache(maxsize=None)  # (an abstract `init` a call otherwise; callers copy, never write)
def _layer_axes(cfg: ModelConfig, mixer: Optional[str], ff: Optional[str]) -> Params:
    """One layer's logical axes (no leading 'layer' axis): its parts', of the leaves their `init` makes."""
    axes = {**dict.fromkeys(("mlp_norm", "attn_post_norm", "mlp_post_norm"), ("embed",)), **hyper.AXES,
            **(MIXERS[mixer][0].AXES if mixer else {}), **(FEED_FORWARD[ff][0].AXES if ff else {})}
    made = jax.eval_shape(functools.partial(_layer_init, cfg=cfg, mixer=mixer, ff=ff), jax.random.PRNGKey(0))
    return {leaf: axes[leaf] for leaf in made}


def param_axes(cfg: ModelConfig) -> Params:
    """Logical-axis tree mirroring init() output (layers stacked on a leading 'layer' axis)."""

    def stacked(tree):
        return {k: ("layer",) + v for k, v in tree.items()}

    axes = {
        "embed": ("vocab", "embed"),
        **{name: stacked(_layer_axes(cfg, mixer, ff))
           for name, (_, mixer, ff) in _layer_kinds(cfg).items()},
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.loop_steps > 1:
        axes["exit_gate"] = (None,)  # d_model weights and the bias: one short vector, held whole
    if cfg.mtp_depth:
        axes["mtp"] = stacked({
            "embed_norm": ("embed",), "hidden_norm": ("embed",), "eh_proj": ("mlp", "embed"),
            "final_norm": ("embed",), **_layer_axes(cfg, "attn", "experts")})
    return axes


def n_params(cfg: ModelConfig) -> int:
    """Approximate parameter count (embeddings + blocks + norms), of what is held: each
    part's own count (beside its `init`), a norm a feed-forward part, one more behind every
    part under cfg.part_post_norm. Capacity-based experts count as the dense MLP they stand
    in for, as they always have. A looped stack's layers are counted once, however often they run, and
    its exit gate is one weight a channel and a bias (one leaf). Under cfg.hc_mult > 1 every part has a
    hyper-connection beside it."""
    d = cfg.d_model

    def layer(mixer, ff):
        ff = "dense" if ff and not cfg.moe_dropless else ff
        return ((MIXERS[mixer][0].n_params(cfg) if mixer else 0)
                + (FEED_FORWARD[ff][0].n_params(cfg) + d if ff else 0)
                + (d * cfg.part_post_norm + hyper.n_params(cfg) * (cfg.hc_mult > 1)) * (bool(mixer) + bool(ff)))

    return (cfg.vocab_size * d * (1 if cfg.tie_embeddings else 2) + d
            + sum(n * layer(mixer, ff) for n, mixer, ff in _layer_kinds(cfg).values())
            + cfg.mtp_depth * (layer("attn", "experts") + 2 * d * d + 3 * d)
            + (d + 1) * (cfg.loop_steps > 1))


def init(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Initialize parameters (f32). Scaled-normal init, wo/w_down scaled by depth."""
    k_emb, k_head, k_layers = jax.random.split(rng, 3)
    d = cfg.d_model

    def norm(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.float32)

    # a tied table is drawn at the head's scale: as the head it makes logits of order 1,
    # where a table of unit entries made them of order sqrt(d) (every part norms its input,
    # so the blocks see the same either way)
    params: Params = {"embed": norm(k_emb, (cfg.vocab_size, d), d**-0.5 if cfg.tie_embeddings else 1.0)}
    kinds = _layer_kinds(cfg)
    if len(kinds) == 1:  # the one-kind model draws its layers' keys as it always did
        kind_keys = {next(iter(kinds)): k_layers}
    else:
        kind_keys = dict(zip(kinds, jax.random.split(k_layers, len(kinds))))
    for name, (n, mixer, ff) in kinds.items():
        params[name] = jax.vmap(functools.partial(_layer_init, cfg=cfg, mixer=mixer, ff=ff))(
            jax.random.split(kind_keys[name], n))
    params["final_norm"] = jnp.ones((d,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(k_head, (d, cfg.vocab_size), d**-0.5)
    if cfg.loop_steps > 1:
        # the exit gate of a looped stack, ONE leaf: d weights (one number a position, of order 1 on a normed
        # stream) and, last, the bias. A leaf of one number would be a row of its own wherever gradients are
        # compared a row a leaf, and the quotient of two errors of one number has no bound
        params["exit_gate"] = jnp.concatenate([norm(jax.random.fold_in(k_head, 1), (d,), d**-0.5), jnp.zeros((1,), jnp.float32)])
    if cfg.mtp_depth:
        def mtp_init(key):
            k_proj, k_block = jax.random.split(key)
            return {
                "embed_norm": jnp.ones((d,), jnp.float32),
                "hidden_norm": jnp.ones((d,), jnp.float32),
                "eh_proj": norm(k_proj, (2 * d, d), (2 * d) ** -0.5),
                "final_norm": jnp.ones((d,), jnp.float32),
                **_layer_init(k_block, cfg, "attn", "experts"),
            }

        params["mtp"] = jax.vmap(mtp_init)(
            jax.random.split(jax.random.fold_in(k_layers, 1), cfg.mtp_depth))
    return params


# ------------------------------------------------------------------------- kernels

def _maybe_remat(body, cfg: ModelConfig):
    """Per-layer rematerialization with a selectable policy (cfg.remat_policy).

    'dots' and 'dots_no_batch' save matmul outputs, so only cheap elementwise ops replay
    in the backward pass (XLA's usual MFU sweet spot), and beside them the rotated q and k
    (ops/flash_attention.py names them: the backward of the projections needs dQ and dK,
    never their own output, so the un-rotated pair is dropped for them and the rotation is
    not run again).

    'full' keeps a layer's input and the short list of what is too dear to make again:
    - the attention core's two results, `out` and its logsumexp (ops/flash_attention.py
      names them where the forward kernel made them): the kernel is quadratic in the
      sequence and its output linear, 84 MB for 4.5 ms a block at [1, 8192, 20, 256], so
      the backward kernels read what the forward pass wrote and the rematerialised layer
      runs no forward kernel; q, k and v are made again from the layer's input by the
      projections it runs anyway. Not under the 'dots' policies: both Mistral depths are
      the greatest that fit, and half a gigabyte more there turns into XLA's own `.remat`
      of MLP products (PERF.md section 7, after PR 26 (1)).
    - a recurrent mixer's input product, ONE array as the einsum wrote it, named by the mixer
      where it is made, so the rematerialised part runs no input product; its norm,
      convolution, gate, scan and output are made again from that and the layer's input (a
      pass over HBM each, and the weights' gradients read them):
        - a gated short convolution's `[B | C | x]` (models/sconv.py): 12 KB a token a part
          (3 x d_model x 2 B) beside the 4 KB of the layer's input, 403 MB for 0.825 TFLOP =
          4.6 ms a part at [4, 8192] and width 2048;
        - a Mamba-2 mixer's `[z | xBC | dt]` (models/ssm.py): 4.6 KB a token a part (2,320
          channels x 2 B), 38 MB for 0.156 TFLOP = 0.9 ms a part at [1, 8192] and a share of
          16 heads;
        - a delta-rule mixer's q | k | v before the convolution (models/kda.py): 6 KB a token
          a part (3 x 8 x 128 x 2 B), 50 MB for 0.206 TFLOP = 1.1 ms a part at [1, 8192] and a
          share of 8 heads; the decay's and the gate's low-rank pairs and beta (a tenth of the
          part's input work) are not kept.
      Not under the 'dots' policies: `checkpoint_dots` keeps every product there already.

    Under EVERY policy:
    - what an expert layer's router made (moe.route names it: the choice, the scores, the
      chosen scores, the count): a recomputed forward pass must neither choose nor score
      again;
    - the inverses of a delta-rule mixer's triangular systems (ops/kda.py names them:
      [Q, Q] a chunk and head, 32 MB a layer at 8 heads and 8,192 positions): the
      substitution is the scan's slowest kernel.

    Which names those are, each part says itself (`KEPT`, by policy or under "every")."""
    policy = cfg.remat_policy
    if not cfg.remat or policy == "none":
        return body
    policies = jax.checkpoint_policies
    if policy not in ("full", "dots", "dots_no_batch"):
        raise ValueError(
            f"unknown remat_policy {policy!r} (expected full | dots | dots_no_batch | none)")
    kept = policies.save_only_these_names(*(
        name for part, _ in (*MIXERS.values(), *FEED_FORWARD.values())
        for name in (*part.KEPT.get(policy, ()), *part.KEPT.get("every", ()))))
    if policy == "full":
        return jax.checkpoint(body, policy=kept)
    dots = policies.checkpoint_dots if policy == "dots" else policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(body, policy=policies.save_from_both_policies(dots, kept))


# A row shorter than this is looked up with a gather whatever the mesh: the
# smallest prefill bucket (llm/config.py:buckets) is 16, so only a decode token
# and a speculative window (k drafts + 1) fall below it.
_ONE_HOT_MIN_ROW = 16


def embed_tokens(params: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Token embedding lookup, sharding-aware: tokens [B, S] -> [B, S, D] in the
    activation dtype.

    When the vocab dim is sharded (tp>1) a plain gather carries a transposed-
    device-order output sharding that GSPMD can only reconcile with the batch-sharded
    activation constraint via involuntary full rematerialization (replicate +
    repartition, wasted HBM/ICI every step). A one-hot matmul instead contracts over
    the vocab shard — GSPMD turns that into a local dot + psum over tp, the
    embed/fsdp dim flows through, and the op lands on the MXU. With vocab unsharded
    (tp=1, incl. single device) the cheaper gather is kept: embed-dim (fsdp) sharding
    flows through a gather cleanly. What decides between them is the row's length
    S: a decode token (S == 1) or a verify window (S = drafts + 1 < 16) keeps the
    gather — a few rows per sequence are too small for the resharding cost to
    matter, no constraint follows them in the serving programs, and the matmul
    would add vocab*d FLOPs per token. (Sharding-in-types can't see Auto-axis
    specs, so the gate is the mesh's tp extent, not the table's actual spec.)
    Semantics note: out-of-range token ids clamp under gather but embed to zeros
    under the one-hot path; valid inputs (< vocab_size) are identical.
    cfg.embed_scale (0 = none) multiplies the result.
    """
    table = params["embed"].astype(cfg.activation_dtype)
    try:
        mesh = jax.sharding.get_abstract_mesh()
        sharded = mesh is not None and not mesh.empty and mesh.shape.get("tp", 1) > 1
    # graftlint: allow[swallowed-exception] degrades to the coded fallback (sharded = False) by design
    except Exception:
        sharded = False
    if not sharded or tokens.shape[-1] < _ONE_HOT_MIN_ROW:
        x = table[tokens]
    else:
        onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=table.dtype)
        x = jnp.einsum("bsv,vd->bsd", onehot, table)
    if cfg.embed_scale:  # in float32, rounded once
        x = (x.astype(jnp.float32) * cfg.embed_scale).astype(x.dtype)
    return x


# --------------------------------------------------------------------------- block
# The decoder block's arithmetic, written once. A program is these parts around an
# attention over its own cache: _block (train step, prefill: ops.attention or ring
# attention) and llm/model_runner.py:_window_core (decode and the verify window:
# per-slot lengths over a layout adapter's cache view). Math only — a sharding
# constraint comes from the caller, so the serving programs carry none.

def _unconstrained(x: jax.Array, *logical_axes) -> jax.Array:
    return x


def _onto(x: jax.Array, out: jax.Array, lp: Params, leaf: str, cfg: ModelConfig,
          constrain=_unconstrained) -> jax.Array:
    """The residual, for mixers and feed-forward parts alike: x + a part's output, which
    goes through the part's own norm first where the layer has one (cfg.part_post_norm:
    `attn_post_norm` behind a mixer, `mlp_post_norm` behind a feed-forward part)."""
    if leaf in lp:
        out = rms_norm(out, lp[leaf], cfg.norm_eps)
    return constrain(x + out, "batch", "seq", "act_embed")


def feed_forward(x: jax.Array, lp: Params, cfg: ModelConfig,
                 token_mask: Optional[jax.Array] = None, constrain=_unconstrained, onto=None):
    """Norm, the dense or MoE feed-forward (whichever the layer's parameters are),
    residual. Returns (x, aux): the capacity-based experts' load-balancing loss (a
    scalar, zero for a dense layer), or what the dropless layer counted and chose
    (moe.expert_layer: {"load": [E], "chosen": [B * S, k]}).
    token_mask [B, S] (1 = real) keeps pad tokens and inactive slots out of the
    experts' capacity; `constrain(array, *logical_axes)` is the caller's sharding
    constraint on the dense product and on the result. `onto(out)`: how the part's
    output joins the stream where that is not x + out (`_block` under cfg.hc_mult > 1,
    where x is the part's reading of n streams)."""
    dt = x.dtype
    onto = onto or (lambda out: _onto(x, out, lp, "mlp_post_norm", cfg, constrain))
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    b, s, d = h.shape
    if "router" not in lp:
        if "w_gate" in lp:
            gate = jnp.einsum("bsd,df->bsf", h, _w(lp["w_gate"], dt))
            up = jnp.einsum("bsd,df->bsf", h, _w(lp["w_up"], dt))
            act = jax.nn.silu(gate) * up
        else:  # cfg.mlp_activation relu2: no gate
            act = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", h, _w(lp["w_up"], dt))))
        ff = constrain(act, "batch", "seq", "act_mlp")
        down = jnp.einsum("bsf,fd->bsd", ff, _w(lp["w_down"], dt))
        return onto(down), jnp.zeros((), jnp.float32)
    if cfg.moe_dropless:
        y2, aux = moe.expert_layer(h.reshape(b * s, d), lp, cfg)
    else:
        y2, aux = moe.moe_mlp(
            h.reshape(b * s, d), lp["router"], lp["w_gate"], lp["w_up"],
            lp["w_down"], cfg,
            mask=None if token_mask is None else token_mask.reshape(b * s),
        )
    return onto(y2.reshape(b, s, d)), aux


def _head(params: Params, normed: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The (tied or untied) head behind the final norm: [B, S, D] -> f32 logits [B, S, vocab]."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", normed, _w(head, cfg.activation_dtype))
    return logits.astype(jnp.float32)


def output_head(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final norm and the (tied or untied) head: x [B, S, D] -> f32 logits [B, S, vocab]."""
    return _head(params, rms_norm(x, params["final_norm"], cfg.norm_eps), cfg)


# ------------------------------------------------------------------------- forward

class KVCache(NamedTuple):
    """Stacked-per-layer KV cache for autoregressive decode.

    k/v: [L, B, max_len, n_kv_heads, head_dim]; length: current fill (same per batch
    row — the paged engine in serve/ handles ragged batches above this level).
    """

    k: jax.Array
    v: jax.Array
    length: jax.Array  # scalar int32


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> KVCache:
    dtype = dtype or cfg.activation_dtype
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype), length=jnp.zeros((), jnp.int32)
    )


def _block(
    x: jax.Array,
    lp: Params,
    cfg: ModelConfig,
    positions: jax.Array,
    segment_ids: Optional[jax.Array],
    cache_kv: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_len: Optional[jax.Array] = None,
    token_mask: Optional[jax.Array] = None,
    windowed: bool = False,
):
    """One layer: the mixer its parameters hold (attention, a Mamba-2 or a Kimi-Delta-
    Attention mixer, a gated short convolution, or none) and then the feed-forward part
    they hold (or none), each
    behind its own norm and residual (`_onto`: the one place a part's output joins the
    stream). Every family with a layer pattern holds one part a
    layer; the others attention and a feed-forward part in each: the decoder block.
    `windowed`: the attention is a `W` part's (config.LAYER_KINDS).
    Under cfg.hc_mult > 1 x is n streams [B, T, n d_model]: a part reads a mixture of them and
    writes through its hyper-connection (models/hyper.py) in `_onto`'s place.
    Returns (x, updated (k,v) if caching, moe aux loss; under cfg.hc_mult > 1 a pair of it and
    the layer's largest projection error [2])."""
    new_kv, aux = None, jnp.zeros((), jnp.float32)
    part = next((part for part, _ in MIXERS.values() if part.LEAF in lp), None)
    y, errs = x, []  # y: what a part reads of the stream (under cfg.hc_mult > 1 a mixture of its copies)
    if part is not None and cfg.hc_mult > 1:
        with jax.named_scope("attn"):
            y, coef, x = _hc_read(x, lp, "attn", cfg, errs)
    if part is not None and part.RECURRENT:
        if segment_ids is not None or cache_kv is not None:
            raise NotImplementedError(
                f"a {part.RECURRENT} layer over packed documents (segment_ids: state and convolution do "
                "not start again at a boundary yet) or under a KV cache (no recurrent state or "
                "convolution tail is kept)")
        # `attn` is where the readers of the trace look for a layer's mixer
        # (benchmarks/metrics/train_scoped_pct.json, train_head_loss_pct.json)
        with jax.named_scope(part.SCOPE) if part.SCOPE else contextlib.nullcontext():
            out = part.mixer(y, lp, cfg)
    elif part is not None:
        out, new_kv = part.mixer(y, lp, cfg, positions, segment_ids, cache_kv, cache_len, windowed)
    if part is not None and cfg.hc_mult > 1:
        with jax.named_scope("attn"):
            x = _hc_write(x, out, coef, cfg)
    elif part is not None:
        # an attention part's residual counts with its output product, so that everything under `attn`
        # and no recurrent mixer's name carries one of attn.SCOPES
        piece = contextlib.nullcontext() if part.RECURRENT else jax.named_scope(attn.OUT_SCOPE)
        with jax.named_scope("attn"), piece:
            x = _onto(x, out, lp, "attn_post_norm", cfg, wsc)
    if "mlp_norm" in lp:
        with jax.named_scope("mlp"):
            if cfg.hc_mult > 1:
                y, coef, x = _hc_read(x, lp, "mlp", cfg, errs)
                x, aux = feed_forward(y, lp, cfg, token_mask, constrain=wsc,
                                      onto=lambda out, x=x: _hc_write(x, out, coef, cfg))
            else:
                x, aux = feed_forward(x, lp, cfg, token_mask, constrain=wsc)
    if cfg.hc_mult > 1:  # beside the part's own: how far the layer's projections ended from doubly stochastic
        aux = (aux, jnp.stack(errs).max(0))
    return x, new_kv, aux


def _hc_read(x: jax.Array, lp: Params, part: str, cfg: ModelConfig, errs: list):
    """Under cfg.hc_mult > 1 (inside the part's scope): (what the part `part` of hyper.PARTS reads of the n streams
    x [B, T, n C], its coefficients and the stream for `_hc_write`: hyper.enter); the projection's error is appended
    to `errs`."""
    y, coef, err, x = hyper.enter(x, lp[f"{part}_hc"], cfg)
    errs.append(err)
    return y, coef, x


def _hc_write(x: jax.Array, out: jax.Array, coef: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The part's output joins the n streams: `_onto`'s place under cfg.hc_mult > 1."""
    x = hyper.write(x, out, coef, cfg)
    with jax.named_scope(hyper.SCOPE):
        return wsc(x, "batch", "seq", "act_embed")


def _pipeline_layers(
    x: jax.Array,
    params: Params,
    cfg: ModelConfig,
    positions: jax.Array,
    segment_ids: Optional[jax.Array],
    token_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Run the layer stack as cfg.pipeline_stages pipeline stages over the "pp" axis.

    Stage-stacks the scanned layer params [L, ...] -> [pp, L/pp, ...] and feeds the
    GPipe schedule (parallel/pipeline.py). Training path only (no KV cache). Packed
    sequences (segment_ids) and MoE token masks ride the schedule as microbatched
    side inputs (pipeline side=...). Returns (x, moe aux loss): MoE composes with
    pp — each stage threads its layers' load-balancing aux through the schedule
    (bubble ticks masked; see pipeline_spmd with_aux).
    """
    from ray_tpu.parallel.pipeline import pipeline

    pp = cfg.pipeline_stages
    if cfg.n_layers % pp:
        raise ValueError(f"n_layers {cfg.n_layers} not divisible by pipeline_stages {pp}")
    layers = params["layers"]
    stacked = jax.tree_util.tree_map(
        lambda p: p.reshape(pp, cfg.n_layers // pp, *p.shape[1:]), layers
    )
    seq_manual = cfg.attention_impl in ("ring", "ulysses")

    has_experts = cfg.n_experts > 0
    from jax.sharding import PartitionSpec as P

    side = {}
    side_spec = {}
    seq_spec = P(None, "sp") if seq_manual else P()
    # positions ride as a side input too — caller-supplied offsets (e.g. a
    # nonzero RoPE start) reach every stage instead of being rebuilt as 0..S-1
    side["positions"] = jnp.broadcast_to(positions, x.shape[:2])
    side_spec["positions"] = seq_spec
    if segment_ids is not None:
        side["segment_ids"] = segment_ids
        side_spec["segment_ids"] = seq_spec
    if token_mask is not None:
        side["token_mask"] = token_mask
        side_spec["token_mask"] = seq_spec

    def stage_fn(stage_params, xm, side_now):
        pos = side_now["positions"]
        seg = side_now.get("segment_ids")
        mask = side_now.get("token_mask")

        def body(carry, lp):
            h, aux_acc = carry
            h, _, aux = _block(h, lp, cfg, pos, seg, token_mask=mask)
            return (h, aux_acc + aux), None

        # aux carry must match the loop body's varying-manual-axes type (it
        # inherits xm's vma plus pp)
        from ray_tpu.parallel.sharding import vary_like

        aux0 = vary_like(jnp.zeros((), jnp.float32), xm)
        fn = _maybe_remat(body, cfg)
        with jax.named_scope(LAYER_LOOP):
            (out, aux), _ = jax.lax.scan(fn, (xm, aux0), stage_params)
        return (out, aux) if has_experts else out

    m = cfg.pipeline_microbatches or pp

    out = pipeline(
        stage_fn,
        stacked,
        x,
        num_microbatches=m,
        x_spec=P(None, "sp", None) if seq_manual else None,
        extra_manual=("sp",) if seq_manual else (),
        with_aux=has_experts,
        side=side,
        side_spec=side_spec,
    )
    return out if has_experts else (out, jnp.zeros((), jnp.float32))


def _pattern_layers(x, params: Params, cfg: ModelConfig, positions, segment_ids, token_mask):
    """x through cfg.layer_pattern. Returns (x, aux as forward's return_aux has it).

    The pattern's period (`pattern_period`) is traced once, each of its layers under its
    own rematerialisation, and scanned over the periods: period r's j-th layer of a kind
    that has c layers a period is row r * c + j of that kind's stack, so a stack
    [n * c, ...] is scanned as [n, c, ...]. One period is run as it stands, with no loop
    around it: each layer's gradient is then written once, into its row."""
    unit, n = pattern_period(cfg.layer_pattern)
    per_unit = {name: count // n for name, (count, _, _) in _layer_kinds(cfg).items()}
    layer = {windowed: _maybe_remat(  # (a static argument of the rematerialised body: a body each)
        lambda h, lp, windowed=windowed: _block(
            h, lp, cfg, positions, segment_ids, token_mask=token_mask, windowed=windowed)[::2], cfg)
        for windowed in {LAYER_KINDS[c].windowed for c in unit}}

    def period(h, stacks):
        at, auxs = dict.fromkeys(stacks, 0), []
        for c in unit:
            name = LAYER_KINDS[c].stack
            with jax.named_scope("layer_params"):  # the layer's rows of its stack: copies a step pays for
                lp = jax.tree.map(lambda a: a[at[name]], stacks[name])  # noqa: B023
            h, aux = layer[LAYER_KINDS[c].windowed](h, lp)
            at[name] += 1
            if LAYER_KINDS[c].ff == "experts":
                auxs.append(aux)
        return h, jax.tree.map(lambda *a: jnp.stack(a), *auxs) if auxs else None

    stacks = {name: params[name] for name in per_unit}
    with jax.named_scope(LAYER_LOOP):
        if n == 1:
            x, auxs = period(x, stacks)
        else:
            x, auxs = jax.lax.scan(period, x, {
                name: jax.tree.map(lambda a: a.reshape(n, per_unit[name], *a.shape[1:]), stack)  # noqa: B023
                for name, stack in stacks.items()})
            auxs = jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), auxs)
    if cfg.moe_dropless and auxs is not None:
        return x, dict(auxs, hidden=x)
    return x, jnp.zeros((), jnp.float32) if auxs is None else auxs.sum()


def _stacked_layers(x, params: Params, cfg: ModelConfig, positions, segment_ids, token_mask,
                    cache: Optional[KVCache] = None):
    """x through the stacks of a model without a pattern, one loop a stack of layers (a leading
    dense stack, then `layers`): a layer's parameters and, when there is a cache (one stack
    only), its K/V (None is an empty pytree: the scan then carries no K/V in or out).
    Returns (x, the last stack's new K/V or None, the last stack's aux a layer: the expert
    layers' where there are any; under cfg.hc_mult > 1 a pair of it and every layer's largest
    projection error)."""
    cache_len = None if cache is None else cache.length

    def body(h, xs):
        lp, kv = xs
        h, new_kv, aux = _block(h, lp, cfg, positions, segment_ids, kv,
                                cache_len, token_mask)
        return h, (new_kv, aux)

    errs = []
    for name in _layer_kinds(cfg):
        with jax.named_scope(LAYER_LOOP):
            x, (new_kv, auxs) = jax.lax.scan(
                _maybe_remat(body, cfg), x,
                (params[name], None if cache is None else (cache.k, cache.v)))
            if cfg.hc_mult > 1:
                auxs, err = auxs
                errs.append(err.max(0))
    if cfg.hc_mult > 1:  # every stack's projection errors, the largest: (the last stack's aux, [2])
        auxs = (auxs, jnp.stack(errs).max(0))
    return x, new_kv, auxs


def looped_outputs(params: Params, tokens: jax.Array, cfg: ModelConfig, *,
                   positions: Optional[jax.Array] = None, segment_ids: Optional[jax.Array] = None):
    """A looped stack (cfg.loop_steps = T > 1; Ouro, arXiv:2510.25741): tokens [B, S] -> [n_1 .. n_T],
    each [B, S, D]. h_0 is the embedding; recurrence t runs every layer over h_{t-1} with the SAME
    parameters, n_t is the (one, shared) final norm of what comes out, and h_t = n_t: the next
    recurrence starts from the normed output. A head and an exit gate read each n_t
    (`forward`: the last one's logits; `expected_exit_loss`: all of them). A layer application
    keeps what `_maybe_remat` says, T x n_layers of them; the gradient of a layer's leaf is the
    sum over its T uses."""
    if positions is None:
        positions = jnp.arange(tokens.shape[1])[None, :]
    with jax.named_scope("embed"):
        x = wsc(embed_tokens(params, tokens, cfg), "batch", "seq", "act_embed")
    outs = []
    for _ in range(cfg.loop_steps):
        # the final norm is the head's, as in every model, and here the recurrence's too: what a recurrence
        # costs beyond its layers
        with jax.named_scope(LOOP_STEP):
            x, _, _ = _stacked_layers(x, params, cfg, positions, segment_ids, None)
            with jax.named_scope("lm_head"):
                x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        outs.append(x)
    return outs


def forward(
    params: Params,
    tokens: jax.Array,
    cfg: ModelConfig,
    *,
    positions: Optional[jax.Array] = None,
    segment_ids: Optional[jax.Array] = None,
    cache: Optional[KVCache] = None,
    return_aux: bool = False,
    token_mask: Optional[jax.Array] = None,  # [B, S] 1=real; MoE capacity masking
    head_rows: Optional[int] = None,  # the head reads a sequence's first `head_rows` positions alone
):
    """tokens [B, S] -> (logits [B, S, vocab] f32, updated cache or None).

    With return_aux=True also returns the summed MoE load-balancing loss (zero for
    dense configs) as a third element; for the dropless expert layer, which has no such
    loss, what each expert layer counted and chose and what the MTP modules go on from
    ({"load": [layers, E], "chosen": [layers, B * S, k], "hidden": the last block's
    output before the final norm [B, S, D]}). Under cfg.hc_mult > 1 it is a dict either way, with `hc_err` [2] (the
    largest distance of any Hres' row and column sums from 1) beside those or beside `aux_loss`, and `hidden` is n streams.

    Under cfg.diffusion_block `tokens` is the block-diffusion objective's doubled row
    [noised ; clean] with `positions` repeated (`block_diffusion_loss` builds both), and
    `head_rows` cuts the noised half out before the final norm and the head.

    Under cfg.loop_steps > 1 the logits are the LAST recurrence's (`looped_outputs`)."""
    b, s = tokens.shape
    if cfg.loop_steps > 1:
        if cache is not None:
            raise NotImplementedError(
                f"a looped stack under a KV cache: every one of its loop_steps ({cfg.loop_steps}) recurrences "
                f"attends over keys and values of its own, a cache of loop_steps x n_layers entries, and "
                "init_kv_cache builds n_layers")
        last = looped_outputs(params, tokens, cfg, positions=positions, segment_ids=segment_ids)[-1]
        with jax.named_scope("lm_head"):
            logits = wsc(_head(params, last, cfg), "batch", "seq", "act_vocab")
        return (logits, None, jnp.zeros((), jnp.float32)) if return_aux else (logits, None)
    if cfg.diffusion_block and s % (2 * cfg.diffusion_block):  # (a cache, packed documents: the mixer refuses them)
        raise NotImplementedError(
            f"the block-diffusion objective (cfg.diffusion_block) takes one doubled row [noised ; clean] of whole "
            f"blocks of {cfg.diffusion_block}, not {s} positions")
    if cfg.hc_mult > 1 and cache is not None:
        raise NotImplementedError(
            f"a stream of hc_mult ({cfg.hc_mult}) copies under a KV cache: the serving programs (llm/model_runner.py) "
            "add attention's output to [B, S, d_model] themselves and keep no stream of n copies a slot")
    if positions is None:  # one row, which every row of the batch shares
        start = cache.length if cache is not None else 0
        positions = jnp.arange(s)[None, :] + start
    with jax.named_scope("embed"):
        x = wsc(embed_tokens(params, tokens, cfg), "batch", "seq", "act_embed")
        if cfg.hc_mult > 1:  # n streams from here to the head: [B, S, n d_model]
            x = wsc(hyper.spread(x, cfg), "batch", "seq", "act_embed")

    new_cache = None
    if len(_layer_kinds(cfg)) > 1 and (cfg.pipeline_stages > 1 or cache is not None):
        raise NotImplementedError(
            "pipeline stages or a KV cache over layers of more than one kind")
    if cfg.layer_pattern:
        if cfg.pipeline_stages > 1 or cache is not None:
            raise NotImplementedError("pipeline stages or a KV cache over a pattern of layers")
        x, aux_total = _pattern_layers(x, params, cfg, positions, segment_ids, token_mask)
    elif cfg.pipeline_stages > 1 and cache is None:
        x, aux_total = _pipeline_layers(x, params, cfg, positions, segment_ids,
                                        token_mask)
    else:
        x, new_kv, auxs = _stacked_layers(x, params, cfg, positions, segment_ids, token_mask, cache)
        if cfg.hc_mult > 1:
            auxs, hc_err = auxs
        # the last stack's: the expert layers' where there are any
        aux_total = dict(auxs, hidden=x) if cfg.moe_dropless else auxs.sum()
        if cfg.hc_mult > 1:  # (a dict either way: the projections' error rides beside the experts' counters or the loss)
            aux_total = dict(aux_total if cfg.moe_dropless else {"aux_loss": aux_total}, hc_err=hc_err)
        if cache is not None:
            new_cache = KVCache(k=new_kv[0], v=new_kv[1], length=cache.length + s)

    if cfg.hc_mult > 1:  # the head reads the sum of the copies
        with jax.named_scope("lm_head"):
            x = hyper.gather(x, cfg)
    if head_rows is not None:
        with jax.named_scope("lm_head"), jax.named_scope("bd_rows"):
            x = x[:, :head_rows]
    with jax.named_scope("lm_head"):
        logits = wsc(output_head(params, x, cfg), "batch", "seq", "act_vocab")
    if return_aux:
        return logits, new_cache, aux_total
    return logits, new_cache


def mtp_logits(params: Params, hidden: jax.Array, tokens: jax.Array, cfg: ModelConfig):
    """The multi-token-prediction modules (DeepSeek-V3, arXiv:2412.19437 section 2.2),
    one after the other. hidden [B, S, D]: the last block's output before the final
    norm, at the positions of tokens[:, :S]; tokens [B, >= S] (what lies past S is looked
    ahead at). Module m (from 1) joins position i's state with the embedding of token
    i + m through `eh_proj`, runs one more block, and its logits at i predict token
    i + m + 1. Embedding and head are the model's. A module runs all S positions, so
    that attention keeps the sequence's length and its kernel (S - m is no multiple of
    a tile); the last m, which look past the tokens, are cut before the head: returns
    [(logits [B, S - m, vocab], the block's aux)] a module."""
    if cfg.hc_mult > 1:
        raise NotImplementedError(
            f"MTP modules over a stream of hc_mult ({cfg.hc_mult}) copies: xing4_0's config.json does not say how a "
            "module's eh_proj reads the n streams, nor whether its block mixes them")
    s = hidden.shape[1]
    tokens = jnp.pad(tokens, ((0, 0), (0, max(0, s + cfg.mtp_depth - tokens.shape[1]))))
    positions = jnp.arange(s)[None, :]
    out = []
    for m in range(1, cfg.mtp_depth + 1):
        mp = jax.tree.map(lambda a: a[m - 1], params["mtp"])
        with jax.named_scope("mtp"):
            ahead = embed_tokens(params, tokens[:, m:m + s], cfg)
            joined = jnp.concatenate([rms_norm(ahead, mp["embed_norm"], cfg.norm_eps),
                                      rms_norm(hidden, mp["hidden_norm"], cfg.norm_eps)], -1)
            x = jnp.einsum("bse,ed->bsd", joined, _w(mp["eh_proj"], joined.dtype))
            hidden, _, aux = _maybe_remat(
                lambda h, lp: _block(h, lp, cfg, positions, None), cfg)(x, mp)
            with jax.named_scope("lm_head"):  # the model's head again, under `mtp`
                logits = output_head({**params, "final_norm": mp["final_norm"]},
                                     hidden[:, :s - m], cfg)
        out.append((logits, aux))
    return out


def balance_router_bias(old: Params, new: Params, load: jax.Array, cfg: ModelConfig) -> Params:
    """`new` with every expert layer's selection bias set to `old`'s moved by the balance
    rule (moe.balance_bias). load [expert layers + MTP modules, E], as loss_fn's
    `expert_load` orders them."""
    n = load.shape[0] - cfg.mtp_depth
    out = dict(new)
    for name, rows in (("layers", load[:n]), ("mtp", load[n:])):
        if "router_bias" in new.get(name, {}):
            out[name] = dict(new[name], router_bias=moe.balance_bias(
                old[name]["router_bias"], rows, cfg.moe_bias_update_rate))
    return out


def _cross_entropy(logits: jax.Array, targets: jax.Array, mask: jax.Array):
    # target-logit minus logsumexp == log_softmax gathered at the target, without
    # materializing a second [B,S,vocab] f32 tensor (1 GB/chip at 8B scale).
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return -((tgt - lse) * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def _token_losses(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """The cross entropy of `targets` [B, S] a position, float32 [B, S] (`_cross_entropy`'s form, not yet averaged)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]


def _expert_counters(load: jax.Array, chosen: jax.Array, cfg: ModelConfig) -> Dict[str, jax.Array]:
    """The dropless layers' counters of a step, a row an expert layer (the MTP modules' last):
    what the balance rule reads, what fell on the experts held here, the windows of the
    layer's buffer that load took (1: the step fitted the rows the held experts can expect;
    more: it overflowed them and was served all the same), and the experts each token chose
    [.., rows, k]."""
    lo, hi = moe.held_range(cfg)
    held = load[:, lo:hi].sum(-1)
    return dict(
        expert_load=load, held_assignments=held,
        fullest_held_expert_rows=load[:, lo:hi].max(-1), experts_chosen=chosen,
        expert_windows=moe.windows_walked(
            held.astype(jnp.int32), moe.window_rows(cfg, chosen.shape[-2])))


def block_diffusion_rows(tokens: jax.Array, masked: jax.Array, cfg: ModelConfig):
    """The block-diffusion objective's row: tokens [B, L] noised to cfg.diffusion_mask_token
    where `masked`, then the sequence as it is, as ONE row [noised ; clean] of 2L ids, and its
    positions 0..L-1 twice ([1, 2L]: every row of the batch shares them)."""
    with jax.named_scope("embed"), jax.named_scope("bd_rows"):
        noised = jnp.where(masked, jnp.asarray(cfg.diffusion_mask_token, tokens.dtype), tokens)
        return jnp.concatenate([noised, tokens], axis=1), jnp.tile(jnp.arange(tokens.shape[1]), 2)[None]


def block_diffusion_loss(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """The block-diffusion objective (cfg.diffusion_block; BD3-LMs, arXiv:2503.09573, as SDAR
    trains it). batch: tokens [B, L], and the realised noise a loader made
    (train/diffusion.py:block_diffusion_noise): masked [B, L] (which positions the noised
    copy hides) and p_mask [B] (the rate they were drawn at). Every sequence goes through
    the layers twice, noised and clean, as one row of 2L positions under the
    block-diffusion mask; the head reads the noised half, and the loss is the cross
    entropy at the masked positions, each at the token's OWN position, weighted by
    1 / p_mask, over B x L. Beside it `ce_loss`, their plain mean, and `masked_tokens`,
    their count; the experts' counters are over all 2L rows a sequence."""
    tokens, masked, p_mask = batch["tokens"], batch["masked"].astype(bool), batch["p_mask"]
    if "segment_ids" in batch or "loss_mask" in batch or cfg.n_experts and not cfg.moe_dropless:
        raise NotImplementedError(
            "the block-diffusion objective over packed documents (segment_ids), under a loss mask of the "
            "batch's own (the noise is the mask) or over the capacity-based experts")
    b, n = tokens.shape
    row, positions = block_diffusion_rows(tokens, masked, cfg)
    logits, _, aux = forward(params, row, cfg, positions=positions, return_aux=True, head_rows=n)
    with jax.named_scope("loss"):
        ce = _token_losses(logits, tokens) * masked
        count = masked.sum().astype(jnp.float32)
        loss = (ce / p_mask.astype(jnp.float32)[:, None]).sum() / (b * n)
        metrics = {"loss": loss, "ce_loss": ce.sum() / jnp.maximum(count, 1.0), "masked_tokens": count,
                   "tokens": jnp.asarray(b * n, jnp.float32)}
    if isinstance(aux, dict):
        metrics.update(_expert_counters(aux["load"], aux["chosen"], cfg))
    return loss, metrics


def exit_distribution(gate_logits: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The exit gates' logits a_1 .. a_{T-1} [T - 1, ...] (lambda_t = sigmoid(a_t): the chance of leaving
    behind recurrence t, having come that far) -> (p, log p) [T, ...] float32, the chance of leaving behind
    each recurrence: p_t = lambda_t prod_{j<t} (1 - lambda_j), and the last takes what is left, p_T =
    prod_{j<T} (1 - lambda_j). In logarithms, so that a gate that saturates makes no 0 x inf."""
    a = gate_logits.astype(jnp.float32)
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-a), axis=0)  # log prod_{j<=t} (1 - lambda_j)
    before = jnp.concatenate([jnp.zeros_like(stayed[:1]), stayed[:-1]])
    log_p = jnp.concatenate([jax.nn.log_sigmoid(a) + before, stayed[-1:]])
    return jnp.exp(log_p), log_p


def expected_exit_loss(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """A looped stack's objective (cfg.loop_steps = T > 1; Ouro's first stage, arXiv:2510.25741, which
    trains the gate and the model together). Behind every recurrence the head's next-token cross
    entropy a position, l_t, and (but for the last) the exit gate lambda_t = sigmoid(w_e . n_t + b_e) (the leaf `exit_gate` = [w_e ; b_e]);
    with p the exit distribution a position (`exit_distribution`),

        loss = mean over positions of [ sum_t p_t l_t - cfg.exit_entropy_weight H(p) ],  H(p) = -sum_t p_t log p_t,

    and the gradient runs through p and through every l_t. A recurrence's head and loss are
    rematerialised as a unit, so that one recurrence's [B, S, vocab] float32 logits live at a time and not T.
    Beside `loss`: `ce_loss` (the expectation alone), `exit_entropy` (H's mean), `exit_step_mean` (the
    mean of sum_t t p_t) and `ce_by_step` [T] (each recurrence's mean cross entropy)."""
    tokens, seg = batch["tokens"], batch.get("segment_ids")
    mask = batch.get("loss_mask")
    mask = (jnp.ones(tokens.shape, jnp.float32) if mask is None else mask.astype(jnp.float32))[:, 1:]
    outs = looped_outputs(params, tokens[:, :-1], cfg, segment_ids=None if seg is None else seg[:, :-1])

    targets = tokens[:, 1:]
    # the leaves a recurrence's outputs read, and no other: the rematerialised unit's backward pass makes a
    # gradient for every leaf it is handed
    read = {leaf: params[leaf] for leaf in ("embed" if cfg.tie_embeddings else "lm_head", "exit_gate")}

    def behind(read, n, gated):  # one recurrence's outputs: (l_t [B, S], a_t [B, S] or None)
        with jax.named_scope("lm_head"):
            logits = wsc(_head(read, n, cfg), "batch", "seq", "act_vocab")
        with jax.named_scope("loss"):
            ce = _token_losses(logits, targets)
        if not gated:  # the last recurrence takes what is left: its gate is never asked
            return ce, None
        with jax.named_scope(EXIT_GATE):
            return ce, jnp.einsum("bsd,d->bs", n.astype(jnp.float32), read["exit_gate"][:-1]) + read["exit_gate"][-1]

    if cfg.remat and cfg.remat_policy != "none":
        behind = jax.checkpoint(behind, static_argnums=(2,))
    ces, gates = zip(*(behind(read, n, t < cfg.loop_steps - 1) for t, n in enumerate(outs)))
    with jax.named_scope(EXIT_GATE):
        p, log_p = exit_distribution(jnp.stack(gates[:-1]))
    with jax.named_scope(EXIT_LOSS):
        count = jnp.maximum(mask.sum(), 1.0)
        mean = lambda a: (a * mask).sum((-2, -1)) / count  # noqa: E731  ([.., B, S] -> [..])
        ce = jnp.stack(ces)
        expected, entropy = mean((p * ce).sum(0)), mean(-(p * log_p).sum(0))
        loss = expected - cfg.exit_entropy_weight * entropy
        steps = jnp.arange(1, cfg.loop_steps + 1, dtype=jnp.float32)[:, None, None]
        metrics = {"loss": loss, "ce_loss": expected, "tokens": count, "exit_entropy": entropy,
                   "exit_step_mean": mean((p * steps).sum(0)), "ce_by_step": mean(ce)}
    return loss, metrics


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy. batch: tokens [B,S]; optional loss_mask/segment_ids.
    With MTP modules (cfg.mtp_depth) the mean of their losses is added at
    cfg.mtp_loss_weight; module m predicts the token m + 1 ahead. The configuration chooses
    the objective: under cfg.diffusion_block it is `block_diffusion_loss`, under
    cfg.loop_steps > 1 `expected_exit_loss`."""
    if cfg.diffusion_block:
        return block_diffusion_loss(params, batch, cfg)
    if cfg.loop_steps > 1:
        return expected_exit_loss(params, batch, cfg)
    tokens = batch["tokens"]
    seg = batch.get("segment_ids")
    logits, _, aux = forward(
        params, tokens[:, :-1], cfg,
        segment_ids=None if seg is None else seg[:, :-1], return_aux=True,
    )
    mask = batch.get("loss_mask")
    mask = jnp.ones(tokens.shape, jnp.float32) if mask is None else mask.astype(jnp.float32)
    with jax.named_scope("loss"):
        ce = _cross_entropy(logits, tokens[:, 1:], mask[:, 1:])
    metrics = {"tokens": jnp.maximum(mask[:, 1:].sum(), 1.0)}
    if cfg.hc_mult > 1:  # a projection that stopped converging shows in the step's metrics
        metrics.update(hc_res_row_err=aux["hc_err"][0], hc_res_col_err=aux["hc_err"][1])
        aux = aux if cfg.moe_dropless else aux["aux_loss"]
    if isinstance(aux, dict):  # the dropless layers' counters; no auxiliary loss: the selection bias balances (train/step.py)
        loss, load, chosen = ce, aux["load"], aux["chosen"]
    else:
        loss, load = ce + aux, None
        metrics["moe_aux_loss"] = aux
    if cfg.mtp_depth:
        if seg is not None or load is None:
            raise NotImplementedError(
                "MTP modules over packed documents (segment_ids) or the capacity-based experts")
        heads = mtp_logits(params, aux["hidden"], tokens, cfg)
        with jax.named_scope("loss"):
            mtp = sum(_cross_entropy(lg, tokens[:, m + 1:], mask[:, m + 1:])
                      for m, (lg, _) in enumerate(heads, 1)) / cfg.mtp_depth
        loss = loss + cfg.mtp_loss_weight * mtp
        metrics["mtp_loss"] = mtp
        load = jnp.concatenate([load] + [a["load"][None] for _, a in heads])
        chosen = jnp.concatenate([chosen] + [a["chosen"][None] for _, a in heads])
    if load is not None:
        metrics.update(_expert_counters(load, chosen, cfg))
    return loss, {"loss": loss, "ce_loss": ce, **metrics}
