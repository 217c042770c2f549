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

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention
from ray_tpu.ops.attention import ROTATED_NAMES, Rotation
from ray_tpu.ops.quant import as_weight as _w
from ray_tpu.parallel.sharding import auto_spec
from ray_tpu.parallel.sharding import with_sharding_constraint as wsc

from .config import ModelConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------- init

def param_axes(cfg: ModelConfig) -> Params:
    """Logical-axis tree mirroring init() output (layers stacked on a leading 'layer' axis)."""

    def L(*axes):
        return ("layer",) + axes

    layers = {
        "attn_norm": L("embed"),
        "wq": L("embed", "heads", "head_dim"),
        "wk": L("embed", "kv_heads", "head_dim"),
        "wv": L("embed", "kv_heads", "head_dim"),
        "wo": L("heads", "head_dim", "embed"),
        "mlp_norm": L("embed"),
    }
    if cfg.n_experts > 0:
        from . import moe as _moe

        layers.update({k: L(*axes) for k, axes in _moe.EXPERT_AXES.items()})
    else:
        layers.update({
            "w_gate": L("embed", "mlp"),
            "w_up": L("embed", "mlp"),
            "w_down": L("mlp", "embed"),
        })
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def init(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Initialize parameters (f32). Scaled-normal init, wo/w_down scaled by depth."""
    k_emb, k_head, k_layers = jax.random.split(rng, 3)
    d, hd, nh, nkv, ff = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff

    def norm(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.float32)

    def layer_init(key):
        ks = jax.random.split(key, 7)
        s_in = d**-0.5
        s_out = (2 * cfg.n_layers * d) ** -0.5
        out = {
            "attn_norm": jnp.ones((d,), jnp.float32),
            "wq": norm(ks[0], (d, nh, hd), s_in),
            "wk": norm(ks[1], (d, nkv, hd), s_in),
            "wv": norm(ks[2], (d, nkv, hd), s_in),
            "wo": norm(ks[3], (nh, hd, d), s_out),
            "mlp_norm": jnp.ones((d,), jnp.float32),
        }
        if cfg.n_experts > 0:
            from . import moe as _moe

            out.update(_moe.init_expert_weights(ks[4], cfg))
        else:
            out.update({
                "w_gate": norm(ks[4], (d, ff), s_in),
                "w_up": norm(ks[5], (d, ff), s_in),
                "w_down": norm(ks[6], (ff, d), (2 * cfg.n_layers * ff) ** -0.5),
            })
        return out

    params: Params = {
        "embed": norm(k_emb, (cfg.vocab_size, d), 1.0),
        "layers": jax.vmap(layer_init)(jax.random.split(k_layers, cfg.n_layers)),
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = norm(k_head, (d, cfg.vocab_size), d**-0.5)
    return params


# ------------------------------------------------------------------------- kernels

def _maybe_remat(body, cfg: ModelConfig):
    """Per-layer rematerialization with a selectable policy (cfg.remat_policy):
    'full' recomputes everything; 'dots' saves matmul outputs so only cheap
    elementwise ops replay in the backward pass (XLA's usual MFU sweet spot)."""
    policy = cfg.remat_policy
    if not cfg.remat or policy == "none":
        return body
    # the rotated q and k (ops/flash_attention.py names them) are kept beside the matmul
    # outputs: the backward of the projections needs dQ and dK, never their own output,
    # so the un-rotated pair is dropped for them and the rotation is not run again
    policies = jax.checkpoint_policies
    rotated = policies.save_only_these_names(*ROTATED_NAMES)
    if policy == "dots":
        return jax.checkpoint(
            body, policy=policies.save_from_both_policies(policies.checkpoint_dots, rotated))
    if policy == "dots_no_batch":
        return jax.checkpoint(body, policy=policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable, rotated))
    if policy != "full":
        raise ValueError(
            f"unknown remat_policy {policy!r} (expected full | dots | dots_no_batch | none)")
    return jax.checkpoint(body)


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
    """
    table = params["embed"].astype(cfg.activation_dtype)
    try:
        mesh = jax.sharding.get_abstract_mesh()
        sharded = mesh is not None and not mesh.empty and mesh.shape.get("tp", 1) > 1
    # graftlint: allow[swallowed-exception] degrades to the coded fallback (sharded = False) by design
    except Exception:
        sharded = False
    if not sharded or tokens.shape[-1] < _ONE_HOT_MIN_ROW:
        return table[tokens]
    onehot = jax.nn.one_hot(tokens, table.shape[0], dtype=table.dtype)
    return jnp.einsum("bsv,vd->bsd", onehot, table)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale).astype(dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE (HF Llama convention). x: [B, S, H, D], positions: [B, S]."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- block
# The decoder block's arithmetic, written once. A program is these parts around an
# attention over its own cache: _block (train step, prefill: ops.attention or ring
# attention) and llm/model_runner.py:_window_core (decode and the verify window:
# per-slot lengths over a layout adapter's cache view). Math only — a sharding
# constraint comes from the caller, so the serving programs carry none.

def _unconstrained(x: jax.Array, *logical_axes) -> jax.Array:
    return x


def qkv_proj(x: jax.Array, lp: Params, cfg: ModelConfig, positions: Optional[jax.Array]):
    """Attention's inputs for one layer: norm, the three projections, RoPE.
    x [B, S, D], positions [B, S] -> q [B, S, H, hd], k and v [B, S, KV, hd].
    Without positions q and k come back un-rotated: the caller hands the rotation on."""
    dt = x.dtype
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, _w(lp["wq"], dt))
    k = jnp.einsum("bsd,dhk->bshk", h, _w(lp["wk"], dt))
    v = jnp.einsum("bsd,dhk->bshk", h, _w(lp["wv"], dt))
    if positions is None:
        return q, k, v
    return rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta), v


def attn_out(x: jax.Array, attn: jax.Array, lp: Params) -> jax.Array:
    """Output projection of attn [B, S, H, hd] and the residual."""
    return x + jnp.einsum("bshk,hkd->bsd", attn, _w(lp["wo"], x.dtype))


def feed_forward(x: jax.Array, lp: Params, cfg: ModelConfig,
                 token_mask: Optional[jax.Array] = None, constrain=_unconstrained):
    """Norm, the dense or MoE feed-forward, residual. Returns (x, moe aux loss).
    token_mask [B, S] (1 = real) keeps pad tokens and inactive slots out of the
    experts' capacity; `constrain(array, *logical_axes)` is the caller's sharding
    constraint on the dense product."""
    dt = x.dtype
    h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    if cfg.n_experts > 0:
        from . import moe as _moe

        b, s, d = h.shape
        y2, aux = _moe.moe_mlp(
            h.reshape(b * s, d), lp["router"], lp["w_gate"], lp["w_up"],
            lp["w_down"], cfg,
            mask=None if token_mask is None else token_mask.reshape(b * s),
        )
        down = y2.reshape(b, s, d)
    else:
        gate = jnp.einsum("bsd,df->bsf", h, _w(lp["w_gate"], dt))
        up = jnp.einsum("bsd,df->bsf", h, _w(lp["w_up"], dt))
        ff = constrain(jax.nn.silu(gate) * up, "batch", "seq", "act_mlp")
        down = jnp.einsum("bsf,fd->bsd", ff, _w(lp["w_down"], dt))
        aux = jnp.zeros((), jnp.float32)
    return x + down, aux


def output_head(params: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Final norm and the (tied or untied) head: x [B, S, D] -> f32 logits [B, S, vocab]."""
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, _w(head, cfg.activation_dtype))
    return logits.astype(jnp.float32)


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
):
    """One decoder block. Returns (x, updated (k,v) if caching, moe aux loss)."""
    # named scopes: metadata only (free at run time); what a reader of the
    # profile uses to tell one fusion from another
    with jax.named_scope("attn"):
        # ops.attention rotates q and k itself (in its kernel's own pass over them, where
        # the Pallas path runs); a cache or the ring takes them rotated
        deferred = cache_kv is None and cfg.attention_impl not in ("ring", "ulysses")
        q, k, v = qkv_proj(x, lp, cfg, None if deferred else positions)
        q = wsc(q, "batch", "seq", "act_heads", "head_dim")

        new_kv = None
        if cache_kv is not None:
            ck, cv = cache_kv
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_len, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_len, axis=1)
            new_kv = (ck, cv)
            attn = attention(
                q, ck, cv, causal=True, q_offset=cache_len, kv_valid_len=cache_len + q.shape[1]
            )
        elif cfg.attention_impl in ("ring", "ulysses"):
            # Sequence-parallel attention: activations stay seq-sharded over "sp"; KV chunks
            # ride the ICI ring (ops/ring_attention.py). If "sp" is already bound manually
            # (pipeline stage traced with extra_manual=("sp",)), call the collective form
            # directly — nested shard_map is not composable.
            from ray_tpu.ops import ring_attention as ra
            from ray_tpu.parallel.sharding import active_manual_axes

            if "sp" in active_manual_axes():
                if cfg.attention_impl == "ring":
                    attn = ra.ring_attention(q, k, v, causal=True, segment_ids=segment_ids)
                else:
                    if segment_ids is not None:
                        # mirror ring_attention_sharded's refusal — dropping the
                        # packing mask here would silently attend across documents
                        raise NotImplementedError(
                            "segment_ids only supported with impl='ring'")
                    attn = ra.ulysses_attention(q, k, v, causal=True)
            else:
                attn = ra.ring_attention_sharded(
                    q, k, v, causal=True, segment_ids=segment_ids, impl=cfg.attention_impl
                )
        else:
            attn = attention(q, k, v, causal=True, segment_ids=segment_ids, impl=cfg.attention_impl,
                             shard_spec=auto_spec("batch", None, "act_heads", None),
                             rotation=Rotation(positions, cfg.rope_theta, rope))
        x = wsc(attn_out(x, attn, lp), "batch", "seq", "act_embed")

    with jax.named_scope("mlp"):
        x, aux = feed_forward(x, lp, cfg, token_mask, constrain=wsc)
        x = wsc(x, "batch", "seq", "act_embed")
    return x, new_kv, aux


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

    moe = cfg.n_experts > 0
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
        (out, aux), _ = jax.lax.scan(fn, (xm, aux0), stage_params)
        return (out, aux) if moe else out

    m = cfg.pipeline_microbatches or pp

    out = pipeline(
        stage_fn,
        stacked,
        x,
        num_microbatches=m,
        x_spec=P(None, "sp", None) if seq_manual else None,
        extra_manual=("sp",) if seq_manual else (),
        with_aux=moe,
        side=side,
        side_spec=side_spec,
    )
    return out if moe else (out, jnp.zeros((), jnp.float32))


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
):
    """tokens [B, S] -> (logits [B, S, vocab] f32, updated cache or None).

    With return_aux=True also returns the summed MoE load-balancing loss (zero for
    dense configs) as a third element."""
    b, s = tokens.shape
    if positions is None:  # one row, which every row of the batch shares
        start = cache.length if cache is not None else 0
        positions = jnp.arange(s)[None, :] + start
    with jax.named_scope("embed"):
        x = wsc(embed_tokens(params, tokens, cfg), "batch", "seq", "act_embed")

    new_cache = None
    if cfg.pipeline_stages > 1 and cache is None:
        x, aux_total = _pipeline_layers(x, params, cfg, positions, segment_ids,
                                        token_mask)
    else:
        # one loop: a layer's parameters and, when there is a cache, its K/V
        # (None is an empty pytree: the scan then carries no K/V in or out)
        cache_len = None if cache is None else cache.length

        def body(h, xs):
            lp, cache_kv = xs
            h, new_kv, aux = _block(h, lp, cfg, positions, segment_ids, cache_kv,
                                    cache_len, token_mask)
            return h, (new_kv, aux)

        x, (new_kv, auxs) = jax.lax.scan(
            _maybe_remat(body, cfg), x,
            (params["layers"], None if cache is None else (cache.k, cache.v)))
        aux_total = auxs.sum()
        if cache is not None:
            new_cache = KVCache(k=new_kv[0], v=new_kv[1], length=cache.length + s)

    with jax.named_scope("lm_head"):
        logits = wsc(output_head(params, x, cfg), "batch", "seq", "act_vocab")
    if return_aux:
        return logits, new_cache, aux_total
    return logits, new_cache


def loss_fn(
    params: Params,
    batch: Dict[str, jax.Array],
    cfg: ModelConfig,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross entropy. batch: tokens [B,S]; optional loss_mask/segment_ids."""
    tokens = batch["tokens"]
    seg = batch.get("segment_ids")
    logits, _, aux = forward(
        params, tokens[:, :-1], cfg,
        segment_ids=None if seg is None else seg[:, :-1], return_aux=True,
    )
    with jax.named_scope("loss"):
        targets = tokens[:, 1:]
        # target-logit minus logsumexp == log_softmax gathered at the target, without
        # materializing a second [B,S,vocab] f32 tensor (1 GB/chip at 8B scale).
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        ll = tgt - lse
        mask = batch.get("loss_mask")
        mask = jnp.ones_like(ll) if mask is None else mask[:, 1:].astype(ll.dtype)
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = -(ll * mask).sum() / denom
        loss = ce + aux
    return loss, {"loss": loss, "ce_loss": ce, "moe_aux_loss": aux, "tokens": denom}
