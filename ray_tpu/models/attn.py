"""Attention as a layer's mixer, behind its own norm and residual: grouped-query heads
(an output gate a channel, a norm a head of q and k where the family has them) or latent
attention (MLA: q through a latent of its own or by one product, v heads of their own width
beside q's and k's), rotated or not. One shape with the other mixers (models/ssm.py, kda.py,
sconv.py): `AXES`, `init`, `mixer`, `n_params` and what llama.py's table of layer kinds
reads of each. `rms_norm` and `rope` are here because attention is their first user; the
serving programs (llm/model_runner.py) call the parts, `qkv_proj` and `attn_out`, around
their own cache.
"""
import contextlib
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import attention
from ray_tpu.ops.attention import FLASH_NAMES, ROTATED_NAMES, Rotation
from ray_tpu.ops.quant import as_weight as _w
from ray_tpu.parallel.sharding import auto_spec
from ray_tpu.parallel.sharding import with_sharding_constraint as wsc

from .config import ModelConfig

# what llama.py's table of layer kinds reads of a mixer (its comment says what each is): packed
# documents and a KV cache are served, `mixer` names its own scope (`attn`), and a remat policy
# keeps the rotated q and k beside the matmul outputs, the flash kernel's `out` and logsumexp
# under `full` (llama._maybe_remat has why)
LEAF, RECURRENT, SCOPE = "attn_norm", None, None
# the part's five pieces by name inside `attn`, as the recurrent mixers name theirs (`ssm_in_proj` ..
# `ssm_out_proj`): every operation of an attention part carries exactly one, forward, made again and
# backward (tests/test_attention_scopes.py), and none starts with `attn_window`, `attn_full` or
# `attn_bd`, which are prefixes the benchmark's older patterns match. `qkv_proj` and `_out_proj` open
# theirs themselves, so the serving programs that call them carry the same names
SCOPES = ("attn_in_proj", "attn_head_norm", "attn_core", "attn_gate", "attn_out_proj")
OUT_SCOPE = SCOPES[-1]  # llama._block puts an attention part's residual (and the norm behind it) there
KEPT = {"dots": ROTATED_NAMES, "dots_no_batch": ROTATED_NAMES, "full": FLASH_NAMES}


AXES = {  # every leaf a layer can have: grouped-query heads (a gate, a norm a head), or the latents
    "attn_norm": ("embed",), "wo": ("heads", "head_dim", "embed"),
    "wq": ("embed", "heads", "head_dim"), "wk": ("embed", "kv_heads", "head_dim"), "wv": ("embed", "kv_heads", "head_dim"),
    "wo_gate": ("embed", "heads", "head_dim"), "q_head_norm": ("head_dim",), "k_head_norm": ("head_dim",),
    "wq_a": ("embed", "latent"), "q_norm": ("latent",), "wq_b": ("latent", "heads", "head_dim"),
    "wkv_a": ("embed", "latent"), "kv_norm": ("latent",), "wkv_b": ("latent", "heads", "head_dim"),
}


def init(ks: jax.Array, cfg: ModelConfig) -> dict:
    """ks: four keys (the first four of the seven a layer splits its own into)."""
    d, hd, nh, nkv = cfg.d_model, cfg.head_dim, cfg.heads_held, cfg.kv_heads_held

    def norm(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.float32)

    s_in, s_out = d**-0.5, (2 * cfg.n_layers * d) ** -0.5
    out = {"attn_norm": jnp.ones((d,), jnp.float32)}
    if not cfg.latent_attention:
        out.update(wq=norm(ks[0], (d, nh, hd), s_in), wk=norm(ks[1], (d, nkv, hd), s_in),
                   wv=norm(ks[2], (d, nkv, hd), s_in), wo=norm(ks[3], (nh, hd, d), s_out))
        if cfg.attn_output_gate:
            out["wo_gate"] = norm(jax.random.fold_in(ks[0], 1), (d, nh, hd), s_in)
        if cfg.attn_qk_norm:
            out.update(q_head_norm=jnp.ones((hd,), jnp.float32), k_head_norm=jnp.ones((hd,), jnp.float32))
        return out
    if cfg.attn_output_gate or cfg.attn_qk_norm:
        raise NotImplementedError("an output gate or a norm a head on latent attention")
    qr, kvr, rd = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    ka, kb = jax.random.split(ks[1])
    # q through its latent, or (q_lora_rank 0: Kimi Linear's q_lora_rank null) by one product
    q = ({"wq_a": norm(ks[0], (d, qr), s_in), "q_norm": jnp.ones((qr,), jnp.float32),
          "wq_b": norm(ka, (qr, nh, hd), qr**-0.5)} if qr else {"wq": norm(ks[0], (d, nh, hd), s_in)})
    return {
        **out, **q,
        # the latent and, behind it, the rotated key every head shares
        "wkv_a": norm(kb, (d, kvr + rd), s_in), "kv_norm": jnp.ones((kvr,), jnp.float32),
        "wkv_b": norm(ks[2], (kvr, nh, cfg.qk_nope_head_dim + cfg.v_dim), kvr**-0.5),
        "wo": norm(ks[3], (nh, cfg.v_dim, d), s_out),
    }


def n_params(cfg: ModelConfig) -> int:
    """What `init` makes, counted."""
    d, h = cfg.d_model, cfg.heads_held
    if cfg.latent_attention:
        qr = cfg.q_lora_rank
        return ((d * qr + qr * h * cfg.head_dim + qr if qr else d * h * cfg.head_dim)
                + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                + cfg.kv_lora_rank * h * (cfg.qk_nope_head_dim + cfg.v_dim)
                + h * cfg.v_dim * d + cfg.kv_lora_rank + d)
    return (d * cfg.head_dim * ((2 + cfg.attn_output_gate) * h + 2 * cfg.kv_heads_held)
            + 2 * cfg.head_dim * cfg.attn_qk_norm + d)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale).astype(dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor (DeepSeek-V3's `yarn_get_mscale`): 1 + 0.1 mscale ln(factor), and 1 where nothing is scaled."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(cfg: ModelConfig, d: int) -> Tuple[int, int]:
    """(low, high): the pairs of a rotated slice d wide between which YaRN blends, from the pair that turns
    cfg.rope_beta_fast times over cfg.rope_original_len positions (below it theta's own frequencies stand) to the one
    that turns cfg.rope_beta_slow times (above it they are divided by cfg.rope_factor)."""
    def pair(turns):
        return d * math.log(cfg.rope_original_len / (turns * 2 * math.pi)) / (2 * math.log(cfg.rope_theta))

    return max(math.floor(pair(cfg.rope_beta_fast)), 0), min(math.ceil(pair(cfg.rope_beta_slow)), d - 1)


def yarn(cfg: ModelConfig, d: int) -> Optional[Tuple[np.ndarray, float]]:
    """(the d / 2 blended frequencies of a rotated slice d wide, what cos and sin are multiplied by) under YaRN, or
    None where cfg.rope_factor scales nothing."""
    if cfg.rope_factor == 1.0:
        return None
    low, high = yarn_range(cfg, d)
    own = cfg.rope_theta ** (-np.arange(d // 2, dtype=np.float64) / (d // 2))
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    freqs = own * (1 - ramp) + own / cfg.rope_factor * ramp
    return freqs.astype(np.float32), (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
                                      / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))


def softmax_scale(cfg: ModelConfig) -> Optional[float]:
    """Attention's softmax scale where it is not head_dim^-1/2 (None): under YaRN times mscale(rope_mscale_all_dim)^2."""
    if cfg.rope_factor == 1.0:
        return None
    return cfg.head_dim ** -0.5 * yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2


def rope(x: jax.Array, positions: jax.Array, theta: float, scaled: Optional[Tuple[np.ndarray, float]] = None) -> jax.Array:
    """Rotate-half RoPE (HF Llama convention). x: [B, S, H, D], positions: [B, S]. `scaled` (`yarn`): the
    frequencies in theta's place, and a factor on cos and sin."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2)) if scaled is None else jnp.asarray(scaled[0])
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaled is not None and scaled[1] != 1.0:
        cos, sin = cos * scaled[1], sin * scaled[1]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def qkv_proj(x: jax.Array, lp: dict, cfg: ModelConfig, positions: Optional[jax.Array]):
    """Attention's inputs for one layer: norm, the projections, a norm a head of q and k
    where the layer has one (cfg.attn_qk_norm: before the rotation), RoPE.
    x [B, S, D], positions [B, S] -> q [B, S, H, hd], k [B, S, KV, hd] and v [B, S, KV, cfg.v_dim].
    Without positions q and k come back un-rotated: the caller hands the rotation on, or
    there is none (latent attention rotates a slice of its heads here, and only here)."""
    dt = x.dtype
    with jax.named_scope("attn_in_proj"):  # (the latent path keeps `mla_q` / `mla_kv` inside it)
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
        if cfg.latent_attention:
            return _latent_qkv(h, lp, cfg, positions)
        q = jnp.einsum("bsd,dhk->bshk", h, _w(lp["wq"], dt))
        k = jnp.einsum("bsd,dhk->bshk", h, _w(lp["wk"], dt))
        v = jnp.einsum("bsd,dhk->bshk", h, _w(lp["wv"], dt))
    with jax.named_scope("attn_head_norm"):  # and the rotation where it is not the kernels' own pass
        if "q_head_norm" in lp:
            q, k = rms_norm(q, lp["q_head_norm"], cfg.norm_eps), rms_norm(k, lp["k_head_norm"], cfg.norm_eps)
        if positions is None:
            return q, k, v
        return rope(q, positions, cfg.rope_theta), rope(k, positions, cfg.rope_theta), v


def rope_pairs_to_halves(d: int):
    """Where each column of a rotated slice lies in a published checkpoint: the
    DeepSeek-V3 lineage rotates the pairs (2i, 2i + 1), `rope` the pairs (i, i + d/2).
    Column j here is the checkpoint's column perm[j]; scores do not see the order, as q
    and k share it. (models/reference/ rotates pairs on the columns put back.)"""
    return [2 * i for i in range(d // 2)] + [2 * i + 1 for i in range(d // 2)]


def _latent_qkv(h: jax.Array, lp: dict, cfg: ModelConfig, positions: Optional[jax.Array]):
    """Latent attention's q, k and v from the normed input h [B, S, D]: q through its
    low-rank latent where the layer has one, else by one product; k's first part and v from
    the shared latent, k's second part one key for all heads; the second parts of q and k
    rotated where there are `positions` (None: cfg.attention_rotation false, Kimi Linear's
    mla_use_nope: both are used as they come). Nothing is absorbed: what comes out is plain
    multi-head attention's input, [B, S, H, nope + rope] twice and [B, S, H, v], v at its own
    width."""
    dt = h.dtype
    nope, kvr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    scaled = yarn(cfg, cfg.qk_rope_head_dim)
    with jax.named_scope("mla_q"):
        if "wq_a" in lp:
            cq = rms_norm(jnp.einsum("bsd,dr->bsr", h, _w(lp["wq_a"], dt)), lp["q_norm"], cfg.norm_eps)
            q = jnp.einsum("bsr,rhk->bshk", cq, _w(lp["wq_b"], dt))
        else:
            q = jnp.einsum("bsd,dhk->bshk", h, _w(lp["wq"], dt))
        if positions is not None:
            q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions, cfg.rope_theta, scaled)], -1)
    with jax.named_scope("mla_kv"):
        ckv = jnp.einsum("bsd,dr->bsr", h, _w(lp["wkv_a"], dt))
        k_rot = ckv[:, :, None, kvr:]  # [B, S, 1, rope]
        if positions is not None:
            k_rot = rope(k_rot, positions, cfg.rope_theta, scaled)
        kv = jnp.einsum("bsr,rhk->bshk", rms_norm(ckv[..., :kvr], lp["kv_norm"], cfg.norm_eps),
                        _w(lp["wkv_b"], dt))
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rot, (*kv.shape[:3], k_rot.shape[-1]))], -1)
    return q, k, kv[..., nope:]


def _out_proj(attn: jax.Array, lp: dict, dt) -> jax.Array:
    with jax.named_scope(OUT_SCOPE):
        return jnp.einsum("bshk,hkd->bsd", attn, _w(lp["wo"], dt))


def attn_out(x: jax.Array, attn: jax.Array, lp: dict) -> jax.Array:
    """Output projection of attn [B, S, H, hd] and the residual (the serving programs')."""
    return x + _out_proj(attn, lp, x.dtype)


def mixer(x, lp, cfg, positions, segment_ids, cache_kv, cache_len, windowed=False):
    """The block's attention: (attention's output, which llama._block adds to x; updated
    (k, v) if caching). `windowed` (a `W` part of the pattern): inside cfg.attn_window and
    rotated whatever cfg.attention_rotation says, which is the `*` parts'. Under
    cfg.diffusion_block x is the doubled row [noised ; clean] and the mask is the
    block-diffusion objective's (ops/attention.py:block_diffusion_keep)."""
    window = cfg.attn_window if windowed else None
    doubled = cfg.diffusion_block or None  # x is the block-diffusion objective's row [noised ; clean]
    if doubled and (cache_kv is not None or segment_ids is not None or window or cfg.latent_attention
                    or cfg.attention_impl in ("ring", "ulysses")):
        raise NotImplementedError(
            "block-diffusion attention (cfg.diffusion_block) under a KV cache (generation a block at a time is "
            "not built: llm/engine.py yields one token a step), over packed documents (segment_ids), inside "
            "a window, on latent attention or on the ring / Ulysses paths")
    if window and (cache_kv is not None or cfg.attention_impl in ("ring", "ulysses")):
        raise NotImplementedError(
            "an attention window under a KV cache (no window of the cache is kept or masked) or on the "
            "ring / Ulysses paths (ops/ring_attention.py takes none)")
    # named scopes: metadata only (free at run time); what a reader of the
    # profile uses to tell one fusion from another. A model of both kinds of attention part
    # names which one this is, inside `attn`
    kind = jax.named_scope("attn_window" if windowed else "attn_full") if cfg.attn_window else contextlib.nullcontext()
    with jax.named_scope("attn"), kind:
        # ops.attention rotates q and k itself (in its kernel's own pass over them, where
        # the Pallas path runs); a cache or the ring takes them rotated
        rotate = cfg.attention_rotation or windowed
        deferred = (rotate and cache_kv is None and cfg.attention_impl not in ("ring", "ulysses")
                    and not cfg.latent_attention)
        q, k, v = qkv_proj(x, lp, cfg, positions if rotate and not deferred else None)
        with jax.named_scope("attn_core"):
            q = wsc(q, "batch", "seq", "act_heads", "head_dim")

            new_kv = None
            if cache_kv is not None:
                ck, cv = cache_kv
                ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_len, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_len, axis=1)
                new_kv = (ck, cv)
                attn = attention(
                    q, ck, cv, causal=True, q_offset=cache_len, kv_valid_len=cache_len + q.shape[1],
                    scale=softmax_scale(cfg),
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
            elif doubled:  # the core alone under a scope of its own: the rotation and the kernels
                with jax.named_scope("attn_bd"):
                    attn = attention(q, k, v, causal=False, block_diffusion=doubled, impl=cfg.attention_impl,
                                     shard_spec=auto_spec("batch", None, "act_heads", None),
                                     rotation=Rotation(positions, cfg.rope_theta, rope) if deferred else None)
            else:
                attn = attention(q, k, v, causal=True, segment_ids=segment_ids, impl=cfg.attention_impl,
                                 scale=softmax_scale(cfg),
                                 shard_spec=auto_spec("batch", None, "act_heads", None), window=window,
                                 rotation=Rotation(positions, cfg.rope_theta, rope) if deferred else None)
        if "wo_gate" in lp:  # a channel of the output, from the layer's normed input
            with jax.named_scope("attn_gate"):
                gate = jnp.einsum("bsd,dhk->bshk", rms_norm(x, lp["attn_norm"], cfg.norm_eps),
                                  _w(lp["wo_gate"], x.dtype))
                attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(attn.dtype)
        return _out_proj(attn, lp, x.dtype), new_kv
