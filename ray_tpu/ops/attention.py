"""Attention entry point with backend dispatch.

`attention()` is the single call sites use; it routes to the Pallas TPU flash kernel
when running on TPU and to a pure-XLA reference implementation elsewhere (CPU tests,
debugging). Both accept GQA (n_kv_heads <= n_heads) and causal masking.

Shapes (batch, seq, heads, head_dim) throughout — "BSHD".
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ray_tpu.parallel.sharding import partitioned_by_gspmd


# checkpoint names of the rotated q and k where the Pallas path's rotate kernel made
# them (ops/flash_attention.py), for a remat policy that keeps them
ROTATED_NAMES = ("rope_q", "rope_k")
# checkpoint names of the forward flash kernel's output and logsumexp where its forward rule
# made them (ops/flash_attention.py), for a remat policy that keeps them: with both kept the
# backward kernels read them and a rematerialised layer does not run the forward kernel again
FLASH_NAMES = ("flash_out", "flash_lse")


class Rotation(NamedTuple):
    """A rotary embedding that q and k still lack when they reach `attention`, which
    then applies it: in the Pallas path's own rotate kernel (rotate-half,
    ops/flash_attention.py:rope_to_heads), elsewhere as `apply(x, positions, theta)`,
    the caller's jax.numpy statement of the rotation."""

    positions: jax.Array  # [B, S], or [1, S] where every row of the batch shares them
    theta: float
    apply: Callable[[jax.Array, jax.Array, float], jax.Array]


def block_diffusion_keep(qi: jax.Array, kj: jax.Array, half: int, block: int) -> jax.Array:
    """The block-diffusion mask (BD3-LMs, arXiv:2503.09573) over a row laid [noised ; clean],
    `half` positions each: whether query row `qi` keeps key row `kj` (broadcast against each
    other). A key is kept iff it is clean and its block lies before the query's, or it is of
    the query's half and block: a noised block sees the clean blocks before it and itself,
    both ways inside the block; a clean block the clean blocks up to and including itself."""
    clean_q, clean_k = qi >= half, kj >= half
    block_q, block_k = (qi - half * clean_q) // block, (kj - half * clean_k) // block
    return (clean_k & (block_k < block_q)) | ((clean_k == clean_q) & (block_k == block_q))


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] by head repetition (GQA)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    q_offset: Optional[jax.Array] = None,
    kv_valid_len: Optional[jax.Array] = None,
    window: Optional[int] = None,
    block_diffusion: Optional[int] = None,
) -> jax.Array:
    """Pure-XLA attention. Numerically the ground truth for the Pallas kernel tests.

    q: [B, Sq, H, D]; k: [B, Skv, Hkv, D]; v: [B, Skv, Hkv, Dv]. Returns [B, Sq, H, Dv].
    `segment_ids`: [B, Skv] int array; attention only within equal segments (packing).
    `q_offset`: kv index of query row 0 (decode-with-cache); default aligns the ends.
    `kv_valid_len`: kv slots >= this are masked out (padded cache tail).
    `window` (causal): key j is kept for query i where 0 <= i - j < window.
    `block_diffusion` (not causal): the row is [noised ; clean], in blocks of this many
    positions (`block_diffusion_keep`).
    """
    # GQA as a grouped contraction: q heads are viewed as [Hkv, n_rep] and K/V
    # are never repeated. (Broadcasting K/V to H heads first is the same math,
    # but at short sequences the v5e compiler overflows its stack costing the
    # convolution it fuses that broadcast into — PR 22, on the chip.)
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    qg = q.reshape(b, sq, hkv, h // hkv, d)
    scale = scale if scale is not None else 1.0 / (d**0.5)
    # f32 logits regardless of input dtype: MXU accumulates in f32 on TPU anyway.
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    kj = jnp.arange(skv)[None, :]
    if causal:
        if q_offset is None:
            q_offset = skv - sq
        qi = jnp.arange(sq)[:, None] + q_offset
        seen = kj <= qi if window is None else (kj <= qi) & (kj > qi - window)
        logits = jnp.where(seen, logits, -jnp.inf)
    if block_diffusion is not None:
        logits = jnp.where(block_diffusion_keep(jnp.arange(sq)[:, None], kj, sq // 2, block_diffusion),
                           logits, -jnp.inf)
    if kv_valid_len is not None:
        logits = jnp.where(kj < kv_valid_len, logits, -jnp.inf)
    if segment_ids is not None:
        seg_q = segment_ids[:, -sq:]
        mask = seg_q[:, :, None] == segment_ids[:, None, :]
        logits = jnp.where(mask[:, None, None, :, :], logits, -jnp.inf)
    # Rows with no valid kv (fully masked) softmax to NaN; zero them instead.
    probs = jnp.nan_to_num(jax.nn.softmax(logits, axis=-1))
    out = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), v)
    return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    q_offset: Optional[jax.Array] = None,
    kv_valid_len: Optional[jax.Array] = None,
    block_kv: int = 512,
    window: Optional[int] = None,
    block_diffusion: Optional[int] = None,
) -> jax.Array:
    """Online-softmax attention over KV blocks ("flash in XLA").

    Scans KV in `block_kv` chunks with a running (max, sum, acc) carry, so peak
    memory is O(B*H*Sq*block_kv) instead of O(B*H*Sq*Skv). Pure lax.scan — compiles
    on any backend; the fallback for long sequences when the Pallas kernel can't
    tile the shape (and the path the 8B HBM-budget proof compiles on CPU).
    Same masking surface as attention_reference.
    """
    n_rep = q.shape[2] // k.shape[2]
    b, sq, h, d = q.shape
    skv = k.shape[1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    n_blk = -(-skv // block_kv)
    pad = n_blk * block_kv - skv
    seg_q = None if segment_ids is None else segment_ids[:, -sq:]
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if segment_ids is not None:
            # Padded slots get segment id -1 (never matches a real segment).
            segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)), constant_values=-1)
    if q_offset is None:
        q_offset = skv - sq
    qi = jnp.arange(sq)[:, None] + q_offset  # [Sq, 1] absolute kv positions

    # Chunk the UN-repeated kv heads; GQA repetition happens per 512-slot block
    # inside the scan body so the repeated copies never exist over the full Skv.
    hkv = k.shape[2]
    kb = k.reshape(b, n_blk, block_kv, hkv, d).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, n_blk, block_kv, hkv, v.shape[-1]).transpose(1, 0, 2, 3, 4)
    seg_b = (
        None
        if segment_ids is None
        else segment_ids.reshape(b, n_blk, block_kv).transpose(1, 0, 2)
    )
    blk_idx = jnp.arange(n_blk)

    def body(carry, xs):
        m, l, acc = carry
        if seg_b is None:
            i, kc, vc = xs
            seg_c = None
        else:
            i, kc, vc, seg_c = xs
        kc = _repeat_kv(kc, n_rep)
        vc = _repeat_kv(vc, n_rep)
        kj = i * block_kv + jnp.arange(block_kv)[None, :]  # [1, blk]
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc, preferred_element_type=jnp.float32)
        logits = logits * scale
        neg = jnp.float32(-1e30)  # finite: keeps fully-masked rows NaN-free
        if causal:
            seen = kj <= qi if window is None else (kj <= qi) & (kj > qi - window)
            logits = jnp.where(seen[None, None], logits, neg)
        if block_diffusion is not None:
            logits = jnp.where(block_diffusion_keep(qi, kj, sq // 2, block_diffusion)[None, None], logits, neg)
        valid = kv_valid_len if kv_valid_len is not None else skv
        logits = jnp.where((kj < valid)[None, None], logits, neg)
        if seg_c is not None:
            mask = seg_q[:, :, None] == seg_c[:, None, :]  # [B, Sq, blk]
            logits = jnp.where(mask[:, None], logits, neg)
        blk_max = jnp.max(logits, axis=-1)  # [B, H, Sq]
        new_m = jnp.maximum(m, blk_max)
        corr = jnp.exp(m - new_m)
        p = jnp.exp(logits - new_m[..., None])  # [B, H, Sq, blk]
        # Kill masked slots exactly: when a whole row is masked new_m == neg and
        # exp(logits - new_m) == 1, which would silently average v.
        p = jnp.where(logits > neg * 0.5, p, 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vc.dtype), vc)
        acc = acc * corr.transpose(0, 2, 1)[..., None] + pv.astype(jnp.float32)
        return (new_m, l, acc), None

    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    acc0 = jnp.zeros((b, sq, h, v.shape[-1]), jnp.float32)
    xs = (blk_idx, kb, vb) if seg_b is None else (blk_idx, kb, vb, seg_b)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, acc0), xs)
    l_t = l.transpose(0, 2, 1)[..., None]  # [B, Sq, H, 1]
    out = jnp.where(l_t > 0, acc / jnp.maximum(l_t, 1e-30), 0.0)
    return out.astype(q.dtype)


# Below this many Sq*Skv logit elements the full [B,H,Sq,Skv] tensor is small enough
# that the one-shot reference path fuses better than a scan of blocks. A product
# threshold keeps single-row decode (Sq=1, any cache length) on the fused path —
# its logits are [B,H,1,Skv], tiny, and a sequential block scan would only add
# per-token latency.
def _chunked_min_logits() -> int:
    """CONFIG.chunked_attention_min_logits, read at trace time."""
    from ray_tpu.config import CONFIG

    return CONFIG.chunked_attention_min_logits

# TPU traces of a training shape that left the Pallas kernel for an XLA path
# (chip_smoke.py asserts it stays 0)
xla_fallback_count = 0


def _log_fallback(q_shape, k_shape, impl: str) -> None:
    """A training shape on a TPU that misses the Pallas kernel is a perf cliff
    (Mosaic can't tile e.g. head_dim 96): say so every time it is traced."""
    import logging

    global xla_fallback_count
    xla_fallback_count += 1
    logging.getLogger(__name__).warning(
        "attention: TPU shape q=%s kv=%s is not Mosaic-tileable "
        "(a head width of q/k or of v that is no multiple of 64, or seq block alignment); using %s XLA path",
        tuple(q_shape), tuple(k_shape), impl,
    )


def _flash_per_shard(q, k, v, *, causal, segment_ids, scale, shard_spec, rotation=None, window=None,
                     block_diffusion=None):
    """The Pallas kernel under an ambient mesh. GSPMD cannot partition a Mosaic
    kernel, and Mosaic refuses to lower while ANY mesh axis is still
    automatic, so the kernel is called per shard with every such axis made
    manual around it. `shard_spec` is the caller's layout of q/k/v over those
    axes; axes an enclosing region already bound manually (a pipeline stage,
    the bucketed grad sync) stay so. Without a spec, or with nothing left to
    split, the kernel is called as it is. With a `rotation` q and k come un-rotated, and
    the rotate kernel runs in the same region, on each shard's own positions."""
    from jax.sharding import PartitionSpec as P

    from .flash_attention import flash_attention

    def kernel(q, k, v, rows):
        return flash_attention(q, k, v, causal=causal, scale=scale, segment_ids=rows.get("seg"), window=window,
                               block_diffusion=block_diffusion,
                               rope=None if rotation is None else (rows["pos"], rotation.theta))

    # what comes a row of the batch (or one row for all of it): segment ids, positions
    rows = {} if segment_ids is None else {"seg": segment_ids}
    if rotation is not None:
        rows["pos"] = rotation.positions
    if shard_spec is None or not partitioned_by_gspmd():
        return kernel(q, k, v, rows)
    mesh = jax.sharding.get_abstract_mesh()
    auto = set(mesh.axis_names) - set(mesh.manual_axes)
    row_specs = {name: P(shard_spec[0] if x.shape[0] > 1 else None, None)
                 for name, x in rows.items()}
    return jax.shard_map(
        kernel, in_specs=(shard_spec,) * 3 + (row_specs,),
        out_specs=shard_spec, axis_names=auto, check_vma=False)(q, k, v, rows)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    q_offset: Optional[jax.Array] = None,
    kv_valid_len: Optional[jax.Array] = None,
    impl: str = "auto",
    shard_spec=None,
    rotation: Optional[Rotation] = None,
    window: Optional[int] = None,
    block_diffusion: Optional[int] = None,
) -> jax.Array:
    """Dispatching attention. impl: auto|pallas|chunked|reference.

    The Pallas path currently covers the training shape (no cache offsets, optional
    segment ids); decode-with-cache shapes use the XLA path, which fuses well anyway.

    shard_spec: how the caller lays q/k/v ([batch, seq, heads, head_dim]) out
    over the ambient mesh, as a PartitionSpec whose seq entry is None (each
    shard a whole attention problem). Only the Pallas path needs it, to run
    the kernel per shard; the XLA paths are partitioned by GSPMD.

    rotation: q and k come without their rotary embedding. Where the Pallas path runs
    (and q and k are one sequence's, their heads whole vregs wide), its rotate kernel
    applies it in front of the flash kernels; every other path applies `rotation.apply`
    first, and so does the Pallas path at head width 64 (the rotate kernel's tiles are
    lane-dense [rows, D]; in jax.numpy the rotation fuses with the projections' epilogue
    and the pad to 128 lanes, as latent attention's does with its concatenation).

    window: a sliding window over one causal sequence (key j is kept for query i where
    0 <= i - j < window), on every path; the Pallas kernels skip the tiles outside the band.

    block_diffusion: q, k and v are one row laid [noised ; clean] (the block-diffusion
    objective's doubled row, models/llama.py), in blocks of this many positions, and a query
    keeps what `block_diffusion_keep` says, on every path; the Pallas kernels walk the tiles
    the mask's three parts reach and no others (ops/flash_attention.py:BlockDiffusion).
    """
    if block_diffusion is not None and (causal or window is not None or segment_ids is not None
                                        or q_offset is not None or kv_valid_len is not None
                                        or q.shape[1] != k.shape[1] or q.shape[1] % 2):
        raise NotImplementedError(
            "block-diffusion attention beside `causal`, a window, packed documents (segment_ids) or a KV cache "
            "(q_offset, kv_valid_len): the mask is over one doubled row [noised ; clean]")
    if window is not None and (not causal or q_offset is not None or kv_valid_len is not None):
        raise NotImplementedError("an attention window under a KV cache (q_offset, kv_valid_len) or without `causal`")
    if impl == "auto":
        on_tpu = jax.default_backend() not in ("cpu", "gpu")
        # The pallas kernel's causal mask assumes query row i is absolute position i,
        # i.e. Sq == Skv; any offset/partial-window shape takes the XLA path.
        same_len = q.shape[1] == k.shape[1]
        # geometries the kernel can't tile must fall back to XLA or TPU compile fails
        from . import flash_attention as _fa

        tileable = _fa.supports(q.shape[1], k.shape[1], q.shape[-1], block_diffusion=block_diffusion,
                                v_head_dim=v.shape[-1])
        if (on_tpu and tileable and q_offset is None and kv_valid_len is None
                and (same_len or not causal)):
            impl = "pallas"
        elif q.shape[1] * k.shape[1] >= _chunked_min_logits():
            # Long sequences that can't take the Pallas kernel: blockwise online
            # softmax keeps peak memory O(Sq*block) instead of O(Sq*Skv).
            impl = "chunked"
        else:
            impl = "reference"
        # Warn only for shapes that WOULD have hit Pallas but for tiling — decode
        # shapes (offsets/valid-len, Sq != Skv) are deliberately XLA-routed.
        if (impl != "pallas" and on_tpu and not tileable
                and q_offset is None and kv_valid_len is None
                and (same_len or not causal)):
            _log_fallback(q.shape, k.shape, impl)
    if rotation is not None and not (impl == "pallas" and q.shape[1] == k.shape[1]
                                     and q.shape[-1] % 128 == 0):
        q, k = (rotation.apply(x, rotation.positions, rotation.theta) for x in (q, k))
        rotation = None
    if impl == "pallas":
        return _flash_per_shard(q, k, v, causal=causal, segment_ids=segment_ids, scale=scale,
                                shard_spec=shard_spec, rotation=rotation, window=window,
                                block_diffusion=block_diffusion)
    if impl == "chunked":
        return attention_chunked(
            q,
            k,
            v,
            causal=causal,
            segment_ids=segment_ids,
            scale=scale,
            q_offset=q_offset,
            kv_valid_len=kv_valid_len,
            window=window,
            block_diffusion=block_diffusion,
        )
    return attention_reference(
        q,
        k,
        v,
        causal=causal,
        segment_ids=segment_ids,
        scale=scale,
        q_offset=q_offset,
        kv_valid_len=kv_valid_len,
        window=window,
        block_diffusion=block_diffusion,
    )
