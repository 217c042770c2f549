"""Pallas TPU flash attention (forward + backward), causal + GQA + segment ids.

Blockwise online-softmax attention (flash v2 style): the S×S score matrix never
materializes in HBM; each (q-block, kv-block) tile is computed in VMEM and folded into
running (max, sum, acc) statistics. Causal q/kv tiles that are fully masked are skipped
entirely, so causal attention does half the FLOPs.

Layout inside the kernel is [B, H, S, D] ("BHSD") so the S×D tiles are contiguous; the
public wrapper takes BSHD like the rest of the framework. GQA is handled in the
BlockSpec index maps (kv head = q head // n_rep) — repeated KV heads are never
materialized.

Backward follows the standard two-kernel split: one pass computes dQ (grid over kv
blocks inner), one computes dK/dV (grid over q blocks inner), both recomputing the
block's probabilities from the saved logsumexp.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tile sizes live in the flag registry: CONFIG.flash_block_q / flash_block_kv
NEG_INF = -1e30


def _block_sizes(sq: int, skv: int, bq: int, bkv: int):
    bq, bkv = min(bq, sq), min(bkv, skv)
    if sq % bq or skv % bkv:
        raise ValueError(f"seq lengths ({sq},{skv}) must be multiples of blocks ({bq},{bkv})")
    return bq, bkv


def _interpret() -> bool:
    """Pallas interpreter on non-TPU backends (CPU tests)."""
    return jax.default_backend() in ("cpu", "gpu")


# ------------------------------------------------------------------- forward kernel


def _fwd_kernel(
    q_ref,  # [bq, D]
    k_ref,  # [bkv, D]
    v_ref,  # [bkv, D]
    seg_q_ref,  # [bq, 128] or None
    seg_kv_ref,  # [bkv, 128] or None
    o_ref,  # [bq, D]
    lse_ref,  # [bq, 128] (lanes replicated)
    m_scr,  # VMEM [bq, 128] f32
    l_scr,  # VMEM [bq, 128] f32
    acc_scr,  # VMEM [bq, D] f32
    *,
    scale: float,
    causal: bool,
    bq: int,
    bkv: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _compute():
        q = q_ref[:]
        k = k_ref[:]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [bq, bkv]
        s = s * scale

        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + qi * bq
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1) + kj * bkv
        if causal:
            s = jnp.where(cols <= rows, s, NEG_INF)
        if seg_q_ref is not None:
            seg_q = seg_q_ref[:, :1]  # [bq, 1]
            seg_kv = seg_kv_ref[:, :1]  # [bkv, 1]
            s = jnp.where(seg_q == seg_kv.T, s, NEG_INF)

        m_prev = m_scr[:, :1]  # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # [bq, bkv]
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)

        acc = acc_scr[:] * alpha
        acc = acc + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[:] = acc
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # Skip tiles strictly above the diagonal.
        @pl.when(kj * bkv <= qi * bq + (bq - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(l_safe)
        lse_ref[:] = jnp.broadcast_to(lse, lse_ref.shape).astype(lse_ref.dtype)


def _fwd(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,
    seg_q: Optional[jax.Array],  # [B, Sq, 128] int32
    seg_kv: Optional[jax.Array],  # [B, Skv, 128]
    scale: float,
    causal: bool,
    bq: int,
    bkv: int,
):
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    n_rep = h // hkv
    bq, bkv = _block_sizes(sq, skv, bq, bkv)
    grid = (b, h, pl.cdiv(sq, bq), pl.cdiv(skv, bkv))

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bkv, d), lambda bi, hi, qi, kj: (bi, hi // n_rep, kj, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [q, k, v]
    if seg_q is not None:
        in_specs.append(pl.BlockSpec((1, bq, 128), lambda bi, hi, qi, kj: (bi, qi, 0)))
        in_specs.append(pl.BlockSpec((1, bkv, 128), lambda bi, hi, qi, kj: (bi, kj, 0)))
        args += [seg_q, seg_kv]

    def kernel(*refs):
        if seg_q is not None:
            q_ref, k_ref, v_ref, sq_ref, skv_ref, o_ref, lse_ref, m_s, l_s, a_s = refs
            sq_r, skv_r = sq_ref.at[0], skv_ref.at[0]
        else:
            q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, a_s = refs
            sq_r = skv_r = None
        _fwd_kernel(
            q_ref.at[0, 0],
            k_ref.at[0, 0],
            v_ref.at[0, 0],
            sq_r,
            skv_r,
            o_ref.at[0, 0],
            lse_ref.at[0, 0],
            m_s,
            l_s,
            a_s,
            scale=scale,
            causal=causal,
            bq=bq,
            bkv=bkv,
        )

    out, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",  # the operation's name in the device trace
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 128), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 128), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(*args)
    return out, lse[..., 0]  # lse: [B, H, Sq]


# ------------------------------------------------------------------ backward kernels


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seg_q_ref, seg_kv_ref, dq_ref, dq_scr,
    *, scale, causal, bq, bkv,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _compute():
        q = q_ref[:]
        k = k_ref[:]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + qi * bq
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1) + kj * bkv
        mask = None
        if causal:
            mask = cols <= rows
        if seg_q_ref is not None:
            m2 = seg_q_ref[:, :1] == seg_kv_ref[:, :1].T
            mask = m2 if mask is None else (mask & m2)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[:, :1])  # [bq, bkv]
        do = do_ref[:].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[:].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[:, :1]) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(kj * bkv <= qi * bq + (bq - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kj == nk - 1)
    def _():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seg_q_ref, seg_kv_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale, causal, bq, bkv,
):
    kj = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _compute():
        q = q_ref[:]
        k = k_ref[:]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bkv]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + qi * bq
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1) + kj * bkv
        mask = None
        if causal:
            mask = cols <= rows
        if seg_q_ref is not None:
            m2 = seg_q_ref[:, :1] == seg_kv_ref[:, :1].T
            mask = m2 if mask is None else (mask & m2)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[:, :1])  # [bq, bkv]
        do = do_ref[:].astype(jnp.float32)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v_ref[:].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[:, :1]) * scale  # [bq, bkv]
        dk_scr[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(qi * bq + (bq - 1) >= kj * bkv)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(
    q, k, v, seg_q, seg_kv, out, lse, dout, scale, causal, bq, bkv
):
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    n_rep = h // hkv
    bq_, bkv_ = _block_sizes(sq, skv, bq, bkv)

    # delta_i = sum_d(dO * O): rowwise, cheap in XLA.
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)  # [B,H,Sq]
    lse_l = jnp.broadcast_to(lse[..., None], (*lse.shape, 128)).astype(jnp.float32)
    delta_l = jnp.broadcast_to(delta[..., None], (*delta.shape, 128))

    # --- dQ pass: grid (b, h, nq, nk) ---
    q_spec = pl.BlockSpec((1, 1, bq_, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bkv_, d), lambda bi, hi, qi, kj: (bi, hi // n_rep, kj, 0))
    row_spec = pl.BlockSpec((1, 1, bq_, 128), lambda bi, hi, qi, kj: (bi, hi, qi, 0))
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
    args = [q, k, v, dout, lse_l, delta_l]
    has_seg = seg_q is not None
    if has_seg:
        in_specs.append(pl.BlockSpec((1, bq_, 128), lambda bi, hi, qi, kj: (bi, qi, 0)))
        in_specs.append(pl.BlockSpec((1, bkv_, 128), lambda bi, hi, qi, kj: (bi, kj, 0)))
        args += [seg_q, seg_kv]

    def dq_kernel(*refs):
        if has_seg:
            (qr, kr, vr, dor, lser, deltar, sqr, skvr, dqr, dqs) = refs
            sq_r, skv_r = sqr.at[0], skvr.at[0]
        else:
            (qr, kr, vr, dor, lser, deltar, dqr, dqs) = refs
            sq_r = skv_r = None
        _bwd_dq_kernel(
            qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], dor.at[0, 0], lser.at[0, 0],
            deltar.at[0, 0], sq_r, skv_r, dqr.at[0, 0], dqs,
            scale=scale, causal=causal, bq=bq_, bkv=bkv_,
        )

    dq = pl.pallas_call(
        dq_kernel,
        name="flash_attention_bwd_dq",  # the operation's name in the device trace
        grid=(b, h, pl.cdiv(sq, bq_), pl.cdiv(skv, bkv_)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq_, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq_, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(*args)

    # --- dK/dV pass: grid (b, h, nk, nq); kv head accumulates over its rep group ---
    # For GQA we accumulate per q-head then sum over the rep group in XLA.
    q_spec2 = pl.BlockSpec((1, 1, bq_, d), lambda bi, hi, kj, qi: (bi, hi, qi, 0))
    kv_spec2 = pl.BlockSpec((1, 1, bkv_, d), lambda bi, hi, kj, qi: (bi, hi // n_rep, kj, 0))
    row_spec2 = pl.BlockSpec((1, 1, bq_, 128), lambda bi, hi, kj, qi: (bi, hi, qi, 0))
    in_specs2 = [q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2]
    args2 = [q, k, v, dout, lse_l, delta_l]
    if has_seg:
        in_specs2.append(pl.BlockSpec((1, bq_, 128), lambda bi, hi, kj, qi: (bi, qi, 0)))
        in_specs2.append(pl.BlockSpec((1, bkv_, 128), lambda bi, hi, kj, qi: (bi, kj, 0)))
        args2 += [seg_q, seg_kv]

    def dkv_kernel(*refs):
        if has_seg:
            (qr, kr, vr, dor, lser, deltar, sqr, skvr, dkr, dvr, dks, dvs) = refs
            sq_r, skv_r = sqr.at[0], skvr.at[0]
        else:
            (qr, kr, vr, dor, lser, deltar, dkr, dvr, dks, dvs) = refs
            sq_r = skv_r = None
        _bwd_dkv_kernel(
            qr.at[0, 0], kr.at[0, 0], vr.at[0, 0], dor.at[0, 0], lser.at[0, 0],
            deltar.at[0, 0], sq_r, skv_r, dkr.at[0, 0], dvr.at[0, 0], dks, dvs,
            scale=scale, causal=causal, bq=bq_, bkv=bkv_,
        )

    dk_per_h, dv_per_h = pl.pallas_call(
        dkv_kernel,
        name="flash_attention_bwd_dkv",  # the operation's name in the device trace
        grid=(b, h, pl.cdiv(skv, bkv_), pl.cdiv(sq, bq_)),
        in_specs=in_specs2,
        out_specs=[
            pl.BlockSpec((1, 1, bkv_, d), lambda bi, hi, kj, qi: (bi, hi, kj, 0)),
            pl.BlockSpec((1, 1, bkv_, d), lambda bi, hi, kj, qi: (bi, hi, kj, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, skv, d), jnp.float32),
            jax.ShapeDtypeStruct((b, h, skv, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv_, d), jnp.float32),
            pltpu.VMEM((bkv_, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(*args2)

    if n_rep > 1:
        dk = dk_per_h.reshape(b, hkv, n_rep, skv, d).sum(axis=2)
        dv = dv_per_h.reshape(b, hkv, n_rep, skv, d).sum(axis=2)
    else:
        dk, dv = dk_per_h, dv_per_h
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ----------------------------------------------------------------------- public API


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_bhsd(q, k, v, seg_lanes, scale, causal, bq, bkv):
    seg_q, seg_kv = (seg_lanes if seg_lanes is not None else (None, None))
    out, _ = _fwd(q, k, v, seg_q, seg_kv, scale, causal, bq, bkv)
    return out


def _flash_fwd_rule(q, k, v, seg_lanes, scale, causal, bq, bkv):
    seg_q, seg_kv = (seg_lanes if seg_lanes is not None else (None, None))
    out, lse = _fwd(q, k, v, seg_q, seg_kv, scale, causal, bq, bkv)
    return out, (q, k, v, seg_lanes, out, lse)


def _flash_bwd_rule(scale, causal, bq, bkv, res, dout):
    q, k, v, seg_lanes, out, lse = res
    seg_q, seg_kv = (seg_lanes if seg_lanes is not None else (None, None))
    dq, dk, dv = _bwd(q, k, v, seg_q, seg_kv, out, lse, dout, scale, causal, bq, bkv)
    return dq, dk, dv, None


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Skv, Hkv, D]
    v: jax.Array,
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,  # [B, Skv]
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
) -> jax.Array:
    """BSHD flash attention. Sq must equal Skv when segment_ids are used."""
    if block_q is None or block_kv is None:
        from ray_tpu.config import CONFIG

        block_q = block_q if block_q is not None else CONFIG.flash_block_q
        block_kv = block_kv if block_kv is not None else CONFIG.flash_block_kv
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    seg_lanes = None
    if segment_ids is not None:
        sq = q.shape[1]
        seg_q = jnp.broadcast_to(
            segment_ids[:, -sq:, None].astype(jnp.int32), (q.shape[0], sq, 128)
        )
        seg_kv = jnp.broadcast_to(
            segment_ids[:, :, None].astype(jnp.int32), (*segment_ids.shape, 128)
        )
        seg_lanes = (seg_q, seg_kv)
    out = _flash_bhsd(qt, kt, vt, seg_lanes, scale, causal, block_q, block_kv)
    return out.transpose(0, 2, 1, 3)
