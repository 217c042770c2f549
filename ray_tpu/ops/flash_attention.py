"""Pallas TPU flash attention (forward + backward), causal + GQA + segment ids.

Blockwise online-softmax attention (flash v2 style): the S×S score matrix never
materializes in HBM; each (q-block, kv-block) tile is computed in VMEM and folded into
running (max, sum, acc) statistics.

Layout inside the kernel is [B, H, S, D] ("BHSD") so the S×D tiles are contiguous; the
public wrapper takes BSHD like the rest of the framework. GQA is handled in the
BlockSpec index maps (kv head = q head // n_rep) — repeated KV heads are never
materialized.

Backward follows the standard two-kernel split: one pass computes dQ (grid over kv
blocks inner), one computes dK/dV (q blocks inner), both recomputing the block's
probabilities from the saved logsumexp. The dK/dV pass works on the TRANSPOSED tile
(kv rows, q columns): its four products are then plain or transposed-right-hand
matmuls, the row statistics (logsumexp, delta) come in as lane vectors of `block_q`
floats, and a kv head's group of query heads is the inner, accumulated grid
dimension, so dK/dV are written once per kv head.

Under `causal` a tile wholly above the diagonal runs nothing, and its index maps name
the block of the nearest computed tile, so that the pipeline issues no copy for it.
Every product feeds the MXU the inputs' own dtype (bf16 in training) and accumulates in
f32; scores, exponentials, logsumexp, delta and all accumulators are f32. Per-row
statistics are kept 128 equal lanes wide inside a kernel and travel between kernels as
lane vectors (`_rows`): as [rows, 1] columns every use of them is a lane broadcast.

Measured on a v5e at [6, 2048, 32/8, 128] bf16 causal, 512 x 512 tiles (PERF.md, PR 26):
forward 2.6 ms, dQ 3.5 ms, dK/dV 3.4 ms a call, which is 50 / 57 / 76 % of the MXU's
bf16 peak on the products the kernels execute (the masked halves of diagonal tiles
included) and 40 / 45 / 60 % on the products causal attention needs.
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
_NT = (((1,), (1,)), ((), ()))  # a @ b.T: contract the minor dimension of both
_NN = (((1,), (0,)), ((), ()))  # a @ b


def _block_sizes(sq: int, skv: int, bq: int, bkv: int):
    bq, bkv = min(bq, sq), min(bkv, skv)
    if sq % bq or skv % bkv:
        raise ValueError(f"seq lengths ({sq},{skv}) must be multiples of blocks ({bq},{bkv})")
    return bq, bkv


def _interpret() -> bool:
    """Pallas interpreter on non-TPU backends (CPU tests)."""
    return jax.default_backend() in ("cpu", "gpu")


def _lanes_to(x, n: int):
    """[rows, 128] with equal lanes -> [rows, n]."""
    if n % 128 == 0:
        return x if n == 128 else jnp.tile(x, (1, n // 128))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _lane_pad(n: int) -> int:
    return -(-n // 128) * 128


def _rows(x, block: int):
    """[..., S] -> [..., S // block, 1, P]: a block of sequence positions as one lane
    vector, P the block rounded up to whole vregs (a short single-tile sequence need
    not be a multiple of 128; the kernels read `[:, :block]`)."""
    x = x.reshape(*x.shape[:-1], x.shape[-1] // block, 1, block)
    pad = _lane_pad(block) - block
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _pallas_call(kernel, *, name: str, **kw):
    """A four-dimensional grid whose last dimension accumulates. `name` is the
    operation's name in the device trace (the benchmark's kernel metrics select by it)."""
    return pl.pallas_call(
        kernel, name=name, interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        **kw)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ------------------------------------------------------------- where a tile lies


def _last_kv_block(qi, bq: int, bkv: int):
    """The last kv block a causal q block attends to."""
    return (qi * bq + (bq - 1)) // bkv


def _first_q_block(kj, bq: int, bkv: int):
    """The first q block that attends to a causal kv block."""
    return (kj * bkv) // bq


def _for_tile(causal: bool, qi, kj, bq: int, bkv: int, tile) -> None:
    """Run `tile()` unless tile (qi, kj) lies wholly above the causal diagonal."""
    if causal:
        pl.when(kj <= _last_kv_block(qi, bq, bkv))(tile)
    else:
        tile()


def _keep(shape, q_axis: int, qi, kj, bq: int, bkv: int, causal: bool, seg_col, seg_row):
    """Which scores of a tile stay (None: all). `q_axis` is the axis query positions
    run along; `seg_col` [rows, 128] and `seg_row` [1, >= cols] are the segment ids
    of the tile's rows and columns. Every computed tile builds the causal mask, also
    those the diagonal does not cross: a second, maskless tile body moved no kernel
    by 0.3 % on the chip (PERF.md, PR 26)."""
    keep = None
    if causal:  # kv position <= q position
        ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
                 - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
        keep = ahead >= kj * bkv - qi * bq
    if seg_col is not None:
        same = seg_col[:, :1] == seg_row[:][:, :shape[1]]
        keep = same if keep is None else (keep & same)
    return keep


# ------------------------------------------------------------------- forward kernel


def _fwd_kernel(
    q_ref,  # [bq, D]
    k_ref,  # [bkv, D]
    v_ref,  # [bkv, D]
    seg_q_ref,  # [bq, 128] or None
    seg_kv_ref,  # [1, bkv] or None
    o_ref,  # [bq, D]
    lse_ref,  # [1, P]
    m_scr,  # VMEM [bq, 128] f32, lanes equal
    l_scr,  # VMEM [bq, 128] f32, lanes equal
    acc_scr,  # VMEM [bq, D] f32
    lse_scr,  # VMEM [P, 128] f32
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

    def tile():
        v = v_ref[:]
        s = _dot(q_ref[:], k_ref[:], _NT) * scale  # [bq, bkv]
        keep = _keep(s.shape, 0, qi, kj, bq, bkv, causal, seg_q_ref, seg_kv_ref)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)
        # The running statistics stay 128 equal lanes wide: as [bq, 1] columns every
        # use of them is a lane broadcast, work done once a row a step, which bound
        # this kernel whatever the tile's width (PERF.md, PR 26).
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes_to(m_new, s.shape[1]))
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[:] = m_new
        acc_scr[:] = acc_scr[:] * _lanes_to(alpha, v.shape[1]) + _dot(p.astype(v.dtype), v, _NN)

    _for_tile(causal, qi, kj, bq, bkv, tile)

    @pl.when(kj == nk - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_scr[:] / _lanes_to(l_safe, acc_scr.shape[1])).astype(o_ref.dtype)
        lse_scr[:bq] = m_scr[:] + jnp.log(l_safe)
        lse_ref[:] = lse_scr[:].T[:1]  # rows become lanes; those past bq are never read


def _q_major_specs(d, n_rep, causal, bq, bkv, has_seg):
    """BlockSpecs of the forward and dQ grids (b, h, q block, kv block): q-side,
    kv-side, per-row statistics (`_rows`: a lane vector a q block), and the segment
    ids of rows and columns."""

    def kv_block(qi, kj):  # a tile above the diagonal names the last block used
        return jnp.minimum(kj, _last_kv_block(qi, bq, bkv)) if causal else kj

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, kj: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bkv, d), lambda bi, hi, qi, kj: (bi, hi // n_rep, kv_block(qi, kj), 0))
    stat_spec = pl.BlockSpec((1, 1, 1, 1, _lane_pad(bq)), lambda bi, hi, qi, kj: (bi, hi, qi, 0, 0))
    seg_specs = []
    if has_seg:
        seg_specs = [
            pl.BlockSpec((1, bq, 128), lambda bi, hi, qi, kj: (bi, qi, 0)),
            pl.BlockSpec((1, 1, 1, _lane_pad(bkv)),
                         lambda bi, hi, qi, kj: (bi, kv_block(qi, kj), 0, 0)),
        ]
    return q_spec, kv_spec, stat_spec, seg_specs


def _unpack(refs, n_in: int, has_seg: bool):
    """(inputs, segment-id pair, the remaining refs), the inputs' blocks indexed
    down to their last two dimensions."""
    def tile(r):
        return r.at[(0,) * (len(r.shape) - 2)]

    n_seg = 2 if has_seg else 0
    segs = [tile(r) for r in refs[n_in:n_in + n_seg]] or [None, None]
    return [tile(r) for r in refs[:n_in]], segs, refs[n_in + n_seg:]


def _fwd(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,
    seg: Optional[dict],  # _segment_lanes(), or None
    scale: float,
    causal: bool,
    bq: int,
    bkv: int,
):
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    bq, bkv = _block_sizes(sq, skv, bq, bkv)
    has_seg = seg is not None
    q_spec, kv_spec, stat_spec, seg_specs = _q_major_specs(d, h // hkv, causal, bq, bkv, has_seg)
    args = [q, k, v] + ([seg["q_col"], _rows(seg["kv"], bkv)] if has_seg else [])

    def kernel(*refs):
        ins, segs, (o_ref, lse_ref, *scratch) = _unpack(refs, 3, has_seg)
        _fwd_kernel(*ins, *segs, o_ref.at[0, 0], lse_ref.at[0, 0, 0], *scratch,
                    scale=scale, causal=causal, bq=bq, bkv=bkv)

    out, lse = _pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(b, h, sq // bq, skv // bkv),
        in_specs=[q_spec, kv_spec, kv_spec] + seg_specs,
        out_specs=[q_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq // bq, 1, _lane_pad(bq)), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((_lane_pad(bq), 128), jnp.float32),
        ],
    )(*args)
    return out, lse  # lse: as `_rows` lays [B, H, Sq] out


# ------------------------------------------------------------------ backward kernels


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seg_q_ref, seg_kv_ref, dq_ref,
    dq_scr, lse_scr, delta_scr,
    *, scale, causal, bq, bkv,
):
    """lse_ref and delta_ref are [1, P]; the tile [bq, bkv] wants them down its rows,
    so the q block's first step turns them once into [bq, 128] with equal lanes."""
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        for row_ref, col_scr in ((lse_ref, lse_scr), (delta_ref, delta_scr)):
            col_scr[:] = jnp.broadcast_to(row_ref[:], (128, row_ref.shape[1])).T[:bq]

    def tile():
        k = k_ref[:]
        s = _dot(q_ref[:], k, _NT) * scale  # [bq, bkv]
        keep = _keep(s.shape, 0, qi, kj, bq, bkv, causal, seg_q_ref, seg_kv_ref)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - _lanes_to(lse_scr[:], s.shape[1]))
        dp = _dot(do_ref[:], v_ref[:], _NT)
        ds = p * (dp - _lanes_to(delta_scr[:], s.shape[1])) * scale
        dq_scr[:] += _dot(ds.astype(k.dtype), k, _NN)

    _for_tile(causal, qi, kj, bq, bkv, tile)

    @pl.when(kj == nk - 1)
    def _():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seg_kv_ref, seg_q_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale, causal, bq, bkv, nq,
):
    """One (kv block, query head of its group, q block) step, on the transposed tile
    [bkv, bq]: lse_ref and delta_ref are [1, P]."""
    kj = pl.program_id(2)
    t = pl.program_id(3)  # (query head within the group, q block), q block minor
    nt = pl.num_programs(3)
    qi = jax.lax.rem(t, nq)

    @pl.when(t == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def tile():
        q = q_ref[:]
        do = do_ref[:]
        st = _dot(k_ref[:], q, _NT) * scale  # [bkv, bq]
        keep = _keep(st.shape, 1, qi, kj, bq, bkv, causal, seg_kv_ref, seg_q_ref)
        if keep is not None:
            st = jnp.where(keep, st, NEG_INF)
        pt = jnp.exp(st - lse_ref[:][:, :bq])
        dv_scr[:] += _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v_ref[:], do, _NT)
        dst = pt * (dpt - delta_ref[:][:, :bq]) * scale
        dk_scr[:] += _dot(dst.astype(q.dtype), q, _NN)

    _for_tile(causal, qi, kj, bq, bkv, tile)

    @pl.when(t == nt - 1)
    def _():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, seg, out, lse, dout, scale, causal, bq, bkv):
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    n_rep = h // hkv
    bq, bkv = _block_sizes(sq, skv, bq, bkv)
    nq, nk = sq // bq, skv // bkv
    has_seg = seg is not None

    # delta_i = sum_d(dO * O): rowwise, cheap in XLA.
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    stats = [lse, _rows(delta, bq)]

    # --- dQ pass: grid (b, h, nq, nk)
    q_spec, kv_spec, stat_spec, seg_specs = _q_major_specs(d, n_rep, causal, bq, bkv, has_seg)
    args = [q, k, v, dout, *stats] + ([seg["q_col"], _rows(seg["kv"], bkv)] if has_seg else [])

    def dq_kernel(*refs):
        ins, segs, (dq_ref, *scratch) = _unpack(refs, 6, has_seg)
        _bwd_dq_kernel(*ins, *segs, dq_ref.at[0, 0], *scratch,
                       scale=scale, causal=causal, bq=bq, bkv=bkv)

    dq = _pallas_call(
        dq_kernel,
        name="flash_attention_bwd_dq",
        grid=(b, h, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec] + seg_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )(*args)

    # --- dK/dV pass: grid (b, kv head, nk, group's query heads x nq), the last summed in
    # the kernel. A q block no row of which sees the kv block names the first that does.
    def q_side(t, kj):  # (query head within the group, q block)
        qi = jax.lax.rem(t, nq)
        return t // nq, (jnp.maximum(qi, _first_q_block(kj, bq, bkv)) if causal else qi)

    def q_map(bi, hk, kj, t):
        r, qi = q_side(t, kj)
        return (bi, hk * n_rep + r, qi, 0)

    def stat_map(bi, hk, kj, t):
        r, qi = q_side(t, kj)
        return (bi, hk * n_rep + r, qi, 0, 0)

    q_spec2 = pl.BlockSpec((1, 1, bq, d), q_map)
    kv_spec2 = pl.BlockSpec((1, 1, bkv, d), lambda bi, hk, kj, t: (bi, hk, kj, 0))
    stat_spec2 = pl.BlockSpec((1, 1, 1, 1, _lane_pad(bq)), stat_map)
    in_specs2 = [q_spec2, kv_spec2, kv_spec2, q_spec2, stat_spec2, stat_spec2]
    args2 = [q, k, v, dout, *stats]
    if has_seg:
        in_specs2 += [
            pl.BlockSpec((1, bkv, 128), lambda bi, hk, kj, t: (bi, kj, 0)),
            pl.BlockSpec((1, 1, 1, _lane_pad(bq)),
                         lambda bi, hk, kj, t: (bi, q_side(t, kj)[1], 0, 0)),
        ]
        args2 += [seg["kv_col"], _rows(seg["q"], bq)]

    def dkv_kernel(*refs):
        ins, segs, (dk_ref, dv_ref, dk_s, dv_s) = _unpack(refs, 6, has_seg)
        _bwd_dkv_kernel(*ins, *segs, dk_ref.at[0, 0], dv_ref.at[0, 0], dk_s, dv_s,
                        scale=scale, causal=causal, bq=bq, bkv=bkv, nq=nq)

    dk, dv = _pallas_call(
        dkv_kernel,
        name="flash_attention_bwd_dkv",
        grid=(b, hkv, nk, n_rep * nq),
        in_specs=in_specs2,
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, skv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, d), jnp.float32),
        ],
    )(*args2)
    return dq, dk, dv


# ----------------------------------------------------------------------- public API


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_bhsd(q, k, v, seg, scale, causal, bq, bkv):
    out, _ = _fwd(q, k, v, seg, scale, causal, bq, bkv)
    return out


def _flash_fwd_rule(q, k, v, seg, scale, causal, bq, bkv):
    out, lse = _fwd(q, k, v, seg, scale, causal, bq, bkv)
    return out, (q, k, v, seg, out, lse)


def _flash_bwd_rule(scale, causal, bq, bkv, res, dout):
    q, k, v, seg, out, lse = res
    dq, dk, dv = _bwd(q, k, v, seg, out, lse, dout, scale, causal, bq, bkv)
    return dq, dk, dv, None


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _segment_lanes(segment_ids: jax.Array, sq: int) -> dict:
    """Segment ids of the q and the kv side, as they are ([B, S]; `_rows` lays them
    along a tile's columns) and down a tile's rows (`*_col`, 128 equal lanes)."""
    seg_kv = segment_ids.astype(jnp.int32)
    out = {"q": seg_kv[:, -sq:], "kv": seg_kv}
    for side in ("q", "kv"):
        out[f"{side}_col"] = jnp.broadcast_to(out[side][:, :, None], (*out[side].shape, 128))
    return out


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
    seg = None if segment_ids is None else _segment_lanes(segment_ids, q.shape[1])
    out = _flash_bhsd(qt, kt, vt, seg, scale, causal, block_q, block_kv)
    return out.transpose(0, 2, 1, 3)
