"""Pallas TPU flash attention (forward + backward), causal + GQA + segment ids.

Blockwise online-softmax attention (flash v2 style): the S×S score matrix never
materializes in HBM; each (q-block, kv-block) tile is computed in VMEM and folded into
running (max, sum, acc) statistics.

Layout inside the kernel is [B, H, S, D] ("BHSD") so the S×D tiles are contiguous; the
public wrapper takes BSHD like the rest of the framework. GQA is handled in the
BlockSpec index maps (kv head = q head // n_rep) — repeated KV heads are never
materialized.

Backward follows the standard two-kernel split: one pass computes dQ, one computes
dK/dV, both recomputing a tile's probabilities from the saved logsumexp. The dK/dV
pass works on the TRANSPOSED tile (kv rows, q columns): its four products are then
plain or transposed-right-hand matmuls, the row statistics (logsumexp, delta) come in
as lane vectors of `block_q` floats, and a kv head's group of query heads is summed in
the kernel, so dK/dV are written once per kv head.

The block a grid step fetches is not the tile a product computes. A step of the
forward and dQ kernels owns one q tile and a SPAN of K/V rows, a step of dK/dV one kv
tile and a span of the q rows of its group's query heads; a `fori_loop` inside the
kernel walks the span's compute tiles (`block_q` x `block_kv`), in ascending
order, with the running statistics and accumulators in VMEM scratch throughout. The
span is derived (`_tiling`): all of the sequence where its blocks fit `SPAN_VMEM_BYTES`
(at head_dim 128 in bf16: K/V up to 16,384 rows, the Q/dO of a group of four up to
2,048), else the largest whole number of tiles that divides it, and then the last grid
dimension runs over spans. A grid step costs 0.35-0.6 us on a v5e whatever it computes
(PERF.md, PR 28): at s2048 a (batch, head) is 4 steps, where a tile a step made 16.

Under `causal` the loop's bounds come from `program_id`: a tile wholly above the
diagonal is not visited, and a span wholly above it names, in its index maps, the
nearest span used, so that the pipeline issues no copy for it. A `window` (key j is kept
for query i where 0 <= i - j < window) is a second edge of the same kind, below the band:
the forward and dQ loops start at the first kv tile the window reaches, dK/dV ends at the
last q tile that still sees the kv tile, and a span wholly outside the band names the
nearest one inside it. A windowed call's kernels carry `_window` behind their names.
Every product feeds the MXU the inputs' own dtype (bf16 in training) and accumulates in
f32; scores, exponentials, logsumexp, delta and all accumulators are f32. Per-row
statistics and segment ids are kept 128 equal lanes wide inside a kernel and travel
between kernels as lane vectors (`_rows`): as [rows, 1] columns every use of them is a
lane broadcast.

Measured on a v5e at [6, 2048, 32/8, 128] bf16 causal, 512 x 512 tiles (PERF.md, PR 28):
forward 2.05 ms, dQ 2.65 ms, dK/dV 3.00 ms a call, which is 64 / 74 / 87 % of the MXU's
bf16 peak on the products the kernels execute (`tile_counts`: 10 tiles a (batch, head),
the masked halves of the diagonal's included) and 51 / 59 / 70 % on the 8 that causal
attention needs.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import FLASH_NAMES, ROTATED_NAMES

# Rows of the kernels' COMPUTE tile on the query and on the key/value side (a multiple
# of 128; a shorter sequence is one tile): the size of the products, not of what a grid
# step fetches (the span, `_tiling`). Measured on a v5e at [6, 2048, 32/8, 128] bf16
# causal (PERF.md, PR 28): 512 x 512 tiles with a span a step run the forward, dQ and
# dK/dV kernels in 2.05 / 2.65 / 3.00 ms, where 1,024 x 1,024 with a tile a step took
# 2.48 / 3.00 / 3.43 and 512 x 512 with a tile a step 2.72 / 3.47 / 3.46.
BLOCK_Q = 512
BLOCK_KV = 512
NEG_INF = -1e30
_NT = (((1,), (1,)), ((), ()))  # a @ b.T: contract the minor dimension of both
_NN = (((1,), (0,)), ((), ()))  # a @ b

# VMEM the kernels may take (Mosaic's default scoped limit on a v5e is 16 MiB of the
# core's 128), and the half of it that the span-long blocks of one kernel may take, both
# pipeline buffers counted: K and V in the forward and dQ kernels; Q, dO, logsumexp and
# delta of a kv head's group of query heads in dK/dV. The other half is for the compute
# tile's blocks, accumulators and f32 intermediates (~6 MB at 512 x 512).
VMEM_LIMIT_BYTES = 32 << 20
SPAN_VMEM_BYTES = VMEM_LIMIT_BYTES // 2


def supports(sq: int, skv: int, head_dim: int, block_q: int = BLOCK_Q,
             block_kv: int = BLOCK_KV) -> bool:
    """Whether the kernels can tile this geometry. Mosaic tiles the lane (last) dim at 128
    and sublanes at 8, and a sequence longer than one compute tile must be a whole number of
    them (`_block_sizes`): head_dim 16, seq 20 or seq 520 would fail the TPU compile ("slice
    shape must be aligned to tiling"). Heads 64 wide run on padded lanes (`NARROW_HEAD`)."""
    def seq_ok(n: int, block: int) -> bool:
        return n % 8 == 0 and (n <= block or n % block == 0)

    return (head_dim == NARROW_HEAD or head_dim % 128 == 0) and seq_ok(sq, block_q) and seq_ok(skv, block_kv)


def _block_sizes(sq: int, skv: int, bq: int, bkv: int):
    bq, bkv = min(bq, sq), min(bkv, skv)
    if sq % bq or skv % bkv:
        raise ValueError(f"seq lengths ({sq},{skv}) must be multiples of blocks ({bq},{bkv})")
    return bq, bkv


def _span(seq: int, tile: int, row_bytes: int, budget: Optional[int] = None) -> int:
    """Rows of the other side's sequence that one grid step fetches: the largest
    multiple of the compute tile that divides the sequence and whose blocks
    (`row_bytes` a row, both pipeline buffers) fit the budget; at least one tile."""
    budget = SPAN_VMEM_BYTES if budget is None else budget
    n = seq // tile
    return tile * max(m for m in range(1, n + 1) if n % m == 0 and (
        m == 1 or m * tile * row_bytes <= budget))


class Tiling(NamedTuple):
    """How the (q, kv) plane of one (batch, head) is cut: compute tiles of bq x bkv; a
    grid step of the forward and dQ kernels is one q tile against `kv_span` rows of K
    and V, one of dK/dV a kv tile against `q_span` rows of its group's query heads."""
    bq: int
    bkv: int
    kv_span: int
    q_span: int


def _tiling(sq, skv, bq, bkv, d, itemsize, n_rep=1) -> Tiling:
    bq, bkv = _block_sizes(sq, skv, bq, bkv)
    row = 2 * 2 * d * itemsize  # two arrays a side, two pipeline buffers each
    # dK/dV: of every query head of the group, and the rows' logsumexp and delta, which
    # a lane vector a tile holds in 8 sublanes
    return Tiling(bq, bkv, _span(skv, bkv, row), _span(sq, bq, n_rep * (row + 2 * 2 * 4 * 8)))


class TileCounts(NamedTuple):
    grid_steps: int  # steps of the last two grid dimensions: those of one (batch, head)
    tiles_computed: int  # compute tiles whose products run
    tiles_needed: float  # the scores attention needs, in compute tiles


def tile_counts(sq: int, skv: int, causal: bool, bq: int, bkv: int, *, head_dim: int = 128,
                itemsize: int = 2, n_rep: int = 1, kv_major: bool = False,
                window: Optional[int] = None) -> TileCounts:
    """What a (batch, query head) costs the forward and dQ kernels, or (`kv_major`) a
    (batch, kv head with its `n_rep` query heads) the dK/dV kernel: from the same
    `_tiling` the kernels' grids are built from. A causal tile is computed if any of
    its scores is kept (kv position <= q position, and inside a `window` more than q
    position - window): with 512 x 512 tiles and a window of 2,048 a q tile meets 5 kv
    tiles where the band needs 4.0."""
    t = _tiling(sq, skv, bq, bkv, head_dim, itemsize, n_rep)
    nq, nk = sq // t.bq, skv // t.bkv
    steps, heads = (nk * (sq // t.q_span), n_rep) if kv_major else (nq * (skv // t.kv_span), 1)
    if not causal:
        return TileCounts(steps, heads * nq * nk, float(heads * nq * nk))
    window = _band(window, sq, skv, causal)
    if window is None:
        computed = sum(min(_last_kv_block(qi, t.bq, t.bkv) + 1, nk) for qi in range(nq))
        m = min(sq, skv)
        kept = m * (m + 1) // 2 + (sq - m) * skv  # row i keeps min(i + 1, skv) scores
    else:  # the forward loop's own bounds (dK/dV walks the same tiles from the other side)
        computed = sum(_last_kv_block(qi, t.bq, t.bkv) - _first_kv_block(qi, t.bq, t.bkv, window) + 1
                       for qi in range(nq))
        kept = window * (window + 1) // 2 + (sq - window) * window  # row i keeps min(i + 1, window) scores
    return TileCounts(steps, heads * computed, heads * kept / (t.bq * t.bkv))


def _band(window: Optional[int], sq: int, skv: int, causal: bool) -> Optional[int]:
    """The window the kernels build a second edge for: None where there is none or it
    reaches the start of the sequence from every query (today's program)."""
    if window is None:
        return None
    if window < 1 or not causal or sq != skv:
        raise ValueError(f"window {window}: a causal band over one sequence (sq {sq}, skv {skv}, causal {causal})")
    return None if window >= skv else int(window)


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


def _pallas_call(kernel, *, name: str,
                 semantics=("parallel", "parallel", "parallel", "arbitrary"), **kw):
    """The flash kernels' grid has four dimensions, the last of which accumulates.
    `name` is the operation's name in the device trace (the benchmark's kernel
    metrics select by it)."""
    return pl.pallas_call(
        kernel, name=name, interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=VMEM_LIMIT_BYTES),
        **kw)


def _named(kernel: str, window: Optional[int]) -> str:
    """A flash kernel's name in the device trace: a windowed call's carries `_window`
    behind it, so that a metric can tell the band's kernels from the triangle's."""
    return kernel if window is None else f"{kernel}_window"


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# ------------------------------------------------------------- where a tile lies


def _last_kv_block(qi, bq: int, bkv: int):
    """The last kv block a causal q block attends to."""
    return (qi * bq + (bq - 1)) // bkv


def _first_q_block(kj, bq: int, bkv: int):
    """The first q block that attends to a causal kv block."""
    return (kj * bkv) // bq


def _first_kv_block(qi, bq: int, bkv: int, window: int):
    """The first kv block a q block's window reaches: that of its first row's oldest key."""
    oldest = qi * bq - (window - 1)
    return (max(oldest, 0) if isinstance(oldest, int) else jnp.maximum(oldest, 0)) // bkv


def _last_q_block(kj, bq: int, bkv: int, window: int):
    """The last q block (the sequence's end apart) whose window still reaches a kv block:
    that of the newest query that sees its last key."""
    return (kj * bkv + (bkv - 1) + (window - 1)) // bq


def _kv_tiles_end(causal: bool, qi, sj, n: int, bq: int, bkv: int):
    """How many of span sj's `n` kv tiles q block qi walks: all, or up to the last
    that the causal diagonal reaches."""
    if not causal:
        return n
    return jnp.clip(_last_kv_block(qi, bq, bkv) + 1 - sj * n, 0, n)


def _kv_tiles_start(window: Optional[int], qi, sj, n: int, bq: int, bkv: int):
    """The first of span sj's `n` kv tiles that q block qi walks: 0, or the first its
    window reaches."""
    if window is None:
        return 0
    return jnp.clip(_first_kv_block(qi, bq, bkv, window) - sj * n, 0, n)


def _q_tiles_end(window: Optional[int], kj, sp, n: int, bq: int, bkv: int):
    """How many of span sp's `n` q tiles kv block kj walks (dK/dV): all, or up to the last
    whose window still reaches it."""
    if window is None:
        return n
    return jnp.clip(_last_q_block(kj, bq, bkv, window) + 1 - sp * n, 0, n)


def _walk(lo, hi, n: int, tile) -> None:
    """Run `tile(t)` for the compute tiles lo <= t < hi of a grid step's span of `n`.
    Under `causal` the bounds come from `program_id`, so a tile wholly on the masked
    side of the diagonal is not visited. A span of one tile is a step of the plain
    tile-a-step grid: t is static and the blocks are read whole."""
    if n > 1:
        jax.lax.fori_loop(lo, hi, lambda t, _: tile(t), None)
    elif isinstance(lo, int) and isinstance(hi, int):
        tile(0)
    else:
        pl.when((lo <= 0) & (hi > 0))(lambda: tile(0))


def _at(t, block: int):
    """Rows of compute tile `t` in a span-long block."""
    return slice(None) if isinstance(t, int) else pl.ds(pl.multiple_of(t * block, block), block)


def _keep(shape, q_axis: int, qi, kj, bq: int, bkv: int, causal: bool, seg_col, seg_row,
          window: Optional[int] = None):
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
        if window is not None:  # and q position - kv position < window
            keep = keep & (ahead < kj * bkv - qi * bq + window)
    if seg_col is not None:
        same = _lanes_to(seg_col[:], shape[1]) == seg_row[:, :shape[1]]
        keep = same if keep is None else (keep & same)
    return keep


# ------------------------------------------------------------------- forward kernel


def _fwd_kernel(
    q_ref,  # [bq, D]
    k_ref,  # [span, D]
    v_ref,  # [span, D]
    seg_q_ref,  # [bq, 128] or None
    seg_kv_ref,  # [span // bkv, 1, P] or None
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
    window: Optional[int] = None,
):
    qi = pl.program_id(2)
    sj = pl.program_id(3)  # which span of K/V
    n = k_ref.shape[0] // bkv

    @pl.when(sj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def tile(t):
        kj = sj * n + t
        v = v_ref[_at(t, bkv)]
        s = _dot(q_ref[:], k_ref[_at(t, bkv)], _NT) * scale  # [bq, bkv]
        keep = _keep(s.shape, 0, qi, kj, bq, bkv, causal, seg_q_ref,
                     None if seg_kv_ref is None else seg_kv_ref[t], window)
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

    _walk(_kv_tiles_start(window, qi, sj, n, bq, bkv), _kv_tiles_end(causal, qi, sj, n, bq, bkv), n, tile)

    @pl.when(sj == pl.num_programs(3) - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_scr[:] / _lanes_to(l_safe, acc_scr.shape[1])).astype(o_ref.dtype)
        lse_scr[:bq] = m_scr[:] + jnp.log(l_safe)
        lse_ref[:] = lse_scr[:].T[:1]  # rows become lanes; those past bq are never read


def _q_major_specs(d, n_rep, causal, t: Tiling, has_seg, window=None):
    """BlockSpecs of the forward and dQ grids (b, h, q block, kv span): q-side,
    kv-side, per-row statistics (`_rows`: a lane vector a q block), and the segment
    ids of rows and columns."""
    bq, bkv = t.bq, t.bkv
    n = t.kv_span // bkv

    def kv_span(qi, sj):  # a span above the diagonal names the last span used, one below the band the first
        if window is not None:
            return jnp.clip(sj, _first_kv_block(qi, bq, bkv, window) // n, _last_kv_block(qi, bq, bkv) // n)
        return jnp.minimum(sj, _last_kv_block(qi, bq, bkv) // n) if causal else sj

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, sj: (bi, hi, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, t.kv_span, d), lambda bi, hi, qi, sj: (bi, hi // n_rep, kv_span(qi, sj), 0))
    stat_spec = pl.BlockSpec((1, 1, 1, 1, _lane_pad(bq)), lambda bi, hi, qi, sj: (bi, hi, qi, 0, 0))
    seg_specs = []
    if has_seg:
        seg_specs = [
            pl.BlockSpec((1, bq, 128), lambda bi, hi, qi, sj: (bi, qi, 0)),
            pl.BlockSpec((1, n, 1, _lane_pad(bkv)),
                         lambda bi, hi, qi, sj: (bi, kv_span(qi, sj), 0, 0)),
        ]
    return q_spec, kv_spec, stat_spec, seg_specs


def _unpack(refs, keeps, has_seg: bool):
    """(inputs, segment-id pair, the remaining refs): each input's block indexed down
    to as many trailing dimensions as `keeps` says, the segment ids of the tile's rows
    to two, and those of the span's columns, a lane vector a tile, to three."""
    def last(r, keep):
        return r.at[(0,) * (len(r.shape) - keep)]

    n_in = len(keeps)
    segs = [last(refs[n_in], 2), last(refs[n_in + 1], 3)] if has_seg else [None, None]
    return [last(r, keep) for r, keep in zip(refs, keeps)], segs, refs[n_in + 2 * has_seg:]


def _fwd(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,
    seg: Optional[dict],  # _segment_lanes(), or None
    scale: float,
    causal: bool,
    bq: int,
    bkv: int,
    window: Optional[int] = None,
):
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    t = _tiling(sq, skv, bq, bkv, d, k.dtype.itemsize)
    bq, bkv = t.bq, t.bkv
    has_seg = seg is not None
    q_spec, kv_spec, stat_spec, seg_specs = _q_major_specs(d, h // hkv, causal, t, has_seg, window)
    args = [q, k, v] + ([seg["q_col"], _rows(seg["kv"], bkv)] if has_seg else [])

    def kernel(*refs):
        ins, segs, (o_ref, lse_ref, *scratch) = _unpack(refs, (2,) * 3, has_seg)
        _fwd_kernel(*ins, *segs, o_ref.at[0, 0], lse_ref.at[0, 0, 0], *scratch,
                    scale=scale, causal=causal, bq=bq, bkv=bkv, window=window)

    out, lse = _pallas_call(
        kernel,
        name=_named("flash_attention_fwd", window),
        grid=(b, h, sq // bq, skv // t.kv_span),
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
    *, scale, causal, bq, bkv, window=None,
):
    """k_ref and v_ref are a span of K/V, [span, D]. lse_ref and delta_ref are [1, P];
    the tile [bq, bkv] wants them down its rows, so the q block's first step turns
    them once into [bq, 128] with equal lanes."""
    qi = pl.program_id(2)
    sj = pl.program_id(3)
    n = k_ref.shape[0] // bkv

    @pl.when(sj == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        for row_ref, col_scr in ((lse_ref, lse_scr), (delta_ref, delta_scr)):
            col_scr[:] = jnp.broadcast_to(row_ref[:], (128, row_ref.shape[1])).T[:bq]

    def tile(t):
        kj = sj * n + t
        k = k_ref[_at(t, bkv)]
        s = _dot(q_ref[:], k, _NT) * scale  # [bq, bkv]
        keep = _keep(s.shape, 0, qi, kj, bq, bkv, causal, seg_q_ref,
                     None if seg_kv_ref is None else seg_kv_ref[t], window)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - _lanes_to(lse_scr[:], s.shape[1]))
        dp = _dot(do_ref[:], v_ref[_at(t, bkv)], _NT)
        ds = p * (dp - _lanes_to(delta_scr[:], s.shape[1])) * scale
        dq_scr[:] += _dot(ds.astype(k.dtype), k, _NN)

    _walk(_kv_tiles_start(window, qi, sj, n, bq, bkv), _kv_tiles_end(causal, qi, sj, n, bq, bkv), n, tile)

    @pl.when(sj == pl.num_programs(3) - 1)
    def _():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seg_kv_ref, seg_q_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale, causal, bq, bkv, window=None,
):
    """One kv block against a span of q rows of the query heads of its group, on the
    transposed tile [bkv, bq]: q_ref and do_ref are [n_rep, span, D], lse_ref and
    delta_ref [n_rep, span // bq, 1, P], seg_q_ref [span // bq, 1, P]."""
    kj = pl.program_id(2)
    sp = pl.program_id(3)  # which span of q rows
    n_rep, n = q_ref.shape[0], q_ref.shape[1] // bq

    @pl.when(sp == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # the first of the span's q tiles that attends to this kv block
    lo = jnp.clip(_first_q_block(kj, bq, bkv) - sp * n, 0, n) if causal else 0
    hi = _q_tiles_end(window, kj, sp, n, bq, bkv)

    def head(r):
        def tile(t):
            qi = sp * n + t
            q = q_ref[r, _at(t, bq)]
            do = do_ref[r, _at(t, bq)]
            st = _dot(k_ref[:], q, _NT) * scale  # [bkv, bq]
            keep = _keep(st.shape, 1, qi, kj, bq, bkv, causal, seg_kv_ref,
                         None if seg_q_ref is None else seg_q_ref[t], window)
            if keep is not None:
                st = jnp.where(keep, st, NEG_INF)
            pt = jnp.exp(st - lse_ref[r, t][:, :bq])
            dv_scr[:] += _dot(pt.astype(do.dtype), do, _NN)
            dpt = _dot(v_ref[:], do, _NT)
            dst = pt * (dpt - delta_ref[r, t][:, :bq]) * scale
            dk_scr[:] += _dot(dst.astype(q.dtype), q, _NN)

        _walk(lo, hi, n, tile)

    _walk(0, n_rep, n_rep, head)

    @pl.when(sp == pl.num_programs(3) - 1)
    def _():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, seg, out, lse, dout, scale, causal, bq, bkv, window=None):
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    n_rep = h // hkv
    t = _tiling(sq, skv, bq, bkv, d, k.dtype.itemsize, n_rep)
    bq, bkv = t.bq, t.bkv
    has_seg = seg is not None

    # delta_i = sum_d(dO * O): rowwise, cheap in XLA.
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    stats = [lse, _rows(delta, bq)]

    # --- dQ pass: grid (b, h, q blocks, kv spans)
    q_spec, kv_spec, stat_spec, seg_specs = _q_major_specs(d, n_rep, causal, t, has_seg, window)
    args = [q, k, v, dout, *stats] + ([seg["q_col"], _rows(seg["kv"], bkv)] if has_seg else [])

    def dq_kernel(*refs):
        ins, segs, (dq_ref, *scratch) = _unpack(refs, (2,) * 6, has_seg)
        _bwd_dq_kernel(*ins, *segs, dq_ref.at[0, 0], *scratch,
                       scale=scale, causal=causal, bq=bq, bkv=bkv, window=window)

    dq = _pallas_call(
        dq_kernel,
        name=_named("flash_attention_bwd_dq", window),
        grid=(b, h, sq // bq, skv // t.kv_span),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec] + seg_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
    )(*args)

    # --- dK/dV pass: grid (b, kv head, kv blocks, q spans), the last and the group's query
    # heads summed in the kernel. A q span no row of which sees the kv block names the
    # first that does (or, past a window, the last).
    n = t.q_span // bq
    last_span = sq // t.q_span - 1

    def q_span(kj, sp):
        if window is not None:
            return jnp.clip(sp, _first_q_block(kj, bq, bkv) // n,
                            jnp.minimum(_last_q_block(kj, bq, bkv, window) // n, last_span))
        return jnp.maximum(sp, _first_q_block(kj, bq, bkv) // n) if causal else sp

    q_spec2 = pl.BlockSpec((1, n_rep, t.q_span, d),
                           lambda bi, hk, kj, sp: (bi, hk, q_span(kj, sp), 0))
    kv_spec2 = pl.BlockSpec((1, 1, bkv, d), lambda bi, hk, kj, sp: (bi, hk, kj, 0))
    stat_spec2 = pl.BlockSpec((1, n_rep, n, 1, _lane_pad(bq)),
                              lambda bi, hk, kj, sp: (bi, hk, q_span(kj, sp), 0, 0))
    in_specs2 = [q_spec2, kv_spec2, kv_spec2, q_spec2, stat_spec2, stat_spec2]
    args2 = [q, k, v, dout, *stats]
    if has_seg:
        in_specs2 += [
            pl.BlockSpec((1, bkv, 128), lambda bi, hk, kj, sp: (bi, kj, 0)),
            pl.BlockSpec((1, n, 1, _lane_pad(bq)),
                         lambda bi, hk, kj, sp: (bi, q_span(kj, sp), 0, 0)),
        ]
        args2 += [seg["kv_col"], _rows(seg["q"], bq)]

    def dkv_kernel(*refs):
        # q, dO and the statistics keep the group's query heads as their leading dimension
        ins, segs, (dk_ref, dv_ref, *scratch) = _unpack(refs, (3, 2, 2, 3, 4, 4), has_seg)
        _bwd_dkv_kernel(*ins, *segs, dk_ref.at[0, 0], dv_ref.at[0, 0], *scratch,
                        scale=scale, causal=causal, bq=bq, bkv=bkv, window=window)

    dk, dv = _pallas_call(
        dkv_kernel,
        name=_named("flash_attention_bwd_dkv", window),
        grid=(b, hkv, skv // bkv, sq // t.q_span),
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


# --------------------------------------------------------- RoPE, in front of the kernels
# q and k reach the flash kernels rotated and head-major, [B, H, S, D]. The layout costs
# nothing: XLA lays a projection's output out for its consumer, and with head_dim the
# lane width a matmul writes [B, H, S, D] as cheaply as [B, S, H, D] (compiled for a
# v5e, both train cells: the projection fusions feed the kernel below, no copy between).
# The rotation is then one pass over HBM in that layout: a grid step reads `rows`
# positions of every head of q and k, rotates each lane-dense [rows, D] tile in f32
# registers and writes it back in the inputs' dtype. Rotate-half, `x * cos + roll(x, D/2)
# * sin_signed`, both halves' angles side by side and the sign folded into the sine: the
# arithmetic of models/llama.py:rope, f32 throughout (on the chip: bit-equal to it). Its
# transpose is the same kernel with the conjugate angle, so the backward keeps nothing
# but the positions. Measured on a v5e at [6, 2048, 32/8, 128] bf16 (PERF.md, PR 30):
# 0.37 ms a run forward or backward, 0.25 GB read and written = 83 % of the HBM roofline,
# where the jax.numpy rope and the transpose took 3.6 ms for the two.


def _rope_tables(positions: jax.Array, d: int, theta: float):
    """cos and signed sine of every position's angles: positions [R, S] -> [R, S, D] f32."""
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([-sin, sin], axis=-1)


def _rope_rows(s: int, row_bytes: int) -> int:
    """Positions a grid step of the rotate kernel moves: the most (a multiple of 16
    that divides the sequence, at most 512) whose blocks, both pipeline buffers, fit
    `SPAN_VMEM_BYTES`; a short or odd sequence is one block."""
    fits = [r for r in range(16, min(s, 512) + 1, 16)
            if s % r == 0 and 2 * r * row_bytes <= SPAN_VMEM_BYTES]
    return max(fits) if fits else s


def _rope_kernel(*refs, n: int, conjugate: bool):
    """refs: n inputs [H, rows, D], cos and signed sine [rows, D], n outputs. The heads
    are walked by a loop, not unrolled: 40 copies of the body were two seconds of
    tracing at every process start, compile cache or not (a cached first step 5.14 s
    against 3.20), for 1 % of the kernel's time (PERF.md, PR 30)."""
    cos_ref, sin_ref = refs[n].at[0], refs[n + 1].at[0]
    for x_ref, o_ref in zip(refs[:n], refs[n + 2:]):
        def head(h, x_ref=x_ref, o_ref=o_ref):
            x = x_ref[0, h].astype(jnp.float32)
            turned = pltpu.roll(x, x.shape[1] // 2, 1) * sin_ref[:]
            y = x * cos_ref[:] - turned if conjugate else x * cos_ref[:] + turned
            o_ref[0, h] = y.astype(o_ref.dtype)

        _walk(0, x_ref.shape[1], x_ref.shape[1], head)


def _rope_call(xs, positions, theta: float, conjugate: bool):
    """Rotate every array of `xs` ([B, H, S, D], any head counts) in one kernel call, by
    the positions' angles or (`conjugate`) back. positions [B, S], or [1, S] where every
    row of the batch shares them: then one row of angles is made and every batch row's
    grid steps fetch it."""
    b, _, s, d = xs[0].shape
    per_row = positions.shape[0] > 1
    rows = _rope_rows(s, 2 * sum(x.shape[1] * d * x.dtype.itemsize for x in xs) + 2 * d * 4)
    blocks = [pl.BlockSpec((1, x.shape[1], rows, d), lambda bi, si: (bi, 0, si, 0)) for x in xs]
    angles = pl.BlockSpec((1, rows, d), lambda bi, si: (bi if per_row else 0, si, 0))
    return _pallas_call(
        functools.partial(_rope_kernel, n=len(xs), conjugate=conjugate),
        name="rope_bwd" if conjugate else "rope_fwd",
        semantics=("parallel", "parallel"),
        grid=(b, s // rows),
        in_specs=blocks + [angles] * 2,
        out_specs=blocks,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs],
    )(*xs, *_rope_tables(positions, d, theta))


def _heads_major(x):
    """[B, S, H, D] <-> [B, H, S, D]: a view, which XLA gives its producer's layout."""
    return x.transpose(0, 2, 1, 3)


def _rotated(q, k, positions, theta):
    return tuple(_rope_call((_heads_major(q), _heads_major(k)), positions, theta, False))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rope_to_heads(q, k, positions, theta):
    """q [B, S, H, D], k [B, S, Hkv, D] un-rotated, positions [B or 1, S] -> the rotated q
    [B, H, S, D] and k [B, Hkv, S, D], as the flash kernels read them. Differentiated,
    the two carry `ROTATED_NAMES` for a remat policy to keep."""
    return _rotated(q, k, positions, theta)


def _named_bits(x, name: str):
    """x under `name` for a remat policy to keep, as its bits. jax.checkpoint rounds a
    float residual once more where it is made (`reduce_precision`, against XLA's excess
    precision); after a kernel, which wrote the dtype itself, that rounds nothing and
    is a pass over HBM of its own (a stand-alone `reduce-precision` of each array a
    layer, compiled for a v5e). The policy saves integers as they are."""
    bits = jax.lax.bitcast_convert_type(x, jnp.dtype(f"uint{8 * x.dtype.itemsize}"))
    return jax.lax.bitcast_convert_type(checkpoint_name(bits, name), x.dtype)


def _rope_fwd_rule(q, k, positions, theta):
    out = _rotated(q, k, positions, theta)
    return tuple(_named_bits(x, name) for x, name in zip(out, ROTATED_NAMES)), positions


def _rope_bwd_rule(theta, positions, cts):
    return (*(_heads_major(g) for g in _rope_call(cts, positions, theta, True)), None)


rope_to_heads.defvjp(_rope_fwd_rule, _rope_bwd_rule)


# ----------------------------------------------------------------------- public API

# The one head width below the lane width that runs the kernels (`flash_attention`): half
# a vreg, so the padded products cost the MXU what the narrow ones would (a 128 x 128
# array contracts 64 in the passes of 128, and writes 64 columns in the passes of 128).
NARROW_HEAD = 64


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_bhsd(q, k, v, seg, scale, causal, bq, bkv, window):
    out, _ = _fwd(q, k, v, seg, scale, causal, bq, bkv, window)
    return out


def _flash_fwd_rule(q, k, v, seg, scale, causal, bq, bkv, window):
    # the kernel's two results carry `FLASH_NAMES`: a policy that keeps both leaves nothing
    # in a rematerialised layer that reads the kernel, and JAX drops its second run there
    out, lse = (_named_bits(x, name)
                for x, name in zip(_fwd(q, k, v, seg, scale, causal, bq, bkv, window), FLASH_NAMES))
    return out, (q, k, v, seg, out, lse)


def _flash_bwd_rule(scale, causal, bq, bkv, window, res, dout):
    q, k, v, seg, out, lse = res
    dq, dk, dv = _bwd(q, k, v, seg, out, lse, dout, scale, causal, bq, bkv, window)
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
    block_q: int = BLOCK_Q,
    block_kv: int = BLOCK_KV,
    rope: Optional[tuple] = None,  # (positions [B or 1, S], theta): q and k come un-rotated
    window: Optional[int] = None,  # key j is kept for query i where 0 <= i - j < window
) -> jax.Array:
    """BSHD flash attention. Sq must equal Skv when segment_ids are used, and with
    `rope`: then the rotate kernel runs in front of the flash kernels (`rope_to_heads`).
    `window` (causal, one sequence) keeps the last `window` keys a query, its own among
    them; one no shorter than the sequence is no window.

    Heads 64 wide (`NARROW_HEAD`) run the same three kernels, under the same names, on
    q, k and v padded with zero lanes to 128: the scores do not see zeros in q and k, the
    output's padded lanes are zero and are cut, and the cut's transpose pads dO, so dq, dk
    and dv come out of the pad's own transpose. Mosaic lays a [.., S, 64] array out in HBM
    in (8, 128) tiles as it is (compiled for a v5e: `memref<..x8192x128xbf16>` behind a
    block of 64), so a kernel of its own at 64 would move the same bytes; what the padding
    adds is the pad and the cut themselves, which XLA fuses into the producer of q, k, v
    and the consumer of the output. They come rotated (`rope` is refused: the rotate
    kernel's tiles are whole vregs)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    if d == NARROW_HEAD:
        if rope is not None:
            raise NotImplementedError(f"the rotate kernel at head width {d}: hand q and k over rotated")
        lanes = ((0, 0),) * 3 + ((0, _lane_pad(d) - d),)
        q, k, v = (jnp.pad(x, lanes) for x in (q, k, v))
    qt, kt = (_heads_major(q), _heads_major(k)) if rope is None else rope_to_heads(q, k, *rope)
    vt = _heads_major(v)
    seg = None if segment_ids is None else _segment_lanes(segment_ids, q.shape[1])
    out = _flash_bhsd(qt, kt, vt, seg, scale, causal, block_q, block_kv,
                      _band(window, q.shape[1], k.shape[1], causal))
    return _heads_major(out)[..., :d]
