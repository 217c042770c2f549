"""Pallas TPU flash attention (forward + backward), causal + GQA + segment ids.

Blockwise online-softmax attention (flash v2 style): the S×S score matrix never
materializes in HBM; each (q-block, kv-block) tile is computed in VMEM and folded into
running (max, sum, acc) statistics.

Layout inside the kernel is [B, H, S, D] ("BHSD") so the S×D tiles are contiguous; the
public wrapper takes BSHD like the rest of the framework. GQA is handled in the
BlockSpec index maps (kv head = q head // n_rep) — repeated KV heads are never
materialized. q and k share a width D and v, the output and dO another, Dv (PR 54:
latent attention's 192 beside 128): the scores, dK and dQ are D wide, the weighted values,
dP's contraction and dV are Dv wide, every block, accumulator and resident sum at its own.
Where the two are equal (every other family) the programs are the ones they were.

The backward is ONE kernel a call (`_bwd_fused_kernel`, PR 53) wherever K and V of a kv
head are one span (`_fuses`: up to 16,384 rows at head_dim 128 in bf16, 8,192 at 256:
every shape the benchmark's cells have). A tile's probabilities are recomputed from the
saved logsumexp ONCE and dV, dK and dQ are all written from them: five products and one
exponential a tile (S, dV, dP, dK, dQ), where the standard two-kernel split, one pass for
dQ and one for dK/dV, makes the scores, the exponentials and dP in each: seven and two.
The kernel works on the TRANSPOSED tile (kv rows, q columns), as the dK/dV pass always
did: its first four products are then plain or transposed-right-hand matmuls and the row
statistics (logsumexp, delta) come in as lane vectors of `block_q` floats; the fifth,
dQ += dS k, takes the one [bkv, bq] transpose a tile, of dS in the inputs' dtype, which
the products do not wait on. A grid step owns one q tile (dQ is its own, in scratch),
and dK and dV of the kv head stay in VMEM as f32 [Skv, D] over the steps of the group's
query heads and q tiles, which the grid runs in order, and are rounded and written once a
kv head: nothing that crosses grid steps goes through HBM. The VMEM asked for is derived
from the shapes (K, V and dK, dV's blocks twice, the sums once: 64 MiB of a core's 128 at
16,384 x 128, 22 MiB at 2,048). Where K and V are longer than a span the two kernels run
as they did, `_bwd_dq_kernel` and `_bwd_dkv_kernel`, the latter summing a kv head's group
of query heads in the kernel.

The block a grid step fetches is not the tile a product computes. A step of the
forward, backward and dQ kernels owns one q tile and a SPAN of K/V rows, a step of dK/dV
one kv tile and a span of the q rows of its group's query heads; a `fori_loop` inside the
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
for query i where 0 <= i - j < window) is a second edge of the same kind, below the band,
and a windowed call's work follows the band on both of its sides (PR 49). Its grids'
last dimension runs over the spans a band reaches, counted from the band's own first
(`_kv_spans`, `_q_spans`: at 16,384 positions inside a window of 2,048 K and V are one span
and a step walks its q tile's band; a dK/dV kernel of its own would run 3 spans of 1,024 q
rows a kv tile where the triangle's grid is 16); only where the sequence's end
cuts a band short does a step walk nothing, and it names the last span used. Inside a
step the walk (`Band`, `_walk_band`) computes the tiles between the band's two edges
whole, and the backward kernel (or two) the two an edge crosses, the diagonal's and the one
`_band_depth` tiles below it, in the `EDGE_PIECE`-row pieces that hold a kept score
(`_edge_pieces`): of the diagonal's tile the upper half of the q rows meets the first
half of the kv rows alone, of the far edge's the lower half meets the second half alone,
so a q tile costs the backward 4.5 tiles where the band needs 4.0 and whole tiles made 5
(`tile_counts`: 135 for 120.0 a head, 150 before and forward). A piece is a part of the
rows that own the accumulators (q rows in the one kernel and in dQ, kv rows in dK/dV), so
those rows' sums keep their order and their results their bits. Measured on a v5e at
[2, 16384, 32 / 4, 128] bf16, window 2,048, a call alone with the two backward kernels
(PERF.md, PR 49): forward 9.45 -> 9.42 ms, dQ 12.07 -> 11.45, dK/dV 16.92 -> 14.13 (the grid
alone 14.85: 3,328 fewer steps, each of which ran the group's loop of 8 heads around an
empty walk), dq, dk and dv bit-equal to the whole tiles'; 128-row pieces read 11.27 / 13.85
backward, 256 x 256 compute tiles 17.9 / 18.1 / 27.9. The forward kernel walks its edge
tiles whole: in pieces it read 9.41 (`_fwd_kernel`). With the one backward kernel, in the
Trinity cell's step (PERF.md, PR 53): forward 9.45 ms, backward 17.97 where dQ and dK/dV
took 11.65 + 14.17, 82 % of the MXU's bf16 peak on the 135 tiles' worth it computes and
73 % on the band's 120. A windowed call's kernels carry `_window` behind their names.
Every product feeds the MXU the inputs' own dtype (bf16 in training) and accumulates in
f32; scores, exponentials, logsumexp, delta and all accumulators are f32. Per-row
statistics and segment ids are kept 128 equal lanes wide inside a kernel and travel
between kernels as lane vectors (`_rows`): as [rows, 1] columns every use of them is a
lane broadcast.

Measured on a v5e, bf16 causal, 512 x 512 tiles, each kernel's own time in a traced train
step (PERF.md, PR 53; a tile's product is 67.1 MFLOP, two of them forward and five backward):
at [6, 2048, 32/8, 128] forward 2.05 ms and backward 3.78 a call (dQ 2.66 + dK/dV 3.00 = 5.66
before), which is 64 / 87 % of the MXU's bf16 peak on the products the kernels execute
(`tile_counts`: 10 tiles a (batch, head), the masked halves of the diagonal's included) and
51 / 69 % on the 8 that causal attention needs; at [2, 16384, 32/4, 128] forward 30.67 and
backward 63.37 (40.95 + 52.32 = 93.26 before): 75 / 91 % on the 528 tiles a head executed,
73 / 88 % on the 512.03 needed; at [1, 8192, 20/20, 256] 4.49 and 10.03 (6.10 + 8.27), at
[4, 8192, 32/8, 64] on padded lanes 16.14 and 32.99 (21.57 + 26.94), under the
block-diffusion mask at [1, 2 x 8192, 32/4, 128] 8.57 and 17.49 (11.45 + 15.65). The
backward asks for 64 MiB of VMEM at the three long shapes (of which Mosaic reports 34 used
at 16,384 x 128 and 52 at 8,192 x 256), 22 MiB at 2,048.
"""
from __future__ import annotations

import functools
import math
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
# Rows of the pieces a windowed call's backward kernels compute the band's two EDGE tiles
# in (`_edge_rows`): the diagonal's tile and the one the window's far edge crosses keep
# about half their scores each, and in 256-row pieces 3 of 4 hold one. Measured on a v5e at
# [2, 16384, 32 / 4, 128] bf16 inside a window of 2,048, a call alone, forward / dQ / dK/dV
# in ms (PERF.md, PR 49): whole tiles 9.45 / 12.07 / 16.92 on the triangle's dK/dV grid and
# 9.42 / 12.07 / 14.85 on the band's; 256-row pieces 9.41 / 11.45 / 14.13; 128-row pieces
# (5 of 8 hold a score) 9.84 / 11.27 / 13.85: the same sum, with twice the bodies to trace.
EDGE_PIECE = 256
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
             block_kv: int = BLOCK_KV, block_diffusion: Optional[int] = None,
             v_head_dim: Optional[int] = None) -> bool:
    """Whether the kernels can tile this geometry. Mosaic tiles the lane (last) dim at 128
    and sublanes at 8, and a sequence longer than one compute tile must be a whole number of
    them (`_block_sizes`): head_dim 16, seq 20 or seq 520 would fail the TPU compile ("slice
    shape must be aligned to tiling"). `head_dim` is q's and k's width and `v_head_dim` v's
    and the output's (None: as wide): each whole vregs of 128 lanes, or whole halves of one
    (64, 192: `NARROW_HEAD`), which run on lanes padded with zeros to the next whole vreg."""
    def seq_ok(n: int, block: int) -> bool:
        return n % 8 == 0 and (n <= block or n % block == 0)

    if block_diffusion is not None:  # the halves of the doubled row, each whole tiles of whole blocks
        try:
            bd = _block_diffusion(block_diffusion, sq, skv, block_q, block_kv)
        except ValueError:
            return False
        sq = skv = bd.half
    widths = (head_dim, head_dim if v_head_dim is None else v_head_dim)
    return all(w > 0 and w % NARROW_HEAD == 0 for w in widths) and seq_ok(sq, block_q) and seq_ok(skv, block_kv)


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


def _tiling(sq, skv, bq, bkv, d, itemsize, n_rep=1, dv=None) -> Tiling:
    """`d`: q's and k's width; `dv`: v's, the output's and dO's (None: as wide)."""
    bq, bkv = _block_sizes(sq, skv, bq, bkv)
    row = 2 * (d + (d if dv is None else dv)) * itemsize  # two arrays a side (K, V; Q, dO), two pipeline buffers each
    # dK/dV: of every query head of the group, and the rows' logsumexp and delta, which
    # a lane vector a tile holds in 8 sublanes
    return Tiling(bq, bkv, _span(skv, bkv, row), _span(sq, bq, n_rep * (row + 2 * 2 * 4 * 8)))


class TileCounts(NamedTuple):
    grid_steps: int  # steps of the last two grid dimensions: those of one (batch, head)
    tiles_computed: float  # compute tiles whose products run, an edge tile's pieces as their share of a tile
    tiles_needed: float  # the scores attention needs, in compute tiles


def tile_counts(sq: int, skv: int, causal: bool, bq: int, bkv: int, *, head_dim: int = 128,
                itemsize: int = 2, n_rep: int = 1, kernel: str = "fwd",
                window: Optional[int] = None, block_diffusion: Optional[int] = None,
                v_head_dim: Optional[int] = None) -> TileCounts:
    """What a (batch, query head) costs the forward (`kernel` "fwd") or the backward kernel
    ("dq": the one kernel of a call whose K and V are a span, `_fuses`, which makes a tile's
    five products once, and the dQ kernel of a longer one, which makes three of the seven;
    both walk a q tile's kv tiles), or a (batch, kv head with its `n_rep` query heads) that
    longer call's dK/dV kernel ("dkv"): from the same `_tiling`, grid lengths and bands
    (`_kv_band`, `_q_band`) the kernels are built from. A causal tile is computed if any of
    its scores is kept (kv position <= q position, and inside a `window` more than q
    position - window); under a window the
    backward kernels compute the two tiles an edge of the band crosses in the pieces that
    hold one: with 512 x 512 tiles, 256-row pieces and a window of 2,048 a q tile meets 5
    kv tiles forward and 4.5 backward where the band needs 4.0. Under `block_diffusion` (the
    row [noised ; clean], `sq` its whole length) a tile is computed if the mask's three parts
    keep one of its scores (`BlockDiffusion`), and the scores kept are half x (half + block)."""
    bd = _block_diffusion(block_diffusion, sq, skv, bq, bkv, causal, window)
    if bd is not None:
        bq, bkv = min(bq, bd.half), min(bkv, bd.half)
    t = _tiling(sq, skv, bq, bkv, head_dim, itemsize, n_rep, v_head_dim)
    nq, nk = sq // t.bq, skv // t.bkv
    window = _band(window, sq, skv, causal)
    steps, heads = ((nk * _q_spans(sq, skv, t, window), n_rep) if kernel == "dkv"
                    else (nq * _kv_spans(sq, skv, t, window), 1))
    if bd is not None:
        ranges = ([_bd_q_ranges(kj, t.bq, t.bkv, bd) for kj in range(nk)] if kernel == "dkv"
                  else [_bd_kv_ranges(qi, t.bq, t.bkv, bd) for qi in range(nq)])
        computed = sum(hi - lo for tile in ranges for lo, hi in tile)
        return TileCounts(steps, heads * computed, heads * bd.half * (bd.half + bd.block) / (t.bq * t.bkv))
    if not causal:
        return TileCounts(steps, heads * nq * nk, float(heads * nq * nk))
    if window is None:
        computed = sum(min(_last_kv_block(qi, t.bq, t.bkv) + 1, nk) for qi in range(nq))
        m = min(sq, skv)
        kept = m * (m + 1) // 2 + (sq - m) * skv  # row i keeps min(i + 1, skv) scores
    else:
        def share(pieces):
            return sum(p.q[1] * p.kv[1] for p in pieces) / (t.bq * t.bkv)

        bands = ([_q_band(kj, nq, t.bq, t.bkv, window) for kj in range(nk)] if kernel == "dkv"
                 else [_kv_band(qi, t.bq, t.bkv, window, kernel == "dq") for qi in range(nq)])
        computed = sum(b.last - b.first + 1 - b.cut_first * (1 - share(b.pieces_first))
                       - b.cut_last * (1 - share(b.pieces_last)) for b in bands)
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


class BlockDiffusion(NamedTuple):
    """The block-diffusion mask (BD3-LMs, arXiv:2503.09573) over a row laid [noised ; clean],
    `half` positions each, in blocks of `block` positions: key c is kept for query r iff c is
    clean and its block lies before r's (for a clean r: before or at), or c and r are of one
    half and one block. `half` is whole compute tiles and a tile whole blocks, so a tile lies in
    one half, a noised q tile meets the noised kv tiles over its own rows and the clean ones up
    to the block before its last row's, a clean q tile the clean ones up to its last row
    (`_bd_kv_ranges`; transposed: `_bd_q_ranges`), and the kernels walk those and no others:
    at [2 x 8192, 512] 288 tiles a head each way where the kept scores are 256.1 tiles' worth,
    the triangle over the doubled row would compute 528 and a dense walk 1,024."""
    block: int
    half: int


def _block_diffusion(block: Optional[int], sq: int, skv: int, bq: int, bkv: int, causal: bool = False,
                     window=None, segment_ids=None) -> Optional[BlockDiffusion]:
    """The mask the kernels walk for `block_diffusion=block`, or None where there is none;
    what they cannot tile is refused (`supports` says so beforehand)."""
    if block is None:
        return None
    half = sq // 2
    tiles = [min(b, half) for b in (bq, bkv)]
    if (causal or window is not None or segment_ids is not None or sq != skv or sq % 2 or block < 1
            or block & (block - 1) or any(half % t or t % block for t in tiles)):
        raise ValueError(
            f"block_diffusion {block}: one row [noised ; clean] (sq {sq}, skv {skv}) whose halves are whole tiles "
            f"({tiles}) of whole blocks, a power of two long; no `causal`, window or segment ids beside it")
    return BlockDiffusion(int(block), half)


def _bd_kv_ranges(qi, bq: int, bkv: int, bd: BlockDiffusion):
    """The kv tiles q tile `qi` meets under the block-diffusion mask, as two half-open ranges
    in ascending order: (the noised tiles over its own positions; (0, 0) for a clean q tile,
    the clean tiles up to the last key its last row keeps)."""
    nq, nk = bd.half // bq, bd.half // bkv
    clean = _flag(qi >= nq)
    i = qi - nq * clean
    own = ((i * bq) // bkv * (1 - clean), (((i + 1) * bq - 1) // bkv + 1) * (1 - clean))
    # a noised row keeps the clean keys before its block, a clean row those up to its block's end
    return own, (nk, nk + ((i + 1) * bq - 1 - bd.block * (1 - clean)) // bkv + 1)


def _bd_q_ranges(kj, bq: int, bkv: int, bd: BlockDiffusion):
    """The same transposed (dK/dV): the q tiles kv tile `kj` meets, as three half-open ranges in
    ascending order, an empty one (0, 0): (the noised tiles over a noised kv tile's own
    positions, the noised tiles from the block behind a clean kv tile's first key, the clean
    tiles from that key's)."""
    nq, nk = bd.half // bq, bd.half // bkv
    clean = _flag(kj >= nk)
    j = kj - nk * clean
    own = ((j * bkv) // bq * (1 - clean), (((j + 1) * bkv - 1) // bq + 1) * (1 - clean))
    behind = (j * bkv + bd.block) // bq
    behind = min(behind, nq) if isinstance(behind, int) else jnp.minimum(behind, nq)
    return own, (behind * clean, nq * clean), ((nq + (j * bkv) // bq) * clean, 2 * nq * clean)


def _nearest_span(step, n: int, first, second):
    """Which span of `n` tiles a grid step fetches where its tiles are walked in two half-open
    ranges (`first` before `second`, either possibly empty): the step's own where that holds a
    tile of one, else the nearest before it that does (the first used, for the steps in front
    of it), so that the pipeline issues no copy for a span of which nothing is read."""
    (a_lo, a_hi), (b_lo, b_hi) = first, second
    in_a = jnp.clip(step, a_lo // n, jnp.maximum(a_hi - 1, a_lo) // n)
    in_b = jnp.clip(step, b_lo // n, jnp.maximum(b_hi - 1, b_lo) // n)
    return jnp.where((a_hi > a_lo) & ((step < b_lo // n) | (b_hi <= b_lo)), in_a, in_b)


def _walk_ranges(ranges, base, n: int, tile) -> None:
    """Run a grid step's share of each of `ranges` = ((first, one past the last), keywords
    of `tile`): its span holds compute tiles base .. base + n - 1."""
    for (lo, hi), kw in ranges:
        _walk(jnp.clip(lo - base, 0, n), jnp.clip(hi - base, 0, n), n, functools.partial(tile, **kw))


def _keep_bd(shape, q_axis: int, qi, kj, bq: int, bkv: int, bd: BlockDiffusion, own: bool):
    """Which scores of a tile stay under the block-diffusion mask. `own`: q and kv tile are
    both noised (a key is kept inside its query's block), else the kv tile is clean (a key is
    kept before its query's block, for a clean query up to that block's end). With r and c a
    score's row and column in the tile and `first` the q tile's first position less the kv
    tile's, each in its half: a key's offset from its query's block is c - (r - r mod block) -
    first (a tile is whole blocks, so r mod block is the position's own)."""
    nq, nk = bd.half // bq, bd.half // bkv
    clean_q = _flag(qi >= nq)
    first = (qi - nq * clean_q) * bq - (kj if own else kj - nk) * bkv
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    offset = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis) - (rows & -bd.block)
    if own:
        return (offset >= first) & (offset < first + bd.block)
    return offset < first + bd.block * clean_q


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


def _pallas_call(kernel, *, name: str, semantics=("parallel", "parallel", "parallel", "arbitrary"),
                 vmem_limit_bytes: int = VMEM_LIMIT_BYTES, **kw):
    """The flash kernels' grid has four dimensions, the last of which accumulates.
    `name` is the operation's name in the device trace (the benchmark's kernel
    metrics select by it)."""
    return pl.pallas_call(
        kernel, name=name, interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=vmem_limit_bytes),
        **kw)


def _named(kernel: str, window: Optional[int], bd: Optional[BlockDiffusion] = None) -> str:
    """A flash kernel's name in the device trace: a windowed call's carries `_window`
    behind it and a block-diffusion call's `_bd`, so that a metric can tell the band's and
    the doubled row's kernels from the triangle's."""
    if bd is not None:
        return f"{kernel}_bd"
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


def _at(t, block: int, part: Optional[tuple] = None):
    """Rows of compute tile `t` in a span-long block: all of them, or the `part` = (first,
    how many) of them that a piece takes."""
    if part is None:
        return slice(None) if isinstance(t, int) else pl.ds(pl.multiple_of(t * block, block), block)
    first, size = part
    if isinstance(t, int):
        return slice(t * block + first, t * block + first + size)
    start = t * block + first if first else t * block
    return pl.ds(pl.multiple_of(start, math.gcd(block, first)), size)


class Piece(NamedTuple):
    """The part of a compute tile that a product runs on: (first, how many) of the q
    tile's rows against (first, how many) of the kv tile's, all static. Where a tile body
    takes `piece=None` it computes the whole tile, and both parts are None."""
    q: tuple
    kv: tuple


def _cut(part: Optional[tuple]):
    """A tile-long block's or scratch buffer's rows that a piece takes: all, or its `part`."""
    return slice(None) if part is None else slice(part[0], part[0] + part[1])


def _lanes_of(ref, at: tuple, part: Optional[tuple]):
    """The lane vector `ref[at]` ([1, P]: a tile's positions side by side), or of it the
    `part` a piece takes: read so from the ref, whose lanes a load can start at any whole
    vreg (a value cut there keeps an offset that Mosaic refuses to broadcast down the rows)."""
    return ref[(*at, slice(None), _cut(part))]


def _edge_rows(bq: int, bkv: int, window: Optional[int]) -> Optional[int]:
    """Rows of the pieces a windowed call computes its two edge tiles in, or None where
    every tile is computed whole: no window, tiles that are not square (the cut below is
    made for a diagonal that runs corner to corner), or a tile that does not hold two pieces
    (`EDGE_PIECE` is whole vregs of lanes; a shorter tile's halves would not be)."""
    if window is None or bq != bkv or bq % EDGE_PIECE or bq == EDGE_PIECE:
        return None
    return EDGE_PIECE


def _band_depth(window: int, block: int) -> int:
    """How many (square) tiles below the diagonal's the tile lies that the window's far
    edge crosses: `qi - _first_kv_block(qi)` and `_last_q_block(kj) - kj` away from the
    sequence's two ends."""
    return (window + block - 2) // block


def _edge_pieces(depth: int, block: int, rows: int, window: int, kv_major: bool = False) -> tuple:
    """The pieces of the tile `depth` tiles below the diagonal's (0: the diagonal's own)
    that hold a kept score, 0 <= depth * block + r - c < window for query row r and key
    row c of the tile: a piece for every `rows` query rows (`kv_major`: key rows, dK/dV's
    side), against the pieces of the other side it reaches, which lie side by side. A
    row's scores are all in its own piece, so its sums keep their order."""
    pieces = []
    for own in range(0, block, rows):
        if kv_major:  # key rows own .. own + rows - 1: the query rows that see one of them
            lo, hi = own - depth * block, own + rows - 1 + window - 1 - depth * block
        else:  # query rows own .. own + rows - 1: the key rows one of them sees
            lo, hi = depth * block + own - window + 1, depth * block + own + rows - 1
        lo, hi = max(lo, 0) // rows * rows, (min(hi, block - 1) // rows + 1) * rows
        if lo < hi:
            pieces.append(Piece((lo, hi - lo), (own, rows)) if kv_major else Piece((own, rows), (lo, hi - lo)))
    return tuple(pieces)


class Band(NamedTuple):
    """The compute tiles that one tile of the other side meets under a window, counted over
    the whole sequence and walked in ascending order: `first` .. `last`, each whole; but where
    `cut_first` (`cut_last`) is 1 and not 0, the first (last) in `pieces_first`
    (`pieces_last`) alone."""
    first: object
    last: object
    cut_first: object = 0
    cut_last: object = 0
    pieces_first: tuple = ()
    pieces_last: tuple = ()


def _flag(x):
    return int(x) if isinstance(x, (bool, int)) else x.astype(jnp.int32)


def _kv_band(qi, bq: int, bkv: int, window: int, pieces: bool = True) -> Band:
    """The kv tiles q tile `qi` meets: from the one its window's far edge crosses,
    `_band_depth` below the diagonal's, to the diagonal's; dQ computes the two in `pieces`
    (nearer the sequence's start the first is tile 0, whole), the forward kernel whole."""
    first, last = _first_kv_block(qi, bq, bkv, window), _last_kv_block(qi, bq, bkv)
    rows = _edge_rows(bq, bkv, window) if pieces else None
    if rows is None:
        return Band(first, last)
    depth = _band_depth(window, bq)  # 0: a window of one key, both edges in the diagonal's tile
    return Band(first, last, _flag(qi >= depth) if depth else 0, 1,
                _edge_pieces(depth, bq, rows, window) if depth else (), _edge_pieces(0, bq, rows, window))


def _q_band(kj, nq: int, bq: int, bkv: int, window: int) -> Band:
    """The q tiles kv tile `kj` meets (dK/dV): from the diagonal's, in pieces, to the last
    whose window reaches it, in pieces (the sequence's last tile, where that comes first, whole)."""
    first, far = _first_q_block(kj, bq, bkv), _last_q_block(kj, bq, bkv, window)
    last = min(far, nq - 1) if isinstance(far, int) else jnp.minimum(far, nq - 1)
    rows = _edge_rows(bq, bkv, window)
    if rows is None:
        return Band(first, last)
    depth = _band_depth(window, bq)
    return Band(first, last, 1, _flag(far <= nq - 1) if depth else 0,
                _edge_pieces(0, bq, rows, window, True), _edge_pieces(depth, bq, rows, window, True) if depth else ())


def _band_steps(band: Band, base, n: int):
    """What the grid step whose span holds compute tiles base .. base + n - 1 does of a
    band: (lo, hi, first edge, last edge): tiles lo <= t < hi of the span whole, and an
    edge = (its tile in the span, whether this step computes it in pieces)."""
    def edge(g, cut):
        t = g - base
        return t, (cut > 0) & (t >= 0) & (t < n)

    return (jnp.clip(band.first + band.cut_first - base, 0, n),
            jnp.clip(band.last + 1 - band.cut_last - base, 0, n),
            edge(band.first, band.cut_first), edge(band.last, band.cut_last))


def _walk_band(band: Band, base, n: int, tile) -> None:
    """Run a grid step's share of a band (`_band_steps`): `tile(t)` for its whole tiles,
    `tile(t, piece)` for an edge's pieces. The edges stand outside the loop: their
    products have other shapes than a whole tile's."""
    lo, hi, *edges = _band_steps(band, base, n)

    def edge(at, pieces):
        t, inside = at
        if pieces:
            @pl.when(inside)
            def _():
                for piece in pieces:
                    tile(0 if n == 1 else t, piece)

    edge(edges[0], band.pieces_first)
    _walk(lo, hi, n, tile)
    edge(edges[1], band.pieces_last)


def _spans_reached(bands, n: int) -> int:
    """The most spans of `n` tiles that one of `bands` (static) touches: the length of a
    windowed grid's last dimension, which counts from a band's own first span."""
    return max(b.last // n - b.first // n + 1 for b in bands)


def _kv_span_of(step, qi, n: int, bq: int, bkv: int, window: Optional[int]):
    """Which span of K/V a step of the forward and dQ grids' last dimension is: the step's
    own number, or under a window that many past the first span the q tile's band reaches."""
    return step if window is None else step + _first_kv_block(qi, bq, bkv, window) // n


def _walk_kv(qi, sj, n: int, tile, causal: bool, bq: int, bkv: int, window: Optional[int],
             pieces: bool, bd: Optional[BlockDiffusion] = None) -> None:
    """The forward and dQ kernels' walk over span sj's kv tiles; `pieces`: a band's two edge
    tiles in their pieces (`tile(t, piece)`); under the block-diffusion mask the q tile's two
    ranges, its own noised tiles as `tile(t, own=True)`."""
    if bd is not None:
        own, seen = _bd_kv_ranges(qi, bq, bkv, bd)
        _walk_ranges(((own, {"own": True}), (seen, {})), sj * n, n, tile)
    elif window is None:
        _walk(0, _kv_tiles_end(causal, qi, sj, n, bq, bkv), n, tile)
    else:
        _walk_band(_kv_band(qi, bq, bkv, window, pieces), sj * n, n, tile)


def _keep(shape, q_axis: int, qi, kj, bq: int, bkv: int, causal: bool, seg_col, seg_row,
          window: Optional[int] = None, piece: Optional[Piece] = None):
    """Which scores of a tile, or of a `piece` of it, stay (None: all). `q_axis` is the axis
    query positions run along; `seg_col` [the tile's rows, 128] and `seg_row` [1, >= cols,
    the piece's own: `_lanes_of`] are the segment ids of rows and columns. Every computed tile builds the causal mask,
    also those the diagonal does not cross: a second, maskless tile body moved no kernel
    by 0.3 % on the chip (PERF.md, PR 26)."""
    keep = None
    if causal:  # kv position <= q position
        ahead = (jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
                 - jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis))
        first = kj * bkv - qi * bq  # of the tile's corner; of the piece's
        if piece is not None and piece.kv[0] != piece.q[0]:
            first = first + (piece.kv[0] - piece.q[0])
        keep = ahead >= first
        if window is not None:  # and q position - kv position < window
            keep = keep & (ahead < first + window)
    if seg_col is not None:
        same = _lanes_to(seg_col[_cut(piece and piece[q_axis])], shape[1]) == seg_row[:, :shape[1]]
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
    bd: Optional[BlockDiffusion] = None,
):
    qi = pl.program_id(2)
    step = pl.program_id(3)
    n = k_ref.shape[0] // bkv
    sj = _kv_span_of(step, qi, n, bq, bkv, window)  # which span of K/V

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def tile(t, own=False):
        kj = sj * n + t
        v = v_ref[_at(t, bkv)]
        s = _dot(q_ref[:], k_ref[_at(t, bkv)], _NT) * scale  # [bq, bkv]
        if bd is not None:
            keep = _keep_bd(s.shape, 0, qi, kj, bq, bkv, bd, own)
        else:
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

    # A band's edge tiles whole, where the backward kernels compute them in pieces: this
    # tile is bound by what is done once a ROW (the two lane reductions, above), which a
    # piece of half the rows halves and a tile's two pieces make whole again: 9.41 ms a
    # call in pieces, 9.42 whole, for four more bodies to trace (PERF.md, PR 49).
    _walk_kv(qi, sj, n, tile, causal, bq, bkv, window, pieces=False, bd=bd)

    @pl.when(step == pl.num_programs(3) - 1)
    def _finalize():
        l = l_scr[:]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_scr[:] / _lanes_to(l_safe, acc_scr.shape[1])).astype(o_ref.dtype)
        lse_scr[:bq] = m_scr[:] + jnp.log(l_safe)
        lse_ref[:] = lse_scr[:].T[:1]  # rows become lanes; those past bq are never read


def _kv_spans(sq: int, skv: int, t: Tiling, window: Optional[int]) -> int:
    """The length of the forward and dQ grids' last dimension: the sequence's spans of K/V
    (under the block-diffusion mask too: a noised q tile's two ranges lie a half apart),
    or under a window the most that a q tile's band reaches."""
    if window is None:
        return skv // t.kv_span
    return _spans_reached([_kv_band(qi, t.bq, t.bkv, window) for qi in range(sq // t.bq)], t.kv_span // t.bkv)


def _q_spans(sq: int, skv: int, t: Tiling, window: Optional[int]) -> int:
    """The same of the dK/dV grid: the sequence's spans of q rows, or those a kv tile's band reaches."""
    if window is None:
        return sq // t.q_span
    return _spans_reached([_q_band(kj, sq // t.bq, t.bq, t.bkv, window) for kj in range(skv // t.bkv)],
                          t.q_span // t.bq)


def _q_major_specs(d, n_rep, causal, t: Tiling, has_seg, window=None, bd=None):
    """BlockSpecs of the forward and dQ grids (b, h, q block, kv span): q-side,
    kv-side, per-row statistics (`_rows`: a lane vector a q block), and the segment
    ids of rows and columns."""
    bq, bkv = t.bq, t.bkv
    n = t.kv_span // bkv

    def kv_span(qi, sj):  # a span above the diagonal names the last span used; a band's are counted from its first
        if bd is not None:  # one that holds none of the q tile's two ranges names the nearest that does
            return _nearest_span(sj, n, *_bd_kv_ranges(qi, bq, bkv, bd))
        if window is not None:
            sj = _kv_span_of(sj, qi, n, bq, bkv, window)
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


def _v_wide_specs(dv, d, q_spec, kv_spec, *args):
    """(the output's and dO's, v's) BlockSpecs: q's and k's own where v is as wide (every
    family but one: the program is then the one it was), else the same blocks `dv` wide."""
    return (q_spec, kv_spec) if dv == d else _q_major_specs(dv, *args)[:2]


def _unpack(refs, keeps, has_seg: bool, seg_keeps=(2, 3)):
    """(inputs, segment-id pair, the remaining refs): each input's block indexed down
    to as many trailing dimensions as `keeps` says, the segment ids of the tile's rows
    to two, and those of the span's columns, a lane vector a tile, to three."""
    def last(r, keep):
        return r.at[(0,) * (len(r.shape) - keep)]

    n_in = len(keeps)
    segs = [last(refs[n_in + i], keep) for i, keep in enumerate(seg_keeps)] if has_seg else [None, None]
    return [last(r, keep) for r, keep in zip(refs, keeps)], segs, refs[n_in + 2 * has_seg:]


def _fwd(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,  # [B, Hkv, Skv, Dv]
    seg: Optional[dict],  # _segment_lanes(), or None
    scale: float,
    causal: bool,
    bq: int,
    bkv: int,
    window: Optional[int] = None,
    bd: Optional[BlockDiffusion] = None,
):
    b, h, sq, d = q.shape
    _, hkv, skv, dv = v.shape
    t = _tiling(sq, skv, bq, bkv, d, k.dtype.itemsize, dv=dv)
    bq, bkv = t.bq, t.bkv
    has_seg = seg is not None
    q_spec, kv_spec, stat_spec, seg_specs = _q_major_specs(d, h // hkv, causal, t, has_seg, window, bd)
    o_spec, v_spec = _v_wide_specs(dv, d, q_spec, kv_spec, h // hkv, causal, t, False, window, bd)
    args = [q, k, v] + ([seg["q_col"], _rows(seg["kv"], bkv)] if has_seg else [])

    def kernel(*refs):
        ins, segs, (o_ref, lse_ref, *scratch) = _unpack(refs, (2,) * 3, has_seg)
        _fwd_kernel(*ins, *segs, o_ref.at[0, 0], lse_ref.at[0, 0, 0], *scratch,
                    scale=scale, causal=causal, bq=bq, bkv=bkv, window=window, bd=bd)

    out, lse = _pallas_call(
        kernel,
        name=_named("flash_attention_fwd", window, bd),
        grid=(b, h, sq // bq, _kv_spans(sq, skv, t, window)),
        in_specs=[q_spec, kv_spec, v_spec] + seg_specs,
        out_specs=[o_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq // bq, 1, _lane_pad(bq)), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((_lane_pad(bq), 128), jnp.float32),
        ],
    )(*args)
    return out, lse  # lse: as `_rows` lays [B, H, Sq] out


# ------------------------------------------------------------------ backward kernels


def _bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seg_kv_ref, seg_q_ref,
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
    *, scale, causal, bq, bkv, n_rep, window=None, bd=None,
):
    """The whole backward of one q tile against ALL of K and V ([Skv, D], `_fuses`), on the
    transposed tile [bkv, bq] as dK/dV's: a kept tile's scores, exponentials and dP are made
    once and dV, dK and dQ written from them, five products where the two kernels run seven.
    dQ is the step's own (`dq_scr`); dK and dV of the kv head are summed over the steps of its
    group's query heads and q tiles in `dk_scr`, `dv_scr` ([Skv, D] f32, which stay in VMEM:
    the grid's heads and q tiles run in order) and written by the group's last step. lse_ref,
    delta_ref and seg_q_ref are the q tile's lane vectors [1, P], seg_kv_ref [Skv, 128]. The
    walk is dQ's (`_walk_kv`, a band's edge tiles in its pieces), so a q row's sums keep
    that kernel's order."""
    hi, qi = pl.program_id(1), pl.program_id(2)
    n = k_ref.shape[0] // bkv
    rep, nq = hi % n_rep, pl.num_programs(2)

    def clear(t):
        dk_scr[_at(t, bkv)] = jnp.zeros((bkv, dk_scr.shape[1]), jnp.float32)
        dv_scr[_at(t, bkv)] = jnp.zeros((bkv, dv_scr.shape[1]), jnp.float32)

    pl.when((rep == 0) & (qi == 0))(lambda: _walk(0, n, n, clear))
    dq_scr[:] = jnp.zeros_like(dq_scr)

    def tile(t, piece=None, own=False):
        cols, kv_part = piece or (None, None)
        at = _at(t, bkv, kv_part)
        q, do, k = q_ref[_cut(cols)], do_ref[_cut(cols)], k_ref[at]
        st = _dot(k, q, _NT) * scale  # [bkv, bq]
        if bd is not None:
            keep = _keep_bd(st.shape, 1, qi, t, bq, bkv, bd, own)
        else:
            keep = _keep(st.shape, 1, qi, t, bq, bkv, causal, None if seg_kv_ref is None else seg_kv_ref.at[_at(t, bkv)],
                         None if seg_q_ref is None else _lanes_of(seg_q_ref, (), cols), window, piece)
        if keep is not None:
            st = jnp.where(keep, st, NEG_INF)
        pt = jnp.exp(st - _lanes_of(lse_ref, (), cols)[:, :st.shape[1]])
        dv_scr[at] += _dot(pt.astype(do.dtype), do, _NN)
        dpt = _dot(v_ref[at], do, _NT)
        dst = (pt * (dpt - _lanes_of(delta_ref, (), cols)[:, :st.shape[1]]) * scale).astype(q.dtype)
        dk_scr[at] += _dot(dst, q, _NN)
        dq_scr[_cut(cols)] += _dot(dst.T, k, _NN)  # the one transpose a tile, in fast memory

    _walk_kv(qi, 0, n, tile, causal, bq, bkv, window, pieces=True, bd=bd)
    dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)

    def write(t):
        dk_ref[_at(t, bkv)] = dk_scr[_at(t, bkv)].astype(dk_ref.dtype)
        dv_ref[_at(t, bkv)] = dv_scr[_at(t, bkv)].astype(dv_ref.dtype)

    pl.when((rep == n_rep - 1) & (qi == nq - 1))(lambda: _walk(0, n, n, write))


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seg_q_ref, seg_kv_ref, dq_ref,
    dq_scr, lse_scr, delta_scr,
    *, scale, causal, bq, bkv, window=None, bd=None,
):
    """k_ref and v_ref are a span of K/V, [span, D]. lse_ref and delta_ref are [1, P];
    the tile [bq, bkv] wants them down its rows, so the q block's first step turns
    them once into [bq, 128] with equal lanes."""
    qi = pl.program_id(2)
    step = pl.program_id(3)
    n = k_ref.shape[0] // bkv
    sj = _kv_span_of(step, qi, n, bq, bkv, window)

    @pl.when(step == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        for row_ref, col_scr in ((lse_ref, lse_scr), (delta_ref, delta_scr)):
            col_scr[:] = jnp.broadcast_to(row_ref[:], (128, row_ref.shape[1])).T[:bq]

    def tile(t, piece=None, own=False):
        q_part, kv_part = piece or (None, None)
        rows = _cut(q_part)
        kj = sj * n + t
        k = k_ref[_at(t, bkv, kv_part)]
        s = _dot(q_ref[rows], k, _NT) * scale  # [bq, bkv]
        if bd is not None:
            keep = _keep_bd(s.shape, 0, qi, kj, bq, bkv, bd, own)
        else:
            keep = _keep(s.shape, 0, qi, kj, bq, bkv, causal, seg_q_ref,
                         None if seg_kv_ref is None else _lanes_of(seg_kv_ref, (t,), kv_part), window, piece)
        if keep is not None:
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - _lanes_to(lse_scr[rows], s.shape[1]))
        dp = _dot(do_ref[rows], v_ref[_at(t, bkv, kv_part)], _NT)
        ds = p * (dp - _lanes_to(delta_scr[rows], s.shape[1])) * scale
        dq_scr[rows] += _dot(ds.astype(k.dtype), k, _NN)

    _walk_kv(qi, sj, n, tile, causal, bq, bkv, window, pieces=True, bd=bd)

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, seg_kv_ref, seg_q_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale, causal, bq, bkv, window=None, nq=None, bd=None,
):
    """One kv block against a span of q rows of the query heads of its group, on the
    transposed tile [bkv, bq]: q_ref and do_ref are [n_rep, span, D], lse_ref and
    delta_ref [n_rep, span // bq, 1, P], seg_q_ref [span // bq, 1, P]. `nq`: the q tiles
    of the sequence, which a windowed call's band ends at."""
    kj = pl.program_id(2)
    step = pl.program_id(3)
    n_rep, n = q_ref.shape[0], q_ref.shape[1] // bq
    # which span of q rows: under a window counted from the first the kv block's band reaches
    sp = step if window is None else step + _first_q_block(kj, bq, bkv) // n

    @pl.when(step == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if bd is not None:  # the kv tile's three ranges of q tiles, two of them empty or one
        own, noised, cleans = _bd_q_ranges(kj, bq, bkv, bd)
    elif window is None:  # the first of the span's q tiles that attends to this kv block
        lo = jnp.clip(_first_q_block(kj, bq, bkv) - sp * n, 0, n) if causal else 0
    else:
        band = _q_band(kj, nq, bq, bkv, window)

    def head(r):
        def tile(t, piece=None, own=False):
            cols, kv_part = piece or (None, None)
            rows = _cut(kv_part)
            qi = sp * n + t
            q = q_ref[r, _at(t, bq, cols)]
            do = do_ref[r, _at(t, bq, cols)]
            st = _dot(k_ref[rows], q, _NT) * scale  # [bkv, bq]
            if bd is not None:
                keep = _keep_bd(st.shape, 1, qi, kj, bq, bkv, bd, own)
            else:
                keep = _keep(st.shape, 1, qi, kj, bq, bkv, causal, seg_kv_ref,
                             None if seg_q_ref is None else _lanes_of(seg_q_ref, (t,), cols), window, piece)
            if keep is not None:
                st = jnp.where(keep, st, NEG_INF)
            pt = jnp.exp(st - _lanes_of(lse_ref, (r, t), cols)[:, :st.shape[1]])
            dv_scr[rows] += _dot(pt.astype(do.dtype), do, _NN)
            dpt = _dot(v_ref[rows], do, _NT)
            dst = pt * (dpt - _lanes_of(delta_ref, (r, t), cols)[:, :st.shape[1]]) * scale
            dk_scr[rows] += _dot(dst.astype(q.dtype), q, _NN)

        if bd is not None:
            _walk_ranges(((own, {"own": True}), (noised, {}), (cleans, {})), sp * n, n, tile)
        elif window is None:
            _walk(lo, n, n, tile)
        else:
            _walk_band(band, sp * n, n, tile)

    _walk(0, n_rep, n_rep, head)

    @pl.when(step == pl.num_programs(3) - 1)
    def _():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _fuses(t: Tiling, skv: int) -> bool:
    """Whether a call's backward is ONE kernel (`_bwd_fused_kernel`): where K and V are one span
    (`_tiling`: their blocks, both pipeline buffers, fit `SPAN_VMEM_BYTES`), because then a kv
    head's dK and dV fit beside them as f32 [Skv, D] and [Skv, Dv], in as many bytes again (two
    arrays of four bytes where K/V are two arrays twice over of two), and so do their output
    blocks. At head width 128 in bf16 that is 16,384 positions, at 256 (and at 256 | 128, q and
    k of 192 on their padded lanes beside v) 8,192: every shape the cells have. A longer
    sequence runs the two kernels that re-make a tile's scores."""
    return t.kv_span == skv


def _bwd_fused(q, k, v, seg, dout, stats, scale, causal, t: Tiling, window, bd):
    """dq, dk, dv of a call whose K and V are one span (`_fuses`), from ONE kernel: grid (b, h, q
    blocks, 1), the heads of a group and their q blocks run in order over the kv head's K, V, dK
    and dV, whose blocks' index maps do not move with them."""
    b, h, sq, d = q.shape
    skv, n_rep, bq, bkv, dv = k.shape[2], h // k.shape[1], t.bq, t.bkv, v.shape[3]
    has_seg = seg is not None
    q_spec, kv_spec, stat_spec, _ = _q_major_specs(d, n_rep, causal, t, False, window, bd)
    do_spec, v_spec = _v_wide_specs(dv, d, q_spec, kv_spec, n_rep, causal, t, False, window, bd)
    args = [q, k, v, dout, *stats]
    in_specs = [q_spec, kv_spec, v_spec, do_spec, stat_spec, stat_spec]
    if has_seg:  # of the tile's rows (kv) and columns (q), as dK/dV's
        args += [seg["kv_col"], _rows(seg["q"], bq)]
        in_specs += [pl.BlockSpec((1, skv, 128), lambda bi, hi, qi, sj: (bi, 0, 0)),
                     pl.BlockSpec((1, 1, 1, _lane_pad(bq)), lambda bi, hi, qi, sj: (bi, qi, 0, 0))]

    def kernel(*refs):
        ins, segs, (dq_ref, dk_ref, dv_ref, *scratch) = _unpack(refs, (2,) * 6, has_seg, (2, 2))
        _bwd_fused_kernel(*ins, *segs, dq_ref.at[0, 0], dk_ref.at[0, 0], dv_ref.at[0, 0], *scratch,
                          scale=scale, causal=causal, bq=bq, bkv=bkv, n_rep=n_rep, window=window, bd=bd)

    lanes = skv * (_lane_pad(d) + _lane_pad(dv))  # of a row of K and V, and of dK and dV
    return _pallas_call(
        kernel,
        name=_named("flash_attention_bwd_dkv_dq", window, bd),
        semantics=("parallel", "arbitrary", "arbitrary", "arbitrary"),
        # K, V and dK, dV's blocks twice (the pipeline's), their sums once, the rows' segment
        # ids; and what the two kernels leave the compute tile
        vmem_limit_bytes=(VMEM_LIMIT_BYTES - SPAN_VMEM_BYTES + lanes * (4 * k.dtype.itemsize + 4)
                          + has_seg * 2 * skv * 128 * 4),
        grid=(b, h, sq // bq, 1),
        in_specs=in_specs,
        # dQ is written over dO, block for block (a grid step reads the one and writes the other of
        # its own q tile, once): with all three gradients live at once the GLM step's temporaries
        # were 0.17 GB above the two kernels' (6.71 for 6.54 GB, compiled for a v5e; PERF.md, PR 53).
        # Where v is not as wide as q, dO is not dQ's shape and dQ is an array of its own
        input_output_aliases={3: 0} if dv == d else {},
        out_specs=[q_spec, kv_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k, v)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32), pltpu.VMEM((skv, d), jnp.float32),
                        pltpu.VMEM((skv, dv), jnp.float32)],
    )(*args)


def _bwd(q, k, v, seg, out, lse, dout, scale, causal, bq, bkv, window=None, bd=None):
    b, h, sq, d = q.shape
    _, hkv, skv, dv = v.shape
    n_rep = h // hkv
    t = _tiling(sq, skv, bq, bkv, d, k.dtype.itemsize, n_rep, dv)
    bq, bkv = t.bq, t.bkv
    has_seg = seg is not None

    # delta_i = sum_d(dO * O): rowwise, cheap in XLA.
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    stats = [lse, _rows(delta, bq)]
    if _fuses(t, skv):
        return _bwd_fused(q, k, v, seg, dout, stats, scale, causal, t, window, bd)

    # --- dQ pass: grid (b, h, q blocks, kv spans)
    q_spec, kv_spec, stat_spec, seg_specs = _q_major_specs(d, n_rep, causal, t, has_seg, window, bd)
    do_spec, v_spec = _v_wide_specs(dv, d, q_spec, kv_spec, n_rep, causal, t, False, window, bd)
    args = [q, k, v, dout, *stats] + ([seg["q_col"], _rows(seg["kv"], bkv)] if has_seg else [])

    def dq_kernel(*refs):
        ins, segs, (dq_ref, *scratch) = _unpack(refs, (2,) * 6, has_seg)
        _bwd_dq_kernel(*ins, *segs, dq_ref.at[0, 0], *scratch,
                       scale=scale, causal=causal, bq=bq, bkv=bkv, window=window, bd=bd)

    dq = _pallas_call(
        dq_kernel,
        name=_named("flash_attention_bwd_dq", window, bd),
        grid=(b, h, sq // bq, _kv_spans(sq, skv, t, window)),
        in_specs=[q_spec, kv_spec, v_spec, do_spec, stat_spec, stat_spec] + seg_specs,
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
    # first that does; under a window the spans are counted from that one, and one past the
    # band's last (only the sequence's end cuts a band short of the grid) names the last.
    n = t.q_span // bq

    def q_span(kj, sp):
        if bd is not None:  # a noised kv tile's one range, a clean one's two
            own, noised, cleans = _bd_q_ranges(kj, bq, bkv, bd)
            return _nearest_span(sp, n, (own[0] + noised[0], own[1] + noised[1]), cleans)
        if window is not None:
            return jnp.minimum(sp + _first_q_block(kj, bq, bkv) // n, _q_band(kj, sq // bq, bq, bkv, window).last // n)
        return jnp.maximum(sp, _first_q_block(kj, bq, bkv) // n) if causal else sp

    def q_like(width):
        return pl.BlockSpec((1, n_rep, t.q_span, width), lambda bi, hk, kj, sp: (bi, hk, q_span(kj, sp), 0))

    def kv_like(width):
        return pl.BlockSpec((1, 1, bkv, width), lambda bi, hk, kj, sp: (bi, hk, kj, 0))

    (q_spec2, kv_spec2), (do_spec2, v_spec2) = ((q_like(w), kv_like(w)) for w in (d, dv))
    stat_spec2 = pl.BlockSpec((1, n_rep, n, 1, _lane_pad(bq)),
                              lambda bi, hk, kj, sp: (bi, hk, q_span(kj, sp), 0, 0))
    in_specs2 = [q_spec2, kv_spec2, v_spec2, do_spec2, stat_spec2, stat_spec2]
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
                        scale=scale, causal=causal, bq=bq, bkv=bkv, window=window, nq=sq // bq, bd=bd)

    dk, dv = _pallas_call(
        dkv_kernel,
        name=_named("flash_attention_bwd_dkv", window, bd),
        grid=(b, hkv, skv // bkv, _q_spans(sq, skv, t, window)),
        in_specs=in_specs2,
        out_specs=[kv_spec2, v_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, skv, d), k.dtype),
            jax.ShapeDtypeStruct((b, hkv, skv, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, dv), jnp.float32),
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

# Half a vreg of lanes: a head width that is whole halves and not whole vregs (64; 192, which
# latent attention's q and k have beside a v of 128) runs the kernels on lanes padded with zeros
# to the next whole vreg (`flash_attention`). The padded products cost the MXU what the narrow
# ones would: a 128 x 128 array contracts 64 in the passes of 128, and 192 in the two of 256, and
# writes 64 columns in the passes of 128.
NARROW_HEAD = 64


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_bhsd(q, k, v, seg, scale, causal, bq, bkv, window, bd):
    out, _ = _fwd(q, k, v, seg, scale, causal, bq, bkv, window, bd)
    return out


def _flash_fwd_rule(q, k, v, seg, scale, causal, bq, bkv, window, bd):
    # the kernel's two results carry `FLASH_NAMES`: a policy that keeps both leaves nothing
    # in a rematerialised layer that reads the kernel, and JAX drops its second run there
    out, lse = (_named_bits(x, name)
                for x, name in zip(_fwd(q, k, v, seg, scale, causal, bq, bkv, window, bd), FLASH_NAMES))
    return out, (q, k, v, seg, out, lse)


def _flash_bwd_rule(scale, causal, bq, bkv, window, bd, res, dout):
    q, k, v, seg, out, lse = res
    dq, dk, dv = _bwd(q, k, v, seg, out, lse, dout, scale, causal, bq, bkv, window, bd)
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
    v: jax.Array,  # [B, Skv, Hkv, Dv]
    *,
    causal: bool = True,
    segment_ids: Optional[jax.Array] = None,  # [B, Skv]
    scale: Optional[float] = None,
    block_q: int = BLOCK_Q,
    block_kv: int = BLOCK_KV,
    rope: Optional[tuple] = None,  # (positions [B or 1, S], theta): q and k come un-rotated
    window: Optional[int] = None,  # key j is kept for query i where 0 <= i - j < window
    block_diffusion: Optional[int] = None,  # the row is [noised ; clean], in blocks of this many positions
) -> jax.Array:
    """BSHD flash attention. Sq must equal Skv when segment_ids are used, and with
    `rope`: then the rotate kernel runs in front of the flash kernels (`rope_to_heads`).
    `window` (causal, one sequence) keeps the last `window` keys a query, its own among
    them; one no shorter than the sequence is no window. `block_diffusion` (not `causal`, one
    row of two halves) keeps what the block-diffusion mask keeps (`BlockDiffusion`); its
    kernels carry `_bd` behind their names.

    v's heads (and the output's) have a width of their own, Dv: the scores contract D, and the
    weighted values, dV and the output are Dv wide, with dK and dQ at D (latent attention's
    192 beside 128: no product runs on a v padded to q's width).

    Heads 64 wide (`NARROW_HEAD`) run the same three kernels, under the same names, on
    q, k and v padded with zero lanes to 128: the scores do not see zeros in q and k, the
    output's padded lanes are zero and are cut, and the cut's transpose pads dO, so dq, dk
    and dv come out of the pad's own transpose. Mosaic lays a [.., S, 64] array out in HBM
    in (8, 128) tiles as it is (compiled for a v5e: `memref<..x8192x128xbf16>` behind a
    block of 64), so a kernel of its own at 64 would move the same bytes; what the padding
    adds is the pad and the cut themselves, which XLA fuses into the producer of q, k, v
    and the consumer of the output. They come rotated (`rope` is refused: the rotate
    kernel's tiles are whole vregs). So does every width of whole halves of a vreg, each of D
    and Dv by itself: q and k 192 wide run on 256 lanes."""
    d, dv = q.shape[-1], v.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    if d % 128:
        if rope is not None:
            raise NotImplementedError(f"the rotate kernel at head width {d}: hand q and k over rotated")
        q, k = (jnp.pad(x, ((0, 0),) * 3 + ((0, _lane_pad(d) - d),)) for x in (q, k))
    if dv % 128:
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, _lane_pad(dv) - dv),))
    qt, kt = (_heads_major(q), _heads_major(k)) if rope is None else rope_to_heads(q, k, *rope)
    vt = _heads_major(v)
    seg = None if segment_ids is None else _segment_lanes(segment_ids, q.shape[1])
    bd = _block_diffusion(block_diffusion, q.shape[1], k.shape[1], block_q, block_kv, causal, window, segment_ids)
    if bd is not None:  # a half is whole tiles
        block_q, block_kv = min(block_q, bd.half), min(block_kv, bd.half)
    out = _flash_bhsd(qt, kt, vt, seg, scale, causal, block_q, block_kv,
                      _band(window, q.shape[1], k.shape[1], causal), bd)
    return _heads_major(out)[..., :dv]
