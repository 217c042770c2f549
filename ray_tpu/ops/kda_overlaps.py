"""A chunk's decayed overlaps (ops/kda.py's A before beta and B) as two Pallas TPU kernels
behind one `jax.custom_vjp`: for a chunk of a head's q, k and G = the running sum of the log
decays inside the chunk, each [Q, K] float32,

    kk_ts = sum_c k_tc k_sc exp(G_tc - G_sc)      b_ts = sum_c q_tc k_sc exp(G_tc - G_sc)

for s <= t and 0 elsewhere, both [Q, Q]: `kda._decayed_overlaps`' sums over the same
sub-chunks (ops/kda.py's docstring has the algebra and its bound), with one more reference
inside a sub-chunk. What differs is where the intermediates live: a grid step holds a chunk
of `_per_step` heads and walks the heads, and a head's sub-chunks, in loops (one body, traced
once), and the pairs' decays, the decayed keys and the factors are made, used and dropped in
fast memory. Nothing with the extents [sub, sub, K] or [sub-chunks, Q, K] reaches HBM in
either pass. A sub-chunk of 32 rows t has three kinds of pairs (t, s):

  s in t's block of 8    a column s at a time against the 8 rows of its block, one vector
                    register: D = G_t - G_s, masked to t >= s BEFORE the exponential, exp,
                    times k_s, times k_t (or q_t), summed over the channels (lanes) and put
                    into lane s of the result's rows: the 16 diagonal 8 x 8 blocks of a chunk
                    of 128, 128 column-registers and 256 lane sums a chunk and head.
  s in an earlier block  through G_r at r = the last position of s's block, s <= r < t:
  of t's sub-chunk  exp(G_t - G_s) = exp(G_t - G_r) exp(G_r - G_s), both factors <= 1
                    because G falls; where G_r - G_s < -87 the second underflows to 0 and
                    the pair with it, whose true weight is below exp(-87) = 1.6e-38 (the
                    bound of the pairs of two sub-chunks, held for two blocks).
  s before the sub-chunk through G at the sub-chunk's start: since = exp(G_t - G_start) and
                    upto = exp(G_start - G_s) masked to s before the sub-chunk, both <= 1.

The last two kinds are ONE product a sub-chunk on the MXU at the highest precision (float32
operands, Mosaic's contract_precision<fp32>): its left operand stacks the groups of rows
(`_stacked`: all 32 times `since`, then the 24, 16 and 8 rows below each block times their
factor through that block's end, keys above queries: 160 rows), its right operand is the
chunk's keys times the columns' factor (`_factors`: upto before the sub-chunk, exp(G_r - G_s)
inside it, 0 behind it), and a group's result is read in its own lanes (`_masks`): what a
group's rows hold in another group's lanes pairs a row with a column under the wrong
reference, is finite (every factor is <= 1) and is never read. No exponential anywhere takes
a positive number.

The backward kernel keeps nothing but the inputs: from d kk and d b it makes a sub-chunk's
decays again and writes d q, d k, d G [Q, K]. With E the pair's decay, dq_t = sum_s db_ts k_s E,
dk_t = sum_s dkk_ts k_s E (k in the row's role) + sum_t' (dkk_t's k_t' + db_t's q_t') E (in
the column's), and dG = q dq + k (dk as row - dk as column): a reference cancels out of a
factored pair (exp(G_t - G_r) exp(G_r - G_s) does not depend on G_r), so neither the
sub-chunk's start nor a block's end gets a gradient. The factored pairs are two products a
sub-chunk: the cotangents in `_stacked`'s rows, each group masked to its lanes, times the
forward's right operand (the row's side, then times the rows' factors), and their transpose
times the forward's left operand (the column's side, then times the columns' factor: the one
product that contracts a left operand's rows, which Mosaic transposes in fast memory; no
cotangent comes transposed from XLA). The diagonal blocks sum over the rows of a register
(sublanes) and pull each cotangent out of its lane with a masked lane sum.

q and k are read where the mixer wrote them, [B, chunks x Q, H x K] with a position's heads
side by side (`rows_block`), and dq, dk written there; G comes, and dG goes, with the chunks
leading, [chunks, B, H, Q, K] (ops/kda_parts.py's docstring has why), as kk and b do.

VMEM a grid step: forward 5 blocks of 64 KB a head at 128 x 128 (q, k, G in, kk, b out),
twice for the pipeline's two buffers, 0.66 MB a head, and one block of scratch (the columns'
factor); backward 8 blocks (d kk, d b in, d q, d k, d G out), 1.05 MB a head, and two of
scratch. `_per_step` takes as many of a chunk's heads a step as `_VMEM_BLOCKS` allows: 8 in
both passes at 128 x 128 (the second half's kernels, ops/kda_parts.py, take 4 by the same
rule).

`supports` says which shapes the kernels tile; off a TPU they run in Pallas' interpreter
(`flash_attention._interpret`'s rule). The scan's second half, which reads b and the inverse
made from kk, is two kernels of the same kind beside these (ops/kda_parts.py), and the walk
over the chunks behind it two more (ops/kda_walk.py), and G itself two in front (ops/kda_prefix.py);
`supports` routes all four pairs, through `kda.takes_kernels`.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa

_HI = jax.lax.Precision.HIGHEST
_ROWS = 8  # rows of a float32 vector register
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_NN = (((1,), (0,)), ((), ()))  # a @ b
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def supports(chunk: int, sub: int, width: int) -> bool:
    """Whether the kernels tile a chunk of `chunk` positions in sub-chunks of `sub` at
    `width` channels: the channels whole 128-lane registers, a sub-chunk whole registers of
    8 rows, the chunk whole sub-chunks."""
    return width % 128 == 0 and sub % _ROWS == 0 and chunk % sub == 0


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_HI, preferred_element_type=jnp.float32)


def _rows_of(i, sub: int):
    return pl.ds(pl.multiple_of(i * sub, sub), sub)


def _pair_decay(g_t, g_s, lo: int, s: int):
    """exp(G_t - G_s) for the rows t = lo.. of a block of 8 against the position s of it, 0 where t < s."""
    t = lo + jax.lax.broadcasted_iota(jnp.int32, (g_t.shape[0], 1), 0)
    return jnp.exp(jnp.where(t >= s, g_t - g_s, -jnp.inf))


def _stacked(k_i, q_i, sub: int):
    """The rows a sub-chunk's one product takes, keys' above queries' in every group: all of them (the pairs
    with the sub-chunks before), then for each block of 8 columns but the last the rows below it."""
    return jnp.concatenate([x[lo:] for lo in range(0, sub, _ROWS) for x in (k_i, q_i)], 0)


def _group(group: int, t_block: int, sub: int, second: bool):
    """Where `_stacked` holds the 8 rows of row block `t_block` in its group 0 (all rows) or `group` <= t_block
    (the rows below column block group - 1): the keys', or the queries' if `second`."""
    lo = group * _ROWS
    start = sum(2 * (sub - at) for at in range(0, lo, _ROWS))
    at = start + second * (sub - lo) + t_block * _ROWS - lo
    return slice(at, at + _ROWS)


def _factors(g_ref, f_ref, g_i, j, i, sub: int):
    """A sub-chunk's decays on both sides of its product. Into f_ref [Q, K], the columns': exp(G_start - G_s)
    for s before the sub-chunk, exp(G_r - G_s) with r the last position of s's block of 8 inside it, 0 behind
    it. Returned [rows of `_stacked`, K], the rows': exp(G_t - G_start), then exp(G_t - G_r) for the rows
    below each block. Every one the exponential of a non-positive number: G falls."""
    rows, base = _rows_of(i, sub), i * sub
    before = g_ref[j, pl.ds(pl.multiple_of(jnp.maximum(base - _ROWS, 0), _ROWS), _ROWS), :][_ROWS - 1:]
    start = jnp.where(i > 0, before, 0.0)  # G at the last position before the sub-chunk, [1, K]
    earlier = jax.lax.broadcasted_iota(jnp.int32, (g_ref.shape[1], 1), 0) < base
    f_ref[:] = jnp.exp(jnp.where(earlier, start - g_ref[j], -jnp.inf))
    ends = [g_i[lo + _ROWS - 1:lo + _ROWS] for lo in range(0, sub, _ROWS)]
    f_ref[rows, :] = jnp.exp(jnp.concatenate([jnp.broadcast_to(r, (_ROWS, r.shape[1])) for r in ends], 0) - g_i)
    since = [jnp.exp(g_i - start)] + [jnp.exp(g_i[(c + 1) * _ROWS:] - r) for c, r in enumerate(ends[:-1])]
    return jnp.concatenate([x for x in since for _ in range(2)], 0)


def _masks(lane, base, sub: int):
    """The lanes (columns s) each group of `_stacked`'s rows is paired with: group 0 those before the
    sub-chunk, group c the block of 8 columns c - 1 inside it."""
    return [lane < base] + [(lane >= base + lo) & (lane < base + lo + _ROWS) for lo in range(0, sub - _ROWS, _ROWS)]


def _fwd_kernel(q_ref, k_ref, g_ref, kk_ref, b_ref, f_ref, *, sub: int):
    per, size, width = g_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, size), 1)

    def head(j, _):
        lanes = pl.ds(pl.multiple_of(j * width, width), width)

        def sub_chunk(i, _):
            rows, base = _rows_of(i, sub), i * sub
            q_i, k_i, g_i = q_ref[rows, lanes], k_ref[rows, lanes], g_ref[j, rows, :]
            left = _stacked(k_i, q_i, sub) * _factors(g_ref, f_ref, g_i, j, i, sub)
            out = _dot(left, k_ref[:, lanes] * f_ref[:], _NT)  # [rows of `_stacked`, Q]: a group's lanes are its mask's
            masks = _masks(lane, base, sub)
            for t in range(sub // _ROWS):  # a block of 8 rows: the lanes of each group, then its own block of 8 columns
                lo = t * _ROWS
                block = slice(lo, lo + _ROWS)
                kk, b = (sum(jnp.where(masks[c], out[_group(c, t, sub, second)], 0.0) for c in range(t + 1))
                         for second in (False, True))
                for s in range(lo, lo + _ROWS):
                    k_decayed = _pair_decay(g_i[block], g_i[s:s + 1], lo, s) * k_i[s:s + 1]
                    here = lane == base + s
                    kk = jnp.where(here, jnp.sum(k_decayed * k_i[block], -1, keepdims=True), kk)
                    b = jnp.where(here, jnp.sum(k_decayed * q_i[block], -1, keepdims=True), b)
                at = pl.ds(pl.multiple_of(base + lo, _ROWS), _ROWS)
                kk_ref[j, at, :] = kk
                b_ref[j, at, :] = b

        jax.lax.fori_loop(0, size // sub, sub_chunk, None)

    jax.lax.fori_loop(0, per, head, None)


def _bwd_kernel(q_ref, k_ref, g_ref, dkk_ref, db_ref, dq_ref, dk_ref, dg_ref, f_ref, col_ref, *, sub: int):
    """dk_ref gathers k's gradient in the row's role, col_ref (scratch) in the column's; dq_ref q's."""
    per, size, width = g_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, size), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 1), 0)

    def head(j, _):
        lanes = pl.ds(pl.multiple_of(j * width, width), width)
        col_ref[:] = jnp.zeros_like(col_ref)

        def sub_chunk(i, _):
            rows, base = _rows_of(i, sub), i * sub
            q_i, k_i, g_i = q_ref[rows, lanes], k_ref[rows, lanes], g_ref[j, rows, :]
            dkk_i, db_i = dkk_ref[j, rows, :], db_ref[j, rows, :]
            decays = _factors(g_ref, f_ref, g_i, j, i, sub)
            # the cotangents in `_stacked`'s rows, each group's outside of its own lanes 0
            cts = jnp.concatenate([jnp.where(mask, x[c * _ROWS:], 0.0) for c, mask in enumerate(_masks(lane, base, sub))
                                   for x in (dkk_i, db_i)], 0)
            as_rows = _dot(cts, k_ref[:, lanes] * f_ref[:], _NN) * decays  # k's (in the row's role) and q's, by group
            col_ref[:] += _dot(cts, _stacked(k_i, q_i, sub) * decays, _TN) * f_ref[:]
            for t in range(sub // _ROWS):
                lo = t * _ROWS
                block = slice(lo, lo + _ROWS)
                dk_t, dq_t = (sum(as_rows[_group(c, t, sub, second)] for c in range(t + 1)) for second in (False, True))
                column = jnp.zeros((_ROWS, width), jnp.float32)
                for s in range(lo, lo + _ROWS):
                    decay = _pair_decay(g_i[block], g_i[s:s + 1], lo, s)
                    here = lane == base + s
                    dkk_s = jnp.sum(jnp.where(here, dkk_i[block], 0.0), -1, keepdims=True)  # d kk[t, s], t = lo..
                    db_s = jnp.sum(jnp.where(here, db_i[block], 0.0), -1, keepdims=True)
                    k_decayed = decay * k_i[s:s + 1]
                    dk_t += dkk_s * k_decayed
                    dq_t += db_s * k_decayed
                    as_column = jnp.sum((dkk_s * k_i[block] + db_s * q_i[block]) * decay, 0, keepdims=True)  # k_s's
                    column = jnp.where(row == s - lo, as_column, column)
                at = pl.ds(pl.multiple_of(base + lo, _ROWS), _ROWS)
                dk_ref[at, lanes] = dk_t
                dq_ref[at, lanes] = dq_t
                col_ref[at, :] += column

        jax.lax.fori_loop(0, size // sub, sub_chunk, None)
        as_row, as_column = dk_ref[:, lanes], col_ref[:]
        dg_ref[j] = q_ref[:, lanes] * dq_ref[:, lanes] + k_ref[:, lanes] * (as_row - as_column)
        dk_ref[:, lanes] = as_row + as_column

    jax.lax.fori_loop(0, per, head, None)


_VMEM_BLOCKS = 10 * 2**20  # bytes of a grid step's blocks, the pipeline's two buffers (of a kernel's 16 MiB)


def _per_step(heads: int, head_bytes: int) -> int:
    """Heads of a chunk a grid step walks: the most of 8, 4, 2, 1 that divide the heads and fit."""
    return next((p for p in (8, 4, 2) if heads % p == 0 and 2 * p * head_bytes <= _VMEM_BLOCKS), 1)


def rows_block(size: int, width: int, per: int, batch: int, chunks: int, heads: int):
    """The block of an array in the mixer's own order of the positions, [B x chunks, Q, H x width]
    (q as the convolution wrote it: a position's heads side by side), that step i of a grid over
    n = (chunk, row of the batch, head) in `per` heads a step reads or writes: the chunk's Q
    positions of those heads, rows of per x width lanes. No transposed copy of q, k or v is made for
    the kernels, and none of their gradients."""
    def at(i):
        chunk_row, head = (i * per) // heads, (i * per) % heads
        return (chunk_row % batch) * chunks + chunk_row // batch, 0, head // per

    return pl.BlockSpec((None, size, per * width), at)


def _call(kernel, name: str, rows, chunked, outs, sub: int, scratch):
    """`_per_step` heads of a chunk a grid step: `rows` [B, chunks, Q, H, K] through `rows_block`, `chunked`
    [chunks, B, H, Q, w] in the grid's own order -> results of either kind, "rows" or a width w, by `outs`."""
    batch, chunks, size, heads, width = rows[0].shape
    n, in_rows = chunks * batch * heads, (batch * chunks, size, heads * width)
    widths = [x.shape[-1] for x in chunked] + [width if w == "rows" else w for w in outs]
    per = _per_step(heads, 4 * size * (width * len(rows) + sum(widths)))
    row = rows_block(size, width, per, batch, chunks, heads)
    block = lambda w: pl.BlockSpec((per, size, w), lambda i: (i, 0, 0))  # noqa: E731
    results = pl.pallas_call(
        functools.partial(kernel, sub=sub), name=name, interpret=_fa._interpret(), grid=(n // per,),
        in_specs=[row] * len(rows) + [block(x.shape[-1]) for x in chunked],
        out_specs=[row if w == "rows" else block(w) for w in outs],
        out_shape=[jax.ShapeDtypeStruct(in_rows if w == "rows" else (n, size, w), jnp.float32) for w in outs],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)))(
            *[x.reshape(in_rows) for x in rows], *[x.reshape(n, size, x.shape[-1]) for x in chunked])
    return [r.reshape(rows[0].shape) if w == "rows" else r.reshape(chunks, batch, heads, size, w)
            for r, w in zip(results, outs)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def overlaps(q, k, run, sub: int):
    """(kk, b) [chunks, B, H, Q, Q] of the module's docstring from q, k [B, chunks, Q, H, K], the
    positions in the mixer's order, and run [chunks, B, H, Q, K], float32."""
    return _overlaps_fwd(q, k, run, sub)[0]


def _overlaps_fwd(q, k, run, sub):
    size, width = run.shape[-2:]
    return tuple(_call(_fwd_kernel, "kda_overlaps_fwd", (q, k), (run,), (size, size), sub,
                       scratch=[pltpu.VMEM((size, width), jnp.float32)])), (q, k, run)


def _overlaps_bwd(sub, kept, cts):
    q, k, run = kept
    size, width = run.shape[-2:]
    return tuple(_call(_bwd_kernel, "kda_overlaps_bwd", (q, k), [run, *cts], ("rows", "rows", width), sub,
                       scratch=[pltpu.VMEM((size, width), jnp.float32)] * 2))


overlaps.defvjp(_overlaps_fwd, _overlaps_bwd)
