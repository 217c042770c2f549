"""A chunk's decayed overlaps (ops/kda.py's A before beta and B) as two Pallas TPU kernels
behind one `jax.custom_vjp`: for a chunk of a head's q, k and G = the running sum of the log
decays inside the chunk, each [Q, K] float32,

    kk_ts = sum_c k_tc k_sc exp(G_tc - G_sc)      b_ts = sum_c q_tc k_sc exp(G_tc - G_sc)

for s <= t and 0 elsewhere, both [Q, Q]: `kda._decayed_overlaps` letter for letter,
the same sub-chunks and the same two kinds of pairs (ops/kda.py's docstring has the algebra
and its bound). What differs is where the intermediates live: a grid step holds one chunk
of one head, q, k, G and the results 64 KB each at 128 x 128, and the pairs' decays, the
decayed keys and the sub-chunks' factors are made, used and dropped in fast memory. Nothing
with the extents [sub, sub, K] or [sub-chunks, Q, K] reaches HBM in either pass.

  pairs inside a sub-chunk   a column s at a time against the rows t >= s of its sub-chunk,
                    from whole vector registers of 8 rows: D = G_t - G_s, masked to t >= s
                    BEFORE the exponential, exp, times k_s, times k_t (or q_t), summed over
                    the channels (lanes) and put into lane s of the result's rows. The rows
                    above s's block of 8 are not touched (10 of 16 register pairs at 32).
  pairs of two sub-chunks    [k_t since_t ; q_t since_t] x (k_s upto_s)^T, one product a
                    sub-chunk on the MXU at the highest precision (float32 operands,
                    Mosaic's contract_precision<fp32>); since = exp(G_t - G_start) and
                    upto = exp(G_start - G_s) masked to s before the sub-chunk, both <= 1.

The backward kernel keeps nothing but the inputs: from d kk and d b it makes a sub-chunk's
decays again and writes d q, d k, d G [Q, K]. With E the pair's decay, dq_t = sum_s db_ts k_s E,
dk_t = sum_s dkk_ts k_s E (k in the row's role) + sum_t' (dkk_t's k_t' + db_t's q_t') E (in
the column's), and dG = q dq + k (dk as row - dk as column): the running sum at a
sub-chunk's start cancels out of a factored pair (exp(G_t - G_b) exp(G_b - G_s) does not
depend on G_b), so it gets no gradient. Its sums run over the rows of a register (sublanes)
or accumulate over the loop on s; the cotangents come a second time transposed (XLA's,
[Q, Q]) so that no product contracts a left operand's rows.

q and k are read where the mixer wrote them, [B, chunks x Q, H x K] with a position's heads
side by side (`rows_block`), and dq, dk written there; G comes, and dG goes, with the chunks
leading, [chunks, B, H, Q, K] (ops/kda_parts.py's docstring has why), as kk and b do.

`supports` says which shapes the kernels tile; off a TPU they run in Pallas' interpreter
(`flash_attention._interpret`'s rule). The scan's second half, which reads b and the inverse
made from kk, is two kernels of the same kind beside these (ops/kda_parts.py; `supports`
routes both halves, through `kda.takes_kernels`).
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


def supports(chunk: int, sub: int, width: int) -> bool:
    """Whether the kernels tile a chunk of `chunk` positions in sub-chunks of `sub` at
    `width` channels: the channels whole 128-lane registers, a sub-chunk whole registers of
    8 rows, the chunk whole sub-chunks."""
    return width % 128 == 0 and sub % _ROWS == 0 and chunk % sub == 0


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, precision=_HI, preferred_element_type=jnp.float32)


def _rows_of(i, sub: int):
    return pl.ds(pl.multiple_of(i * sub, sub), sub)


def _start_of(g_ref, i, sub: int):
    """G at the last position before sub-chunk i (i > 0), [1, K]."""
    return g_ref[pl.ds(pl.multiple_of(i * sub - _ROWS, _ROWS), _ROWS), :][_ROWS - 1:]


def _upto(g_ref, start, base):
    """exp(G_start - G_s) for the chunk's s before `base`, 0 from there on: [Q, K]."""
    earlier = jax.lax.broadcasted_iota(jnp.int32, (g_ref.shape[0], 1), 0) < base
    return jnp.exp(jnp.where(earlier, start - g_ref[:], -jnp.inf))


def _pair_decay(g_t, g_s, lo: int, s: int):
    """exp(G_t - G_s) for the rows t = lo.. of a sub-chunk against its row s, 0 where t < s."""
    t = lo + jax.lax.broadcasted_iota(jnp.int32, (g_t.shape[0], 1), 0)
    return jnp.exp(jnp.where(t >= s, g_t - g_s, -jnp.inf))


def _fwd_kernel(q_ref, k_ref, g_ref, kk_ref, b_ref, *, sub: int):
    size = q_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, size), 1)

    def sub_chunk(i, _):
        rows, base = _rows_of(i, sub), i * sub
        q_i, k_i, g_i = q_ref[rows, :], k_ref[rows, :], g_ref[rows, :]

        @pl.when(i == 0)
        def _():
            kk_ref[rows, :] = jnp.zeros((sub, size), jnp.float32)
            b_ref[rows, :] = jnp.zeros((sub, size), jnp.float32)

        @pl.when(i > 0)
        def _():
            start = _start_of(g_ref, i, sub)
            since = jnp.exp(g_i - start)
            both = _dot(jnp.concatenate([k_i * since, q_i * since], 0), k_ref[:] * _upto(g_ref, start, base), _NT)
            kk_ref[rows, :] = both[:sub]
            b_ref[rows, :] = both[sub:]

        for lo in range(0, sub, _ROWS):  # the block of 8 columns s = lo.. against the rows t >= lo
            q_t, k_t, g_t = q_i[lo:], k_i[lo:], g_i[lo:]
            kk, b = jnp.zeros((sub - lo, size), jnp.float32), jnp.zeros((sub - lo, size), jnp.float32)
            for s in range(lo, lo + _ROWS):
                k_decayed = _pair_decay(g_t, g_i[s:s + 1], lo, s) * k_i[s:s + 1]
                here = lane == base + s
                kk = jnp.where(here, jnp.sum(k_decayed * k_t, -1, keepdims=True), kk)
                b = jnp.where(here, jnp.sum(k_decayed * q_t, -1, keepdims=True), b)
            below = pl.ds(pl.multiple_of(base + lo, _ROWS), sub - lo)
            kk_ref[below, :] += kk
            b_ref[below, :] += b

    jax.lax.fori_loop(0, size // sub, sub_chunk, None)


def _bwd_kernel(q_ref, k_ref, g_ref, dkk_ref, db_ref, dkk_t_ref, db_t_ref, dq_ref, dk_ref, dg_ref, col_ref,
                *, sub: int):
    """dk_ref gathers k's gradient in the row's role, col_ref (scratch) in the column's; dq_ref q's."""
    size, width = q_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, size), 1)
    position = jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 1), 0)
    col_ref[:] = jnp.zeros_like(col_ref)

    def sub_chunk(i, _):
        rows, base = _rows_of(i, sub), i * sub
        q_i, k_i, g_i = q_ref[rows, :], k_ref[rows, :], g_ref[rows, :]
        dkk_i, db_i = dkk_ref[rows, :], db_ref[rows, :]

        @pl.when(i == 0)
        def _():
            dq_ref[rows, :] = jnp.zeros((sub, width), jnp.float32)
            dk_ref[rows, :] = jnp.zeros((sub, width), jnp.float32)

        @pl.when(i > 0)
        def _():
            start = _start_of(g_ref, i, sub)
            upto = _upto(g_ref, start, base)
            since = jnp.exp(g_i - start)
            rows_side = _dot(jnp.concatenate([dkk_i, db_i], 0), k_ref[:] * upto, _NN)
            rows_side *= jnp.concatenate([since, since], 0)
            dk_ref[rows, :] = rows_side[:sub]
            dq_ref[rows, :] = rows_side[sub:]
            # the columns' side: every s before the sub-chunk against its rows, the others' rows 0
            inside = (position >= base) & (position < base + sub)
            since_all = jnp.exp(jnp.where(inside, g_ref[:] - start, -jnp.inf))
            d_upto = _dot(jnp.concatenate([dkk_t_ref[:], db_t_ref[:]], 1),
                          jnp.concatenate([k_ref[:] * since_all, q_ref[:] * since_all], 0), _NN)
            col_ref[:] += d_upto * upto

        for lo in range(0, sub, _ROWS):
            q_t, k_t, g_t = q_i[lo:], k_i[lo:], g_i[lo:]
            dk_t, dq_t = jnp.zeros((sub - lo, width), jnp.float32), jnp.zeros((sub - lo, width), jnp.float32)
            column = jnp.zeros((_ROWS, width), jnp.float32)
            for s in range(lo, lo + _ROWS):
                decay = _pair_decay(g_t, g_i[s:s + 1], lo, s)
                here = lane == base + s
                dkk_s = jnp.sum(jnp.where(here, dkk_i[lo:], 0.0), -1, keepdims=True)  # d kk[t, s], t = lo..
                db_s = jnp.sum(jnp.where(here, db_i[lo:], 0.0), -1, keepdims=True)
                k_decayed = decay * k_i[s:s + 1]
                dk_t += dkk_s * k_decayed
                dq_t += db_s * k_decayed
                as_column = jnp.sum((dkk_s * k_t + db_s * q_t) * decay, 0, keepdims=True)  # [1, K]: k_s's
                column = jnp.where(row == s - lo, as_column, column)
            below = pl.ds(pl.multiple_of(base + lo, _ROWS), sub - lo)
            dk_ref[below, :] += dk_t
            dq_ref[below, :] += dq_t
            col_ref[pl.ds(pl.multiple_of(base + lo, _ROWS), _ROWS), :] += column

    jax.lax.fori_loop(0, size // sub, sub_chunk, None)
    as_row, as_column = dk_ref[:], col_ref[:]
    dg_ref[:] = q_ref[:] * dq_ref[:] + k_ref[:] * (as_row - as_column)
    dk_ref[:] = as_row + as_column


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


def _call(kernel, name: str, rows, chunked, outs, sub: int, scratch=()):
    """One grid step a chunk and head: `rows` [B, chunks, Q, H, K] through `rows_block`, `chunked`
    [chunks, B, H, Q, w] in the grid's own order -> results of either kind, "rows" or a width w, by `outs`."""
    batch, chunks, size, heads, width = rows[0].shape
    n, in_rows = chunks * batch * heads, (batch * chunks, size, heads * width)
    row = rows_block(size, width, 1, batch, chunks, heads)
    block = lambda w: pl.BlockSpec((None, size, w), lambda i: (i, 0, 0))  # noqa: E731
    results = pl.pallas_call(
        functools.partial(kernel, sub=sub), name=name, interpret=_fa._interpret(), grid=(n,),
        in_specs=[row] * len(rows) + [block(x.shape[-1]) for x in chunked],
        out_specs=[row if w == "rows" else block(w) for w in outs],
        out_shape=[jax.ShapeDtypeStruct(in_rows if w == "rows" else (n, size, w), jnp.float32) for w in outs],
        scratch_shapes=list(scratch),
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
    size = run.shape[-2]
    return tuple(_call(_fwd_kernel, "kda_overlaps_fwd", (q, k), (run,), (size, size), sub)), (q, k, run)


def _overlaps_bwd(sub, kept, cts):
    q, k, run = kept
    size, width = run.shape[-2:]
    return tuple(_call(_bwd_kernel, "kda_overlaps_bwd", (q, k), [run, *cts, *(x.mT for x in cts)],
                       ("rows", "rows", width), sub, scratch=[pltpu.VMEM((size, width), jnp.float32)]))


overlaps.defvjp(_overlaps_fwd, _overlaps_bwd)
