"""The hyper-connections' passes over the stream (models/hyper.py: a part's entry, `read`, and its writing, `write`)
as four Pallas TPU kernels behind two `jax.custom_vjp` rules, so that a pass reads the stream once and writes it at
most once. S = the stream's bytes (n C channels a position; 235 MB at [1, 8192] x 4 x 3584 bfloat16, 0.29 ms at a
v5e's 819 GB/s), A = an activation's (C channels, 59 MB); ms a call in that cell's step (my chip runs, PR 63):

    hc_read_fwd   x -> r = (mean(x^2) + eps)^(-1/2), m = r (phi^T x), Hpre = sigmoid(alpha_pre m[0:n] + b[0:n]),
                  y = sum_i Hpre[i] X[i].  Reads S, writes A (and m, r: float32 rows a position).  0.34 ms.
    hc_read_bwd   d y, d m (what XLA's Sinkhorn backward hands back), the cotangent that reached x through the writing
                  -> d x = through + Hpre[i] d y + phi (r d m) + the norm's term in x, summed in float32 a channel and
                  rounded ONCE; phi's gradient accumulated over the grid in float32; d(alpha_pre m + b) a position.
                  Reads 2 S + A, writes S (in place of `through`).  0.9-1.0 ms.
    hc_write_fwd  X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] o.  Reads S + A, writes S.  0.70-0.79 ms.
    hc_write_bwd  d X' -> d o = sum_i Hpost[i] d X'[i], Hres^T d X' (in place of d X'), and the cotangents of Hpost
                  and Hres a position, float32, summed over the channels.  Reads 2 S + A, writes S + A.  1.13-1.21 ms.

All four wait on HBM (650-740 GB/s of the bytes above), none on the vector unit.

LAYOUT. The kernels take the stream POSITIONS MINOR, [B, n, C, T]: a position a lane, a copy's channels on the
sublanes. That is how the compiled step holds the stream, y and o already (`bf16[1,8192,14336]{1,2,0}`: what the
products on either side of a part want), so the transposes around a call are changes of names to XLA's layout
assignment and no array of the stream's size is copied or re-tiled (a kernel that takes [B, T, n C] row-major pays a
235 MB copy a call, 0.73 ms: PERF.md section 6, PR 61's first form). It is also what the mixtures want: a coefficient
is a row [1, T] a position-tile, broadcast along the sublanes, `hyper.coefficients`' own [2n + n^2, B T] rows as they
stand; a sum over the channels is register-wise adds with one eight-row fold at the end. The activations (y, the
part's output and their cotangents) cross this module's boundary `turned`, [B, C, T]: models/hyper.py turns them
outside the hyper-connection's named scope, so that the product that writes o and the norm that reads y, which take the
turn into their fusions, are not counted as the hyper-connection's; at the stream's two ends it also pins them (`pinned`).

The two mixtures are elementwise along C: a grid over (rows of the batch, position tiles, channel blocks), the four
copies one block of the [B, n, C, T] operand, the body a loop over chunks of `_ROWS` channels (the backward pass a
column of `_LANES` positions at a time, its n + n^2 sums the loop's carry). The entry needs a position's whole row
before y (r and Hpre depend on all of x): a grid over (rows of the batch, position tiles), the tile [n, C, positions]
whole in VMEM, walked twice. Its two products run on the MXU with the operands in the activations' type and float32
accumulation, as `hyper._product` has them; the cotangent of m is rounded to that type in front of its products
exactly where `hyper._product_bwd` rounds it. The coefficient matrix goes in as [n C / chunk, Kp, chunk] (Kp = 2n +
n^2 padded to whole bfloat16 tiles with zero rows; a chunk of channels on the lanes), so that a chunk's columns are a
leading index.

`supports` says which shapes tile; models/hyper.py routes by it (and by `sharding.partitioned_by_gspmd`); off a TPU
the kernels run in Pallas' interpreter.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa
from .kda_overlaps import _NN, _NT, _TN
from .kda_overlaps import _rows_of as _rows  # (i, size) -> the block's rows [i size, (i + 1) size)

_F32 = jnp.float32
_ROWS = 16  # channels a step of a mixture's loop: a bfloat16 tile's sublanes, two float32 registers' a 128-lane column
_LANES = 128  # positions a loop of the writing's backward pass covers: its 20 sums stay in registers, [8, 128] each
_TILES = (512, 256, 128)
_VMEM_BLOCKS = 48 << 20  # what a call's pipelined blocks (two buffers each) may take of a v5e's 128 MiB


def supports(n: int, channels: int, positions: int) -> bool:
    """Whether the kernels tile a stream of `n` copies of `channels` at `positions` a row of the batch."""
    return n > 1 and channels % 128 == 0 and positions % 128 == 0


def _largest(extent: int, fits=lambda size: True) -> int:
    return next((size for size in _TILES if extent % size == 0 and fits(size)), 128)


def _fold(a):
    """[8 x 2^k, lanes] -> [8, lanes]: the sum of its 8-row registers, pairwise."""
    while a.shape[0] > 8:
        half = a.shape[0] // 2
        a = a[:half] + a[half:]
    return a


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def turned(a):
    """An activation [B, T, C] <-> [B, C, T], as `read` hands y on and `write` takes the part's output: no data moves
    where the positions are the array's minor dimension already. The callers make the turn OUTSIDE the
    hyper-connection's named scope: the product that writes o and the norm that reads y take the turn into their
    fusions, and a fusion counts under every scope it carries."""
    return a.transpose(0, 2, 1)


def pinned(a):
    """An activation `turned`, [B, C, T], held to the order of its extents in memory (the positions minor) whoever makes
    or reads it: at the stream's two ends, where XLA's layout assignment would rather turn the stream (235 MB, n times
    the pass) than the embedding's rows or the sum in front of the head."""
    return with_layout_constraint(a, Layout(major_to_minor=tuple(range(a.ndim))))


def _minor(x, n: int):
    """The stream [B, T, n C] -> [B, n, C, T]."""
    b, t, d = x.shape
    return turned(x).reshape(b, n, d // n, t)


def _flat(x):
    """The stream [B, n, C, T] -> [B, T, n C]."""
    b, n, c, t = x.shape
    return turned(x.reshape(b, n * c, t))


def _call(kernel, name, grid, in_specs, out_specs, out_shape, semantics, blocks_bytes, scratch=(), aliases=None):
    return pl.pallas_call(
        kernel, name=name, interpret=_fa._interpret(), grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=list(scratch), input_output_aliases=aliases or {},
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics,
                                             vmem_limit_bytes=2 * blocks_bytes + (16 << 20)))


# ------------------------------------------------------------------- the writing

def _coefficient_rows(coef_ref, n: int, cols):
    """Hpost [n] and Hres [n][n] of a block's positions `cols`, each a row [1, lanes]."""
    post = [coef_ref[i:i + 1, cols] for i in range(n)]
    res = [[coef_ref[n + i * n + j:n + i * n + j + 1, cols] for j in range(n)] for i in range(n)]
    return post, res


def _write_fwd_kernel(x_ref, o_ref, coef_ref, out_ref):
    n, channels, _ = x_ref.shape
    post, res = _coefficient_rows(coef_ref, n, slice(None))

    def chunk(s, _):
        at = _rows(s, _ROWS)
        copies, o = [x_ref[j, at, :].astype(_F32) for j in range(n)], o_ref[at, :].astype(_F32)
        for i in range(n):
            out_ref[i, at, :] = (sum(res[i][j] * copies[j] for j in range(n)) + post[i] * o).astype(out_ref.dtype)

    jax.lax.fori_loop(0, channels // _ROWS, chunk, None)


def _write_bwd_kernel(g_ref, x_ref, o_ref, coef_ref, dx_ref, do_ref, dcoef_ref):
    n, channels, tile = x_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _():
        dcoef_ref[:] = jnp.zeros_like(dcoef_ref)

    for lo in range(0, tile, _LANES):  # a column of positions at a time: the sums below are the loop's carry
        cols = slice(lo, lo + _LANES)
        post, res = _coefficient_rows(coef_ref, n, cols)

        def chunk(s, sums):
            at = _rows(s, _ROWS)
            g = [g_ref[i, at, cols].astype(_F32) for i in range(n)]
            copies, o = [x_ref[j, at, cols].astype(_F32) for j in range(n)], o_ref[at, cols].astype(_F32)
            do_ref[at, cols] = sum(post[i] * g[i] for i in range(n)).astype(do_ref.dtype)
            for j in range(n):
                dx_ref[j, at, cols] = sum(res[i][j] * g[i] for i in range(n)).astype(dx_ref.dtype)
            with_o = [g[i] * o for i in range(n)]
            with_x = [g[i] * copies[j] for i in range(n) for j in range(n)]
            return tuple(acc + _fold(new) for acc, new in zip(sums, with_o + with_x))

        zero = jnp.zeros((8, _LANES), _F32)
        sums = jax.lax.fori_loop(0, channels // _ROWS, chunk, (zero,) * (n + n * n))
        for k, acc in enumerate(sums):
            dcoef_ref[k:k + 1, cols] += jnp.sum(acc, axis=0, keepdims=True)


def _write_blocks(x):
    """The grid and the blocks of a pass of the writing over x [B, n, C, T]: (grid, the stream's block, an
    activation's, the coefficients' [n + n^2, B T], bytes of the stream's block)."""
    b, n, c, t = x.shape
    tile, block = _largest(t), _largest(c)
    stream = pl.BlockSpec((None, n, block, tile), lambda r, p, k: (r, 0, k, p))
    one = pl.BlockSpec((None, block, tile), lambda r, p, k: (r, k, p))
    coef = pl.BlockSpec((n + n * n, tile), lambda r, p, k: (0, r * (t // tile) + p))
    return (b, t // tile, c // block), stream, one, coef, n * block * tile * x.dtype.itemsize


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def write(x, out, coef, n: int):
    """The stream behind a part, [B, T, n C]: copy i is sum_j Hres[i, j] X[j] + Hpost[i] out, summed in float32 a
    channel and rounded once. x [B, T, n C]; out [B, C, T], the part's output `turned`; coef [n + n^2, B T] float32:
    Hpost, then Hres row-major."""
    return _write_fwd(x, out, coef, n)[0]


def _write_fwd(x, out, coef, n):
    xm = _minor(x, n)
    grid, stream, one, rows, size = _write_blocks(xm)
    new = _call(_write_fwd_kernel, "hc_write_fwd", grid, [stream, one, rows], stream,
                jax.ShapeDtypeStruct(xm.shape, x.dtype), ("parallel",) * 3, 3 * size)(xm, out, coef)
    return _flat(new), (x, out, coef)


def _write_bwd(n, kept, g):
    x, out, coef = kept
    xm, gm = _minor(x, n), _minor(g.astype(x.dtype), n)
    grid, stream, one, rows, size = _write_blocks(xm)
    dx, do, dcoef = _call(
        _write_bwd_kernel, "hc_write_bwd", grid, [stream, stream, one, rows], [stream, one, rows],
        [jax.ShapeDtypeStruct(xm.shape, x.dtype), jax.ShapeDtypeStruct(out.shape, out.dtype),
         jax.ShapeDtypeStruct(coef.shape, _F32)],
        ("parallel", "parallel", "arbitrary"), 4 * size, aliases={0: 0})(gm, xm, out, coef)
    return _flat(dx), do, dcoef


write.defvjp(_write_fwd, _write_bwd)


# ------------------------------------------------------------------- the entry

def _hpre(ab_ref, m, n: int):
    return jax.nn.sigmoid(ab_ref[:, 0:1] * m[:n] + ab_ref[:, 1:2])


def _read_fwd_kernel(x_ref, phi_ref, ab_ref, y_ref, m_ref, r_ref, hpre_ref, *, eps: float):
    n, channels, tile = x_ref.shape
    _, kp, chunk = phi_ref.shape
    per = channels // chunk

    sums = (jnp.zeros((8, tile), _F32), jnp.zeros((kp, tile), _F32))
    for i in range(n):
        def product(s, sums, i=i):
            x = x_ref[i, _rows(s, chunk), :]
            square = jnp.square(x.astype(_F32))
            return sums[0] + _fold(square), sums[1] + _dot(phi_ref[i * per + s], x, _NN)

        sums = jax.lax.fori_loop(0, per, product, sums)
    r = jax.lax.rsqrt(jnp.sum(sums[0], axis=0, keepdims=True) / (n * channels) + eps)
    m = sums[1] * r
    m_ref[:], r_ref[:], hpre_ref[:] = m, r, _hpre(ab_ref, m, n)
    pre = [hpre_ref[i:i + 1, :] for i in range(n)]

    def mix(s, _):
        at = _rows(s, _ROWS)
        y_ref[at, :] = sum(pre[i] * x_ref[i, at, :].astype(_F32) for i in range(n)).astype(y_ref.dtype)

    jax.lax.fori_loop(0, channels // _ROWS, mix, None)


def _read_bwd_kernel(dy_ref, dm_ref, through_ref, x_ref, phi_ref, m_ref, r_ref, ab_ref,
                     dx_ref, dphi_ref, dlogit_ref, hpre_ref, dm_all_ref):
    n, channels, tile = x_ref.shape
    _, kp, chunk = phi_ref.shape
    per = channels // chunk

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _():
        dphi_ref[:] = jnp.zeros_like(dphi_ref)

    def overlap(s, sums):  # d Hpre[i] = <d y, X[i]> over the channels
        at = _rows(s, _ROWS)
        dy = dy_ref[at, :].astype(_F32)
        return tuple(acc + _fold(dy * x_ref[i, at, :].astype(_F32)) for i, acc in enumerate(sums))

    sums = jax.lax.fori_loop(0, channels // _ROWS, overlap, (jnp.zeros((8, tile), _F32),) * n)
    m, r = m_ref[:], r_ref[:]
    hpre_ref[:] = _hpre(ab_ref, m, n)
    for i, acc in enumerate(sums):
        dlogit_ref[i:i + 1, :] = jnp.sum(acc, axis=0, keepdims=True)
    dlogit = dlogit_ref[:] * hpre_ref[:] * (1 - hpre_ref[:])
    dlogit_ref[:] = dlogit
    dm_all_ref[:] = dm_ref[:]
    dm_all_ref[0:n, :] += ab_ref[:, 0:1] * dlogit
    dm = dm_all_ref[:]
    norm = -jnp.sum(dm * m, axis=0, keepdims=True) * r * r / (n * channels)  # d r (d r / d x) = norm * x
    du = (dm * r).astype(x_ref.dtype)  # as every product's cotangent in the model is in the activations' type

    for i in range(n):
        pre = hpre_ref[i:i + 1, :]

        def back(s, _, i=i, pre=pre):
            at = _rows(s, chunk)
            x = x_ref[i, at, :]
            dx = (through_ref[i, at, :].astype(_F32) + pre * dy_ref[at, :].astype(_F32)
                  + _dot(phi_ref[i * per + s], du, _TN) + norm * x.astype(_F32))
            dx_ref[i, at, :] = dx.astype(dx_ref.dtype)
            dphi_ref[i * per + s] += _dot(du, x, _NT)

        jax.lax.fori_loop(0, per, back, None)


def _by_chunk(phi, chunk: int):
    """phi [n C, K] -> [n C / chunk, Kp, chunk]: a chunk of channels' columns of phi^T, zero rows behind the K up to
    Kp, whole bfloat16 tiles of 16 rows."""
    d, k = phi.shape
    return jnp.pad(phi, ((0, 0), (0, -k % 16))).reshape(d // chunk, chunk, -1).transpose(0, 2, 1)


def _read_blocks(x, streams: int):
    """The grid and blocks of a pass of the entry over x [B, n, C, T] that holds `streams` tiles of the stream and
    one of an activation in VMEM."""
    b, n, c, t = x.shape
    position = (streams * n + 1) * c * x.dtype.itemsize
    tile = _largest(t, lambda size: 2 * size * position <= _VMEM_BLOCKS)
    stream = pl.BlockSpec((None, n, c, tile), lambda r, p: (r, 0, 0, p))
    one = pl.BlockSpec((None, c, tile), lambda r, p: (r, 0, p))
    rows = lambda k: pl.BlockSpec((k, tile), lambda r, p: (0, r * (t // tile) + p))  # noqa: E731
    whole = lambda a: pl.BlockSpec(a.shape, lambda r, p: (0,) * a.ndim)  # noqa: E731
    return (b, t // tile), stream, one, rows, whole, tile, tile * position


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def read(x, phi, ab, n: int, eps: float):
    """A part's entry: (y [B, C, T], what the part reads of the stream x [B, T, n C], to be `turned`; m [2n + n^2, B T]
    float32, the normed coefficient product, positions minor; x again, for the writing: through it the writing's
    cotangent for x reaches this rule's backward pass as an operand and the stream's cotangent is summed and rounded
    once). phi [n C, 2n + n^2] in the stream's type; ab [n, 2] float32: alpha_pre and b[0:n], a row a copy."""
    return _read_fwd(x, phi, ab, n, eps)[0]


def _read_fwd(x, phi, ab, n, eps):
    b, t, _ = x.shape
    k, xm = phi.shape[1], _minor(x, n)
    by_chunk = _by_chunk(phi, _largest(xm.shape[2]))
    grid, stream, one, rows, whole, tile, size = _read_blocks(xm, 1)
    kp = by_chunk.shape[1]
    y, m, r = _call(
        functools.partial(_read_fwd_kernel, eps=eps), "hc_read_fwd", grid, [stream, whole(by_chunk), whole(ab)],
        [one, rows(kp), rows(1)],
        [jax.ShapeDtypeStruct(xm.shape[:1] + xm.shape[2:], x.dtype), jax.ShapeDtypeStruct((kp, b * t), _F32),
         jax.ShapeDtypeStruct((1, b * t), _F32)],
        ("parallel", "parallel"), size, scratch=[pltpu.VMEM((n, tile), _F32)])(xm, by_chunk, ab)
    return (y, m[:k], x), (x, by_chunk, ab, m, r)


def _read_bwd(n, eps, kept, cts):
    x, by_chunk, ab, m, r = kept
    dy, dm, through = cts
    xm, k, kp = _minor(x, n), dm.shape[0], by_chunk.shape[1]
    grid, stream, one, rows, whole, tile, size = _read_blocks(xm, 3)
    dx, dphi, dlogit = _call(
        _read_bwd_kernel, "hc_read_bwd", grid,
        [one, rows(kp), stream, stream, whole(by_chunk), rows(kp), rows(1), whole(ab)],
        [stream, whole(by_chunk), rows(n)],
        [jax.ShapeDtypeStruct(xm.shape, x.dtype), jax.ShapeDtypeStruct(by_chunk.shape, _F32),
         jax.ShapeDtypeStruct((n, m.shape[1]), _F32)],
        ("arbitrary", "arbitrary"), size, scratch=[pltpu.VMEM((n, tile), _F32), pltpu.VMEM((kp, tile), _F32)],
        aliases={2: 0})(dy.astype(x.dtype), jnp.pad(dm.astype(_F32), ((0, kp - k), (0, 0))),
                        _minor(through.astype(x.dtype), n), xm, by_chunk, m, r, ab)
    dphi = dphi.transpose(0, 2, 1).reshape(-1, kp)[:, :k].astype(by_chunk.dtype)
    dab = jnp.stack([jnp.sum(dlogit * m[:n], axis=1), jnp.sum(dlogit, axis=1)], axis=1)
    return _flat(dx), dphi, dab


read.defvjp(_read_fwd, _read_bwd)
